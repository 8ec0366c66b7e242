"""The benchmark's own yardstick: discovery, the traffic generator, the
plain reference, comparisons, and the reduction of traces to metrics."""
