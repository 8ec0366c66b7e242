"""Reduction of the program's own spans (``repro.obs.trace``) to host
times per layer. The harness activates one ``Trace`` on its thread for
the traced window, so every span the program opens there hangs under
that trace's root."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def inclusive_s(root, names: Iterable[str],
                not_under: Iterable[str] = ()) -> Tuple[float, int]:
    """(seconds, count) of the spans named in ``names``: each counted
    whole and once (a span under another of ``names`` is inside it
    already), and none that lies under a span named in ``not_under``."""
    names, not_under = set(names), set(not_under)
    total, count = 0.0, 0

    def walk(span, blocked: bool) -> None:
        nonlocal total, count
        for child in span.children:
            if child.name in not_under:
                continue
            if child.name in names and not blocked:
                total += child.duration
                count += 1
                walk(child, True)
            else:
                walk(child, blocked)

    walk(root, False)
    return total, count


def per_step_ms(ctx, names: Iterable[str],
                not_under: Iterable[str] = ()) -> Optional[float]:
    """Milliseconds per step of the traced window in those spans; None
    where the window opened none of them."""
    seconds, count = inclusive_s(ctx.root, names, not_under)
    if count == 0:
        return None
    return seconds / ctx.steps * 1e3
