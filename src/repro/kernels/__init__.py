"""Hardware kernels behind the relational operators, with backend dispatch.

Layout:

* ``registry``       — logical kernel name → per-backend impls, selected at
                       plan and call time by runtime capability detection
                       (``dense`` / ``pallas-interpret`` / ``pallas-tpu``
                       / ``pallas-gpu``).
* ``compat``         — probes whether the jaxlib carries the Triton
                       lowering the ``pallas-gpu`` tier needs.
* ``autotune``       — block-size autotuner keyed by
                       ``(kernel, shape-bucket, dtype, backend)`` with an
                       in-process + on-disk JSON cache.
* ``ops``            — public wrappers and the registration site of the
                       built-in kernels; padding/alignment lives here.
* ``masked_matmul``  — block-gated A×B (PNMF SDDMM pattern, paper §6).
* ``merge_join``     — block-skip overlay join (paper §4.3/§4.7).
* ``bloom_probe``    — V2V Bloom-join membership probe (paper §4.7).
* ``ref``            — pure-jnp oracles; the ``dense`` backend and the
                       correctness reference for every other backend.

Adding a kernel = registering a ``dense`` oracle + at least one Pallas
backend under one name (see ``registry`` module docstring and
``docs/kernels.md``); the parity sweep in ``tests/test_kernel_registry.py``
and the autotuner pick it up from the registry metadata.
"""
