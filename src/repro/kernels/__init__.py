"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel directory contains:
  kernel.py - pl.pallas_call with explicit BlockSpec VMEM tiling
  ops.py    - jit'd public wrapper (mode from :func:`interpret_mode`)
  ref.py    - pure-jnp oracle used by the allclose test sweeps

  flash_attention - blockwise online-softmax attention (GQA, causal);
                    sequential kv-grid with VMEM (m, l, acc) carry;
                    differentiable: FA-2 two-pass backward kernels
                    (kernel_bwd.py) wired through a custom VJP
  ssd_scan        - Mamba-2 SSD chunked scan; inter-chunk SSM state lives
                    in VMEM scratch across the sequential chunk grid
  lifetime_scan   - GainSight's frontend hot loop: segmented lifetime
                    extraction + histogram over sorted event streams
                    (the paper's own analysis made TPU-native)
"""


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in interpret mode on this process's
    jax backend: interpreted on ``cpu`` (tests, rehearsals), compiled on
    ``tpu``.  Any other backend raises, and so does a backend that fails
    to initialize: a kernel never falls back to the interpreter in
    silence."""
    import jax
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the jax backend is {backend!r}")
