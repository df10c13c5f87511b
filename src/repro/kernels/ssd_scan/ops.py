"""jit'd public wrapper for the SSD Pallas kernel (pads + dispatches)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_chunked


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, D=None, chunk: int = 64):
    """x: [b,l,h,p]; dt: [b,l,h]; A: [h]; B/C: [b,l,n]; D: [h] or None."""
    b, l, h, p = x.shape
    if D is None:
        D = jnp.zeros((h,), jnp.float32)
    pad = (-l) % chunk
    if pad:
        # dt=0 padding contributes nothing: da=0 and dt*x=0
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan_chunked(x, dt, A, B, C, D, chunk=chunk,
                         interpret=interpret_mode())
    return y[:, :l]
