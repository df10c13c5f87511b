"""jit'd public wrappers for the flash-attention Pallas kernels.

``flash_attention`` accepts the model layout [B, S, H, hd] (heads after
sequence) and is fully differentiable: the custom VJP dispatches the
Pallas backward kernels (FA-2 two-pass), so neither direction ever
materializes S^2 probabilities in HBM.  The kernels run compiled on a
TPU and interpreted on the CPU backend (:func:`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.kernel_bwd import \
    flash_attention_bwd_bhsd


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, causal, q_block, kv_block):
    o, _ = flash_attention_bhsd(q, k, v, causal=causal, q_block=q_block,
                                kv_block=kv_block,
                                interpret=interpret_mode())
    return o


def _flash_fwd(q, k, v, causal, q_block, kv_block):
    o, lse = flash_attention_bhsd(q, k, v, causal=causal,
                                  q_block=q_block, kv_block=kv_block,
                                  interpret=interpret_mode())
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, q_block, kv_block, res, do):
    q, k, v, o, lse = res
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    dq, dk_h, dv_h = flash_attention_bwd_bhsd(
        q, k, v, o, lse, do, causal=causal, q_block=q_block,
        kv_block=kv_block, interpret=interpret_mode())
    # GQA: sum per-query-head contributions into kv heads
    Skv = k.shape[2]
    dk = dk_h.reshape(B, KV, G, Skv, hd).sum(2).astype(k.dtype)
    dv = dv_h.reshape(B, KV, G, Skv, hd).sum(2).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_flash_bhsd.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "q_block", "kv_block"))
def flash_attention(q, k, v, *, causal=True, q_block=128, kv_block=128):
    """q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] -> [B, Sq, H, hd].

    Differentiable (Pallas fwd + bwd kernels)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash_bhsd(qt, kt, vt, causal, q_block, kv_block)
    return o.transpose(0, 2, 1, 3)
