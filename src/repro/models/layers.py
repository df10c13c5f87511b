"""Shared model building blocks (pure functional JAX).

Parameters are nested dicts of jnp arrays; every ``init_*`` returns
``(params, specs)`` where ``specs`` mirrors the params tree with logical
sharding templates (see ``repro.distributed.sharding``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.distributed.sharding import BATCH, FSDP, MODEL, constrain


def _norm_init(key, shape, dtype):
    return jnp.ones(shape, dtype)


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale or 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, g, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def layernorm(x, g, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def apply_norm(kind, x, g, eps=1e-6):
    return rmsnorm(x, g, eps) if kind == "rmsnorm" else layernorm(x, g, eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings (full or partial fraction; chatglm3 uses 1/2)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, fraction: float, base: float = 10000.0):
    rot = int(head_dim * fraction) // 2 * 2
    if rot == 0:
        return None
    inv = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv  # [rot/2]


def apply_rope(x, positions, inv_freqs):
    """x: [..., S, H, hd]; positions: [..., S] (int)."""
    if inv_freqs is None:
        return x
    rot = inv_freqs.shape[0] * 2
    xr, xp = x[..., :rot], x[..., rot:]
    ang = positions[..., None].astype(jnp.float32) * inv_freqs  # [..., S, r/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    xr = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dtype),
        "wk": _dense_init(ks[1], (D, KV * hd), dtype),
        "wv": _dense_init(ks[2], (D, KV * hd), dtype),
        "wo": _dense_init(ks[3], (H * hd, D), dtype),
    }
    s = {
        "wq": (FSDP, MODEL), "wk": (FSDP, MODEL), "wv": (FSDP, MODEL),
        "wo": (MODEL, FSDP),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
        s["bq"] = (MODEL,)
        s["bk"] = (MODEL,)
        s["bv"] = (MODEL,)
    return p, s


def blockwise_attention(q, k, v, *, causal, q_offset=0, q_block=512,
                        kv_block=1024, probs_dtype=jnp.float32):
    """Memory-bounded attention: online-softmax over kv blocks, scanned
    over q blocks.  Pure-jnp twin of ``repro.kernels.flash_attention``.

    q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] with H % KV == 0.
    q_offset: absolute position of q[0] (for causal decode/chunking).
    probs_dtype: storing the exp'd probabilities in bf16 halves the HBM
      traffic of the materialized per-block score tensors (§Perf); the
      running max/denominator/accumulator stay fp32.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    nq = math.ceil(Sq / q_block)
    nk = math.ceil(Skv / kv_block)
    pq, pk = nq * q_block, nk * kv_block

    qp = jnp.pad(q, ((0, 0), (0, pq - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk - Skv), (0, 0), (0, 0)))
    # [B, nq, qb, KV, G, hd]
    qp = qp.reshape(B, nq, q_block, KV, G, hd)
    kp = kp.reshape(B, nk, kv_block, KV, hd)
    vp = vp.reshape(B, nk, kv_block, KV, hd)

    kv_valid = (jnp.arange(pk) < Skv).reshape(nk, kv_block)

    def q_step(_, qi):
        qb = qp[:, qi] * scale  # [B, qb, KV, G, hd]
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb = kp[:, ki]      # [B, kb, KV, hd]
            vb = vp[:, ki]
            s = jnp.einsum("bqkgh,bpkh->bkgqp", qb, kb,
                           preferred_element_type=jnp.float32)
            k_pos = ki * kv_block + jnp.arange(kv_block)
            mask = kv_valid[ki][None, :]
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqp,bpkh->bkgqh", p.astype(probs_dtype),
                vb.astype(probs_dtype),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # [B, KV, G, qb, hd] -> [B, qb, KV*G, hd]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, hd)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, pq, H, hd)
    return out[:, :Sq]


def qchunk_attention(q, k, v, *, causal, q_offset=0, q_block=512,
                     probs_dtype=jnp.float32):
    """Single-scan attention: q in chunks, full-K softmax per chunk.

    vs blockwise_attention: no online-softmax carry, so each q chunk
    materializes ~3 tensors (scores, probs, out) instead of the ~10
    per-(q,kv)-block intermediates of the double scan - ~3x less HBM
    traffic at the cost of a [qb, Skv] working set (fits VMEM/HBM for the
    assigned shapes).  §Perf beyond-paper optimization.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q_block = min(q_block, Sq)
    nq = math.ceil(Sq / q_block)
    pq = nq * q_block
    qp = jnp.pad(q, ((0, 0), (0, pq - Sq), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, q_block, KV, G, hd)
    kv_pos = jnp.arange(Skv)

    def q_step(_, qi):
        qb = qp[:, qi] * scale
        s = jnp.einsum("bqkgh,bpkh->bkgqp", qb, k,
                       preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_offset + qi * q_block + jnp.arange(q_block)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(probs_dtype)
        o = jnp.einsum("bkgqp,bpkh->bkgqh", p, v.astype(probs_dtype),
                       preferred_element_type=jnp.float32)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, hd)
        return None, o.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, pq, H, hd)
    return out[:, :Sq]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flashref_attention(q, k, v, causal=True, q_block=512,
                       probs_dtype=jnp.float32):
    """Flash-attention recompute semantics in pure jnp (§Perf).

    Forward saves only (q, k, v, out, logsumexp); the backward recomputes
    scores/probs per q chunk instead of reading S^2 fp32 residual stacks
    from HBM - the XLA-visible twin of the Pallas kernel's backward, and
    the profiler-guided fix for the dominant HBM term of the baseline.
    """
    o, _ = _flashref_fwd_impl(q, k, v, causal, q_block, probs_dtype)
    return o


def _flashref_fwd_impl(q, k, v, causal, q_block, probs_dtype):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qb_ = min(q_block, Sq)
    nq = math.ceil(Sq / qb_)
    pq = nq * qb_
    qp = jnp.pad(q, ((0, 0), (0, pq - Sq), (0, 0), (0, 0)))
    qp = qp.reshape(B, nq, qb_, KV, G, hd)
    kv_pos = jnp.arange(Skv)

    def q_step(_, qi):
        qc = qp[:, qi].astype(jnp.float32) * scale
        s = jnp.einsum("bqkgh,bpkh->bkgqp", qc, k.astype(jnp.float32))
        if causal:
            q_pos = qi * qb_ + jnp.arange(qb_)
            s = jnp.where((q_pos[:, None] >= kv_pos[None, :])
                          [None, None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)                  # [B,KV,G,qb]
        p = jnp.exp(s - lse[..., None]).astype(probs_dtype)
        o = jnp.einsum("bkgqp,bpkh->bkgqh", p, v.astype(probs_dtype),
                       preferred_element_type=jnp.float32)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, qb_, H, hd)
        return None, (o.astype(q.dtype), lse)

    _, (blocks, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, pq, H, hd)[:, :Sq]
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, pq)[..., :Sq]
    return out, lse


def _flashref_fwd(q, k, v, causal, q_block, probs_dtype):
    o, lse = _flashref_fwd_impl(q, k, v, causal, q_block, probs_dtype)
    return o, (q, k, v, o, lse)


def _flashref_bwd(causal, q_block, probs_dtype, res, do):
    q, k, v, o, lse = res
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qb_ = min(q_block, Sq)
    nq = math.ceil(Sq / qb_)
    pq = nq * qb_

    def pad_q(x):
        return jnp.pad(x, ((0, 0), (0, pq - Sq)) + ((0, 0),) *
                       (x.ndim - 2))

    qp = pad_q(q).reshape(B, nq, qb_, KV, G, hd)
    dop = pad_q(do).reshape(B, nq, qb_, KV, G, hd)
    op = pad_q(o).reshape(B, nq, qb_, KV, G, hd)
    lsep = jnp.pad(lse, ((0, 0),) * 3 + ((0, pq - Sq),))
    lsep = lsep.reshape(B, KV, G, nq, qb_)
    kv_pos = jnp.arange(Skv)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def q_step(carry, qi):
        dk_acc, dv_acc = carry
        qc = qp[:, qi].astype(jnp.float32) * scale   # [B,qb,KV,G,hd]
        doc = dop[:, qi].astype(jnp.float32)
        oc = op[:, qi].astype(jnp.float32)
        ls = lsep[:, :, :, qi]                       # [B,KV,G,qb]
        s = jnp.einsum("bqkgh,bpkh->bkgqp", qc, kf)
        if causal:
            q_pos = qi * qb_ + jnp.arange(qb_)
            s = jnp.where((q_pos[:, None] >= kv_pos[None, :])
                          [None, None, None], s, -1e30)
        p = jnp.exp(s - ls[..., None])               # recomputed probs
        dog = doc.transpose(0, 2, 3, 1, 4)           # [B,KV,G,qb,hd]
        dv = jnp.einsum("bkgqp,bkgqh->bpkh", p, dog)
        dp = jnp.einsum("bkgqh,bpkh->bkgqp", dog, vf)
        delta = jnp.sum(doc * oc, axis=-1).transpose(0, 2, 3, 1)
        ds = p * (dp - delta[..., None])
        dq = jnp.einsum("bkgqp,bpkh->bqkgh", ds, kf) * scale
        # qc carries the 1/sqrt(hd) scale already, so dk needs none
        dk = jnp.einsum("bkgqp,bqkgh->bpkh", ds, qc)
        return (dk_acc + dk, dv_acc + dv), dq

    zero_kv = jnp.zeros((B, Skv, KV, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_step, (zero_kv, zero_kv),
                                 jnp.arange(nq))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, pq, KV, G, hd)
    dq = dq[:, :Sq].reshape(B, Sq, H, hd)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flashref_attention.defvjp(_flashref_fwd, _flashref_bwd)


def reference_attention(q, k, v, *, causal, q_offset=0):
    """Naive attention (small shapes / oracles only)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bpkh->bkgqp", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if causal:
        q_pos = q_offset + jnp.arange(Sq)
        mask = q_pos[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqp,bpkh->bkgqh", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def attention_block(p, cfg, x, *, positions, causal=True, kv_cache=None,
                    cache_index=None, inv_freqs=None, context=None,
                    return_kv=False, stacked_cache=None, layer_index=None):
    """Full attention block. Returns (out, new_kv_cache).

    kv_cache: optional (k, v) of shape [B, S_max, KV, hd] for decode - the
      fresh k/v are written at ``cache_index`` and attention runs over the
      valid prefix.
    stacked_cache: §Perf 'decode_inplace' - the FULL [L, B, S, KV, hd]
      cache pair threaded through the layer-scan carry; only the new
      token's k/v are written (one in-place DUS) instead of re-stacking
      the whole cache through scan outputs.  Returns the updated stack.
    context: cross-attention source (whisper decoder); replaces k/v input.
    return_kv: prefill - return the rope'd (k, v) so callers can seed a
      decode cache.
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.kv_heads, cfg.hd
    src = context if context is not None else x
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, src.shape[1], KV, hd)
    v = v.reshape(B, src.shape[1], KV, hd)
    q = constrain(q, (BATCH, None, MODEL, None))
    k = constrain(k, (BATCH, None, MODEL, None))

    if context is None and inv_freqs is not None:
        q = apply_rope(q, positions, inv_freqs)
        k = apply_rope(k, positions, inv_freqs)

    new_cache = None
    if stacked_cache is not None:
        # decode-in-place: single-token DUS into the carried stack
        ck_all, cv_all = stacked_cache
        zero = jnp.int32(0)
        ck_all = jax.lax.dynamic_update_slice(
            ck_all, k[None].astype(ck_all.dtype),
            (layer_index, zero, cache_index, zero, zero))
        cv_all = jax.lax.dynamic_update_slice(
            cv_all, v[None].astype(cv_all.dtype),
            (layer_index, zero, cache_index, zero, zero))
        ck = jax.lax.dynamic_index_in_dim(ck_all, layer_index, 0, False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, layer_index, 0, False)
        pdt = jnp.dtype(cfg.attn_probs_dtype)
        S_max = ck.shape[1]
        pos_mask = jnp.arange(S_max) <= cache_index
        qg = q.reshape(B, S, KV, H // KV, hd)
        s = jnp.einsum("bqkgh,bpkh->bkgqp", qg, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        s = jnp.where(pos_mask[None, None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1).astype(pdt)
        o = jnp.einsum("bkgqp,bpkh->bkgqh", pr, cv.astype(pdt),
                       preferred_element_type=jnp.float32)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(x.dtype)
        o = constrain(o, (BATCH, None, MODEL, None))
        out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd), p["wo"])
        if cfg.tp_bf16_reduce:
            out = out.astype(jnp.bfloat16)
        return out, (ck_all, cv_all)
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                 cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                 cache_index, axis=1)
        new_cache = (ck, cv)
        pdt = jnp.dtype(cfg.attn_probs_dtype)
        if S > 1:
            # prefill regime: causal attention over the fresh k/v
            # (memory-bounded; never materializes S x S scores).
            # qchunk/flashref single-scan softmax materializes ~3x fewer
            # intermediates than the double-scan (§Perf) at short/medium
            # sequence; past ~8k the [qb, S] full-K tensors cost more
            # than the double-scan's bounded blocks (measured: deepseek
            # prefill_32k 34.4s -> 38.8s) - fwd only, length-gated.
            if cfg.attn_impl in ("qchunk", "flashref") and \
                    src.shape[1] <= 8192:
                o = qchunk_attention(q, k, v, causal=True,
                                     probs_dtype=pdt)
            else:
                o = blockwise_attention(q, k, v, causal=True,
                                        probs_dtype=pdt)
        else:
            # decode: attend over the valid cache prefix only
            S_max = ck.shape[1]
            pos_mask = jnp.arange(S_max) <= cache_index
            qg = q.reshape(B, S, KV, H // KV, hd)
            s = jnp.einsum("bqkgh,bpkh->bkgqp", qg, ck,
                           preferred_element_type=jnp.float32)
            s = s / math.sqrt(hd)
            s = jnp.where(pos_mask[None, None, None, None, :], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(pdt)
            o = jnp.einsum("bkgqp,bpkh->bkgqh", pr, cv.astype(pdt),
                           preferred_element_type=jnp.float32)
            o = o.transpose(0, 3, 1, 2, 4).reshape(
                B, S, H, hd).astype(x.dtype)
    else:
        pdt = jnp.dtype(cfg.attn_probs_dtype)
        if cfg.attn_impl == "flash" and context is None:
            from repro.kernels.flash_attention import ops as fa_ops
            o = fa_ops.flash_attention(q, k, v, causal=causal)
        elif S * src.shape[1] <= 256 * 256:
            o = reference_attention(q, k, v, causal=causal and
                                    context is None)
        elif cfg.attn_impl == "qchunk":
            o = qchunk_attention(q, k, v, causal=causal and
                                 context is None, probs_dtype=pdt)
        elif cfg.attn_impl == "flashref":
            o = flashref_attention(q, k, v, causal and context is None,
                                   512, pdt)
        else:
            o = blockwise_attention(q, k, v, causal=causal and
                                    context is None, probs_dtype=pdt)
        if return_kv:
            new_cache = (k, v)
    o = constrain(o, (BATCH, None, MODEL, None))
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd), p["wo"])
    if cfg.tp_bf16_reduce:
        out = out.astype(jnp.bfloat16)
    out = checkpoint_name(out, "proj_out")
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and MoE
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype):
    ks = jax.random.split(key, 3)
    p = {
        "w_gate": _dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": _dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": _dense_init(ks[2], (d_ff, d_model), dtype),
    }
    s = {"w_gate": (FSDP, MODEL), "w_up": (FSDP, MODEL),
         "w_down": (MODEL, FSDP)}
    return p, s


def mlp_block(p, x, cfg=None):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = constrain(h, (BATCH, None, MODEL))
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    if cfg is not None and cfg.tp_bf16_reduce:
        y = y.astype(jnp.bfloat16)
    return checkpoint_name(y, "proj_out")


def init_moe(key, cfg, dtype):
    D = cfg.d_model
    E, Fe = cfg.moe_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (D, E), jnp.float32),
        "we_gate": _dense_init(ks[1], (E, D, Fe), dtype),
        "we_up": _dense_init(ks[2], (E, D, Fe), dtype),
        "we_down": _dense_init(ks[3], (E, Fe, D), dtype),
    }
    if cfg.moe_local_dispatch:
        # expert-parallel shard_map dispatch needs whole experts per rank
        s = {
            "router": (None, None),
            "we_gate": (MODEL, None, None),
            "we_up": (MODEL, None, None),
            "we_down": (MODEL, None, None),
        }
    else:
        s = {
            "router": (None, None),
            "we_gate": (MODEL, FSDP, None),
            "we_up": (MODEL, FSDP, None),
            "we_down": (MODEL, None, FSDP),
        }
    if cfg.moe_shared_experts:
        sp, ss = init_mlp(ks[4], D, Fe * cfg.moe_shared_experts, dtype)
        p["shared"] = sp
        s["shared"] = ss
    return p, s


def _moe_dispatch_compute(xt, logits, wg, wu, wd, E, K, C, e_base):
    """Local sort-based dispatch + expert FFN for experts
    [e_base, e_base + E_loc).  Pure function: reused by the global
    (GSPMD) and local (shard_map expert-parallel) paths."""
    T, D = xt.shape
    E_loc = wg.shape[0]
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), K)  # [T, K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos = jnp.arange(T * K) - jnp.searchsorted(sorted_e, sorted_e,
                                               side="left")
    local_e = sorted_e - e_base
    keep = (pos < C) & (local_e >= 0) & (local_e < E_loc)
    slot = jnp.where(keep, local_e * C + pos, E_loc * C)

    src_tok = order // K
    buf = jnp.zeros((E_loc * C + 1, D), xt.dtype)
    buf = buf.at[slot].set(xt[src_tok], mode="drop")
    ex_in = buf[:-1].reshape(E_loc, C, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex_in, wg))
    h = h * jnp.einsum("ecd,edf->ecf", ex_in, wu)
    ex_out = jnp.einsum("ecf,efd->ecd", h, wd)

    flat_out = ex_out.reshape(E_loc * C, D)
    routed = jnp.where(keep[:, None],
                       flat_out[jnp.clip(slot, 0, E_loc * C - 1)], 0.0)
    g = gates.reshape(-1)[order][:, None].astype(xt.dtype)
    y = jnp.zeros((T, D), xt.dtype).at[src_tok].add(routed * g)
    return y


def _moe_local_dispatch(p, cfg, xt, logits, capacity_factor):
    """Expert-parallel dispatch under shard_map (§Perf, 'moe_local').

    Activations are replicated along the model axis, so every expert-owner
    rank dispatches its own experts' tokens locally; the only
    communication is one psum of the combined output over 'model' -
    replacing GSPMD's pathological [T*K, D] fp32 all-reduces.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    mesh = shd.get_mesh()
    T, D = xt.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    ep = mesh.shape["model"]
    E_loc = E // ep
    T_loc = T // dp
    C = max(1, int(capacity_factor * K * T_loc / E))

    def local_fn(xt_l, logits_l, wg, wu, wd):
        e_base = jax.lax.axis_index("model") * E_loc
        y = _moe_dispatch_compute(xt_l, logits_l, wg, wu, wd,
                                  E, K, C, e_base)
        return jax.lax.psum(y, "model")

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axes, None), P(batch_axes, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch_axes, None),
    )(xt, logits.astype(jnp.float32), p["we_gate"], p["we_up"],
      p["we_down"])


def moe_block(p, cfg, x, capacity_factor: float = 1.25):
    """Sort-based top-k MoE dispatch (GShard-style with fixed capacity).

    Tokens are routed to their top-k experts; each expert processes at most
    C tokens (overflow dropped, standard practice).  The grouped-expert
    einsum shards E over MODEL = expert parallelism.

    With cfg.moe_local_dispatch the dispatch runs expert-parallel under
    shard_map (one output psum instead of GSPMD scatter all-reduces).
    """
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_topk
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])

    from repro.distributed import sharding as shd
    if cfg.moe_local_dispatch and shd.get_mesh() is not None:
        y = _moe_local_dispatch(p, cfg, xt, logits, capacity_factor)
        y = y.reshape(B, S, D)
        if "shared" in p:
            y = y + mlp_block(p["shared"], x)
        _, idx = jax.lax.top_k(logits, 1)
        me = jax.nn.one_hot(idx[:, 0], E).mean(0)
        pe = jax.nn.softmax(logits, -1).mean(0)
        return y, E * jnp.sum(me * pe)

    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), K)  # [T, K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    C = max(1, int(capacity_factor * K * T / E))
    flat_e = idx.reshape(-1)                       # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank of each routed token within its expert
    pos = jnp.arange(T * K) - jnp.searchsorted(sorted_e,
                                               sorted_e, side="left")
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)  # overflow -> dump row

    src_tok = order // K
    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[slot].set(xt[src_tok], mode="drop")
    ex_in = buf[:-1].reshape(E, C, D)
    ex_in = constrain(ex_in, (MODEL, None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex_in, p["we_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", ex_in, p["we_up"])
    ex_out = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    ex_out = constrain(ex_out, (MODEL, None, None))

    flat_out = ex_out.reshape(E * C, D)
    routed = jnp.where(keep[:, None],
                       flat_out[jnp.clip(slot, 0, E * C - 1)], 0.0)
    g = gates.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((T, D), x.dtype).at[src_tok].add(routed * g)
    y = y.reshape(B, S, D)

    if "shared" in p:
        y = y + mlp_block(p["shared"], x)
    # load-balancing auxiliary loss (Switch-style)
    me = jax.nn.one_hot(idx[:, 0], E).mean(0)
    pe = jax.nn.softmax(logits, -1).mean(0)
    aux = E * jnp.sum(me * pe)
    return y, aux
