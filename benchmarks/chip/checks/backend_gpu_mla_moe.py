"""Plain reference of backend ``gpu_mla_moe``: the op-stream lowering of
a DeepSeek-V2 block stack (multi-head latent attention, a leading dense
layer, then sparse-expert layers with shared experts) and the L1/L2
cache hierarchy it is replayed through.

NumPy and the standard library only; nothing of the program under test
is imported.  The per-op emitters (tiled GEMM, two-pass normalization,
three-pass in-place softmax, strided transpose, elementwise) and the
hierarchy are backend ``gpu``'s, loaded from ``checks/backend_gpu.py``.
This file adds, as frozen copies of their definition:

* :func:`route` - each token's experts at one layer: the greedy top-k,
  token by token, of ``h @ W_r + skew * g`` in float64, drawn from
  ``default_rng([seed, layer])`` as ``h ~ N(0,1)[T, d_model]``, ``W_r ~
  N(0,1)[d_model, E] / sqrt(d_model)``, ``g ~ N(0,1)[E]``; ties go to
  the lower expert.
* row gather and row scatter-add at token-row granularity (the experts'
  dispatch and combine);
* :func:`lower_mla_moe_stream` - the stream itself: per layer, latent
  attention (query projection, latent down-projection with the shared
  RoPE key, latent norm, up-projection to per-head keys and values,
  RoPE on the RoPE parts, the dense lowering's scores / softmax / P x V
  conventions, output projection, residual); then a gated MLP (the
  leading dense layers) or router GEMM and softmax, the shared experts
  as one gated MLP over every token, and for each routed expert that
  has tokens: gather, gated MLP, scatter-add; residual.

A program change that alters the stream is a different workload, not a
faster one.
"""

from __future__ import annotations

import numpy as np

from chipbench.manifest import decoder, load_module

_gpu = load_module("checks", "backend_gpu")
LINE_BYTES = _gpu.LINE_BYTES


def reference_trace(config, key):
    """The configuration's trace relabelled by ``key``, as ``{"trace":
    (time, line, is_write, hit, subpartition), "mode", "clock_hz",
    "block_bits"}``."""
    run, d = config["run"], decoder(config)
    h = run["hierarchy"]
    t, a, w = lower_mla_moe_stream(d, run["tokens"], run["line_sample"],
                                   run["routing"]["seed"],
                                   run["routing"]["skew"])
    trace = _gpu.simulate_hierarchy(
        t, a ^ np.int64(key << run["relabel_shift"]), w, l1=h["l1"],
        l2=h["l2"], l2_latency=h["l2_latency"],
        write_allocate=h["write_allocate"])
    return {"trace": trace, "mode": "cache", "clock_hz": h["clock_hz"],
            "block_bits": h["l1"]["line_bytes"] * 8}


def route(seq, d_model, n_experts, topk, seed, layer, skew):
    """``[seq][topk]`` experts of each token at MoE layer ``layer``."""
    rng = np.random.default_rng([int(seed), int(layer)])
    h = rng.standard_normal((seq, d_model))
    w = rng.standard_normal((d_model, n_experts)) / np.sqrt(d_model)
    g = rng.standard_normal(n_experts)
    logits = h @ w + skew * g
    return [sorted(range(n_experts), key=lambda e: (-logits[t, e], e))[:topk]
            for t in range(seq)]


class _Stream(_gpu._Stream):
    """Backend ``gpu``'s stream with row gather and row scatter-add."""

    def view(self, x, offset, nbytes):
        return _gpu._Tensor(x.base + offset, nbytes)

    def row_lines(self, x, rows, row_bytes):
        out = []
        for r in rows:
            lo = (x.base + r * row_bytes) // LINE_BYTES
            hi = (x.base + (r + 1) * row_bytes - 1) // LINE_BYTES
            out.extend(range(lo, hi + 1))
        return np.array(out, np.int64)

    def gather(self, src, out, rows, row_bytes):
        t0 = self.t
        src_lines = self.row_lines(src, rows, row_bytes)
        out_lines = self.row_lines(out, range(len(rows)), row_bytes)
        cycles = int((len(src_lines) + len(out_lines)) * LINE_BYTES
                     / _gpu._BYTES_PER_CYCLE)
        self.emit(src_lines, t0, t0 + cycles, False)
        self.emit(out_lines, t0 + cycles // 2, t0 + cycles, True)
        self.done(t0, cycles)

    def scatter_add(self, src, out, rows, row_bytes, dt):
        t0 = self.t
        src_lines = self.row_lines(src, range(len(rows)), row_bytes)
        out_lines = self.row_lines(out, rows, row_bytes)
        n_elem = len(rows) * row_bytes // dt
        cycles = int(max(n_elem / _gpu._FLOPS_PER_CYCLE,
                         (len(src_lines) + 2 * len(out_lines)) * LINE_BYTES
                         / _gpu._BYTES_PER_CYCLE))
        self.emit(src_lines, t0, t0 + cycles, False)
        self.emit(out_lines, t0, t0 + cycles, False)
        self.emit(out_lines, t0 + cycles // 2, t0 + cycles, True)
        self.done(t0, cycles)


def _mlp_weights(s, d_model, d_ff, dt):
    return (s.weight(d_model * d_ff * dt), s.weight(d_model * d_ff * dt),
            s.weight(d_ff * d_model * dt))


def _gated_mlp(s, xin, weights, M, d_model, d_ff, dt):
    w_gate, w_up, w_down = weights
    g = s.alloc(M * d_ff * dt)
    s.gemm(xin, w_gate, g, M, d_ff, d_model, dt)
    u = s.alloc(M * d_ff * dt)
    s.gemm(xin, w_up, u, M, d_ff, d_model, dt)
    s.elementwise([g, u], g, 5, dt)
    s.release(u)
    y = s.alloc(M * d_model * dt)
    s.gemm(g, w_down, y, M, d_model, d_ff, dt)
    s.release(g)
    return y


def _mla(s, x, d, seq, dt):
    D, H, dc = d["d_model"], d["n_heads"], d["kv_lora_rank"]
    dn, dr, dv = (d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                  d["v_head_dim"])
    wq = s.weight(D * H * (dn + dr) * dt)
    wkva = s.weight(D * (dc + dr) * dt)
    wkvb = s.weight(dc * H * (dn + dv) * dt)
    wo = s.weight(H * dv * D * dt)
    xn = s.alloc(x.nbytes)
    s.normalization(x, xn, dt)
    q = s.alloc(seq * H * (dn + dr) * dt)
    s.gemm(xn, wq, q, seq, H * (dn + dr), D, dt)
    ckv = s.alloc(seq * (dc + dr) * dt)
    s.gemm(xn, wkva, ckv, seq, dc + dr, D, dt)
    s.release(xn)
    k_rope = s.view(ckv, seq * dc * dt, seq * dr * dt)
    cn = s.alloc(seq * dc * dt)
    s.normalization(s.view(ckv, 0, seq * dc * dt), cn, dt)
    kv = s.alloc(seq * H * (dn + dv) * dt)
    s.gemm(cn, wkvb, kv, seq, H * (dn + dv), dc, dt)
    s.release(cn)
    q_rope = s.view(q, seq * H * dn * dt, seq * H * dr * dt)
    s.elementwise([q_rope], q_rope, 3, dt)
    s.elementwise([k_rope], k_rope, 3, dt)
    scores = s.alloc(H * seq * seq * dt // 8)
    kt = s.alloc(seq * (H * dn + dr) * dt)
    s.transpose(s.view(kv, 0, seq * H * dn * dt),
                s.view(kt, 0, seq * H * dn * dt))
    s.transpose(k_rope, s.view(kt, seq * H * dn * dt, seq * dr * dt))
    s.gemm(q, kt, scores, seq, seq, dn + dr, dt)
    s.softmax(scores, dt)
    attn = s.alloc(seq * H * dv * dt)
    s.gemm(scores, s.view(kv, seq * H * dn * dt, seq * H * dv * dt), attn,
           seq, dv, seq, dt)
    for t in (scores, kt, kv, ckv, q):
        s.release(t)
    proj = s.alloc(seq * D * dt)
    s.gemm(attn, wo, proj, seq, D, H * dv, dt)
    s.release(attn)
    s.elementwise([x, proj], x, 1, dt)
    s.release(proj)


def _moe(s, xn, choice, d, seq, dt):
    D, E, F = d["d_model"], d["n_experts"], d["moe_d_ff"]
    wr = s.weight(D * E * dt)
    shared = _mlp_weights(s, D, d["n_shared"] * F, dt) \
        if d["n_shared"] else None
    experts = [_mlp_weights(s, D, F, dt) for _ in range(E)]
    logits = s.alloc(seq * E * dt)
    s.gemm(xn, wr, logits, seq, E, D, dt)
    s.softmax(logits, dt)
    s.release(logits)
    if shared:
        y = _gated_mlp(s, xn, shared, seq, D, d["n_shared"] * F, dt)
    else:
        y = s.alloc(seq * D * dt)
        s.elementwise([], y, 0, dt)
    for e in range(E):
        rows = [t for t in range(seq) if e in choice[t]]
        if not rows:
            continue
        xe = s.alloc(len(rows) * D * dt)
        s.gather(xn, xe, rows, D * dt)
        ye = _gated_mlp(s, xe, experts[e], len(rows), D, F, dt)
        s.release(xe)
        s.scatter_add(ye, y, rows, D * dt, dt)
        s.release(ye)
    return y


def lower_mla_moe_stream(d, seq, sample, route_seed, route_skew,
                         dtype_bytes=2):
    """``(time_cycles, byte_addr, is_write)`` of one forward pass of the
    block stack whose widths ``d`` gives (the configuration's
    ``decoder`` map)."""
    dt = dtype_bytes
    D = d["d_model"]
    s = _Stream(sample)
    x = s.alloc(seq * D * dt)
    for layer in range(d["n_layers"]):
        _mla(s, x, d, seq, dt)
        xn = s.alloc(x.nbytes)
        if layer < d["first_dense"]:
            w = _mlp_weights(s, D, d["d_ff"], dt)
            s.normalization(x, xn, dt)
            y = _gated_mlp(s, xn, w, seq, D, d["d_ff"], dt)
        else:
            choice = route(seq, D, d["n_experts"], d["topk"], route_seed,
                           layer, route_skew)
            s.normalization(x, xn, dt)
            y = _moe(s, xn, choice, d, seq, dt)
        s.elementwise([x, y], x, 1, dt)
        s.release(y)
        s.release(xn)
    return s.finish()
