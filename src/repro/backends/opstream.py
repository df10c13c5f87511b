"""Operator-level address-stream generation (paper §5.1, adapted).

The paper's GPU backend replays NVBit-captured SASS through Accel-Sim.
Neither tool exists here, so we generate the address streams *from the
workload structure itself*: every framework model lowers to a sequence of
operators (GEMM, elementwise, normalization/reduction, transpose, residual),
and each operator emits the byte-address stream its tiled execution would
issue on a SIMD machine.  The streams are replayed through
``repro.backends.cachesim`` to obtain hit/miss-annotated L1/L2 traces.

Line-sampling: for large tensors we keep only lines whose hashed index
falls under ``1/sample``; because sampling is *per line*, every access to a
kept line is preserved, so per-line lifetime sequences remain exact and the
lifetime distribution is an unbiased subsample (the same argument PKA makes
for kernels, made for addresses).

Per-op kernel counters (reads/writes/flops/cycles) are recorded for PKA
(Table 4) and kernel-level lifetime attribution (Fig 5).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.api import ProfileResult, register_backend
from repro.core.trace import Trace, chunk_trace
from repro.runtime import obs

LINE_BYTES = 128
FLOPS_PER_CYCLE = 1.0e5          # ~100 TFLOP/s at 1 GHz
BYTES_PER_CYCLE = 2000.0         # ~2 TB/s at 1 GHz
_HASH = np.uint64(11400714819323198485)
ACT_BASE = 1 << 34                # activations live above weights


@dataclasses.dataclass
class TensorRef:
    name: str
    base: int          # byte address
    nbytes: int

    @property
    def n_lines(self) -> int:
        return max(1, self.nbytes // LINE_BYTES)


@dataclasses.dataclass(frozen=True)
class KernelStat:
    name: str
    op: str
    start: int
    cycles: int
    reads: int          # line reads issued (unsampled counts)
    writes: int
    flops: int


class StreamBuilder:
    """Bump allocator + op emitters producing a byte-address stream."""

    def __init__(self, sample: int = 1, seed: int = 0):
        self.sample = max(1, sample)
        self.t = 0
        self._weight_base = 0
        self._act_base = ACT_BASE
        self._free: list[TensorRef] = []
        self.times: list[np.ndarray] = []
        self.addrs: list[np.ndarray] = []
        self.writes: list[np.ndarray] = []
        self.kernels: list[KernelStat] = []

    # ---------------- allocation ----------------
    def alloc_weight(self, name: str, nbytes: int) -> TensorRef:
        nbytes = _round_line(nbytes)
        t = TensorRef(name, self._weight_base, nbytes)
        self._weight_base += nbytes
        if self._weight_base > ACT_BASE:
            raise ValueError(f"weights past {ACT_BASE} bytes would alias "
                             "the activations")
        return t

    def alloc(self, name: str, nbytes: int) -> TensorRef:
        nbytes = _round_line(nbytes)
        for i, f in enumerate(self._free):       # first-fit reuse
            if f.nbytes >= nbytes:
                self._free.pop(i)
                return TensorRef(name, f.base, nbytes)
        t = TensorRef(name, self._act_base, nbytes)
        self._act_base += nbytes
        return t

    def free(self, t: TensorRef) -> None:
        self._free.insert(0, TensorRef("free", t.base, t.nbytes))

    # ---------------- emission helpers ----------------
    def _keep(self, lines: np.ndarray) -> np.ndarray:
        if self.sample == 1:
            return lines
        h = (lines.astype(np.uint64) * _HASH) >> np.uint64(33)
        return lines[(h % np.uint64(self.sample)) == 0]

    def _emit(self, lines: np.ndarray, t0: int, t1: int, is_write: bool):
        lines = self._keep(np.asarray(lines, np.int64))
        n = len(lines)
        if n == 0:
            return
        ts = t0 + (np.arange(n, dtype=np.int64) * max(t1 - t0, 1)) // n
        self.times.append(ts)
        self.addrs.append(lines * LINE_BYTES)
        self.writes.append(np.full(n, is_write, bool))

    def _lines(self, t: TensorRef, start: int = 0, n: int | None = None):
        base = t.base // LINE_BYTES
        n = t.n_lines if n is None else n
        return base + np.arange(start, start + n, dtype=np.int64)

    def _record(self, name, op, start, cycles, reads, writes, flops):
        self.kernels.append(KernelStat(
            name=name, op=op, start=start, cycles=max(cycles, 1),
            reads=reads, writes=writes, flops=flops))
        self.t = start + max(cycles, 1)

    # ---------------- operators ----------------
    def gemm(self, name: str, a: TensorRef, bmat: TensorRef, c: TensorRef,
             M: int, N: int, K: int, dtype_bytes: int = 2,
             bm: int = 64, bn: int = 64):
        """Tiled GEMM: output tiles serialized; A row-panels and B
        col-panels re-read once per opposing tile (classic SIMD blocking)."""
        t0 = self.t
        flops = 2 * M * N * K
        a_panel = max(1, (bm * K * dtype_bytes) // LINE_BYTES)
        b_panel = max(1, (K * bn * dtype_bytes) // LINE_BYTES)
        c_tile = max(1, (bm * bn * dtype_bytes) // LINE_BYTES)
        m_t, n_t = math.ceil(M / bm), math.ceil(N / bn)
        total_reads = m_t * n_t * (a_panel + b_panel)
        total_writes = m_t * n_t * c_tile
        cycles = int(max(flops / FLOPS_PER_CYCLE,
                         (total_reads + total_writes)
                         * LINE_BYTES / BYTES_PER_CYCLE))
        tile_cyc = max(1, cycles // (m_t * n_t))
        t = t0
        for mt in range(m_t):
            for nt in range(n_t):
                self._emit(self._lines(a, mt * a_panel % a.n_lines,
                                       min(a_panel, a.n_lines)),
                           t, t + tile_cyc // 2, False)
                self._emit(self._lines(bmat, nt * b_panel % bmat.n_lines,
                                       min(b_panel, bmat.n_lines)),
                           t, t + tile_cyc // 2, False)
                self._emit(self._lines(c, (mt * n_t + nt) * c_tile
                                       % c.n_lines,
                                       min(c_tile, c.n_lines)),
                           t + tile_cyc - 1, t + tile_cyc, True)
                t += tile_cyc
        self._record(name, "gemm", t0, cycles, total_reads, total_writes,
                     flops)

    def elementwise(self, name: str, ins: list, out: TensorRef,
                    flops_per_elem: int = 1, dtype_bytes: int = 2):
        t0 = self.t
        n_elem = out.nbytes // dtype_bytes
        reads = sum(x.n_lines for x in ins)
        writes = out.n_lines
        cycles = int(max(n_elem * flops_per_elem / FLOPS_PER_CYCLE,
                         (reads + writes) * LINE_BYTES / BYTES_PER_CYCLE))
        for x in ins:
            self._emit(self._lines(x), t0, t0 + cycles, False)
        self._emit(self._lines(out), t0 + cycles // 2, t0 + cycles, True)
        self._record(name, "elementwise", t0, cycles, reads, writes,
                     n_elem * flops_per_elem)

    def normalization(self, name: str, x: TensorRef, out: TensorRef,
                      dtype_bytes: int = 2):
        """Two-pass mean/var + scale: reads x twice -> long-lived data
        (paper Fig 5: normalization exceeds GCRAM retention)."""
        t0 = self.t
        n_elem = x.nbytes // dtype_bytes
        reads, writes = 2 * x.n_lines, out.n_lines
        cycles = int(max(4 * n_elem / FLOPS_PER_CYCLE,
                         (reads + writes) * LINE_BYTES / BYTES_PER_CYCLE))
        self._emit(self._lines(x), t0, t0 + cycles // 2, False)
        self._emit(self._lines(x), t0 + cycles // 2, t0 + cycles, False)
        self._emit(self._lines(out), t0 + cycles // 2, t0 + cycles, True)
        self._record(name, "normalization", t0, cycles, reads, writes,
                     4 * n_elem)

    def softmax(self, name: str, x: TensorRef, dtype_bytes: int = 2):
        """In-place 3-pass softmax (max, exp-sum, scale)."""
        t0 = self.t
        n_elem = x.nbytes // dtype_bytes
        reads, writes = 3 * x.n_lines, x.n_lines
        cycles = int(max(5 * n_elem / FLOPS_PER_CYCLE,
                         (reads + writes) * LINE_BYTES / BYTES_PER_CYCLE))
        third = cycles // 3
        self._emit(self._lines(x), t0, t0 + third, False)
        self._emit(self._lines(x), t0 + third, t0 + 2 * third, False)
        self._emit(self._lines(x), t0 + 2 * third, t0 + cycles, False)
        self._emit(self._lines(x), t0 + 2 * third, t0 + cycles, True)
        self._record(name, "softmax", t0, cycles, reads, writes, 5 * n_elem)

    def transpose(self, name: str, x: TensorRef, out: TensorRef,
                  rows: int = 0, cols: int = 0):
        """Strided copy: reads linger across the whole op -> long lifetimes
        (paper Fig 5: transpose exceeds Si-GCRAM retention)."""
        t0 = self.t
        reads, writes = x.n_lines, out.n_lines
        cycles = int((reads + writes) * LINE_BYTES / BYTES_PER_CYCLE * 4)
        self._emit(self._lines(x), t0, t0 + cycles, False)
        # scattered writes: permute line order deterministically
        lines = self._lines(out)
        perm = np.argsort((lines * 2654435761) % (1 << 32), kind="stable")
        self._emit(lines[perm], t0, t0 + cycles, True)
        self._record(name, "transpose", t0, cycles, reads, writes, 0)

    def _row_lines(self, t: TensorRef, rows, row_bytes: int) -> np.ndarray:
        """Lines of rows ``rows`` of ``t`` (rows of ``row_bytes`` stored
        one after another), row by row in the order given."""
        rows = np.asarray(rows, np.int64)
        first = (t.base + rows * row_bytes) // LINE_BYTES
        last = (t.base + (rows + 1) * row_bytes - 1) // LINE_BYTES
        n = last - first + 1
        offs = np.arange(int(n.sum()), dtype=np.int64) \
            - np.repeat(np.cumsum(n) - n, n)
        return np.repeat(first, n) + offs

    def gather_rows(self, name: str, src: TensorRef, out: TensorRef,
                    rows, row_bytes: int):
        """Row gather (an MoE dispatch): row ``rows[i]`` of ``src`` is
        read and written as row ``i`` of ``out``."""
        t0 = self.t
        src_lines = self._row_lines(src, rows, row_bytes)
        out_lines = self._row_lines(out, np.arange(len(rows)), row_bytes)
        reads, writes = len(src_lines), len(out_lines)
        cycles = int((reads + writes) * LINE_BYTES / BYTES_PER_CYCLE)
        self._emit(src_lines, t0, t0 + cycles, False)
        self._emit(out_lines, t0 + cycles // 2, t0 + cycles, True)
        self._record(name, "gather", t0, cycles, reads, writes, 0)

    def scatter_add_rows(self, name: str, src: TensorRef, out: TensorRef,
                         rows, row_bytes: int, dtype_bytes: int = 2):
        """Row scatter-add (an MoE combine): row ``i`` of ``src`` is added
        into row ``rows[i]`` of ``out``, which is read and written back."""
        t0 = self.t
        src_lines = self._row_lines(src, np.arange(len(rows)), row_bytes)
        out_lines = self._row_lines(out, rows, row_bytes)
        reads = len(src_lines) + len(out_lines)
        writes = len(out_lines)
        n_elem = len(rows) * row_bytes // dtype_bytes
        cycles = int(max(n_elem / FLOPS_PER_CYCLE,
                         (reads + writes) * LINE_BYTES / BYTES_PER_CYCLE))
        self._emit(src_lines, t0, t0 + cycles, False)
        self._emit(out_lines, t0, t0 + cycles, False)
        self._emit(out_lines, t0 + cycles // 2, t0 + cycles, True)
        self._record(name, "scatter_add", t0, cycles, reads, writes, n_elem)

    # ---------------- trace assembly ----------------
    def finish(self):
        if not self.times:
            z = np.zeros(0, np.int64)
            return z, z, np.zeros(0, bool)
        t = np.concatenate(self.times)
        a = np.concatenate(self.addrs)
        w = np.concatenate(self.writes)
        order = np.argsort(t, kind="stable")
        return t[order], a[order], w[order]


def _round_line(nbytes: int) -> int:
    return max(LINE_BYTES,
               ((nbytes + LINE_BYTES - 1) // LINE_BYTES) * LINE_BYTES)


@register_backend("opstream")
class OpStreamBackend:
    """Registry adapter exposing the raw operator address stream.

    Workload: a callable op program ``fn(sb: StreamBuilder)`` or a filled
    builder.  The result is the line-granular DRAM-side stream *before*
    any cache model (every access "hits"), analyzed scratchpad-mode -
    useful for footprint/reuse studies; feed the same workload to the
    ``cachesim`` backend for hit/miss-annotated L1/L2 traces.
    """
    name = "opstream"
    mode = "scratchpad"

    def run(self, workload, *, sample: int = 1, seed: int = 0,
            clock_hz: float = 1.0e9,
            chunk_events: int | None = None) -> ProfileResult:
        if hasattr(workload, "finish"):
            sb = workload
        elif callable(workload):
            sb = StreamBuilder(sample=sample, seed=seed)
            workload(sb)
        else:
            raise TypeError("opstream workload must be a StreamBuilder or "
                            "a callable op program fn(sb)")
        t, a, w = sb.finish()
        trace = Trace(
            time_cycles=t, addr=a // LINE_BYTES, is_write=w,
            hit=np.ones(len(t), bool),
            subpartition=np.zeros(len(t), np.int32),
            clock_hz=clock_hz, block_bits=LINE_BYTES * 8,
            names=("stream",))
        kernels = [k.__dict__ for k in sb.kernels]
        if chunk_events:
            return ProfileResult(chunks=chunk_trace(trace, chunk_events),
                                 kernels=kernels, mode=self.mode)
        return ProfileResult(trace=trace, kernels=kernels, mode=self.mode)


# --------------------------------------------------------------------------
# Workload lowerings (paper Table 5 analogues, driven by framework configs)
# --------------------------------------------------------------------------

def _view(t: TensorRef, offset: int, nbytes: int) -> TensorRef:
    """Bytes ``[offset, offset + nbytes)`` of ``t`` as a tensor of its
    own (a column block of a fused projection's output)."""
    return TensorRef(t.name, t.base + offset, nbytes)


def _events_since(sb: StreamBuilder, first: int) -> int:
    """Events ``sb`` emitted since it held ``first`` emission blocks."""
    return sum(len(a) for a in sb.times[first:])


def _gqa_attention(sb: StreamBuilder, p: str, x: TensorRef, d_model: int,
                   n_heads: int, kv_heads: int, seq: int,
                   dtype_bytes: int) -> None:
    """Pre-norm grouped-query attention with its residual, in place on
    ``x``: fused QKV projection, transposed K, one head's scores GEMM
    into a scores buffer of an eighth of all heads' scores, in-place
    softmax, P x V, output projection."""
    hd = d_model // n_heads
    wqkv = sb.alloc_weight(p + "wqkv",
                           d_model * (d_model + 2 * kv_heads * hd)
                           * dtype_bytes)
    wo = sb.alloc_weight(p + "wo", d_model * d_model * dtype_bytes)
    xn = sb.alloc(p + "xn", x.nbytes)
    sb.normalization(p + "ln1", x, xn, dtype_bytes)
    qkv = sb.alloc(p + "qkv",
                   seq * (d_model + 2 * kv_heads * hd) * dtype_bytes)
    sb.gemm(p + "qkv_proj", xn, wqkv, qkv, seq,
            d_model + 2 * kv_heads * hd, d_model, dtype_bytes)
    sb.free(xn)
    # attention scores + value gemm
    scores = sb.alloc(p + "scores", n_heads * seq * seq * dtype_bytes // 8)
    kt = sb.alloc(p + "kT", seq * kv_heads * hd * dtype_bytes)
    sb.transpose(p + "k_transpose", qkv, kt)
    sb.gemm(p + "qk", qkv, kt, scores, seq, seq, hd, dtype_bytes)
    sb.softmax(p + "softmax", scores, dtype_bytes)
    attn = sb.alloc(p + "attn", seq * d_model * dtype_bytes)
    sb.gemm(p + "pv", scores, qkv, attn, seq, hd, seq, dtype_bytes)
    sb.free(scores)
    sb.free(kt)
    sb.free(qkv)
    proj = sb.alloc(p + "proj", seq * d_model * dtype_bytes)
    sb.gemm(p + "o_proj", attn, wo, proj, seq, d_model, d_model,
            dtype_bytes)
    sb.free(attn)
    sb.elementwise(p + "residual1", [x, proj], x, 1, dtype_bytes)
    sb.free(proj)


@obs.span("opstream.lower")
def transformer_ops(
    sb: StreamBuilder,
    d_model: int,
    n_heads: int,
    kv_heads: int,
    d_ff: int,
    seq: int,
    n_layers: int = 2,
    dtype_bytes: int = 2,
) -> None:
    """Lower a dense decoder block stack to the op stream (one fwd
    pass): grouped-query attention and an ungated two-GEMM MLP."""
    x = sb.alloc("x", seq * d_model * dtype_bytes)
    for li in range(n_layers):
        p = f"L{li}."
        _gqa_attention(sb, p, x, d_model, n_heads, kv_heads, seq,
                       dtype_bytes)
        w1 = sb.alloc_weight(p + "w1", d_model * d_ff * dtype_bytes)
        w2 = sb.alloc_weight(p + "w2", d_ff * d_model * dtype_bytes)
        xn = sb.alloc(p + "xn2", x.nbytes)
        sb.normalization(p + "ln2", x, xn, dtype_bytes)
        h = sb.alloc(p + "h", seq * d_ff * dtype_bytes)
        sb.gemm(p + "ffn_up", xn, w1, h, seq, d_ff, d_model, dtype_bytes)
        sb.elementwise(p + "ffn_act", [h], h, 4, dtype_bytes)
        y = sb.alloc(p + "y", seq * d_model * dtype_bytes)
        sb.gemm(p + "ffn_down", h, w2, y, seq, d_model, d_ff, dtype_bytes)
        sb.free(h)
        sb.elementwise(p + "residual2", [x, y], x, 1, dtype_bytes)
        sb.free(y)
        sb.free(xn)


def mla_attention(sb: StreamBuilder, p: str, x: TensorRef, *, seq: int,
                  d_model: int, n_heads: int, kv_lora_rank: int,
                  qk_nope_head_dim: int, qk_rope_head_dim: int,
                  v_head_dim: int, dtype_bytes: int = 2) -> None:
    """Pre-norm multi-head latent attention (DeepSeek-V2, no query
    LoRA) with its residual, in place on ``x``.

    ``q_proj`` gives every head's query (no-RoPE part, then RoPE part);
    ``kv_a`` gives the ``kv_lora_rank``-wide latent and the one RoPE key
    all heads share; the latent is normalized and ``kv_b`` projects it
    to every head's no-RoPE key and value.  RoPE runs in place on the
    queries' and the shared key's RoPE parts.  A fused projection's
    output is stored as column blocks, one after another.  Scores,
    softmax and P x V keep :func:`transformer_ops`'s conventions: a
    transposed copy of K (the no-RoPE keys, then the shared RoPE key),
    one head's scores GEMM over the query head dim into a scores buffer
    of an eighth of all heads' scores, in-place softmax, P x V of one
    head's value width."""
    dt = dtype_bytes
    H, dc = n_heads, kv_lora_rank
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    wq = sb.alloc_weight(p + "wq", d_model * H * (dn + dr) * dt)
    wkva = sb.alloc_weight(p + "wkv_a", d_model * (dc + dr) * dt)
    wkvb = sb.alloc_weight(p + "wkv_b", dc * H * (dn + dv) * dt)
    wo = sb.alloc_weight(p + "wo", H * dv * d_model * dt)
    xn = sb.alloc(p + "xn", x.nbytes)
    sb.normalization(p + "ln1", x, xn, dt)
    q = sb.alloc(p + "q", seq * H * (dn + dr) * dt)
    sb.gemm(p + "q_proj", xn, wq, q, seq, H * (dn + dr), d_model, dt)
    ckv = sb.alloc(p + "ckv", seq * (dc + dr) * dt)
    sb.gemm(p + "kv_a", xn, wkva, ckv, seq, dc + dr, d_model, dt)
    sb.free(xn)
    latent = _view(ckv, 0, seq * dc * dt)
    k_rope = _view(ckv, seq * dc * dt, seq * dr * dt)
    cn = sb.alloc(p + "c_kv", seq * dc * dt)
    sb.normalization(p + "kv_norm", latent, cn, dt)
    kv = sb.alloc(p + "kv", seq * H * (dn + dv) * dt)
    sb.gemm(p + "kv_b", cn, wkvb, kv, seq, H * (dn + dv), dc, dt)
    sb.free(cn)
    q_rope = _view(q, seq * H * dn * dt, seq * H * dr * dt)
    sb.elementwise(p + "rope_q", [q_rope], q_rope, 3, dt)
    sb.elementwise(p + "rope_k", [k_rope], k_rope, 3, dt)
    scores = sb.alloc(p + "scores", H * seq * seq * dt // 8)
    kt = sb.alloc(p + "kT", seq * (H * dn + dr) * dt)
    sb.transpose(p + "k_transpose", _view(kv, 0, seq * H * dn * dt),
                 _view(kt, 0, seq * H * dn * dt))
    sb.transpose(p + "k_rope_transpose", k_rope,
                 _view(kt, seq * H * dn * dt, seq * dr * dt))
    sb.gemm(p + "qk", q, kt, scores, seq, seq, dn + dr, dt)
    sb.softmax(p + "softmax", scores, dt)
    attn = sb.alloc(p + "attn", seq * H * dv * dt)
    sb.gemm(p + "pv", scores, _view(kv, seq * H * dn * dt, seq * H * dv * dt),
            attn, seq, dv, seq, dt)
    sb.free(scores)
    sb.free(kt)
    sb.free(kv)
    sb.free(ckv)
    sb.free(q)
    proj = sb.alloc(p + "proj", seq * d_model * dt)
    sb.gemm(p + "o_proj", attn, wo, proj, seq, d_model, H * dv, dt)
    sb.free(attn)
    sb.elementwise(p + "residual1", [x, proj], x, 1, dt)
    sb.free(proj)


def _mlp_weights(sb: StreamBuilder, p: str, d_model: int, d_ff: int,
                 dtype_bytes: int) -> tuple:
    return (sb.alloc_weight(p + "w_gate", d_model * d_ff * dtype_bytes),
            sb.alloc_weight(p + "w_up", d_model * d_ff * dtype_bytes),
            sb.alloc_weight(p + "w_down", d_ff * d_model * dtype_bytes))


def gated_mlp(sb: StreamBuilder, p: str, xin: TensorRef, weights: tuple,
              M: int, d_model: int, d_ff: int,
              dtype_bytes: int = 2) -> TensorRef:
    """SwiGLU MLP over the ``M`` rows of ``xin``: gate and up GEMMs,
    SiLU(gate) * up in place on the gate, down GEMM.  ``weights`` are
    ``(w_gate, w_up, w_down)``; returns the new ``[M, d_model]`` output,
    which the caller frees."""
    dt = dtype_bytes
    w_gate, w_up, w_down = weights
    g = sb.alloc(p + "gate", M * d_ff * dt)
    sb.gemm(p + "gate_proj", xin, w_gate, g, M, d_ff, d_model, dt)
    u = sb.alloc(p + "up", M * d_ff * dt)
    sb.gemm(p + "up_proj", xin, w_up, u, M, d_ff, d_model, dt)
    sb.elementwise(p + "silu_mul", [g, u], g, 5, dt)
    sb.free(u)
    y = sb.alloc(p + "y", M * d_model * dt)
    sb.gemm(p + "down_proj", g, w_down, y, M, d_model, d_ff, dt)
    sb.free(g)
    return y


def route_tokens(seq: int, d_model: int, n_experts: int, topk: int,
                 seed: int, layer: int, skew: float) -> np.ndarray:
    """``[seq, topk]`` experts each token goes to at MoE layer ``layer``:
    the greedy top-k (softmax scoring keeps the logits' order) of
    ``h @ W_r + skew * g`` in float64, with ``h ~ N(0,1)[seq, d_model]``,
    ``W_r ~ N(0,1)[d_model, n_experts] / sqrt(d_model)`` and a per-expert
    popularity ``g ~ N(0,1)[n_experts]``, drawn in that order from
    ``default_rng([seed, layer])``; ties go to the lower expert.  Fixed
    per (seed, layer), so every request lowers the same shapes; ``skew``
    stands for topic-skewed prompts (an assumption, not a measured
    router)."""
    rng = np.random.default_rng([int(seed), int(layer)])
    h = rng.standard_normal((seq, d_model))
    w = rng.standard_normal((d_model, n_experts)) / np.sqrt(d_model)
    g = rng.standard_normal(n_experts)
    logits = h @ w + skew * g
    return np.argsort(-logits, axis=1, kind="stable")[:, :topk]


def moe_ffn(sb: StreamBuilder, p: str, xn: TensorRef, choice: np.ndarray,
            *, d_model: int, n_experts: int, d_ff: int, n_shared: int = 0,
            dtype_bytes: int = 2) -> TensorRef:
    """The MoE FFN over the normalized tokens ``xn``, routed as
    ``choice`` (``[seq, topk]`` experts per token, :func:`route_tokens`):
    router GEMM and softmax; the ``n_shared`` shared experts as one
    gated MLP of width ``n_shared * d_ff`` over every token, whose output
    starts the layer's output (with none, the output is zero-filled);
    then for each routed expert, in order, that has tokens: gather its
    rows of ``xn``, a gated MLP of width ``d_ff`` over them, scatter-add
    its rows into the output.  An expert with no token issues no access.
    All ``n_experts`` experts' weights are held.  Returns the output,
    which the caller frees."""
    dt = dtype_bytes
    seq, topk = choice.shape
    tokens = np.bincount(choice.ravel(), minlength=n_experts)
    obs.count("moe_token_slots", seq * topk)
    obs.count("moe_max_expert_tokens", int(tokens.max()))
    obs.count("moe_experts_active", int((tokens > 0).sum()))
    wr = sb.alloc_weight(p + "w_router", d_model * n_experts * dt)
    shared = (_mlp_weights(sb, p + "shared.", d_model, n_shared * d_ff, dt)
              if n_shared else None)
    experts = [_mlp_weights(sb, f"{p}e{e}.", d_model, d_ff, dt)
               for e in range(n_experts)]
    logits = sb.alloc(p + "router", seq * n_experts * dt)
    sb.gemm(p + "route", xn, wr, logits, seq, n_experts, d_model, dt)
    sb.softmax(p + "route_softmax", logits, dt)
    sb.free(logits)
    if shared:
        y = gated_mlp(sb, p + "shared.", xn, shared, seq, d_model,
                      n_shared * d_ff, dt)
    else:
        y = sb.alloc(p + "moe_out", seq * d_model * dt)
        sb.elementwise(p + "moe_zero", [], y, 0, dt)
    for e in np.flatnonzero(tokens):
        rows = np.flatnonzero((choice == e).any(axis=1))
        xe = sb.alloc(f"{p}e{e}.x", len(rows) * d_model * dt)
        sb.gather_rows(f"{p}e{e}.gather", xn, xe, rows, d_model * dt)
        ye = gated_mlp(sb, f"{p}e{e}.", xe, experts[e], len(rows), d_model,
                       d_ff, dt)
        sb.free(xe)
        sb.scatter_add_rows(f"{p}e{e}.combine", ye, y, rows, d_model * dt,
                            dt)
        sb.free(ye)
    return y


@obs.span("opstream.lower")
def moe_decoder_ops(
    sb: StreamBuilder,
    d_model: int,
    n_heads: int,
    seq: int,
    n_layers: int,
    *,
    d_ff: int,
    moe_experts: int,
    moe_topk: int,
    moe_d_ff: int | None = None,
    moe_shared_experts: int = 0,
    moe_first_dense: int = 0,
    kv_heads: int | None = None,
    kv_lora_rank: int = 0,
    qk_nope_head_dim: int = 0,
    qk_rope_head_dim: int = 0,
    v_head_dim: int = 0,
    route_seed: int = 0,
    route_skew: float = 0.5,
    dtype_bytes: int = 2,
) -> None:
    """Lower a sparse-expert decoder block stack to the op stream (one
    fwd pass).  Attention is latent (:func:`mla_attention`) where
    ``kv_lora_rank`` is set, else grouped-query with ``kv_heads``
    (:func:`transformer_ops`'s).  The first ``moe_first_dense`` layers
    have a gated MLP of width ``d_ff``; every later one a
    :func:`moe_ffn` with experts of width ``moe_d_ff`` (default
    ``d_ff``).  Spans ``opstream.mla`` and ``opstream.moe`` hold each
    layer's attention and MoE half-block, with the events each emitted
    (``opstream_events``)."""
    dt = dtype_bytes
    x = sb.alloc("x", seq * d_model * dt)
    for li in range(n_layers):
        p = f"L{li}."
        if kv_lora_rank:
            with obs.span("opstream.mla", layer=li):
                first = len(sb.times)
                mla_attention(sb, p, x, seq=seq, d_model=d_model,
                              n_heads=n_heads, kv_lora_rank=kv_lora_rank,
                              qk_nope_head_dim=qk_nope_head_dim,
                              qk_rope_head_dim=qk_rope_head_dim,
                              v_head_dim=v_head_dim, dtype_bytes=dt)
                obs.count("opstream_events", _events_since(sb, first))
        else:
            _gqa_attention(sb, p, x, d_model, n_heads, kv_heads or n_heads,
                           seq, dt)
        if li < moe_first_dense:
            w = _mlp_weights(sb, p, d_model, d_ff, dt)
            xn = sb.alloc(p + "xn2", x.nbytes)
            sb.normalization(p + "ln2", x, xn, dt)
            y = gated_mlp(sb, p, xn, w, seq, d_model, d_ff, dt)
            sb.elementwise(p + "residual2", [x, y], x, 1, dt)
            sb.free(y)
            sb.free(xn)
            continue
        choice = route_tokens(seq, d_model, moe_experts, moe_topk,
                              route_seed, li, route_skew)
        active = int(np.unique(choice).size)
        with obs.span("opstream.moe", layer=li, experts_active=active):
            first = len(sb.times)
            xn = sb.alloc(p + "xn2", x.nbytes)
            sb.normalization(p + "ln2", x, xn, dt)
            y = moe_ffn(sb, p, xn, choice, d_model=d_model,
                        n_experts=moe_experts, d_ff=moe_d_ff or d_ff,
                        n_shared=moe_shared_experts, dtype_bytes=dt)
            sb.elementwise(p + "residual2", [x, y], x, 1, dt)
            sb.free(y)
            sb.free(xn)
            obs.count("opstream_events", _events_since(sb, first))


def resnet_ops(sb: StreamBuilder, blocks: list[tuple[int, int, int, int]],
               dtype_bytes: int = 2) -> None:
    """CNN stages as im2col GEMMs + residuals (resnet-18/50 style).

    blocks: (out_hw, out_c, in_c, k) per conv.
    """
    for i, (hw, oc, ic, k) in enumerate(blocks):
        M, N, K = hw * hw, oc, k * k * ic
        a = sb.alloc(f"c{i}.im2col", M * K * dtype_bytes)
        w = sb.alloc_weight(f"c{i}.w", K * N * dtype_bytes)
        y = sb.alloc(f"c{i}.y", M * N * dtype_bytes)
        sb.gemm(f"c{i}.conv", a, w, y, M, N, K, dtype_bytes)
        sb.free(a)
        out = sb.alloc(f"c{i}.bnrelu", y.nbytes)
        sb.normalization(f"c{i}.bn", y, out, dtype_bytes)
        sb.free(y)
        sb.free(out)


def polybench_conv_ops(sb: StreamBuilder, dim: int = 2,
                       n: int = 128, dtype_bytes: int = 4) -> None:
    """PolyBench 2D/3D convolution: one big stencil pass."""
    size = n ** dim * dtype_bytes
    a = sb.alloc("A", size)
    b = sb.alloc("B", size)
    # stencil = k reads of shifted A per output
    sb.elementwise("stencil", [a] * (3 ** dim), b, 3 ** dim, dtype_bytes)
    sb.free(a)
    sb.free(b)
