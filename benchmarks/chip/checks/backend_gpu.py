"""Plain reference of backend ``gpu``: the op-stream lowering of a
decoder block stack and the L1/L2 cache hierarchy it is replayed
through.

NumPy and the standard library only; nothing of the program under test
is imported.  Each function states the semantics it holds the program
to:

* :func:`lower_decoder_stream` - the byte-address stream of a decoder
  block stack as the op-stream lowering defines it (tiled GEMMs,
  two-pass normalization, three-pass in-place softmax, strided
  transpose, residual adds; per-line hashed sampling).  A frozen copy
  of that definition: a program change that alters the stream is a
  different workload, not a faster one.  Like the program's lowering,
  its MLP is two GEMMs (up, down) with no gate and its QKV projection
  has no bias.
* :func:`simulate_hierarchy` - a two-level write-back, write-allocate,
  LRU cache hierarchy replayed one access at a time in plain Python,
  with the L2 stream made of L1 fills (reads) and dirty evictions
  (writes) ``l2_latency`` cycles later.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.manifest import decoder

LINE_BYTES = 128
_FLOPS_PER_CYCLE = 1.0e5
_BYTES_PER_CYCLE = 2000.0
_HASH = np.uint64(11400714819323198485)


def reference_trace(config, key):
    """The configuration's trace relabelled by ``key``, as ``{"trace":
    (time, line, is_write, hit, subpartition), "mode", "clock_hz",
    "block_bits"}``."""
    run, d = config["run"], decoder(config)
    h = run["hierarchy"]
    t, a, w = lower_decoder_stream(
        d["d_model"], d["n_heads"], d["kv_heads"], d["d_ff"],
        run["tokens"], d["n_layers"], run["line_sample"])
    trace = simulate_hierarchy(
        t, a ^ np.int64(key << run["relabel_shift"]), w, l1=h["l1"],
        l2=h["l2"], l2_latency=h["l2_latency"],
        write_allocate=h["write_allocate"])
    return {"trace": trace, "mode": "cache", "clock_hz": h["clock_hz"],
            "block_bits": h["l1"]["line_bytes"] * 8}


# ---------------------------------------------------------------------------
# op-stream lowering of a decoder block stack
# ---------------------------------------------------------------------------

def _round_line(nbytes):
    return max(LINE_BYTES, -(-nbytes // LINE_BYTES) * LINE_BYTES)


class _Tensor:
    __slots__ = ("base", "nbytes")

    def __init__(self, base, nbytes):
        self.base, self.nbytes = base, nbytes

    @property
    def n_lines(self):
        return max(1, self.nbytes // LINE_BYTES)


class _Stream:
    """Bump allocator with first-fit reuse of freed activations, and the
    per-op access emitters."""

    def __init__(self, sample):
        self.sample = max(1, sample)
        self.t = 0
        self.weight_base = 0
        self.act_base = 1 << 34
        self.free_list = []
        self.times, self.addrs, self.writes = [], [], []

    def weight(self, nbytes):
        nbytes = _round_line(nbytes)
        out = _Tensor(self.weight_base, nbytes)
        self.weight_base += nbytes
        return out

    def alloc(self, nbytes):
        nbytes = _round_line(nbytes)
        for i, f in enumerate(self.free_list):
            if f.nbytes >= nbytes:
                self.free_list.pop(i)
                return _Tensor(f.base, nbytes)
        out = _Tensor(self.act_base, nbytes)
        self.act_base += nbytes
        return out

    def release(self, x):
        self.free_list.insert(0, _Tensor(x.base, x.nbytes))

    def lines(self, x, start=0, n=None):
        n = x.n_lines if n is None else n
        return x.base // LINE_BYTES + np.arange(start, start + n,
                                                dtype=np.int64)

    def emit(self, lines, t0, t1, is_write):
        lines = np.asarray(lines, np.int64)
        if self.sample > 1:
            h = (lines.astype(np.uint64) * _HASH) >> np.uint64(33)
            lines = lines[(h % np.uint64(self.sample)) == 0]
        n = len(lines)
        if n == 0:
            return
        self.times.append(t0 + (np.arange(n, dtype=np.int64)
                                * max(t1 - t0, 1)) // n)
        self.addrs.append(lines * LINE_BYTES)
        self.writes.append(np.full(n, is_write, bool))

    def done(self, start, cycles):
        self.t = start + max(cycles, 1)

    def gemm(self, a, b, c, M, N, K, dt, bm=64, bn=64):
        t0 = self.t
        a_panel = max(1, (bm * K * dt) // LINE_BYTES)
        b_panel = max(1, (K * bn * dt) // LINE_BYTES)
        c_tile = max(1, (bm * bn * dt) // LINE_BYTES)
        m_t, n_t = math.ceil(M / bm), math.ceil(N / bn)
        reads = m_t * n_t * (a_panel + b_panel)
        writes = m_t * n_t * c_tile
        cycles = int(max(2 * M * N * K / _FLOPS_PER_CYCLE,
                         (reads + writes) * LINE_BYTES / _BYTES_PER_CYCLE))
        tile = max(1, cycles // (m_t * n_t))
        t = t0
        for mt in range(m_t):
            for nt in range(n_t):
                self.emit(self.lines(a, mt * a_panel % a.n_lines,
                                     min(a_panel, a.n_lines)),
                          t, t + tile // 2, False)
                self.emit(self.lines(b, nt * b_panel % b.n_lines,
                                     min(b_panel, b.n_lines)),
                          t, t + tile // 2, False)
                self.emit(self.lines(c, (mt * n_t + nt) * c_tile % c.n_lines,
                                     min(c_tile, c.n_lines)),
                          t + tile - 1, t + tile, True)
                t += tile
        self.done(t0, cycles)

    def elementwise(self, ins, out, flops_per_elem, dt):
        t0 = self.t
        reads = sum(x.n_lines for x in ins)
        cycles = int(max(out.nbytes // dt * flops_per_elem
                         / _FLOPS_PER_CYCLE,
                         (reads + out.n_lines) * LINE_BYTES
                         / _BYTES_PER_CYCLE))
        for x in ins:
            self.emit(self.lines(x), t0, t0 + cycles, False)
        self.emit(self.lines(out), t0 + cycles // 2, t0 + cycles, True)
        self.done(t0, cycles)

    def normalization(self, x, out, dt):
        t0 = self.t
        cycles = int(max(4 * (x.nbytes // dt) / _FLOPS_PER_CYCLE,
                         (2 * x.n_lines + out.n_lines) * LINE_BYTES
                         / _BYTES_PER_CYCLE))
        self.emit(self.lines(x), t0, t0 + cycles // 2, False)
        self.emit(self.lines(x), t0 + cycles // 2, t0 + cycles, False)
        self.emit(self.lines(out), t0 + cycles // 2, t0 + cycles, True)
        self.done(t0, cycles)

    def softmax(self, x, dt):
        t0 = self.t
        cycles = int(max(5 * (x.nbytes // dt) / _FLOPS_PER_CYCLE,
                         4 * x.n_lines * LINE_BYTES / _BYTES_PER_CYCLE))
        third = cycles // 3
        self.emit(self.lines(x), t0, t0 + third, False)
        self.emit(self.lines(x), t0 + third, t0 + 2 * third, False)
        self.emit(self.lines(x), t0 + 2 * third, t0 + cycles, False)
        self.emit(self.lines(x), t0 + 2 * third, t0 + cycles, True)
        self.done(t0, cycles)

    def transpose(self, x, out):
        t0 = self.t
        cycles = int((x.n_lines + out.n_lines) * LINE_BYTES
                     / _BYTES_PER_CYCLE * 4)
        self.emit(self.lines(x), t0, t0 + cycles, False)
        lines = self.lines(out)
        perm = np.argsort((lines * 2654435761) % (1 << 32), kind="stable")
        self.emit(lines[perm], t0, t0 + cycles, True)
        self.done(t0, cycles)

    def finish(self):
        t = np.concatenate(self.times)
        a = np.concatenate(self.addrs)
        w = np.concatenate(self.writes)
        order = np.argsort(t, kind="stable")
        return t[order], a[order], w[order]


def lower_decoder_stream(d_model, n_heads, kv_heads, d_ff, seq, n_layers,
                         sample, dtype_bytes=2):
    """``(time_cycles, byte_addr, is_write)`` of one forward pass of a
    dense decoder block stack (pre-norm attention + MLP)."""
    dt = dtype_bytes
    hd = d_model // n_heads
    qkv_w = d_model + 2 * kv_heads * hd
    s = _Stream(sample)
    x = s.alloc(seq * d_model * dt)
    for _ in range(n_layers):
        wqkv = s.weight(d_model * qkv_w * dt)
        wo = s.weight(d_model * d_model * dt)
        w1 = s.weight(d_model * d_ff * dt)
        w2 = s.weight(d_ff * d_model * dt)
        xn = s.alloc(x.nbytes)
        s.normalization(x, xn, dt)
        qkv = s.alloc(seq * qkv_w * dt)
        s.gemm(xn, wqkv, qkv, seq, qkv_w, d_model, dt)
        s.release(xn)
        scores = s.alloc(n_heads * seq * seq * dt // 8)
        kt = s.alloc(seq * kv_heads * hd * dt)
        s.transpose(qkv, kt)
        s.gemm(qkv, kt, scores, seq, seq, hd, dt)
        s.softmax(scores, dt)
        attn = s.alloc(seq * d_model * dt)
        s.gemm(scores, qkv, attn, seq, hd, seq, dt)
        s.release(scores)
        s.release(kt)
        s.release(qkv)
        proj = s.alloc(seq * d_model * dt)
        s.gemm(attn, wo, proj, seq, d_model, d_model, dt)
        s.release(attn)
        s.elementwise([x, proj], x, 1, dt)
        s.release(proj)
        xn = s.alloc(x.nbytes)
        s.normalization(x, xn, dt)
        h = s.alloc(seq * d_ff * dt)
        s.gemm(xn, w1, h, seq, d_ff, d_model, dt)
        s.elementwise([h], h, 4, dt)
        y = s.alloc(seq * d_model * dt)
        s.gemm(h, w2, y, seq, d_model, d_ff, dt)
        s.release(h)
        s.elementwise([x, y], x, 1, dt)
        s.release(y)
        s.release(xn)
    return s.finish()


# ---------------------------------------------------------------------------
# two-level write-back cache hierarchy
# ---------------------------------------------------------------------------

def cache_level(lines, writes, n_sets, ways):
    """One write-allocate LRU level, one access at a time.  Each set is a
    dict from line to dirty bit whose insertion order is the recency
    order (least recent first).  Returns per-access ``(hit, fill,
    evicted_line (-1 if none), evicted_dirty)``."""
    sets = [dict() for _ in range(n_sets)]
    hit, ev_at, ev_line, ev_dirty = [], [], [], []
    for i, (a, w) in enumerate(zip(lines.tolist(), writes.tolist())):
        s = sets[a % n_sets]
        d = s.pop(a, None)
        if d is not None:
            s[a] = d or w
            hit.append(True)
            continue
        hit.append(False)
        if len(s) == ways:
            victim = next(iter(s))
            ev_at.append(i)
            ev_line.append(victim)
            ev_dirty.append(s.pop(victim))
        s[a] = w
    n = len(hit)
    hit = np.array(hit, bool)
    evicted = np.full(n, -1, np.int64)
    dirty = np.zeros(n, bool)
    evicted[ev_at] = ev_line
    dirty[ev_at] = ev_dirty
    return hit, ~hit, evicted, dirty


def simulate_hierarchy(time_cycles, byte_addr, is_write, *, l1, l2,
                       l2_latency, write_allocate=True):
    """L1 -> L2 replay of a byte-address stream.  ``l1``/``l2`` are
    ``{"size_kb", "ways", "line_bytes"}``.  Returns the merged trace as
    ``(time, line, is_write, hit, subpartition)`` in time order (L1
    first on equal times), lines at L1 line granularity."""
    if not write_allocate:
        raise ValueError("the reference models write-allocate caches only")
    line_bytes = l1["line_bytes"]

    def n_sets(c):
        return max(1, c["size_kb"] * 1024 // (c["line_bytes"] * c["ways"]))

    t = np.asarray(time_cycles, np.int64)
    lines = np.asarray(byte_addr, np.int64) // line_bytes
    w = np.asarray(is_write, bool)
    hit1, fill1, ev, ev_dirty = cache_level(lines, w, n_sets(l1), l1["ways"])
    wb = ev_dirty & (ev >= 0)
    l2_t = np.concatenate([t[fill1], t[wb]]) + l2_latency
    l2_a = np.concatenate([lines[fill1], ev[wb]])
    l2_w = np.concatenate([np.zeros(int(fill1.sum()), bool),
                           np.ones(int(wb.sum()), bool)])
    order = np.argsort(l2_t, kind="stable")
    l2_t, l2_a, l2_w = l2_t[order], l2_a[order], l2_w[order]
    hit2 = cache_level(l2_a, l2_w, n_sets(l2), l2["ways"])[0]
    times = np.concatenate([t, l2_t])
    order = np.argsort(times, kind="stable")
    return (times[order], np.concatenate([lines, l2_a])[order],
            np.concatenate([w, l2_w])[order],
            np.concatenate([hit1, hit2])[order],
            np.concatenate([np.zeros(len(t), np.int32),
                            np.ones(len(l2_t), np.int32)])[order])
