"""Plain reference of backend ``systolic``: the GEMM list of a decoder
block stack on a weight-stationary systolic array with three
scratchpads.

NumPy and the standard library only; nothing of the program under test
is imported.  Each function states the semantics it holds the program
to:

* :func:`decoder_gemms` - the GEMM list of a decoder block stack; like
  the program's, its MLP is two GEMMs (up, down) with no gate.
* :func:`systolic_trace` - a weight-stationary systolic array with
  direct-mapped ifmap / filter / ofmap scratchpads.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.manifest import decoder


def reference_trace(config, key):
    """The configuration's trace relabelled by ``key``, as ``{"trace":
    (time, slot, is_write, hit, subpartition), "mode", "clock_hz",
    "block_bits"}``; every scratchpad access is a hit."""
    run, d = config["run"], decoder(config)
    s = run["systolic"]
    gemms = decoder_gemms(d["d_model"], d["n_heads"], d["kv_heads"],
                          d["d_ff"], run["tokens"], d["n_layers"])
    t, slot, w, sub = systolic_trace(
        gemms, rows=s["rows"], cols=s["cols"], ifmap_kb=s["ifmap_kb"],
        filter_kb=s["filter_kb"], ofmap_kb=s["ofmap_kb"],
        word_bytes=s["word_bytes"], drain_latency=s["drain_latency"],
        dataflow=s["dataflow"])
    trace = (t, slot ^ np.int64(key << run["relabel_shift"]), w,
             np.ones(len(t), bool), sub)
    return {"trace": trace, "mode": "scratchpad", "clock_hz": s["clock_hz"],
            "block_bits": s["rows"] * s["word_bytes"] * 8}


# ---------------------------------------------------------------------------
# weight-stationary systolic array with three scratchpads
# ---------------------------------------------------------------------------

def decoder_gemms(d_model, n_heads, kv_heads, d_ff, seq, n_layers):
    """``(M, N, K)`` of every GEMM of a dense decoder block stack:
    fused QKV projection, scores, probabilities x values, output
    projection, MLP up and down."""
    hd = d_model // n_heads
    qkv_w = d_model + 2 * kv_heads * hd
    return [g for _ in range(n_layers) for g in (
        (seq, qkv_w, d_model), (seq, seq, hd), (seq, hd, seq),
        (seq, d_model, d_model), (seq, d_ff, d_model),
        (seq, d_model, d_ff))]


def systolic_trace(gemms, *, rows, cols, ifmap_kb, filter_kb, ofmap_kb,
                   word_bytes, drain_latency, dataflow="ws"):
    """Scratchpad trace of a GEMM list on a weight-stationary array, as
    ``(time, slot, is_write, subpartition)`` sorted by time (stable on
    emission order).  Buffers are direct-mapped over groups (one group
    = the words feeding one array edge in one cycle): a read of a group
    that is not resident first writes it (a fetch), block-prefetched a
    tile ahead for the stationary filter, half a buffer ahead for the
    streamed ifmap.  ofmap partials are written when drained, read back
    by the next K tile, and read out ``drain_latency`` cycles after the
    last one."""
    if dataflow != "ws":
        raise ValueError("the reference models the ws dataflow only")
    caps = [max(4, kb * 1024 // (width * word_bytes)) for kb, width in
            ((ifmap_kb, rows), (filter_kb, cols), (ofmap_kb, cols))]
    occupant = [np.full(c, -1, np.int64) for c in caps]
    out_t, out_a, out_w, out_s = [], [], [], []

    def emit(times, slots, is_write, sub):
        times = np.asarray(times, np.int64)
        out_t.append(times)
        out_a.append(np.asarray(slots, np.int64))
        out_w.append(np.full(times.shape, is_write, bool))
        out_s.append(np.full(times.shape, sub, np.int32))

    def read(sub, ids, times, prefetch=None):
        slots = ids % caps[sub]
        need = occupant[sub][slots] != ids
        if need.any():
            if prefetch is not None:
                wt = prefetch + np.arange(int(need.sum()), dtype=np.int64)
            else:
                wt = np.maximum(times[need] - max(1, caps[sub] // 2), 0)
            emit(wt, slots[need], True, sub)
            occupant[sub][slots[need]] = ids[need]
        emit(times, slots, False, sub)

    t = 0
    base = [0, 0, 0]
    for M, N, K in gemms:
        t0 = t
        n_t, k_t = math.ceil(N / cols), math.ceil(K / rows)
        for nt in range(n_t):
            for kt in range(k_t):
                dur = rows + M + cols
                read(1, base[1] + (nt * k_t + kt) * rows + np.arange(rows),
                     t + np.arange(rows),
                     prefetch=max(t - dur, t0 - rows))
                read(0, base[0] + kt * M + np.arange(M),
                     t + rows + np.arange(M))
                oids = base[2] + nt * M + np.arange(M)
                oslots = oids % caps[2]
                drain = t + rows + np.arange(M) + cols
                if kt > 0:
                    emit(t + rows + np.arange(M), oslots, False, 2)
                emit(drain, oslots, True, 2)
                occupant[2][oslots] = oids
                if kt == k_t - 1:
                    emit(drain + drain_latency, oslots, False, 2)
                t += dur
        base[0] += K * M + rows * 16
        base[1] += K * N + cols * 16
        base[2] += M * N + cols * 16
    t_all = np.concatenate(out_t)
    order = np.argsort(t_all, kind="stable")
    return (t_all[order], np.concatenate(out_a)[order],
            np.concatenate(out_w)[order], np.concatenate(out_s)[order])
