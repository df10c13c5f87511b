"""Sparse-expert and latent-attention lowerings of the op stream
(``repro.backends.opstream``): the weights each layer's GEMMs read at
published widths, seeded routing, row gather/scatter-add, the MoE
builders of the workload registry, spans and counters."""

import numpy as np
import pytest

from repro.backends import opstream
from repro.backends.opstream import (ACT_BASE, LINE_BYTES, StreamBuilder,
                                     moe_decoder_ops, route_tokens)
from repro.configs.base import get_config

SEQ = 128
# DeepSeek-V2-Lite, published widths (config.json)
V2_LITE = dict(d_ff=10944, moe_experts=64, moe_topk=6, moe_d_ff=1408,
               moe_shared_experts=2, moe_first_dense=1, kv_lora_rank=512,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)


def _gemms(monkeypatch):
    """Record every GEMM as (name, M, N, K, B operand)."""
    calls = []
    real = StreamBuilder.gemm

    def gemm(self, name, a, bmat, c, M, N, K, dtype_bytes=2, **kw):
        calls.append((name, M, N, K, bmat))
        return real(self, name, a, bmat, c, M, N, K, dtype_bytes, **kw)
    monkeypatch.setattr(StreamBuilder, "gemm", gemm)
    return calls


def _weight_bytes(calls, layer):
    """GEMM weight bytes read at ``layer``, by part of the block."""
    out = {"attention": 0, "dense_mlp": 0, "shared": 0, "router": 0}
    experts = {}
    for name, M, N, K, b in calls:
        lay, _, op = name.partition(".")
        if lay != f"L{layer}" or b.base >= ACT_BASE:     # scores, P x V
            continue
        assert b.nbytes == -(-N * K * 2 // LINE_BYTES) * LINE_BYTES
        if op in ("q_proj", "kv_a", "kv_b", "o_proj"):
            out["attention"] += b.nbytes
        elif op in ("gate_proj", "up_proj", "down_proj"):
            out["dense_mlp"] += b.nbytes
        elif op.startswith("shared."):
            out["shared"] += b.nbytes
        elif op == "route":
            out["router"] += b.nbytes
        else:
            e = int(op.split(".")[0][1:])
            experts[e] = experts.get(e, 0) + b.nbytes
    return out, experts


def test_weight_bytes_per_layer_at_published_widths(monkeypatch):
    calls = _gemms(monkeypatch)
    sb = StreamBuilder(sample=1 << 40)        # kernel counters unsampled
    moe_decoder_ops(sb, 2048, 16, SEQ, 3, **V2_LITE)
    dense, none = _weight_bytes(calls, 0)
    assert dense == {"attention": 27_525_120, "dense_mlp": 134_479_872,
                     "shared": 0, "router": 0} and none == {}
    for layer in (1, 2):
        parts, experts = _weight_bytes(calls, layer)
        assert parts == {"attention": 27_525_120, "dense_mlp": 0,
                         "shared": 34_603_008, "router": 262_144}
        tokens = np.bincount(route_tokens(SEQ, 2048, 64, 6, 0, layer,
                                          0.5).ravel(), minlength=64)
        assert sorted(experts) == list(np.flatnonzero(tokens))
        assert set(experts.values()) == {17_301_504}
        # each active expert's GEMMs run at M = its token count
        for name, M, _, _, _ in calls:
            if name.startswith(f"L{layer}.e"):
                assert M == tokens[int(name.split(".")[1][1:])]
    # kernel counters agree: 2 M N K flops per GEMM
    gemms = [k for k in sb.kernels if k.op == "gemm"]
    assert [k.flops for k in gemms] == [2 * M * N * K
                                        for _, M, N, K, _ in calls]


def test_param_count_of_deepseek_v2_lite():
    assert get_config("deepseek_v2_lite").param_count() == 15_706_484_224


def test_routing_is_seeded_and_skewed():
    a = route_tokens(SEQ, 2048, 64, 6, 0, 1, 0.5)
    assert np.array_equal(a, route_tokens(SEQ, 2048, 64, 6, 0, 1, 0.5))
    assert not np.array_equal(a, route_tokens(SEQ, 2048, 64, 6, 0, 2, 0.5))
    assert all(len(set(row)) == 6 for row in a.tolist())
    flat = np.bincount(route_tokens(SEQ, 2048, 64, 6, 0, 1, 0.0).ravel(),
                       minlength=64)
    skewed = np.bincount(a.ravel(), minlength=64)
    assert skewed.sum() == flat.sum() == SEQ * 6
    assert skewed.max() > flat.max()


def test_row_gather_and_scatter_add_lines():
    sb = StreamBuilder()
    src = sb.alloc("src", 6 * 192)            # rows of 192 B straddle lines
    out = sb.alloc("out", 2 * 192)
    base = src.base // LINE_BYTES
    sb.gather_rows("g", src, out, [4, 1], 192)
    t, a, w = sb.finish()
    reads = a[~w] // LINE_BYTES - base
    # row 4 = bytes 768..959 -> lines 6, 7; row 1 = 192..383 -> lines 1, 2
    assert reads.tolist() == [6, 7, 1, 2]
    assert (a[w] // LINE_BYTES - out.base // LINE_BYTES).tolist() == \
        [0, 1, 1, 2]
    sb = StreamBuilder()
    src = sb.alloc("src", 2 * 256)
    out = sb.alloc("out", 4 * 256)
    sb.scatter_add_rows("s", src, out, [3, 0], 256)
    k = sb.kernels[0]
    assert (k.op, k.reads, k.writes, k.flops) == ("scatter_add", 8, 4, 256)
    t, a, w = sb.finish()
    ob = out.base // LINE_BYTES
    assert sorted((a[w] // LINE_BYTES - ob).tolist()) == [0, 1, 6, 7]
    # every written line was read before (or as) it is written
    for line in set(a[w].tolist()):
        assert t[(a == line) & ~w].min() <= t[(a == line) & w].min()


def test_spans_and_counters():
    from repro.runtime import obs
    cfg = get_config("deepseek_v2_lite", smoke=True)
    before = {s["id"] for s in obs.snapshot()["spans"]}
    sb = StreamBuilder()
    moe_decoder_ops(sb, cfg.d_model, cfg.n_heads, 16, 3, d_ff=cfg.d_ff,
                    moe_experts=cfg.moe_experts, moe_topk=cfg.moe_topk,
                    moe_d_ff=cfg.moe_d_ff,
                    moe_shared_experts=cfg.moe_shared_experts,
                    moe_first_dense=0, kv_lora_rank=cfg.kv_lora_rank,
                    qk_nope_head_dim=cfg.qk_nope_head_dim,
                    qk_rope_head_dim=cfg.qk_rope_head_dim,
                    v_head_dim=cfg.v_head_dim)
    t, _, _ = sb.finish()
    new = [s for s in obs.snapshot()["spans"] if s["id"] not in before]
    mla = [s for s in new if s["name"] == "opstream.mla"]
    moe = [s for s in new if s["name"] == "opstream.moe"]
    assert [s["attrs"]["layer"] for s in mla] == [0, 1, 2]
    assert [s["attrs"]["layer"] for s in moe] == [0, 1, 2]
    for s in moe:
        choice = route_tokens(16, cfg.d_model, cfg.moe_experts,
                              cfg.moe_topk, 0, s["attrs"]["layer"], 0.5)
        n = np.bincount(choice.ravel(), minlength=cfg.moe_experts)
        assert s["attrs"]["experts_active"] == int((n > 0).sum())
        assert s["counts"]["moe_experts_active"] == int((n > 0).sum())
        assert s["counts"]["moe_max_expert_tokens"] == int(n.max())
        assert s["counts"]["moe_token_slots"] == 16 * cfg.moe_topk
    # with no dense layer, the two spans hold every event emitted
    assert sum(s["counts"]["opstream_events"] for s in mla + moe) == len(t)


def _program_kernels(name, monkeypatch, **params):
    from repro.workloads import get_workload
    calls = _gemms(monkeypatch)
    spec = get_workload(name)
    program, _ = spec.with_params(**params).build("cachesim")
    sb = StreamBuilder(sample=1 << 40)
    program(sb)
    return {c[0]: c[1:4] for c in calls}, spec


def test_deepseek_moe_16b_builder(monkeypatch):
    g, spec = _program_kernels("deepseek_moe_16b", monkeypatch)
    assert spec.version == 2
    # the leading dense layer: its own 10944-wide gated MLP
    assert g["L0.gate_proj"] == (SEQ, 10944, 2048)
    assert g["L0.down_proj"] == (SEQ, 2048, 10944)
    # then 2 shared experts as one 2816-wide MLP and 1408-wide experts
    assert g["L1.shared.up_proj"] == (SEQ, 2816, 2048)
    experts = {k.split(".")[1] for k in g if k.startswith("L1.e")}
    tokens = np.bincount(route_tokens(SEQ, 2048, 64, 6, 0, 1, 0.5).ravel(),
                         minlength=64)
    assert experts == {f"e{e}" for e in np.flatnonzero(tokens)}
    assert all(g[f"L1.{e}.gate_proj"][1:] == (1408, 2048) for e in experts)
    assert sum(g[f"L1.{e}.gate_proj"][0] for e in experts) == SEQ * 6


def test_phi_moe_builders(monkeypatch):
    g, _ = _program_kernels("phi3_5_moe", monkeypatch)
    assert not any(".shared." in k for k in g)
    assert "L0.route" in g and "L1.route" in g      # no dense layer
    gates = [v for k, v in g.items()
             if k.startswith("L0.e") and k.endswith("gate_proj")]
    assert gates and all(v[1:] == (6400, 4096) for v in gates)
    assert sum(v[0] for v in gates) == SEQ * 2
    g, spec = _program_kernels("phi-moe-sample", monkeypatch)
    assert spec.version == 2
    ups = [v for k, v in g.items() if k.endswith(".up_proj")]
    assert ups and all(v[1:] == (4096, 1024) for v in ups)
    assert sum(v[0] for v in ups) == 64 * 2


def test_weights_may_not_alias_activations():
    sb = StreamBuilder()
    sb.alloc_weight("w", ACT_BASE)
    with pytest.raises(ValueError, match="alias"):
        sb.alloc_weight("w2", 1)


def test_no_sampled_expert_branch_left():
    """The dense lowering takes no expert arguments: sparse experts go
    through moe_decoder_ops."""
    with pytest.raises(TypeError):
        opstream.transformer_ops(StreamBuilder(), 64, 2, 2, 128, 8,
                                 moe_experts=4, moe_topk=2)


def test_deepseek_v2_lite_workload(monkeypatch):
    from repro.configs.base import ARCH_IDS
    g, spec = _program_kernels("deepseek-v2-lite", monkeypatch)
    assert spec.backends == ("cachesim", "opstream")
    assert "deepseek_v2_lite" not in ARCH_IDS          # no JAX model
    assert g["L0.q_proj"] == (SEQ, 16 * 192, 2048)
    assert g["L0.kv_a"] == (SEQ, 576, 2048)
    assert g["L0.kv_b"] == (SEQ, 16 * 256, 512)
    assert g["L0.qk"] == (SEQ, SEQ, 192)
    assert g["L0.gate_proj"] == (SEQ, 10944, 2048)
    assert g["L1.shared.gate_proj"] == (SEQ, 2816, 2048)
