"""Backend ``gpu_mla_moe`` at small sizes on the CPU: the program's
stream, routing and simulated trace equal the plain reference's; a whole
run of the tiny cell is correct, and with one routed token moved to
another expert it is not; ``moe_lower_s`` reads the program's
``opstream.moe`` spans."""

import sys
import types

import numpy as np
import pytest

from bench_cells import tiny_cell
from chipbench import cell as cellmod
from chipbench import manifest, program
from chipbench.cell import Context
from chipbench.manifest import load_module

CONFIG = "tiny.gpu_mla_moe"


def _ref():
    return load_module("checks", "backend_gpu_mla_moe")


def test_stream_and_trace_equal_the_reference():
    config = tiny_cell(CONFIG, "answer").config
    d, run = manifest.decoder(config), config["run"]
    backend = program.load_backend(config)
    # the raw stream, before any cache
    from repro.backends.opstream import StreamBuilder, moe_decoder_ops
    sb = StreamBuilder(sample=run["line_sample"])
    moe_decoder_ops(
        sb, d["d_model"], d["n_heads"], run["tokens"], d["n_layers"],
        d_ff=d["d_ff"], moe_experts=d["n_experts"], moe_topk=d["topk"],
        moe_d_ff=d["moe_d_ff"], moe_shared_experts=d["n_shared"],
        moe_first_dense=d["first_dense"], kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        route_seed=run["routing"]["seed"], route_skew=run["routing"]["skew"])
    got = sb.finish()
    ref = _ref().lower_mla_moe_stream(d, run["tokens"], run["line_sample"],
                                      run["routing"]["seed"],
                                      run["routing"]["skew"])
    assert len(got[0]) == len(ref[0]) > 10_000
    for g, r in zip(got, ref):
        assert np.array_equal(g, r)
    # rows of 96 * 2 bytes straddle cache lines
    assert (d["d_model"] * 2) % 128
    # the simulated L1/L2 trace, relabelled, event for event
    key = 2**21 + 3
    tr = backend.session(key, program.Spans()).trace
    got = tuple(np.asarray(x) for x in (tr.time_cycles, tr.addr,
                                        tr.is_write, tr.hit,
                                        tr.subpartition))
    ref = _ref().reference_trace(config, key)["trace"]
    assert len(got[0]) == len(ref[0])
    for g, r in zip(got, ref):
        assert np.array_equal(g, r)


@pytest.mark.parametrize("seq,d_model,n_experts,topk,seed,layer,skew", [
    (16, 96, 12, 2, 0, 1, 0.5), (16, 96, 12, 2, 0, 2, 0.5),
    (128, 2048, 64, 6, 0, 1, 0.5), (128, 2048, 64, 6, 0, 4, 0.5),
    (40, 64, 16, 4, 2**40 + 7, 3, 2.0), (33, 64, 8, 1, 5, 1, 0.0)])
def test_routing_equals_the_reference(seq, d_model, n_experts, topk, seed,
                                      layer, skew):
    from repro.backends.opstream import route_tokens
    got = route_tokens(seq, d_model, n_experts, topk, seed, layer, skew)
    ref = _ref().route(seq, d_model, n_experts, topk, seed, layer, skew)
    assert got.shape == (seq, topk)
    assert got.tolist() == ref


def test_tiny_routing_leaves_an_expert_empty():
    from repro.backends.opstream import route_tokens
    config = tiny_cell(CONFIG, "answer").config
    d = manifest.decoder(config)
    counts = [np.bincount(route_tokens(config["run"]["tokens"],
                                       d["d_model"], d["n_experts"],
                                       d["topk"], 0, layer, 0.5).ravel(),
                          minlength=d["n_experts"])
              for layer in range(d["first_dense"], d["n_layers"])]
    assert all((c == 0).any() and c.max() > 2 for c in counts)


def _run(cell):
    return cellmod.run(cell, 2**31 + 5, 0.5, 0)


def test_sound_run_is_correct(cpu_as_chip):
    r = _run(tiny_cell(CONFIG, "answer"))
    assert r["correct"], r["checks"]
    assert r["setup"]["compiles_in_window"] == 0
    assert r["metrics"]["answer_s"]["value"] > 0


def test_moved_token_makes_run_incorrect(cpu_as_chip, monkeypatch):
    """One routed token of one layer sent to an expert it did not
    choose: the program's trace no longer matches the reference."""
    from repro.backends import opstream
    real = opstream.route_tokens

    def moved(seq, d_model, n_experts, topk, seed, layer, skew):
        choice = real(seq, d_model, n_experts, topk, seed, layer, skew)
        if layer == 1:
            choice = choice.copy()
            free = [e for e in range(n_experts) if e not in choice[0]]
            choice[0, 0] = free[0]
        return choice
    monkeypatch.setattr(opstream, "route_tokens", moved)
    r = _run(tiny_cell(CONFIG, "answer"))
    assert not r["correct"]
    got = r["checks"]["trace_mismatch"]
    assert got["value"] > got["limit"], r["checks"]


def _ctx():
    return Context(cell=None, setup_s=0.0, window_s=5.0, n_done=2, units=2,
                   spans=[("backend", 0, 1.0, 2.0), ("backend", 1, 2.5, 4.0)],
                   work=None, summary=None, peaks={})


def test_moe_lower_s_reads_the_moe_spans(monkeypatch):
    spans = [_span(1, "opstream.moe", 1.2, 1.5),
             _span(2, "opstream.mla", 1.5, 1.6),
             _span(3, "opstream.moe", 3.0, 3.2),
             _span(4, "opstream.moe", 9.0, 9.5)]     # after the window
    prog = load_module("metrics", "_program")
    monkeypatch.setattr(prog, "_obs", lambda: types.SimpleNamespace(
        snapshot=lambda: {"spans": spans, "totals": {}}))
    metric = load_module("metrics", "moe_lower_s")
    value, extra = metric.read(_ctx())
    assert value == pytest.approx(0.25) and extra == {"spans": 2}
    spans[:] = spans[1:2]          # no MoE layer lowered: no reading
    assert metric.read(_ctx()) is None


def test_moe_lower_s_none_without_the_recorder(monkeypatch):
    import repro.runtime
    monkeypatch.setitem(sys.modules, "repro.runtime.obs", None)
    monkeypatch.delattr(repro.runtime, "obs", raising=False)
    assert load_module("metrics", "moe_lower_s").read(_ctx()) is None


def _span(i, name, t0, t1):
    return {"id": i, "parent": None, "name": name, "attrs": {},
            "start_ns": int(t0 * 1e9), "end_ns": int(t1 * 1e9),
            "counts": {}}
