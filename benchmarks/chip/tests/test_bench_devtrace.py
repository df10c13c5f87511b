"""The trace reducer on a small trace recorded on one TPU v5e: one
``chatglm3_6b.gpu.answer`` window (its module events and host spans,
and the first 200 device operations, names cut to 100 characters)."""

import json
import os

import pytest

from chipbench.devtrace import Summary

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def recorded():
    with open(os.path.join(DATA, "trace_gpu_answer.json")) as f:
        return json.load(f)


def _merged_ns(intervals):
    """Length of the union of ``[start, end)`` intervals, by a plain
    sweep."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def test_window_and_layer_device_time(recorded):
    s = Summary.from_json(recorded)
    assert s.window_s == pytest.approx(36.993040462)
    assert s.chips() == ["0"]
    # the 200 recorded operations all fall in the cachesim span (the
    # start of the L1 scan); none in analyze or compose
    ops = recorded["ops"]["0"]
    want = _merged_ns([(o[1], o[1] + o[2]) for o in ops]) * 1e-9
    assert s.busy_in("cachesim") == pytest.approx(want)
    assert s.busy_in("analyze") == 0.0
    assert s.busy_in("no such span") == 0.0


def test_layer_device_time_from_modules(recorded):
    recorded["ops"] = {}
    s = Summary.from_json(recorded)
    # four refresh-aware slabs in compose: 2 x 11.201 s at L1 and
    # 2 x 5.280 s at L2, and two rf_fused calls
    assert s.busy_in("compose") == pytest.approx(
        2 * 11.201264 + 2 * 5.279746 + 0.000224 + 0.000156, rel=1e-5)
    # two lifetime extractions in analyze
    assert s.busy_in("analyze") == pytest.approx(0.815595 + 0.743379,
                                                 rel=1e-5)


def test_busy_from_operations(recorded):
    s = Summary.from_json(recorded)
    ops = recorded["ops"]["0"]
    want = _merged_ns([(o[1], o[1] + o[2]) for o in ops]) * 1e-9
    assert s.busy_s() == pytest.approx(want)
    assert 0 < s.busy_s() < 1e-3


def test_busy_from_modules_without_operations(recorded):
    recorded["ops"] = {}
    s = Summary.from_json(recorded)
    mods = recorded["modules"]["0"]
    want = _merged_ns([(m[1], m[1] + m[2]) for m in mods]) * 1e-9
    assert s.busy_s() == pytest.approx(want)
    # the answer keeps the chip busy 93% of the window
    assert s.busy_s() / s.window_s == pytest.approx(0.933, abs=0.002)
    names = [n for n, _ in s.top_ops(3)]
    assert names[0].startswith("jit__ra_grouped")


def test_idle_gaps_are_named_by_host_spans(recorded):
    s = Summary.from_json(recorded)
    gaps = s.idle_gaps(2)
    # the window opens with the lowering on the host, then the first
    # operations run; after the 200 recorded ones the chip reads idle
    # through compose
    assert [g[0] for g in gaps] == ["compose", "lower"]
    assert sum(g[1] for g in gaps) == pytest.approx(
        s.window_s - s.busy_s(), rel=1e-6)


def test_leaf_operations_only(recorded):
    s = Summary.from_json(recorded)
    top = s.top_ops(10)
    assert len(top) <= 10
    assert all(sec > 0 for _, sec in top)
    assert sum(sec for _, sec in s.top_ops(10 ** 6)) <= s.busy_s() + 1e-9
