"""The readers of the program's own spans and counters against values
computed by hand on a synthetic window: the clock alignment, each
metric, and the None every reader returns on a program that records no
spans."""

import sys
import types

import pytest

from chipbench import devtrace
from chipbench.cell import Context
from chipbench.manifest import load_module

S = 1_000_000_000          # ns per second
OFF = -S // 2              # trace clock = host clock - 0.5 s


def _span(i, parent, name, t0, t1, attrs=None, counts=None):
    return {"id": i, "parent": parent, "name": name, "attrs": attrs or {},
            "start_ns": int(t0 * S), "end_ns": int(t1 * S),
            "counts": counts or {}}


# host seconds; two answers in the window [1.0, 6.0]
PROGRAM = [
    _span(1, None, "session.profile", 0.2, 0.4),           # before it
    _span(3, 2, "cachesim.partition", 1.1, 1.2, {"level": "L1"}),
    _span(4, 2, "cachesim.scan", 1.2, 1.5, {"level": "L1"},
          {"h2d_bytes": 1_000_000, "d2h_bytes": 500_000}),
    _span(5, 2, "cachesim.gather", 1.5, 1.6, {"level": "L1"}),
    _span(7, 6, "cachesim.partition", 1.65, 1.7, {"level": "L2"}),
    _span(6, 2, "cachesim.l2_stream", 1.6, 1.8),
    _span(2, None, "session.profile", 1.1, 1.9),
    _span(8, None, "frontend.stats", 2.2, 2.8, {"subpartition": "L1"},
          {"d2h_bytes": 2_000_000}),
    _span(10, 9, "compose.trace_view", 3.1, 3.4),
    _span(11, 9, "executor.slab", 3.5, 4.5, {"kernel": "ra_grouped"},
          {"slab_rows": 8, "slab_real_rows": 1, "h2d_bytes": 100}),
    _span(12, 9, "executor.slab", 4.6, 4.8, {"kernel": "rf_fused"},
          {"slab_rows": 8, "slab_real_rows": 1}),
    _span(13, 9, "executor.slab", 4.9, 5.8, {"kernel": "ra_grouped"},
          {"slab_rows": 8, "slab_real_rows": 3}),
    _span(9, None, "compose.evaluate", 3.1, 5.9, counts={"h2d_bytes": 16}),
    _span(14, None, "session.profile", 10.0, 11.0),        # after it
]

# the benchmark's own spans (name, request, start_s, end_s), host clock
BENCH = [("backend", 0, 1.0, 2.0), ("analyze", 0, 2.0, 3.0),
         ("compose", 0, 3.0, 6.0)]


def _trace_ns(host_s):
    return int(host_s * S) + OFF


def _summary():
    """The trace of the window: the benchmark's spans (one read 1 us
    late), and device operations at host seconds 1.3-1.4 (the L1 scan),
    2.3-2.7 (statistics), 3.6-4.2 (first slab), 4.7-4.75 (second) and
    5.7-5.9 (third, 0.1 s of it inside the slab)."""
    spans = [["window", _trace_ns(0.5), 7 * S]]
    for name, _, t0, t1 in BENCH:
        late = 1000 if name == "analyze" else 0
        spans.append([name, _trace_ns(t0) + late, int((t1 - t0) * S)])
    ops = [["op", _trace_ns(a), int((b - a) * S)] for a, b in (
        (1.3, 1.4), (2.3, 2.7), (3.6, 4.2), (4.7, 4.75), (5.7, 5.9))]
    return devtrace.Summary({"0": ops}, {}, spans)


def _ctx(summary=True):
    return Context(cell=None, setup_s=1.0, window_s=5.0, n_done=2,
                   units=2, spans=list(BENCH), work=None,
                   summary=_summary() if summary else None, peaks={})


@pytest.fixture
def program(monkeypatch):
    """The program's recorder, holding the spans above."""
    from repro.runtime import obs
    prog = load_module("metrics", "_program")
    fake = types.SimpleNamespace(
        snapshot=lambda: {"spans": [dict(s) for s in PROGRAM],
                          "totals": {}},
        self_ns=obs.self_ns)
    monkeypatch.setattr(prog, "_obs", lambda: fake)
    return prog


def _read(name, ctx):
    return load_module("metrics", name).read(ctx)


def test_alignment(program):
    ctx = _ctx()
    # three matched spans: offsets OFF, OFF + 1000, OFF
    assert program.offset_ns(ctx) == OFF
    got = program.spans(ctx)
    # the window is 1.0-6.0 s: the spans before and after it are out
    assert sorted(s["id"] for s in got) == list(range(2, 14))
    summary = program.on_trace(ctx)
    assert summary.t0 == ctx.summary.t0 and summary.t1 == ctx.summary.t1
    slab = next(s for s in summary.spans if s[0] == "executor.slab")
    assert slab == ["executor.slab", _trace_ns(3.5), S]
    assert program.per_request(ctx, 3.0) == 1.5
    assert program.on_trace(_ctx(summary=False)) is None


def test_cachesim_host_s(program):
    # partition 0.1 + gather 0.1 + L2 stream 0.2 less its 0.05 s child,
    # + that child 0.05; the scan is left out; per answer: / 2
    assert _read("cachesim_host_s", _ctx()) == pytest.approx(0.2)


def test_stats_s(program):
    value, extra = _read("stats_s", _ctx())
    assert value == pytest.approx(0.3)
    assert extra["device_s"] == pytest.approx(0.2)


def test_trace_view_s(program):
    assert _read("trace_view_s", _ctx()) == pytest.approx(0.15)


def test_executor_device_s(program):
    value, extra = _read("executor_device_s", _ctx())
    # 0.6 + 0.05 + 0.1 s busy inside the three slabs, per answer
    assert value == pytest.approx(0.375)
    assert extra == {"slabs": 3}
    assert _read("executor_device_s", _ctx(summary=False)) is None


def test_ra_slab_fill(program):
    value, extra = _read("ra_slab_fill", _ctx())
    # the refresh-aware slabs only: (1 + 3) real rows of 16
    assert value == 25.0
    assert extra == {"slabs": 2, "rows": 16}


def test_transfer_mb(program):
    value, extra = _read("transfer_mb", _ctx())
    assert value == pytest.approx((1_000_000 + 500_000 + 2_000_000 + 100
                                   + 16) / 1e6 / 2)
    assert extra["h2d_mb"] == pytest.approx(1_000_116 / 1e6 / 2)
    assert extra["d2h_mb"] == pytest.approx(2.5 / 2)


READERS = ("cachesim_host_s", "stats_s", "trace_view_s",
           "executor_device_s", "ra_slab_fill", "transfer_mb")


@pytest.mark.parametrize("name", READERS)
def test_none_without_the_recorder(monkeypatch, name):
    import repro.runtime
    monkeypatch.setitem(sys.modules, "repro.runtime.obs", None)
    monkeypatch.delattr(repro.runtime, "obs", raising=False)
    assert load_module("metrics", "_program").spans(_ctx()) is None
    assert _read(name, _ctx()) is None
