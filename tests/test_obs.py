"""The span and counter recorder (``repro.runtime.obs``) and the spans a
``ProfileSession`` answer records: nesting, self time, counts, the ring's
bound, threads, the import contract, the profiler trace, and results
left bit-identical by the instrumentation."""

import glob
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.runtime import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _since(mark):
    """Finished spans recorded after the span id ``mark``."""
    return [s for s in obs.snapshot()["spans"] if s["id"] > mark]


def _mark():
    with obs.span("mark"):
        pass
    return obs.snapshot()["spans"][-1]["id"]


def test_nesting_and_parent_ids():
    m = _mark()
    with obs.span("a", k=1):
        with obs.span("b"):
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    got = {s["name"]: s for s in _since(m)}
    assert [s["name"] for s in _since(m)] == ["c", "b", "d", "a"]
    assert got["a"]["parent"] is None
    assert got["b"]["parent"] == got["d"]["parent"] == got["a"]["id"]
    assert got["c"]["parent"] == got["b"]["id"]
    assert got["a"]["attrs"] == {"k": 1}
    for s in got.values():
        assert s["start_ns"] <= s["end_ns"]
    assert got["a"]["start_ns"] <= got["b"]["start_ns"]
    assert got["d"]["end_ns"] <= got["a"]["end_ns"]


def test_span_as_decorator_and_on_error():
    m = _mark()

    @obs.span("deco", x="y")
    def f(v):
        return v + 1

    assert f(1) == 2 and f(2) == 3
    with pytest.raises(ValueError):
        with obs.span("raises"):
            raise ValueError("boom")
    with obs.span("after"):
        pass
    got = _since(m)
    assert [s["name"] for s in got] == ["deco", "deco", "raises", "after"]
    assert len({s["id"] for s in got}) == 4
    # the failed span closed: the next one is a root again
    assert all(s["parent"] is None for s in got)


def test_self_time():
    spans = [
        {"id": 1, "parent": None, "start_ns": 0, "end_ns": 100},
        {"id": 2, "parent": 1, "start_ns": 10, "end_ns": 30},
        {"id": 3, "parent": 1, "start_ns": 50, "end_ns": 60},
        {"id": 4, "parent": 2, "start_ns": 12, "end_ns": 20},
        # two children run in worker threads, overlapping: their union
        {"id": 5, "parent": None, "start_ns": 200, "end_ns": 300},
        {"id": 6, "parent": 5, "start_ns": 210, "end_ns": 260},
        {"id": 7, "parent": 5, "start_ns": 240, "end_ns": 280},
    ]
    assert obs.self_ns(spans) == {1: 70, 2: 12, 3: 10, 4: 8, 5: 30, 6: 50,
                                  7: 40}
    m = _mark()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    got = {s["name"]: s for s in _since(m)}
    own = obs.self_ns(_since(m))
    out, inner = got["outer"], got["inner"]
    assert own[out["id"]] == (out["end_ns"] - out["start_ns"]
                              - (inner["end_ns"] - inner["start_ns"]))
    assert own[inner["id"]] == inner["end_ns"] - inner["start_ns"]


def test_counts_land_in_the_innermost_span():
    before = obs.snapshot()["totals"].get("test_n", 0)
    m = _mark()
    with obs.span("outer"):
        obs.count("test_n", 2)
        with obs.span("inner"):
            obs.count("test_n", 5)
            obs.count("test_n")
        obs.count("test_other", 7)
    obs.count("test_n", 100)          # no span open: the total only
    got = {s["name"]: s for s in _since(m)}
    assert got["inner"]["counts"] == {"test_n": 6}
    assert got["outer"]["counts"] == {"test_n": 2, "test_other": 7}
    assert obs.snapshot()["totals"]["test_n"] - before == 108


def test_the_ring_stays_bounded():
    for _ in range(obs.RING + 10):
        with obs.span("fill"):
            pass
    spans = obs.snapshot()["spans"]
    assert len(spans) == obs.RING
    ids = [s["id"] for s in spans]
    assert ids == sorted(ids) and ids[-1] - ids[0] == obs.RING - 1


def test_threads_nest_on_their_own():
    barrier = threading.Barrier(4)
    m = _mark()

    def work(i):
        with obs.span("t", i=i):
            barrier.wait(timeout=10)
            with obs.span("t.inner", i=i):
                obs.count("test_thread", i)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    got = _since(m)
    outer = {s["attrs"]["i"]: s for s in got if s["name"] == "t"}
    inner = {s["attrs"]["i"]: s for s in got if s["name"] == "t.inner"}
    assert sorted(outer) == sorted(inner) == [0, 1, 2, 3]
    for i in range(4):
        assert outer[i]["parent"] is None
        assert inner[i]["parent"] == outer[i]["id"]
        assert inner[i]["counts"] == {"test_thread": i}


def test_import_is_stdlib_only():
    code = ("import sys; import repro.runtime.obs; "
            "bad = sorted(m for m in ('jax', 'numpy') if m in sys.modules); "
            "print(bad); sys.exit(1 if bad else 0)")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# a ProfileSession answer on a tiny stream
# ---------------------------------------------------------------------------

POLICIES = ("refresh-free", "refresh-aware", "bank-quantized:refresh-aware@8")


def _answer(engine="jax"):
    """A gpu-backend answer: lower, cache-simulate, analyze, compose under
    three policies; small caches and a slow clock, so that every policy
    spreads the lifetimes over all three devices."""
    from repro.backends.cachesim import CacheConfig, HierarchyConfig
    from repro.backends.opstream import StreamBuilder, transformer_ops
    from repro.core import ProfileSession
    sb = StreamBuilder()
    transformer_ops(sb, 256, 4, 2, 512, 32, n_layers=1)
    session = ProfileSession("gpu")
    session.profile(sb.finish(), config=HierarchyConfig(
        l1=CacheConfig(size_kb=16, ways=8),
        l2=CacheConfig(size_kb=64, ways=16), clock_hz=1e6))
    session.analyze()
    comps = {}
    for policy in POLICIES:
        session.compose(policy=policy, engine=engine)
        for name in ("L1", "L2"):
            comps[policy, name] = session.composition(name)
    return session, comps


def _tree(spans):
    by_id = {s["id"]: s for s in spans}
    return by_id, lambda s: by_id.get(s["parent"], {}).get("name")


def test_answer_emits_the_span_tree():
    _answer()                             # compiles
    m = _mark()
    session, _ = _answer()
    spans = _since(m)
    by_id, parent = _tree(spans)
    names = {s["name"] for s in spans}
    assert names == {
        "opstream.lower", "session.profile", "cachesim.partition",
        "cachesim.scan", "cachesim.gather", "cachesim.l2_stream",
        "cachesim.merge", "session.analyze", "lifetime.extract",
        "frontend.stats", "session.compose", "compose.evaluate",
        "compose.address_groups", "compose.trace_view",
        "executor.residence", "executor.slab", "compose.epilogue"}
    roots = [s for s in spans if s["parent"] is None]
    assert [s["name"] for s in roots] == [
        "opstream.lower", "session.profile", "session.analyze",
        "session.compose", "session.compose", "session.compose"]
    for s in roots[1:]:
        assert s["attrs"]["session"] == session.id
    assert [s["attrs"]["policy"] for s in roots[3:]] == list(POLICIES)
    want = {"cachesim.partition": "session.profile",
            "cachesim.scan": "session.profile",
            "cachesim.gather": "session.profile",
            "cachesim.l2_stream": "session.profile",
            "cachesim.merge": "session.profile",
            "lifetime.extract": "session.analyze",
            "frontend.stats": "session.analyze",
            "compose.evaluate": "session.compose",
            "compose.address_groups": "compose.evaluate",
            "compose.trace_view": "compose.evaluate",
            "executor.residence": "compose.evaluate",
            "executor.slab": "compose.evaluate",
            "compose.epilogue": "compose.evaluate"}
    for s in spans:
        if s["name"] in want:
            assert parent(s) == want[s["name"]], s
    scans = [s for s in spans if s["name"] == "cachesim.scan"]
    assert [s["attrs"]["level"] for s in scans] == ["L1", "L2"]
    ext = [s for s in spans if s["name"] == "lifetime.extract"]
    sub = np.asarray(session.trace.subpartition)
    assert [(s["attrs"]["subpartition"], s["attrs"]["events"])
            for s in ext] == [("L1", int((sub == 0).sum())),
                              ("L2", int((sub == 1).sum()))]
    ev = [s for s in spans if s["name"] == "compose.evaluate"]
    assert [(s["attrs"]["subpartition"], s["attrs"]["policy"],
             s["attrs"]["candidates"]) for s in ev] == [
        (n, p, 1) for p in POLICIES for n in ("L1", "L2")]
    # one address grouping and trace view per subpartition, one
    # residence set per subpartition and family of kernels: built on
    # first use only
    for name in ("compose.address_groups", "compose.trace_view"):
        assert len([s for s in spans if s["name"] == name]) == 2
    res = [s["attrs"]["arrays"] for s in spans
           if s["name"] == "executor.residence"]
    assert res == ["value", "value", "addr", "addr"]
    slabs = [s for s in spans if s["name"] == "executor.slab"]
    assert [s["attrs"]["kernel"] for s in slabs] == \
        ["rf_fused"] * 2 + ["ra_grouped"] * 4
    for s in slabs:
        assert s["counts"]["slab_rows"] == 8
        assert s["counts"]["slab_real_rows"] == 1
        assert s["counts"]["h2d_bytes"] > 0 and s["counts"]["d2h_bytes"] > 0


def test_h2d_bytes_are_the_bytes_uploaded(monkeypatch):
    import jax.numpy as jnp
    _answer()                             # compiles: no tracing below
    uploaded = []
    real = jnp.asarray

    def spy(a, *args, **kw):
        out = real(a, *args, **kw)
        if isinstance(a, (np.ndarray, np.generic)):
            uploaded.append(out.nbytes)
        return out

    monkeypatch.setattr(jnp, "asarray", spy)
    m = _mark()
    _answer()
    spans = _since(m)
    counted = sum(s["counts"].get("h2d_bytes", 0) for s in spans)
    assert uploaded and counted == sum(uploaded)
    pulled = sum(s["counts"].get("d2h_bytes", 0) for s in spans)
    assert pulled > 0


# Compositions of _answer() at the parent commit of the instrumentation
# (energy_j and capacity fractions as float.hex), engine="jax".
BEFORE = {
    ("refresh-free", "L1"): ("0x1.94313cfbba646p-24", (
        "0x1.f8d1a3468d1a3p-1", "0x1.62c58b162c58bp-8",
        "0x1.1a3468d1a3469p-7")),
    ("refresh-free", "L2"): ("0x1.4ef9710d215f7p-23", (
        "0x1.c8c183060c183p-1", "0x1.f3e7cf9f3e7d0p-9",
        "0x1.aa54a952a54a9p-4")),
    ("refresh-aware", "L1"): ("0x1.9392eabf96c60p-24", (
        "0x1.fcc993264c993p-1", "0x1.42850a142850ap-11",
        "0x1.72e5cb972e5ccp-8")),
    ("refresh-aware", "L2"): ("0x1.4ef29fac186cep-23", (
        "0x1.c9a3468d1a347p-1", "0x1.62c58b162c58bp-7",
        "0x1.868d1a3468d1ap-4")),
    ("bank-quantized:refresh-aware@8", "L1"): ("0x1.9392eabf96c60p-24", (
        "0x1.0000000000000p+0", "0x1.0000000000000p-3",
        "0x1.0000000000000p-3")),
    ("bank-quantized:refresh-aware@8", "L2"): ("0x1.4ef29fac186cep-23", (
        "0x1.0000000000000p+0", "0x1.0000000000000p-3",
        "0x1.0000000000000p-3")),
}


def test_compositions_are_bit_identical_to_before():
    _, comps = _answer()
    for key, (energy, fracs) in BEFORE.items():
        c = comps[key]
        assert c.energy_j.hex() == energy, key
        assert tuple(float(f).hex() for f in c.capacity_fractions) == \
            fracs, key


def test_sweep_workers_nest_under_the_sweep():
    from repro.sweep import DeviceGrid
    session, _ = _answer(engine="numpy")
    grid = DeviceGrid(mixes=(0.0, 1.0), retention_scales=(1.0, 2.0))
    serial = session.sweep(grid, workers=1, attach=False)
    m = _mark()
    threaded = session.sweep(grid, workers=4, attach=False)
    assert [p.composition.energy_j for p in threaded.points] == \
        [p.composition.energy_j for p in serial.points]
    spans = _since(m)
    by_id, parent = _tree(spans)
    (sweep,) = [s for s in spans if s["name"] == "session.sweep"]
    ev = [s for s in spans if s["name"] == "compose.evaluate"]
    assert sorted(s["attrs"]["subpartition"] for s in ev) == ["L1", "L2"]
    for s in ev:
        assert s["parent"] == sweep["id"]
    for s in spans:
        if s["name"] == "compose.epilogue":
            assert parent(s) == "compose.evaluate"
            e = by_id[s["parent"]]
            assert e["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= e["end_ns"]


def test_spans_appear_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    _answer()                             # compiles outside the trace
    m = _mark()
    with jax.profiler.trace(str(tmp_path)):
        _answer()
    spans = _since(m)
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    by_name = {}
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        by_name.setdefault(s["name"], []).append(
            s["end_ns"] - s["start_ns"])
    assert set(by_name) <= set(host), set(by_name) - set(host)
    for name, durs in by_name.items():
        # the k-th span of a name against the k-th event of that name
        evs = [d for _, d in sorted(host[name])]
        assert len(evs) == len(durs), name
        for got, want in zip(durs, evs):
            assert abs(got - want) < 1_000_000, name
