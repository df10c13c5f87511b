"""The seed changes every request's addresses and nothing else: for both
backends, the program's per-subpartition event counts, lifetime
multisets and compositions are the same under two relabelling keys,
while the addresses differ."""

import numpy as np
import pytest

from bench_cells import tiny_cell
from chipbench import program
from chipbench import traffic as gen


@pytest.mark.parametrize("config", ["tiny.gpu", "tiny.systolic"])
def test_relabel_keeps_the_work(config):
    cell = tiny_cell(config, "answer")
    backend = program.load_backend(cell.config)
    keys = [gen.relabel_key(cell.traffic, seed, 0) for seed in (1, 2**40 + 7)]
    assert keys[0] != keys[1]
    got = []
    for key in keys:
        s = backend.session(key, program.Spans())
        s.analyze()
        rec = program.session_record(s)
        s.compose(policy="refresh-aware", engine="jax")
        rec["energy"] = {n: s.composition(n).energy_j for n in rec["subs"]}
        got.append(rec)
    a, b = got
    assert not np.array_equal(a["trace"][1], b["trace"][1])
    for name in a["subs"]:
        (sa, st_a), (sb, st_b) = a["subs"][name], b["subs"][name]
        assert st_a == st_b
        for i in (2, 3):        # lifetime lengths and read counts
            assert np.array_equal(np.sort(sa[i]), np.sort(sb[i]))
        assert a["energy"][name] == pytest.approx(b["energy"][name],
                                                  rel=1e-12)
    # per-subpartition event counts and the time/write/hit columns agree
    for i in (0, 2, 3, 4):
        assert np.array_equal(a["trace"][i], b["trace"][i])


def test_sweep_grids_have_one_shape():
    cell = tiny_cell("tiny.gpu", "sweep")
    grids = [gen.grid_scales(cell.traffic, s, i)
             for s in (3, 2**33 + 1) for i in (0, 1)]
    for r, a, e in grids:
        assert (len(r), len(a), len(e)) == (4, 2, 2)
        assert all(0.5 <= v <= 2.0 for v in r + a + e)
    assert len({g[0] for g in grids}) == len(grids)
