"""``correct`` at small sizes on the CPU: a sound run passes; the
control (the reference in float32 in the program's place) fails the
energy limit; and with the timed path broken underneath, a whole run
(set-up, window, check) reports ``correct`` false for each fault a cell
can have."""

import numpy as np
import pytest

import control
from bench_cells import tiny_cell
from chipbench import cell as cellmod
from chipbench import reference

CELLS = [("tiny.gpu", "answer"), ("tiny.systolic", "answer"),
         ("tiny.gpu", "sweep")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_control_fails_and_program_passes(cpu_as_chip, config, traffic):
    cell = tiny_cell(config, traffic)
    _, s = control.readings(cell, [5, 2**35 + 11])
    limits = reference.limits()
    assert all(s["lower"][k] <= limits[k] for k in limits)
    assert s["upper"]["energy_rel_err"] > limits["energy_rel_err"]


def test_cpu_is_not_a_chip():
    with pytest.raises(SystemExit, match="needs 1 TPU chip"):
        cellmod.chip_devices(1)


def _run(cell):
    return cellmod.run(cell, 2**31 + 5, 0.5, 0)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_run_is_correct(cpu_as_chip, config, traffic):
    r = _run(tiny_cell(config, traffic))
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["setup"]["compiles_in_window"] == 0


def _flip_one_hit(monkeypatch):
    from repro.backends import cachesim
    real = cachesim._simulate_level

    def broken(*a, **kw):
        hit, fill, ev, dirty = real(*a, **kw)
        hit = hit.copy()
        hit[len(hit) // 2] ^= True
        return hit, fill, ev, dirty
    monkeypatch.setattr(cachesim, "_simulate_level", broken)


def _shift_one_slot(monkeypatch):
    from repro.backends import systolic
    real = systolic._TraceBuilder.emit

    def broken(self, times, addrs, is_write, sub):
        addrs = np.array(addrs, np.int64)
        if len(self.t) == 3 and addrs.size:
            addrs[0] += 1
        return real(self, times, addrs, is_write, sub)
    monkeypatch.setattr(systolic._TraceBuilder, "emit", broken)


def _drop_one_lifetime(monkeypatch):
    import dataclasses
    from repro.core import api
    real = api.lifetimes_of_trace

    def broken(*a, **kw):
        out = real(*a, **kw)
        valid = np.asarray(out.valid).copy()
        valid[np.flatnonzero(valid)[0]] = False
        return dataclasses.replace(out, valid=valid)
    monkeypatch.setattr(api, "lifetimes_of_trace", broken)


def _perturb_energy(monkeypatch):
    from repro.compose import executor
    real = executor.run_batch

    def broken(*a, **kw):
        e, f = real(*a, **kw)
        return e * (1 + 1e-7), f
    monkeypatch.setattr(executor, "run_batch", broken)


def _half_the_lifetimes(monkeypatch):
    """Half of the batch left out, the mean taken over the rest: the
    executor bills every other lifetime and doubles the sum."""
    import dataclasses
    from repro.compose import engine
    real = engine.sorted_trace_view

    def broken(stats, raw, clock_hz=1.0e9):
        v = real(stats, raw, clock_hz)
        keep = np.arange(v.n_lt) % 2 == 0
        bits = np.zeros(v.n_lt)
        bits[keep] = 2 * np.diff(v.prefix_bits)[keep]
        rb = np.zeros(v.n_lt)
        rb[keep] = 2 * np.diff(v.prefix_read_bits)[keep]
        return dataclasses.replace(
            v, prefix_bits=np.concatenate([[0.0], np.cumsum(bits)]),
            prefix_read_bits=np.concatenate([[0.0], np.cumsum(rb)]))
    monkeypatch.setattr(engine, "sorted_trace_view", broken)


FAULTS = [
    ("tiny.gpu", "answer", _flip_one_hit, "trace_mismatch"),
    ("tiny.systolic", "answer", _shift_one_slot, "trace_mismatch"),
    ("tiny.gpu", "answer", _drop_one_lifetime, "lifetime_mismatch"),
    ("tiny.systolic", "answer", _perturb_energy, "energy_rel_err"),
    ("tiny.gpu", "sweep", _perturb_energy, "energy_rel_err"),
    ("tiny.gpu", "answer", _half_the_lifetimes, "energy_rel_err"),
]


@pytest.mark.parametrize("config,traffic,fault,number", FAULTS,
                         ids=[f"{c}-{t}-{f.__name__.strip('_')}"
                              for c, t, f, _ in FAULTS])
def test_fault_makes_run_incorrect(cpu_as_chip, monkeypatch, config,
                                   traffic, fault, number):
    fault(monkeypatch)
    r = _run(tiny_cell(config, traffic))
    assert not r["correct"]
    got = r["checks"][number]
    assert got["value"] > got["limit"], r["checks"]
