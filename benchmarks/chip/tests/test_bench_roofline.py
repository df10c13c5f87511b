"""Each roofline's work count against a value computed by hand, and the
share arithmetic."""

import math

import pytest

from chipbench.manifest import load_module, peaks


def test_cachesim_work():
    m = load_module("metrics", "cachesim_roofline")
    # 1,000 accesses through 8-way sets: 17 compares/updates, 20 bytes
    assert m.least_work(1000, 8) == (17000, 20000)
    assert m.least_work(10, 16) == (330, 200)


def test_lifetime_work():
    m = load_module("metrics", "lifetime_roofline")
    # 1,024 events: 1024 * 10 sort compares + 6 * 1024; 18 B per event
    # in, 28 B per lifetime out
    assert m.least_work(1024, 100) == (1024 * 10 + 6 * 1024,
                                       18 * 1024 + 2800)
    # a length that is not a power of two rounds the sort depth up
    assert m.least_work(1000, 0)[0] == 1000 * 10 + 6000


def test_executor_work():
    m = load_module("metrics", "executor_roofline.sweep")
    # refresh-aware, one 3-device candidate, 100 lifetimes, 10 addresses:
    # 10*3*100 + 2*100 + 100 + 3*100 + 2*10 = 3620 ops;
    # 28*100 bytes in, 8*(1+3) out
    assert m.least_work("refresh-aware", [3], 100, 10) == (3620, 2832)
    # the SRAM-only anchor has one device: 10*100 + 0 + 100 + 100 + 0
    assert m.least_work("refresh-aware", [1], 100, 10) == (1200, 2816)
    # refresh-free: per device two binary searches and four prefix reads
    ops, nbytes = m.least_work("refresh-free", [3, 1], 1000, 100)
    assert ops == 4 * (10 + 7 + 4)
    assert nbytes == 24 * 1000 + 8 * 100 + 8 * 4 + 8 * 2


def test_share_and_bound():
    share = load_module("metrics", "_roofline").share
    v5e = peaks("TPU v5 lite")
    # 819 MB at 819 GB/s is 1 ms; against 4 ms of device time: 25%
    got, extra = share(1.0, 819e6, v5e["flops_bf16"], v5e["hbm_bw"], 4e-3)
    assert got == pytest.approx(25.0)
    assert extra == {"bound": "memory"}
    got, extra = share(197e12, 0.0, v5e["flops_bf16"], v5e["hbm_bw"], 2.0)
    assert got == pytest.approx(50.0) and extra == {"bound": "compute"}
    assert share(1.0, 1.0, 1.0, 1.0, 0.0) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("TPU v99")
    assert math.isclose(peaks("TPU v5 lite")["hbm_bw"], 819e9)
