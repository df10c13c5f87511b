"""The device stages compile for a TPU v5e (described, not attached).

Each test lowers one jitted stage of the profiler's device path at the
shapes the main path gives it and compiles it with the TPU compiler for
one chip of a described ``v5e:2x2`` topology.  Nothing runs, so these
say nothing about results or times; they catch, at no chip time, a
stage the chip's compiler refuses (an op Mosaic cannot lower, a block
shape that does not tile, a program that does not fit).

Programs with a sort (``repro.core.lifetime._extract_lifetimes``) take
a minute or more to compile for the TPU even at 2**16 events, so they
are not here.  The topology is described inside a module fixture: only
the worker that runs this file loads the TPU compiler, and a host that
cannot describe one skips these tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends import cachesim
from repro.compat import enable_x64
from repro.compose import executor
from repro.kernels.lifetime_scan.kernel import lifetime_scan_sorted

L1 = cachesim.HierarchyConfig().l1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables compiled for a described chip cannot be read back
    # without one: keep them out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_cachesim_set_parallel_scan_compiles_at_l1_geometry(one_chip):
    S = _spec(one_chip)
    with enable_x64():
        compiled = cachesim._simulate_cache_sets.lower(
            S((L1.n_sets, 4096), jnp.int64), S((L1.n_sets,), jnp.int32),
            ways=L1.ways, write_allocate=True).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("n_cands,n_dev", [(8, 2), (8, 4)])
def test_refresh_free_executor_compiles_at_floor_buckets(one_chip, n_cands,
                                                         n_dev):
    S = _spec(one_chip)
    C, D = n_cands, n_dev
    L, A = executor._L_MIN, executor._A_MIN
    f64 = jnp.float64
    with enable_x64():
        compiled = executor._rf_fused.lower(
            S((C, D), f64), S((C, D), f64), S((C, D), f64),
            S((C, D), bool), S((C,), jnp.int64), S((L,), f64),
            S((L + 1,), f64), S((L + 1,), f64), S((A,), f64),
            S((), jnp.int64), S((), jnp.int64)).compile()
    assert compiled.memory_analysis() is not None


def test_refresh_aware_executor_compiles_at_floor_buckets(one_chip):
    S = _spec(one_chip)
    C, D = executor._C_MIN, 4
    L, A = executor._L_MIN, executor._A_MIN
    f64 = jnp.float64
    with enable_x64():
        compiled = executor._ra_grouped.lower(
            S((C, D), f64), S((C, D), f64), S((C, D), f64),
            S((C, D), bool), S((L,), f64), S((L,), f64), S((L,), f64),
            S((L,), jnp.int32), S((), jnp.int64), S((), jnp.int64),
            S((), jnp.int64), S((C, D, executor._FIX_MIN), jnp.int64),
            S((C, D, executor._FIX_MIN), f64), n_seg=A).compile()
    assert compiled.memory_analysis() is not None
    text = compiled.as_text()
    # padded rows skip the row's work: a branch, not a select of both
    assert "conditional(" in text
    # the per-address sums are scans: the one scatter left is the fix
    # lists' (the segment_sum kernel held 2 + D more)
    assert text.count(" scatter(") == 1


def test_lifetime_scan_kernel_compiles_for_tpu(one_chip):
    S = _spec(one_chip)
    n, n_bins = 1 << 16, 64
    i32 = jnp.int32
    fn = jax.jit(lambda *a: lifetime_scan_sorted(
        *a, block=256, n_bins=n_bins, interpret=False))
    compiled = fn.lower(S((n,), i32), S((n,), i32), S((n,), i32),
                        S((n,), i32), S((n_bins + 1,), i32),
                        S((n_bins + 1,), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
