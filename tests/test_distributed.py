"""Distribution tests that need >1 device: run in subprocesses with
XLA_FLAGS host-device virtualization (the parent pytest process has
already locked jax to 1 CPU device)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n_devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pipeline_parallel_matches_sequential():
    _run("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import pipeline_forward
mesh = make_mesh((4,), ("stage",))
S, M, mb, d = 4, 6, 2, 8
W = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3
xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
stage_fn = lambda p, x: jnp.tanh(x @ p)
out = pipeline_forward(mesh, "stage", stage_fn, W, xs)
ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ W[s])
assert float(jnp.max(jnp.abs(out - ref))) < 1e-6
print("ok")
""", n_devices=4)


def test_moe_local_dispatch_matches_global():
    _run("""
import jax, jax.numpy as jnp, dataclasses
from repro.configs import get_config
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.models import layers as L
mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("phi3_5_moe", smoke=True)
p, _ = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
sharding.set_mesh(None)
cfg_g = dataclasses.replace(cfg, moe_local_dispatch=False)
y_g, _ = jax.jit(lambda p, x: L.moe_block(p, cfg_g, x, 8.0))(p, x)
sharding.set_mesh(mesh)
cfg_l = dataclasses.replace(cfg, moe_local_dispatch=True)
y_l, _ = jax.jit(lambda p, x: L.moe_block(p, cfg_l, x, 8.0))(p, x)
assert float(jnp.max(jnp.abs(y_g - y_l))) < 1e-5
print("ok")
""", n_devices=8)


def test_sharded_train_step_runs_on_virtual_mesh():
    """A real sharded train step (not just lower/compile) on 8 virtual
    devices: params FSDP+TP sharded, batch DP sharded, loss finite."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.distributed import sharding
from repro.launch.steps import (abstract_params, make_optimizer,
                                make_train_step)
from repro.launch.mesh import make_mesh
from repro.models.api import batch_shardings, batch_specs, build
mesh = make_mesh((4, 2), ("data", "model"))
sharding.set_mesh(mesh)
cfg = get_config("tinyllama_1_1b", smoke=True)
api = build(cfg)
params, specs = api.init(jax.random.PRNGKey(0))
p_sh = sharding.tree_shardings_for(
    jax.eval_shape(lambda p: p, params), specs)
params = jax.device_put(params, p_sh)
opt = make_optimizer(cfg)
opt_state = opt.init(params)
shape = ShapeCell("t", "train", 64, 4)
batch = api.make_batch(jax.random.PRNGKey(1), shape)
step = jax.jit(make_train_step(api, opt), donate_argnums=(0, 1))
params, opt_state, m = step(params, opt_state, batch)
assert np.isfinite(float(m["loss"]))
# param shardings survived the step
leaf = jax.tree.leaves(params)[3]
assert len(leaf.sharding.device_set) >= 2
print("ok", float(m["loss"]))
""", n_devices=8)


def test_compressed_psum_shard_map():
    _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim.compression import compressed_psum
mesh = make_mesh((4,), ("data",))
x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 13.0
out = jax.shard_map(lambda b: compressed_psum(b, "data"), mesh=mesh,
                    in_specs=P("data"), out_specs=P("data"))(x)
ref = jnp.tile(x.sum(0, keepdims=True) / 1.0, (4, 1)) * 0 + x.sum(0)
# int8 quantization: tolerance = shared-scale resolution
import numpy as np
assert np.allclose(np.asarray(out[0]), np.asarray(x.sum(0)),
                   atol=float(jnp.abs(x).max()) / 32), out[0]
print("ok")
""", n_devices=4)
