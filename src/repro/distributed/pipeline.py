"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

For 1000+ node deployments the layer stack is split into S stages mapped
onto a ``stage`` mesh axis; microbatches flow stage-to-stage through
``jax.lax.ppermute`` ring shifts under shard_map.  The schedule below is
the classic GPipe fill-drain loop expressed as a single lax.scan of
S + M - 1 ticks (S stages, M microbatches): at every tick each stage
processes the activation it holds and passes it to its successor.

Usage is orthogonal to the DP/TP axes of `launch.mesh`: the stage axis can
be any mesh axis (in tests we pipeline over 'data'; in a production
(pod, data, model) mesh the natural stage axis for very deep models is
'pod', giving DP x PP x TP).

This module implements the *forward* pipeline (inference / activation
checkpointed training uses it for both directions via jax.vjp through
shard_map, which JAX supports natively).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(mesh, stage_axis: str, stage_fn, params_stacked,
                     x_microbatches):
    """Run x through S pipeline stages with M microbatches.

    stage_fn(stage_params, x) -> x   (same shape in/out)
    params_stacked: pytree with leading [S, ...] dim, sharded over
      ``stage_axis`` (each device holds its own stage's params).
    x_microbatches: [M, mb, ...] replicated input microbatches.

    Returns [M, mb, ...] outputs (available on the last stage; replicated
    back for convenience via a final ppermute ring-collect).
    """
    S = mesh.shape[stage_axis]
    M = x_microbatches.shape[0]

    def local_fn(params_s, xs):
        # params_s: this stage's params (leading dim 1); xs: [M, mb, ...]
        params_s = jax.tree.map(lambda a: a[0], params_s)
        stage = jax.lax.axis_index(stage_axis)
        n_ticks = S + M - 1
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            held, outs = carry
            # stage 0 injects microbatch t (when available)
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(stage == 0,
                             xs[inject],
                             held)
            active = (t - stage >= 0) & (t - stage < M)
            y = stage_fn(params_s, x_in)
            y = jnp.where(active, y, held)
            # pass to the next stage (ring shift by +1)
            passed = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % S) for i in range(S)])
            # last stage records its finished microbatch
            done_idx = t - (S - 1)
            outs = jnp.where(
                (stage == S - 1) & (done_idx >= 0) & (done_idx < M),
                outs.at[jnp.clip(done_idx, 0, M - 1)].set(y),
                outs)
            return (passed, outs), None

        held0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        # mark the carries as stage-varying for shard_map's VMA tracking
        held0 = jax.lax.pcast(held0, (stage_axis,), to="varying")
        outs0 = jax.lax.pcast(outs0, (stage_axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, (held0, outs0),
                                    jnp.arange(n_ticks))
        # replicate the last stage's outputs to every stage (masked psum:
        # ppermute requires unique sources, so broadcast-by-reduction)
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, 0.0), stage_axis)
        return outs

    in_specs = (jax.tree.map(lambda _: P(stage_axis), params_stacked),
                P())
    return jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=P())(params_stacked, x_microbatches)
