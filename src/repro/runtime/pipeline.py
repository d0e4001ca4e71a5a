"""Pipeline parallelism: GPipe microbatch schedule over a mesh axis.

Stages hold disjoint slices of the layer stack (stage i owns periods
[i·P/S, (i+1)·P/S)); activations rotate stage-to-stage with
``jax.lax.ppermute`` inside ``shard_map``.  The schedule is the classic
GPipe fill-drain: T = n_micro + n_stages − 1 ticks, bubble fraction
(S−1)/(T).  Backward works through autodiff (ppermute transposes to the
reverse permutation), giving a correct-if-memory-hungry 1F-then-1B;
activation remat inside the stage fn keeps it tractable.

This is an OPTIONAL distribution mode (off in the dry-run meshes, where
'pod' takes a DP role); it exists so the framework covers PP and is
correctness-tested on small meshes in tests/test_pipeline.py.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def gpipe_forward(
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params,          # pytree, leading axis = n_stages (sharded on axis)
    microbatches: Array,   # (n_micro, mb, ...) replicated input
    mesh: Mesh,
    axis: str = "pipe",
) -> Array:
    """Run the pipeline; returns (n_micro, mb, ...) outputs (last stage's)."""
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    total = n_micro + n_stages - 1

    def shard_body(params_local, mbs):
        # params_local: this stage's slice (leading axis 1) — squeeze it.
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(mbs[0])           # incoming activation
        outs = jnp.zeros_like(mbs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (clamped; bubbles compute junk
            # that is never written out)
            feed = mbs[jnp.clip(t, 0, n_micro - 1)]
            x = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params_local, x)
            # completed microbatch id at the LAST stage this tick
            mb_id = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (mb_id >= 0) & (mb_id < n_micro)
            outs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_id, 0, n_micro - 1), 0),
                lambda o: o,
                outs)
            # rotate activations downstream
            buf = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, total, tick, (buf, outs))
        # deliver final outputs from the last stage to every device
        # (masked psum = broadcast; ppermute requires a bijection)
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    from jax import shard_map
    specs_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    return shard_map(
        shard_body, mesh=mesh,
        in_specs=(specs_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def make_stage_fn(apply_period, n_periods_per_stage: int):
    """Wrap a per-period apply into a stage fn (scans its period slice)."""
    def stage_fn(stage_periods, x):
        def body(h, pp):
            return apply_period(pp, h), None
        out, _ = jax.lax.scan(body, x, stage_periods)
        return out
    return stage_fn
