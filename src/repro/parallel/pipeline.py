"""Pipeline parallelism: GPipe schedule over a mesh axis via ppermute.

Each device along the `stage` axis holds one stage's parameters; microbatches
stream through the ring with ``collective_permute``. Used for depth-dominated
models when TP+DP alone can't hold a stage's working set; composes with the
other axes (the stage axis is just another mesh axis).

    y = pipeline_apply(stage_fn, stage_params, x_microbatches, mesh, "stage")

``stage_params`` leaves are stacked (n_stages, ...) and sharded so stage i's
slice lives on stage-axis index i.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   mesh: Mesh, axis: str = "stage"):
    """Run x through n_stages stages with a GPipe schedule.

    stage_fn(params_slice, h) -> h  (one stage's computation)
    stage_params: pytree, leaves (n_stages, ...)
    x: (n_micro, mb, ...) microbatched input (activation-shaped: stage 0
       consumes it; the output collects stage n-1's results).
    Returns (n_micro, mb, ...) outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    total = n_micro + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_slice, xs):
        # params_slice: (1, ...) leaves — my stage; xs: (n_micro, mb, ...)
        params_local = jax.tree.map(lambda p: p[0], params_slice)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros_like(xs)

        def step(state, t):
            carry, outs = state
            # stage 0 injects microbatch t (if still in range)
            inject = jnp.where(t < n_micro, 1, 0)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            h_in = jnp.where((stage == 0) & (inject == 1),
                             xs[mb_idx], carry)
            h_out = stage_fn(params_local, h_in)
            # last stage commits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            commit = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_slice(
                    o, h_out[None], (jnp.maximum(out_idx, 0),)
                    + (0,) * (o.ndim - 1)),
                lambda o: o, outs)
            # ship to next stage
            carry = jax.lax.ppermute(h_out, axis, fwd)
            return (carry, outs), None

        (carry, outs), _ = jax.lax.scan(
            step, (carry_in, outs), jnp.arange(total))
        # only the last stage holds real outputs; broadcast via psum of the
        # masked tensor so every stage returns the same value
        mask = (stage == n_stages - 1).astype(xs.dtype)
        return jax.lax.psum(outs * mask, axis)

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(pspec, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)
