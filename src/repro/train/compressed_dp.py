"""Cross-pod data parallelism with int8 error-feedback gradient compression.

The `pod` axis crosses the slow inter-pod links (DCN / optical), so its
gradient all-reduce is the one worth compressing. This wraps a per-pod train
step in ``shard_map`` over the pod axis: each pod computes grads on its local
batch shard, the pod-axis mean is taken with the int8 error-feedback
collective (``repro.parallel.collectives``), and the residual quantization
error is carried in the optimizer state so the update remains unbiased over
time (error feedback).

Inside a pod, GSPMD handles DP/TP/SP exactly as in the plain step — shard_map
is applied only over `pod`.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import OptimizerConfig
from repro.models.model import Model
from repro.optim.adamw import OptState, adamw_update
from repro.parallel.collectives import compressed_psum
from repro.train.train_step import make_loss_fn


class CompressedState(NamedTuple):
    opt: OptState
    error: Any          # error-feedback residual pytree (f32, like params)


def init_compressed_state(params, opt_state: OptState) -> CompressedState:
    err = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return CompressedState(opt=opt_state, error=err)


def make_compressed_train_step(model: Model, opt_cfg: OptimizerConfig,
                               mesh: Mesh, pod_axis: str = "pod"):
    """train_step(params, CompressedState, batch) with int8-EF pod sync."""
    loss_fn = make_loss_fn(model)

    def local_step(params, state: CompressedState, batch):
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        # pod-mean with int8 error feedback (slow-link compression)
        mean_grads, new_err = compressed_psum(grads, pod_axis, state.error)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, params, mean_grads, state.opt)
        metrics = {**metrics, **opt_metrics,
                   "loss": jax.lax.pmean(metrics["loss"], pod_axis)}
        return new_params, CompressedState(opt=new_opt, error=new_err), metrics

    # only the batch is pod-sharded; params/state replicated across pods
    def batch_spec(x):
        return P(pod_axis)

    def step(params, state, batch):
        in_specs = (
            jax.tree.map(lambda _: P(), params),
            jax.tree.map(lambda _: P(), state),
            jax.tree.map(lambda _: P(pod_axis), batch),
        )
        out_specs = (
            jax.tree.map(lambda _: P(), params),
            jax.tree.map(lambda _: P(), state),
            {"loss": P(), "aux": P(), "lr": P(), "grad_norm": P()},
        )
        fn = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return fn(params, state, batch)

    return step
