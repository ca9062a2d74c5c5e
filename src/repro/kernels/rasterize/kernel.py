"""Pallas TPU kernel: batched depo rasterization + fused Box–Muller fluctuation.

TPU adaptation of the paper's rasterization CUDA kernel (§3):

* GPU version: one thread block per depo, 20×20 threads, one launch per depo
  (concurrency < 1000 — the paper's identified flaw).
* TPU version: ONE ``pallas_call`` for all N depos. Grid = N / DEPO_BLOCK;
  each grid step rasterizes DEPO_BLOCK depos into a VMEM-resident
  (DEPO_BLOCK, PW, PT) patch block. The per-axis erf weights are computed as
  (B, PW) / (B, PT) VPU ops and combined by a broadcasted outer product —
  O(pw+pt) transcendentals per depo, vectorized across the depo block.
* Fluctuation is FUSED into the same kernel (the paper's separate
  "Fluctuation" step): Box–Muller (paper §4.3.1) over a pre-computed uniform
  pool (the paper's "random number pool"), applied to the binomial
  normal-approximation with no extra HBM round-trip.

Patch dims are padded to TPU tiles: PT (ticks, lane axis) -> 128, PW
(wires, sublane axis) -> multiple of 8. Padding pixels are masked to zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

_SQRT2 = 1.4142135623730951


def _rasterize_kernel(wire_ref, tick_ref, sw_ref, st_ref, q_ref,
                      w0_ref, t0_ref, u1_ref, u2_ref, out_ref,
                      *, pw: int, pt: int, fluctuate: bool):
    """One grid step: rasterize a block of B depos.

    Refs:
      wire/tick/sw/st/q/w0/t0 : (B, 1) f32 depo parameters (VMEM)
      u1, u2                  : (B, PW, PT) f32 uniforms for Box–Muller
      out                     : (B, PW, PT) f32 patches
    """
    b, pw_pad, pt_pad = out_ref.shape

    wire = wire_ref[:, 0][:, None]            # (B, 1)
    tick = tick_ref[:, 0][:, None]
    sw = sw_ref[:, 0][:, None]
    st = st_ref[:, 0][:, None]
    q = q_ref[:, 0][:, None, None]            # (B, 1, 1)
    w0 = w0_ref[:, 0][:, None]
    t0 = t0_ref[:, 0][:, None]

    # per-axis bin-integrated Gaussian weights (VPU transcendentals)
    iw = jax.lax.broadcasted_iota(jnp.float32, (b, pw_pad), 1)
    lo_w = jax.lax.erf((w0 + iw - wire) / (sw * _SQRT2))
    hi_w = jax.lax.erf((w0 + iw + 1.0 - wire) / (sw * _SQRT2))
    ww = jnp.maximum(0.5 * (hi_w - lo_w), 0.0)   # (B, PW); clamp f32 tails
    ww = jnp.where(iw < pw, ww, 0.0)          # mask wire padding

    it = jax.lax.broadcasted_iota(jnp.float32, (b, pt_pad), 1)
    lo_t = jax.lax.erf((t0 + it - tick) / (st * _SQRT2))
    hi_t = jax.lax.erf((t0 + it + 1.0 - tick) / (st * _SQRT2))
    wt = jnp.maximum(0.5 * (hi_t - lo_t), 0.0)   # (B, PT)
    wt = jnp.where(it < pt, wt, 0.0)          # mask tick padding

    patch = q * ww[:, :, None] * wt[:, None, :]   # (B, PW, PT) outer product

    if fluctuate:
        # binomial -> normal approximation, noise via Box–Muller of the pool
        u1 = jnp.maximum(u1_ref[...], 1e-12)
        u2 = u2_ref[...]
        normal = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
        p = jnp.clip(patch / jnp.maximum(q, 1.0), 0.0, 1.0)
        var = jnp.maximum(patch * (1.0 - p), 0.0)
        patch = jnp.maximum(patch + jnp.sqrt(var) * normal, 0.0)

    out_ref[...] = patch


def rasterize_pallas(wire, tick, sigma_w, sigma_t, charge, w0, t0, u1, u2, *,
                     pw: int, pt: int, pw_pad: int = 0, pt_pad: int = 128,
                     depo_block: int = 256, fluctuate: bool = True,
                     interpret: bool | None = None):
    """Rasterize all depos in one pallas_call.

    Args: depo params (N,) f32 (w0/t0 pre-cast to f32); u1/u2 (N, PW, PT)
    uniform pools. Returns (N, PW_pad, PT_pad) f32 patches (padding zeroed).
    """
    interpret = default_interpret() if interpret is None else interpret
    n = wire.shape[0]
    pw_pad = pw_pad or ((pw + 7) // 8 * 8)
    assert pt <= pt_pad and pw <= pw_pad
    assert n % depo_block == 0, f"pad depo count {n} to a multiple of {depo_block}"
    grid = (n // depo_block,)

    def col(x):
        return x.astype(jnp.float32).reshape(n, 1)

    scalar_spec = pl.BlockSpec((depo_block, 1), lambda i: (i, 0))
    pool_spec = pl.BlockSpec((depo_block, pw_pad, pt_pad), lambda i: (i, 0, 0))

    kernel = functools.partial(_rasterize_kernel, pw=pw, pt=pt,
                               fluctuate=fluctuate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scalar_spec] * 7 + [pool_spec, pool_spec],
        out_specs=pl.BlockSpec((depo_block, pw_pad, pt_pad),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, pw_pad, pt_pad), jnp.float32),
        interpret=interpret,
    )(col(wire), col(tick), col(sigma_w), col(sigma_t), col(charge),
      col(w0), col(t0), u1, u2)
