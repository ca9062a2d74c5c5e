"""Pallas TPU kernel: owner-computes tile-binned scatter-add.

TPU adaptation of ``Kokkos::atomic_add`` (paper §5, Fig. 5). TPUs have no
usable device atomics, so the scatter is inverted into a gather:

  * the output grid is cut into (TW, TT) VMEM tiles;
  * depos are pre-binned (ops.py) into per-tile lists — a depo appears in the
    list of every tile its patch overlaps (≤4 tiles when tile ≥ patch);
  * the kernel grid is (n_tiles, K): tile i accumulates its k-th depo's
    patch into a VMEM-resident accumulator. The patch block is fetched by a
    *scalar-prefetch-driven* BlockSpec index_map (the depo id list lives in
    SMEM), so each grid step DMAs exactly one patch into VMEM.

The accumulation is bitwise deterministic (fixed order per tile), unlike
atomics — a correctness upgrade over the paper's approach, for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _scatter_kernel(ids_ref, w0_ref, t0_ref, patch_ref, out_ref, *,
                    k_max: int, tw: int, tt: int, pw_pad: int, pt_pad: int,
                    tiles_t: int):
    """Grid step (i, k): accumulate depo ids[i*K+k]'s patch into tile i.

    ids/w0/t0 are scalar-prefetch refs (SMEM): ids (n_tiles*K,), w0/t0 (N,).
    patch_ref: (1, PW, PT) VMEM block of the selected depo's patch.
    out_ref: (TW, TT) VMEM accumulator for tile i (revisited across k).
    """
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = ids_ref[i * k_max + k]

    @pl.when(d >= 0)
    def _accum():
        tile_w0 = (i // tiles_t) * tw
        tile_t0 = (i % tiles_t) * tt
        off_w = w0_ref[jnp.maximum(d, 0)] - tile_w0   # may be negative
        off_t = t0_ref[jnp.maximum(d, 0)] - tile_t0
        # patches may arrive in a narrow dtype (cfg.patch_dtype="bfloat16"):
        # the DMA moves the narrow bits, the VMEM accumulation stays f32
        patch = patch_ref[0].astype(jnp.float32)      # (PW, PT)
        # place the patch into a zero-padded staging buffer at a dynamic
        # offset, then add the tile window — static shapes, dynamic offsets.
        buf = jnp.zeros((tw + 2 * pw_pad, tt + 2 * pt_pad), patch.dtype)
        buf = jax.lax.dynamic_update_slice(
            buf, patch, (off_w + pw_pad, off_t + pt_pad))
        out_ref[...] += jax.lax.dynamic_slice(
            buf, (pw_pad, pt_pad), (tw, tt))


def _scatter_kernel_compact(tiles_ref, ids_ref, w0_ref, t0_ref, patch_ref,
                            out_ref, *, k_max: int, tw: int, tt: int,
                            pw_pad: int, pt_pad: int, tiles_t: int):
    """Grid step (i, k): accumulate depo ids[i*K+k] into ACTIVE tile i.

    Identical to ``_scatter_kernel`` except the tile coordinate comes from
    the scalar-prefetched active-tile list (``tiles_ref[i]`` is a global tile
    id, -1 padded) and the output is one (1, TW, TT) block per active slot —
    kernel work scales with occupied tiles, not detector tiles.
    """
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    t_id = tiles_ref[i]
    d = ids_ref[i * k_max + k]

    @pl.when((t_id >= 0) & (d >= 0))
    def _accum():
        tile_w0 = (jnp.maximum(t_id, 0) // tiles_t) * tw
        tile_t0 = (jnp.maximum(t_id, 0) % tiles_t) * tt
        off_w = w0_ref[jnp.maximum(d, 0)] - tile_w0   # may be negative
        off_t = t0_ref[jnp.maximum(d, 0)] - tile_t0
        patch = patch_ref[0].astype(jnp.float32)      # (PW, PT)
        buf = jnp.zeros((tw + 2 * pw_pad, tt + 2 * pt_pad), jnp.float32)
        buf = jax.lax.dynamic_update_slice(
            buf, patch, (off_w + pw_pad, off_t + pt_pad))
        out_ref[0] += jax.lax.dynamic_slice(
            buf, (pw_pad, pt_pad), (tw, tt))


def scatter_add_pallas(patches, w0, t0, tile_ids, *, num_wires: int,
                       num_ticks: int, tw: int, tt: int, k_max: int,
                       interpret: bool | None = None):
    """Owner-computes scatter-add.

    patches  : (N, PW_pad, PT_pad) f32 (zero-padded beyond the true patch)
    w0, t0   : (N,) int32 patch origins
    tile_ids : (n_tiles * k_max,) int32 depo ids per tile, -1 padded
    Returns the (num_wires_padded, num_ticks_padded) grid (tile-aligned).
    """
    interpret = default_interpret() if interpret is None else interpret
    n, pw_pad, pt_pad = patches.shape
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t
    assert tw >= pw_pad and tt >= pt_pad, "tile must cover a padded patch"

    kernel = functools.partial(
        _scatter_kernel, k_max=k_max, tw=tw, tt=tt, pw_pad=pw_pad,
        pt_pad=pt_pad, tiles_t=tiles_t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, k_max),
        in_specs=[
            pl.BlockSpec(
                (1, pw_pad, pt_pad),
                # fetch the patch of the depo this (tile, k) step handles
                lambda i, k, ids, w0s, t0s: (
                    jnp.maximum(ids[i * k_max + k], 0), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (tw, tt), lambda i, k, ids, w0s, t0s: (i // tiles_t, i % tiles_t)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles_w * tw, tiles_t * tt),
                                       jnp.float32),
        interpret=interpret,
    )(tile_ids, w0, t0, patches)


def scatter_add_pallas_compact(patches, w0, t0, active_tiles, tile_ids, *,
                               num_wires: int, num_ticks: int, tw: int,
                               tt: int, k_max: int, interpret: bool | None = None):
    """Active-tile owner-computes scatter-add.

    active_tiles : (n_active,) int32 global tile ids of occupied tiles, -1
                   padded to the occupancy bucket
    tile_ids     : (n_active * k_max,) int32 depo ids per active tile
    Returns (n_active, tw, tt) f32 tile blocks — the caller scatters them
    back into the full grid (see ``fused_sim.kernel.scatter_tiles_to_grid``).
    """
    interpret = default_interpret() if interpret is None else interpret
    n, pw_pad, pt_pad = patches.shape
    tiles_t = (num_ticks + tt - 1) // tt
    n_active = active_tiles.shape[0]
    assert tw >= pw_pad and tt >= pt_pad, "tile must cover a padded patch"

    kernel = functools.partial(
        _scatter_kernel_compact, k_max=k_max, tw=tw, tt=tt, pw_pad=pw_pad,
        pt_pad=pt_pad, tiles_t=tiles_t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_active, k_max),
        in_specs=[
            pl.BlockSpec(
                (1, pw_pad, pt_pad),
                lambda i, k, tiles, ids, w0s, t0s: (
                    jnp.maximum(ids[i * k_max + k], 0), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, tw, tt),
                               lambda i, k, tiles, ids, w0s, t0s: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_active, tw, tt), jnp.float32),
        interpret=interpret,
    )(active_tiles, tile_ids, w0, t0, patches)
