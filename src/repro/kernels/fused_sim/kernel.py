"""Pallas TPU kernel: FUSED rasterize + fluctuate + scatter-add (Fig. 4++).

The paper's Fig. 4 keeps data on-device between stages; this kernel goes one
step further: the (N, 24, 128) patch array never exists in HBM at all. Each
output tile evaluates its depos' bin-integrated Gaussians directly at tile
coordinates and accumulates in VMEM — at MicroBooNE scale (100k depos) this
removes ~1.2 GB of HBM write+read traffic, trading it for ~2x more VPU
transcendentals (erf over tile extents instead of patch extents): a good
trade at 819 GB/s vs ~100+ Gexp/s.

Two additions over the original fused kernel:

  * in-kernel counter RNG — binomial-approximation charge fluctuation is
    applied to each (depo, tile) contribution *inside* the kernel, seeded per
    (depo, tile) from the sim key: ``pltpu.prng_seed``/``prng_random_bits``
    when Mosaic-compiled on TPU, and the portable counter hash from
    ``repro.core.fluctuate`` under the interpreter (which has no TPU PRNG
    lowering). This lifts the old ``fluctuate=False`` restriction: the fused
    strategy now competes in the physics-default configuration.
  * an active-tile variant (``fused_rasterize_scatter_compact``) whose grid
    runs over a *compacted* list of occupied tiles (scalar-prefetched tile
    coordinates) instead of the dense ``(n_tiles, k_max)`` product — kernel
    work scales with occupied readout area, not detector area. Track-like
    depo sets leave most tiles empty; see ``ops.py`` for the binning and the
    occupancy bucketing that bounds retraces.

Grid/binning layout matches ``kernels/scatter_add`` (owner-computes tiles,
scalar-prefetched per-tile depo lists).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fluctuate import box_muller, counter_normals, uniform_from_bits
from repro.kernels import default_interpret

_SQRT2 = 1.4142135623730951
#: stream-id mixing constants (distinct odd 32-bit constants so the
#: (depo, tile) -> stream map is injective enough for statistics)
_C_DEPO = 0x9E3779B9
_C_TILE = 0x7FEB352D


def _tile_normals(s0, s1, d, t_id, *, tw: int, tt: int, tpu_prng: bool):
    """(TW, TT) std normals for one (depo, tile) grid step.

    Seeded from the sim key (``s0``/``s1``, two int32 scalar-prefetch words)
    plus the (depo id, PLANE-LOCAL global tile id) pair, so the dense and
    compacted kernels — and each plane of the multi-plane kernels, which
    pass their plane's own seed words — draw identical streams and their
    fluctuated grids agree bit for bit.
    """
    if tpu_prng:
        # compiled TPU path: hardware PRNG, seeded per (depo, tile)
        pltpu.prng_seed(s0, s1, d, t_id)
        b1 = pltpu.bitcast(pltpu.prng_random_bits((tw, tt)), jnp.uint32)
        b2 = pltpu.bitcast(pltpu.prng_random_bits((tw, tt)), jnp.uint32)
        return box_muller(1.0 - uniform_from_bits(b1), uniform_from_bits(b2))
    # portable path (interpreter / any backend): stateless counter hash
    row = jax.lax.broadcasted_iota(jnp.uint32, (tw, tt), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (tw, tt), 1)
    pix = row * jnp.uint32(tt) + col
    stream = (d.astype(jnp.uint32) * jnp.uint32(_C_DEPO)
              ^ t_id.astype(jnp.uint32) * jnp.uint32(_C_TILE))
    return counter_normals(s0.astype(jnp.uint32), s1.astype(jnp.uint32),
                           stream, pix)


def _depo_tile_contrib(d, dp, t_id, wire_ref, tick_ref, sw_ref, st_ref, q_ref,
                       w0_ref, t0_ref, s0, s1, *, tw: int, tt: int,
                       pw: int, pt: int, tiles_t: int, fluctuate: bool,
                       tpu_prng: bool):
    """(TW, TT) charge contribution of depo ``d`` to global tile ``t_id``.

    Rasterizes the depo's bin-integrated Gaussian at the tile's absolute
    coordinates (masked to the patch support) and, when ``fluctuate``,
    applies the per-pixel binomial normal approximation with in-kernel
    randomness. Pixels outside the patch support have zero mean and zero
    variance, so they stay exactly 0.0 with or without fluctuation.

    ``d`` seeds the RNG stream (plane-LOCAL depo id); ``dp`` indexes the
    parameter refs — the multi-plane kernels flatten their (P, N) operands,
    so ``dp = d + plane * N`` there, while the single-plane kernels pass
    ``dp = d``. ``t_id`` is likewise the plane-local global tile id.
    """
    wire = wire_ref[dp]
    tick = tick_ref[dp]
    sw = sw_ref[dp]
    st = st_ref[dp]
    q = q_ref[dp]
    w0 = w0_ref[dp].astype(jnp.float32)  # patch origin (absolute)
    t0 = t0_ref[dp].astype(jnp.float32)
    tile_w0 = ((t_id // tiles_t) * tw).astype(jnp.float32)
    tile_t0 = ((t_id % tiles_t) * tt).astype(jnp.float32)

    # absolute wire/tick coordinates of this tile's rows/cols
    aw = tile_w0 + jax.lax.broadcasted_iota(jnp.float32, (tw, 1), 0)
    at = tile_t0 + jax.lax.broadcasted_iota(jnp.float32, (1, tt), 1)

    lo_w = jax.lax.erf((aw - wire) / (sw * _SQRT2))
    hi_w = jax.lax.erf((aw + 1.0 - wire) / (sw * _SQRT2))
    ww = jnp.maximum(0.5 * (hi_w - lo_w), 0.0)        # (TW, 1)
    in_w = (aw >= w0) & (aw < w0 + pw)                # patch support
    ww = jnp.where(in_w, ww, 0.0)

    lo_t = jax.lax.erf((at - tick) / (st * _SQRT2))
    hi_t = jax.lax.erf((at + 1.0 - tick) / (st * _SQRT2))
    wt = jnp.maximum(0.5 * (hi_t - lo_t), 0.0)        # (1, TT)
    in_t = (at >= t0) & (at < t0 + pt)
    wt = jnp.where(in_t, wt, 0.0)

    vals = q * ww * wt
    if fluctuate:
        # binomial normal approximation, matching core.fluctuate:
        # mean = vals, var = vals * (1 - vals / q), clamped at zero
        normals = _tile_normals(s0, s1, d, t_id, tw=tw, tt=tt,
                                tpu_prng=tpu_prng)
        qq = jnp.maximum(q, 1.0)
        p = jnp.clip(vals / qq, 0.0, 1.0)
        var = jnp.maximum(vals * (1.0 - p), 0.0)
        vals = jnp.maximum(vals + jnp.sqrt(var) * normals, 0.0)
    return vals


def _fused_kernel(ids_ref, wire_ref, tick_ref, sw_ref, st_ref, q_ref,
                  w0_ref, t0_ref, seed_ref, out_ref, *, k_max: int, tw: int,
                  tt: int, pw: int, pt: int, tiles_t: int, fluctuate: bool,
                  tpu_prng: bool):
    """Grid step (i, k): rasterize depo ids[i*K+k] straight into tile i."""
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = ids_ref[i * k_max + k]

    @pl.when(d >= 0)
    def _accum():
        dd = jnp.maximum(d, 0)
        out_ref[...] += _depo_tile_contrib(
            dd, dd, i, wire_ref, tick_ref, sw_ref, st_ref, q_ref,
            w0_ref, t0_ref, seed_ref[0], seed_ref[1], tw=tw, tt=tt, pw=pw,
            pt=pt, tiles_t=tiles_t, fluctuate=fluctuate, tpu_prng=tpu_prng)


def _fused_kernel_compact(tiles_ref, ids_ref, wire_ref, tick_ref, sw_ref,
                          st_ref, q_ref, w0_ref, t0_ref, seed_ref, out_ref, *,
                          k_max: int, tw: int, tt: int, pw: int, pt: int,
                          tiles_t: int, fluctuate: bool, tpu_prng: bool):
    """Grid step (i, k): rasterize depo ids[i*K+k] into ACTIVE tile i.

    ``tiles_ref[i]`` holds the global tile id of the i-th occupied tile
    (scalar-prefetched; -1 pads the bucketed active list). Inactive grid
    steps only zero their output block.
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
        dd = jnp.maximum(d, 0)
        out_ref[0] += _depo_tile_contrib(
            dd, dd, jnp.maximum(t_id, 0), wire_ref, tick_ref,
            sw_ref, st_ref, q_ref, w0_ref, t0_ref, seed_ref[0], seed_ref[1],
            tw=tw, tt=tt, pw=pw, pt=pt, tiles_t=tiles_t, fluctuate=fluctuate,
            tpu_prng=tpu_prng)


def _fused_kernel_multiplane(ids_ref, wire_ref, tick_ref, sw_ref, st_ref,
                             q_ref, w0_ref, t0_ref, seed_ref, out_ref, *,
                             k_max: int, tw: int, tt: int, pw: int, pt: int,
                             tiles_t: int, n_tiles: int, n_depos: int,
                             fluctuate: bool, tpu_prng: bool):
    """Grid step (i, k) over the PLANE-MAJOR flat tile axis i = p*T + t.

    Every depo's parameters are loaded once per overlapped tile across ALL
    planes of one launch: the params are the per-plane projections stacked
    (and flattened plane-major), the depo ids are each plane's binned lists
    concatenated, and the RNG seed words are the per-plane folded subkeys —
    so plane p's output block is bit-identical to the single-plane kernel
    run with ``fold_in(kf, p)``.
    """
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = i // n_tiles
    t_local = i - p * n_tiles
    d = ids_ref[i * k_max + k]

    @pl.when(d >= 0)
    def _accum():
        dd = jnp.maximum(d, 0)
        out_ref[...] += _depo_tile_contrib(
            dd, dd + p * n_depos, t_local, wire_ref, tick_ref, sw_ref,
            st_ref, q_ref, w0_ref, t0_ref, seed_ref[2 * p],
            seed_ref[2 * p + 1], tw=tw, tt=tt, pw=pw, pt=pt, tiles_t=tiles_t,
            fluctuate=fluctuate, tpu_prng=tpu_prng)


def _fused_kernel_multiplane_compact(tiles_ref, ids_ref, wire_ref, tick_ref,
                                     sw_ref, st_ref, q_ref, w0_ref, t0_ref,
                                     seed_ref, out_ref, *, k_max: int,
                                     tw: int, tt: int, pw: int, pt: int,
                                     tiles_t: int, n_cap: int, n_depos: int,
                                     fluctuate: bool, tpu_prng: bool):
    """Active-tile multi-plane kernel: i runs over the plane-major
    concatenation of each plane's compacted tile list (``n_cap`` slots per
    plane); ``tiles_ref[i]`` is the PLANE-LOCAL global tile id."""
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = i // n_cap
    t_id = tiles_ref[i]
    d = ids_ref[i * k_max + k]

    @pl.when((t_id >= 0) & (d >= 0))
    def _accum():
        dd = jnp.maximum(d, 0)
        out_ref[0] += _depo_tile_contrib(
            dd, dd + p * n_depos, jnp.maximum(t_id, 0), wire_ref, tick_ref,
            sw_ref, st_ref, q_ref, w0_ref, t0_ref, seed_ref[2 * p],
            seed_ref[2 * p + 1], tw=tw, tt=tt, pw=pw, pt=pt, tiles_t=tiles_t,
            fluctuate=fluctuate, tpu_prng=tpu_prng)


def _seed_operand(seed):
    """(2,) int32 scalar-prefetch operand from raw PRNG key data (or None)."""
    if seed is None:
        return jnp.zeros((2,), jnp.int32)
    return jnp.asarray(seed).astype(jnp.uint32).reshape(-1)[:2].view(jnp.int32)


def _seed_operand_planes(seeds, num_planes: int):
    """(2P,) int32 scalar-prefetch operand from stacked (P, ...) key data."""
    if seeds is None:
        return jnp.zeros((2 * num_planes,), jnp.int32)
    seeds = jnp.asarray(seeds).astype(jnp.uint32).reshape(num_planes, -1)
    return seeds[:, :2].reshape(-1).view(jnp.int32)


def fused_rasterize_scatter(wire, tick, sigma_w, sigma_t, charge, w0, t0,
                            tile_ids, *, num_wires: int, num_ticks: int,
                            tw: int, tt: int, k_max: int, pw: int, pt: int,
                            interpret: bool | None = None, seed=None,
                            fluctuate: bool = False):
    """Depos -> charge grid in ONE kernel (no patch array in HBM).

    Scalar-prefetch operands: tile_ids (n_tiles*k_max,) int32 (-1 padded),
    depo params (N,) f32 / int32, seed (2,) int32 raw key data (only read
    when ``fluctuate``).
    """
    interpret = default_interpret() if interpret is None else interpret
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t

    kernel = functools.partial(_fused_kernel, k_max=k_max, tw=tw, tt=tt,
                               pw=pw, pt=pt, tiles_t=tiles_t,
                               fluctuate=fluctuate, tpu_prng=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(n_tiles, k_max),
        in_specs=[],
        out_specs=pl.BlockSpec(
            (tw, tt), lambda i, k, *refs: (i // tiles_t, i % tiles_t)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles_w * tw, tiles_t * tt),
                                       jnp.float32),
        interpret=interpret,
    )(tile_ids, wire.astype(jnp.float32), tick.astype(jnp.float32),
      sigma_w.astype(jnp.float32), sigma_t.astype(jnp.float32),
      charge.astype(jnp.float32), w0.astype(jnp.int32), t0.astype(jnp.int32),
      _seed_operand(seed))
    return out[:num_wires, :num_ticks]


def fused_rasterize_scatter_compact(wire, tick, sigma_w, sigma_t, charge,
                                    w0, t0, active_tiles, tile_ids, *,
                                    num_wires: int, num_ticks: int, tw: int,
                                    tt: int, k_max: int, pw: int, pt: int,
                                    interpret: bool | None = None, seed=None,
                                    fluctuate: bool = False):
    """Active-tile fused kernel: grid (n_active, k_max), not (n_tiles, k_max).

    active_tiles : (n_active,) int32 global tile ids, -1 padded
    tile_ids     : (n_active * k_max,) int32 depo ids per active tile
    The kernel emits one (tw, tt) block per active slot; the blocks are then
    scattered back into the full grid (an O(occupied area) write).
    """
    interpret = default_interpret() if interpret is None else interpret
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t
    n_active = active_tiles.shape[0]

    kernel = functools.partial(_fused_kernel_compact, k_max=k_max, tw=tw,
                               tt=tt, pw=pw, pt=pt, tiles_t=tiles_t,
                               fluctuate=fluctuate, tpu_prng=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(n_active, k_max),
        in_specs=[],
        out_specs=pl.BlockSpec((1, tw, tt), lambda i, k, *refs: (i, 0, 0)),
    )
    blocks = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_active, tw, tt), jnp.float32),
        interpret=interpret,
    )(active_tiles, tile_ids, wire.astype(jnp.float32),
      tick.astype(jnp.float32), sigma_w.astype(jnp.float32),
      sigma_t.astype(jnp.float32), charge.astype(jnp.float32),
      w0.astype(jnp.int32), t0.astype(jnp.int32), _seed_operand(seed))
    return scatter_tiles_to_grid(blocks, active_tiles, tiles_w, tiles_t,
                                 tw, tt)[:num_wires, :num_ticks]


def fused_rasterize_scatter_multiplane(wire, tick, sigma_w, sigma_t, charge,
                                       w0, t0, tile_ids, *, num_planes: int,
                                       num_wires: int, num_ticks: int,
                                       tw: int, tt: int, k_max: int, pw: int,
                                       pt: int, interpret: bool | None = None,
                                       seeds=None, fluctuate: bool = False):
    """All P planes' charge grids in ONE kernel launch (dense tile layout).

    Depo params are the per-plane projections, shape (P, N) each (flattened
    plane-major for the scalar-prefetch refs); ``tile_ids`` is the
    concatenation of each plane's dense (n_tiles*k_max,) binned depo lists
    (plane-LOCAL depo ids); ``seeds`` is (P, 2) raw key data of the
    per-plane folded subkeys. Returns (P, num_wires, num_ticks) f32 —
    plane p bit-identical to ``fused_rasterize_scatter`` with plane p's
    params and seed.
    """
    interpret = default_interpret() if interpret is None else interpret
    n = wire.shape[-1]
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t

    kernel = functools.partial(
        _fused_kernel_multiplane, k_max=k_max, tw=tw, tt=tt, pw=pw, pt=pt,
        tiles_t=tiles_t, n_tiles=n_tiles, n_depos=n, fluctuate=fluctuate,
        tpu_prng=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(num_planes * n_tiles, k_max),
        in_specs=[],
        # i = p*n_tiles + t_local, so i // tiles_t = p*tiles_w + block row
        # and i % tiles_t = block col: the single-plane index map extends
        # unchanged to the plane-major stacked output
        out_specs=pl.BlockSpec(
            (tw, tt), lambda i, k, *refs: (i // tiles_t, i % tiles_t)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (num_planes * tiles_w * tw, tiles_t * tt), jnp.float32),
        interpret=interpret,
    )(tile_ids, wire.astype(jnp.float32).reshape(-1),
      tick.astype(jnp.float32).reshape(-1),
      sigma_w.astype(jnp.float32).reshape(-1),
      sigma_t.astype(jnp.float32).reshape(-1),
      charge.astype(jnp.float32).reshape(-1),
      w0.astype(jnp.int32).reshape(-1), t0.astype(jnp.int32).reshape(-1),
      _seed_operand_planes(seeds, num_planes))
    out = out.reshape(num_planes, tiles_w * tw, tiles_t * tt)
    return out[:, :num_wires, :num_ticks]


def fused_rasterize_scatter_multiplane_compact(
        wire, tick, sigma_w, sigma_t, charge, w0, t0, active_tiles, tile_ids,
        *, num_planes: int, num_wires: int, num_ticks: int, tw: int, tt: int,
        k_max: int, pw: int, pt: int, interpret: bool | None = None,
        seeds=None,
        fluctuate: bool = False):
    """Active-tile multi-plane fused kernel: grid (P*n_cap, k_max).

    active_tiles : (P*n_cap,) int32 plane-LOCAL global tile ids, -1 padded
                   (each plane's compacted list occupies n_cap slots)
    tile_ids     : (P*n_cap*k_max,) int32 plane-local depo ids
    Returns (P, num_wires, num_ticks) f32, bit-identical per plane to the
    dense multi-plane kernel (RNG streams key on plane-local tile ids,
    which compaction preserves).
    """
    interpret = default_interpret() if interpret is None else interpret
    n = wire.shape[-1]
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t
    n_cap = active_tiles.shape[0] // num_planes

    kernel = functools.partial(
        _fused_kernel_multiplane_compact, k_max=k_max, tw=tw, tt=tt, pw=pw,
        pt=pt, tiles_t=tiles_t, n_cap=n_cap, n_depos=n, fluctuate=fluctuate,
        tpu_prng=not interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(num_planes * n_cap, k_max),
        in_specs=[],
        out_specs=pl.BlockSpec((1, tw, tt), lambda i, k, *refs: (i, 0, 0)),
    )
    blocks = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_planes * n_cap, tw, tt),
                                       jnp.float32),
        interpret=interpret,
    )(active_tiles, tile_ids, wire.astype(jnp.float32).reshape(-1),
      tick.astype(jnp.float32).reshape(-1),
      sigma_w.astype(jnp.float32).reshape(-1),
      sigma_t.astype(jnp.float32).reshape(-1),
      charge.astype(jnp.float32).reshape(-1),
      w0.astype(jnp.int32).reshape(-1), t0.astype(jnp.int32).reshape(-1),
      _seed_operand_planes(seeds, num_planes))
    grids = scatter_tiles_to_grid_planes(blocks, active_tiles, num_planes,
                                         tiles_w, tiles_t, tw, tt)
    return grids[:, :num_wires, :num_ticks]


def scatter_tiles_to_grid(blocks, active_tiles, tiles_w: int, tiles_t: int,
                          tw: int, tt: int):
    """Place (n_active, tw, tt) tile blocks into the full padded grid.

    Padding slots (active_tiles == -1) are dropped; unoccupied tiles stay
    zero. The write is proportional to the occupied area.
    """
    n_tiles = tiles_w * tiles_t
    dest = jnp.where(active_tiles >= 0, active_tiles, n_tiles)
    full = jnp.zeros((n_tiles, tw, tt), blocks.dtype)
    full = full.at[dest].set(blocks, mode="drop")
    return full.reshape(tiles_w, tiles_t, tw, tt).swapaxes(1, 2).reshape(
        tiles_w * tw, tiles_t * tt)


def scatter_tiles_to_grid_planes(blocks, active_tiles, num_planes: int,
                                 tiles_w: int, tiles_t: int, tw: int,
                                 tt: int):
    """Place (P*n_cap, tw, tt) tile blocks into (P, W_pad, T_pad) grids.

    ``active_tiles`` holds plane-LOCAL tile ids in plane-major n_cap-slot
    runs; each plane's blocks scatter into its own grid (padding slots
    dropped, unoccupied tiles stay zero)."""
    n_tiles = tiles_w * tiles_t
    n_cap = active_tiles.shape[0] // num_planes
    offs = jnp.repeat(
        jnp.arange(num_planes, dtype=jnp.int32) * n_tiles, n_cap)
    dest = jnp.where(active_tiles >= 0, active_tiles + offs,
                     num_planes * n_tiles)
    full = jnp.zeros((num_planes * n_tiles, tw, tt), blocks.dtype)
    full = full.at[dest].set(blocks, mode="drop")
    return full.reshape(num_planes, tiles_w, tiles_t, tw, tt).swapaxes(
        2, 3).reshape(num_planes, tiles_w * tw, tiles_t * tt)
