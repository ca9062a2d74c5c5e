"""Binning preprocessor + jit'd wrappers for the owner-computes scatter kernel.

Two launch layouts:

  dense   : the Pallas grid covers every (tile, k) pair — simple, but work
            scales with *detector* area even when track-like depos leave most
            readout tiles empty.
  compact : depos are binned, empty tiles dropped, and the grid runs over the
            compacted (n_active, k_max) list with the global tile coordinate
            scalar-prefetched. Occupancy is measured on the host when inputs
            are concrete (bucketed to a power of two so retrace count stays
            logarithmic); under a trace it falls back to the static bound
            min(n_tiles, next_pow2(4N)) — each depo's patch overlaps at most
            4 tiles, so the bound is exact for sparse events and degrades to
            the dense layout only when the detector is saturated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.scatter_add.kernel import (scatter_add_pallas,
                                              scatter_add_pallas_compact)


def next_pow2(n: int, lo: int = 8) -> int:
    """Smallest power of two >= max(n, lo) — the retrace-bounding bucket."""
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


def _candidate_tiles(w0, t0, pw_pad: int, pt_pad: int, tiles_t: int,
                     tw: int, tt: int, n_tiles: int):
    """Per-depo candidate tile ids (N, 4) + first-occurrence mask (N, 4).

    A padded patch at (w0, t0) spans [w0, w0+pw_pad) x [t0, t0+pt_pad) and
    overlaps at most 4 tiles when tile >= padded patch: the tiles containing
    its 4 corners. Corners sharing a tile are deduped via ``first``.
    """
    n = w0.shape[0]
    tiles_w = n_tiles // tiles_t
    cw0 = w0 // tw
    ct0 = t0 // tt
    # clamp the far corner to the last tile row/col: a PADDED patch may spill
    # past the tiled extent even though its in-grid pixels do not, and an
    # unclamped tick overflow would alias tile (w, tiles_t) onto the valid
    # tile (w+1, 0) — burning a k_max slot there (worst case evicting a
    # genuine depo) and falsely marking it active for the compact layout
    cw1 = jnp.minimum((w0 + pw_pad - 1) // tw, tiles_w - 1)
    ct1 = jnp.minimum((t0 + pt_pad - 1) // tt, tiles_t - 1)
    cand_w = jnp.stack([cw0, cw0, cw1, cw1], 1)          # (N, 4)
    cand_t = jnp.stack([ct0, ct1, ct0, ct1], 1)
    tile = cand_w * tiles_t + cand_t                     # (N, 4)
    first = jnp.ones_like(tile, dtype=bool)
    for a in range(1, 4):
        dup = jnp.zeros((n,), bool)
        for b in range(a):
            dup = dup | (tile[:, a] == tile[:, b])
        first = first.at[:, a].set(~dup)
    return tile, first


def _sorted_tile_runs(w0, t0, pw_pad: int, pt_pad: int, num_wires: int,
                      num_ticks: int, tw: int, tt: int):
    """Sort (tile, depo) pairs by tile and annotate the equal-tile runs.

    Returns (tile_s, depo_s, is_first, rank, seg_id, n_tiles): entries sorted
    by tile id (invalid entries pushed past ``n_tiles``), each entry's rank
    within its run, and the 0-based run index ``seg_id`` (valid runs first,
    since the sort is ascending).
    """
    n = w0.shape[0]
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t

    tile, first = _candidate_tiles(w0, t0, pw_pad, pt_pad, tiles_t, tw, tt,
                                   n_tiles)
    depo_id = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                               (n, 4))
    tile_flat = jnp.where(first, tile, n_tiles).reshape(-1)  # invalid -> n_tiles
    depo_flat = depo_id.reshape(-1)
    tile_s, depo_s = jax.lax.sort_key_val(tile_flat, depo_flat)
    # rank within equal-tile run = position - first position of the run
    idx = jnp.arange(tile_s.shape[0], dtype=jnp.int32)
    is_first = jnp.concatenate([jnp.array([True]),
                                tile_s[1:] != tile_s[:-1]])
    run_start = jnp.where(is_first, idx, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, run_start)
    rank = idx - run_start
    seg_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    return tile_s, depo_s, is_first, rank, seg_id, n_tiles


def bin_depos_to_tiles(w0, t0, pw_pad: int, pt_pad: int, num_wires: int,
                       num_ticks: int, tw: int, tt: int, k_max: int):
    """Build per-tile depo id lists (n_tiles*k_max,), -1 padded.

    A padded patch at (w0, t0) spans [w0, w0+pw_pad) x [t0, t0+pt_pad) and can
    overlap at most 4 tiles when tile >= padded patch. Each depo is appended
    to every overlapping tile's list. Overflow beyond k_max is dropped
    (choose k_max generously; tests assert no drops).
    """
    tile_s, depo_s, _, rank, _, n_tiles = _sorted_tile_runs(
        w0, t0, pw_pad, pt_pad, num_wires, num_ticks, tw, tt)
    valid = (tile_s < n_tiles) & (rank < k_max)
    slot = jnp.where(valid, tile_s * k_max + rank, n_tiles * k_max)
    ids = jnp.full((n_tiles * k_max + 1,), -1, jnp.int32)
    ids = ids.at[slot].set(jnp.where(valid, depo_s, -1), mode="drop")
    return ids[:-1], n_tiles


def bin_depos_to_tiles_compact(w0, t0, pw_pad: int, pt_pad: int,
                               num_wires: int, num_ticks: int, tw: int,
                               tt: int, k_max: int, n_cap: int):
    """Compacted binning: active tile list + per-active-tile depo lists.

    Returns (active_tiles, ids): active_tiles (n_cap,) int32 global tile ids
    (-1 padded), ids (n_cap * k_max,) int32 depo ids (-1 padded). ``n_cap``
    must be >= the true number of occupied tiles (min(n_tiles, 4*N) always
    is); overflowing tiles would be silently dropped.
    """
    tile_s, depo_s, is_first, rank, seg_id, n_tiles = _sorted_tile_runs(
        w0, t0, pw_pad, pt_pad, num_wires, num_ticks, tw, tt)
    valid = (tile_s < n_tiles) & (rank < k_max) & (seg_id < n_cap)
    slot = jnp.where(valid, seg_id * k_max + rank, n_cap * k_max)
    ids = jnp.full((n_cap * k_max + 1,), -1, jnp.int32)
    ids = ids.at[slot].set(jnp.where(valid, depo_s, -1), mode="drop")

    head = is_first & (tile_s < n_tiles) & (seg_id < n_cap)
    tiles = jnp.full((n_cap + 1,), -1, jnp.int32)
    tiles = tiles.at[jnp.where(head, seg_id, n_cap)].set(
        jnp.where(head, tile_s, -1), mode="drop")
    return tiles[:n_cap], ids[:-1]


@functools.partial(jax.jit, static_argnames=("pw_pad", "pt_pad", "num_wires",
                                             "num_ticks", "tw", "tt"))
def count_active_tiles(w0, t0, *, pw_pad: int, pt_pad: int, num_wires: int,
                       num_ticks: int, tw: int, tt: int):
    """Number of readout tiles touched by at least one depo patch (0-d int)."""
    tile_s, _, is_first, _, _, n_tiles = _sorted_tile_runs(
        w0, t0, pw_pad, pt_pad, num_wires, num_ticks, tw, tt)
    return jnp.sum(is_first & (tile_s < n_tiles)).astype(jnp.int32)


def active_tile_cap(w0, pw_pad: int, pt_pad: int, num_wires: int,
                    num_ticks: int, tw: int, tt: int, t0=None) -> int:
    """Static-or-measured occupancy bucket for the compact launch layout.

    With concrete inputs (eager call): count the truly occupied tiles on the
    host and round up to a power of two — retraces are bounded at
    log2(n_tiles) distinct caps. Under a trace (inside a jit'd pipeline) the
    count is unavailable, so fall back to the static bound
    min(n_tiles, next_pow2(4N)).

    Known trade-off: the eager path sorts the 4N candidate entries twice
    (once here for the count, once inside the cap-shaped jit for the actual
    binning) plus one host sync. Reusing the sorted runs would mean passing
    them through the jit boundary as operands; at current scales the kernel
    dominates and the simpler API wins.
    """
    n = w0.shape[0]
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    n_tiles = tiles_w * tiles_t
    if isinstance(w0, jax.core.Tracer) or t0 is None or isinstance(
            t0, jax.core.Tracer):
        return min(n_tiles, next_pow2(4 * n))
    n_act = int(count_active_tiles(
        w0, t0, pw_pad=pw_pad, pt_pad=pt_pad, num_wires=num_wires,
        num_ticks=num_ticks, tw=tw, tt=tt))
    return min(n_tiles, next_pow2(n_act))


def default_k_max(n: int, num_wires: int, num_ticks: int, tw: int,
                  tt: int) -> int:
    """Heuristic per-tile list length: expected uniform occupancy x8 safety,
    bucketed to a power of two so the jit cache stays small. Shared by the
    dense/compact scatter kernels and the fused rasterize+scatter wrappers,
    so every kernel family buckets identically."""
    tiles = ((num_wires + tw - 1) // tw) * ((num_ticks + tt - 1) // tt)
    return next_pow2(int(4 * n / tiles * 8))


@functools.partial(jax.jit, static_argnames=("num_wires", "num_ticks", "tw",
                                             "tt", "k_max", "interpret"))
def scatter_add_tiles(patches, w0, t0, *, num_wires: int, num_ticks: int,
                      tw: int = 64, tt: int = 256, k_max: int = 0,
                      interpret: bool | None = None):
    """Full owner-computes scatter-add: bin then accumulate (dense layout).

    ``interpret=None`` auto-selects by backend (compiled on TPU, interpreter
    elsewhere). Returns (num_wires, num_ticks) f32 grid.
    """
    n, pw_pad, pt_pad = patches.shape
    tw = max(tw, pw_pad)
    tt = max(tt, pt_pad)
    if k_max == 0:
        k_max = default_k_max(n, num_wires, num_ticks, tw, tt)
    ids, _ = bin_depos_to_tiles(w0, t0, pw_pad, pt_pad, num_wires, num_ticks,
                                tw, tt, k_max)
    grid = scatter_add_pallas(
        patches, w0.astype(jnp.int32), t0.astype(jnp.int32), ids,
        num_wires=num_wires, num_ticks=num_ticks, tw=tw, tt=tt, k_max=k_max,
        interpret=interpret)
    return grid[:num_wires, :num_ticks]


@functools.partial(jax.jit, static_argnames=("num_wires", "num_ticks", "tw",
                                             "tt", "k_max", "n_cap",
                                             "interpret"))
def _scatter_add_tiles_compact_jit(patches, w0, t0, *, num_wires: int,
                                   num_ticks: int, tw: int, tt: int,
                                   k_max: int, n_cap: int, interpret: bool):
    from repro.kernels.fused_sim.kernel import scatter_tiles_to_grid

    n, pw_pad, pt_pad = patches.shape
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    active, ids = bin_depos_to_tiles_compact(
        w0, t0, pw_pad, pt_pad, num_wires, num_ticks, tw, tt, k_max, n_cap)
    blocks = scatter_add_pallas_compact(
        patches, w0.astype(jnp.int32), t0.astype(jnp.int32), active, ids,
        num_wires=num_wires, num_ticks=num_ticks, tw=tw, tt=tt, k_max=k_max,
        interpret=interpret)
    grid = scatter_tiles_to_grid(blocks, active, tiles_w, tiles_t, tw, tt)
    return grid[:num_wires, :num_ticks]


def scatter_add_tiles_compact(patches, w0, t0, *, num_wires: int,
                              num_ticks: int, tw: int = 64, tt: int = 256,
                              k_max: int = 0, n_active: int | None = None,
                              interpret: bool | None = None):
    """Active-tile owner-computes scatter-add (compact layout).

    Kernel work is (n_active_bucket x k_max) instead of (n_tiles x k_max):
    proportional to occupied readout area. ``n_active`` overrides the
    occupancy measurement (it is bucketed, and must be >= the true count).
    """
    n, pw_pad, pt_pad = patches.shape
    tw = max(tw, pw_pad)
    tt = max(tt, pt_pad)
    if k_max == 0:
        k_max = default_k_max(n, num_wires, num_ticks, tw, tt)
    tiles_w = (num_wires + tw - 1) // tw
    tiles_t = (num_ticks + tt - 1) // tt
    if n_active is not None:
        n_cap = min(tiles_w * tiles_t, next_pow2(n_active))
    else:
        n_cap = active_tile_cap(w0, pw_pad, pt_pad, num_wires, num_ticks,
                                tw, tt, t0=t0)
    return _scatter_add_tiles_compact_jit(
        patches, w0, t0, num_wires=num_wires, num_ticks=num_ticks, tw=tw,
        tt=tt, k_max=k_max, n_cap=n_cap, interpret=interpret)
