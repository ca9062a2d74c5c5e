"""jit'd wrappers for the fused rasterize+fluctuate+scatter kernel.

``simulate_charge_grid``        — dense tile grid (one step per detector tile)
``simulate_charge_grid_compact``— active-tile grid (one step per OCCUPIED
                                  tile; see ``kernels.scatter_add.ops`` for
                                  the occupancy bucketing)

Both accept an optional PRNG ``key``: when given (and only then) the kernel
applies binomial-approximation charge fluctuation *in kernel*, seeded per
(depo, tile) from the key — no patch array and no normals array ever exist
in HBM. ``key=None`` keeps the original deterministic behavior.
"""
from __future__ import annotations

import functools

import jax

import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.depo import DepoSet, depo_patch_origin
from repro.kernels.fused_sim.kernel import (
    fused_rasterize_scatter, fused_rasterize_scatter_compact,
    fused_rasterize_scatter_multiplane,
    fused_rasterize_scatter_multiplane_compact)
from repro.kernels.scatter_add.ops import (active_tile_cap,
                                           bin_depos_to_tiles,
                                           bin_depos_to_tiles_compact,
                                           default_k_max, next_pow2)


def _grid_dims(cfg: LArTPCConfig, tw: int, tt: int):
    tiles_w = (cfg.num_wires + tw - 1) // tw
    tiles_t = (cfg.num_ticks + tt - 1) // tt
    return tiles_w, tiles_t, tiles_w * tiles_t


def _resolve_k_max(k_max: int, n: int, cfg: LArTPCConfig, tw: int,
                   tt: int) -> int:
    """Explicit k_max, or the bucketed heuristic shared with scatter_add."""
    return k_max or default_k_max(n, cfg.num_wires, cfg.num_ticks, tw, tt)


def _seed_from(key):
    return None if key is None else jax.random.key_data(key)


def _seeds_from(keys):
    """Stacked (P, 2) raw key data from stacked per-plane keys (or None)."""
    return None if keys is None else jax.random.key_data(keys)


@functools.partial(jax.jit, static_argnames=("cfg", "tw", "tt", "k_max",
                                             "interpret"))
def simulate_charge_grid(depos: DepoSet, cfg: LArTPCConfig, tw: int = 64,
                         tt: int = 256, k_max: int = 0,
                         interpret: bool | None = None, key=None):
    """Fused depos -> S(t, x) charge grid (dense tile layout).

    ``key`` enables in-kernel charge fluctuation (see module docstring);
    ``interpret=None`` auto-selects by backend: Mosaic-compiled on TPU, the
    portable Pallas interpreter elsewhere (``repro.kernels.default_interpret``).
    """
    w0, t0 = depo_patch_origin(depos, cfg)
    k_max = _resolve_k_max(k_max, depos.n, cfg, tw, tt)
    # bin by the TRUE patch extent (the kernel masks to [w0, w0+pw))
    ids, _ = bin_depos_to_tiles(w0, t0, cfg.patch_wires, cfg.patch_ticks,
                                cfg.num_wires, cfg.num_ticks, tw, tt, k_max)
    return fused_rasterize_scatter(
        depos.wire, depos.tick, depos.sigma_w, depos.sigma_t, depos.charge,
        w0, t0, ids, num_wires=cfg.num_wires, num_ticks=cfg.num_ticks,
        tw=tw, tt=tt, k_max=k_max, pw=cfg.patch_wires, pt=cfg.patch_ticks,
        interpret=interpret, seed=_seed_from(key), fluctuate=key is not None)


@functools.partial(jax.jit, static_argnames=("cfg", "tw", "tt", "k_max",
                                             "n_cap", "interpret"))
def _simulate_compact_jit(depos: DepoSet, cfg: LArTPCConfig, tw: int, tt: int,
                          k_max: int, n_cap: int, interpret: bool, key):
    w0, t0 = depo_patch_origin(depos, cfg)
    active, ids = bin_depos_to_tiles_compact(
        w0, t0, cfg.patch_wires, cfg.patch_ticks, cfg.num_wires,
        cfg.num_ticks, tw, tt, k_max, n_cap)
    return fused_rasterize_scatter_compact(
        depos.wire, depos.tick, depos.sigma_w, depos.sigma_t, depos.charge,
        w0, t0, active, ids, num_wires=cfg.num_wires, num_ticks=cfg.num_ticks,
        tw=tw, tt=tt, k_max=k_max, pw=cfg.patch_wires, pt=cfg.patch_ticks,
        interpret=interpret, seed=_seed_from(key), fluctuate=key is not None)


def simulate_charge_grid_compact(depos: DepoSet, cfg: LArTPCConfig,
                                 tw: int = 64, tt: int = 256, k_max: int = 0,
                                 interpret: bool | None = None, key=None,
                                 n_active: int | None = None):
    """Fused depos -> S(t, x) over OCCUPIED tiles only.

    Kernel work is (n_active_bucket x k_max): with concrete (eager) inputs
    the occupancy is measured on the host and bucketed to a power of two;
    under an outer jit it falls back to the static min(n_tiles, 4N) bound.
    Bit-identical to ``simulate_charge_grid`` for the same key: RNG streams
    are seeded by the *global* tile id, which compaction preserves.
    """
    _, _, n_tiles = _grid_dims(cfg, tw, tt)
    k_max = _resolve_k_max(k_max, depos.n, cfg, tw, tt)
    if n_active is not None:
        n_cap = min(n_tiles, next_pow2(n_active))
    else:
        w0, t0 = depo_patch_origin(depos, cfg)
        n_cap = active_tile_cap(w0, cfg.patch_wires, cfg.patch_ticks,
                                cfg.num_wires, cfg.num_ticks, tw, tt, t0=t0)
    return _simulate_compact_jit(depos, cfg, tw, tt, k_max, n_cap, interpret,
                                 key)


@functools.partial(jax.jit, static_argnames=("cfg", "tw", "tt", "k_max",
                                             "interpret"))
def simulate_charge_grid_multiplane(depos: DepoSet, cfg: LArTPCConfig,
                                    tw: int = 64, tt: int = 256,
                                    k_max: int = 0,
                                    interpret: bool | None = None,
                                    keys=None):
    """Fused depos -> (P, W, T) charge grids, ONE launch for all planes.

    ``depos`` carries a leading plane axis (P, N) — the per-plane
    projections of one event's physical depos. ``keys`` is the stacked
    per-plane subkey array (``fold_in(kf, p)`` per plane) enabling
    in-kernel fluctuation; plane p's grid is bit-identical to
    ``simulate_charge_grid`` run on plane p's depos with plane p's key.
    """
    num_planes, n = depos.wire.shape
    w0, t0 = depo_patch_origin(depos, cfg)
    k_max = _resolve_k_max(k_max, n, cfg, tw, tt)
    # per-plane dense binned lists (plane-LOCAL depo ids), concatenated
    # plane-major — matching the kernel's flat i = p*n_tiles + t layout
    ids = jnp.concatenate([
        bin_depos_to_tiles(w0[p], t0[p], cfg.patch_wires, cfg.patch_ticks,
                           cfg.num_wires, cfg.num_ticks, tw, tt, k_max)[0]
        for p in range(num_planes)])
    return fused_rasterize_scatter_multiplane(
        depos.wire, depos.tick, depos.sigma_w, depos.sigma_t, depos.charge,
        w0, t0, ids, num_planes=num_planes, num_wires=cfg.num_wires,
        num_ticks=cfg.num_ticks, tw=tw, tt=tt, k_max=k_max,
        pw=cfg.patch_wires, pt=cfg.patch_ticks, interpret=interpret,
        seeds=_seeds_from(keys), fluctuate=keys is not None)


@functools.partial(jax.jit, static_argnames=("cfg", "tw", "tt", "k_max",
                                             "n_cap", "interpret"))
def _simulate_multiplane_compact_jit(depos: DepoSet, cfg: LArTPCConfig,
                                     tw: int, tt: int, k_max: int,
                                     n_cap: int, interpret: bool, keys):
    num_planes, _ = depos.wire.shape
    w0, t0 = depo_patch_origin(depos, cfg)
    actives, ids = [], []
    for p in range(num_planes):
        a, i = bin_depos_to_tiles_compact(
            w0[p], t0[p], cfg.patch_wires, cfg.patch_ticks, cfg.num_wires,
            cfg.num_ticks, tw, tt, k_max, n_cap)
        actives.append(a)
        ids.append(i)
    return fused_rasterize_scatter_multiplane_compact(
        depos.wire, depos.tick, depos.sigma_w, depos.sigma_t, depos.charge,
        w0, t0, jnp.concatenate(actives), jnp.concatenate(ids),
        num_planes=num_planes, num_wires=cfg.num_wires,
        num_ticks=cfg.num_ticks, tw=tw, tt=tt, k_max=k_max,
        pw=cfg.patch_wires, pt=cfg.patch_ticks, interpret=interpret,
        seeds=_seeds_from(keys), fluctuate=keys is not None)


def simulate_charge_grid_multiplane_compact(depos: DepoSet,
                                            cfg: LArTPCConfig, tw: int = 64,
                                            tt: int = 256, k_max: int = 0,
                                            interpret: bool | None = None,
                                            keys=None,
                                            n_active: int | None = None):
    """Fused multi-plane charge grids over OCCUPIED tiles only.

    Every plane's compacted tile list gets the SAME bucketed capacity
    ``n_cap`` (the max over planes of the measured occupancy, or the
    static min(n_tiles, 4N) bound under a trace) so the concatenated
    launch stays rectangular. Bit-identical to
    ``simulate_charge_grid_multiplane`` for the same keys.
    """
    _, _, n_tiles = _grid_dims(cfg, tw, tt)
    num_planes = depos.wire.shape[0]
    k_max = _resolve_k_max(k_max, depos.n, cfg, tw, tt)
    if n_active is not None:
        n_cap = min(n_tiles, next_pow2(n_active))
    else:
        w0, t0 = depo_patch_origin(depos, cfg)
        n_cap = max(
            active_tile_cap(w0[p], cfg.patch_wires, cfg.patch_ticks,
                            cfg.num_wires, cfg.num_ticks, tw, tt, t0=t0[p])
            for p in range(num_planes))
    return _simulate_multiplane_compact_jit(depos, cfg, tw, tt, k_max, n_cap,
                                            interpret, keys)
