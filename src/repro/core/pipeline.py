"""End-to-end LArTPC signal simulation pipelines.

Two strategies, mirroring the paper's Fig. 3 vs Fig. 4:

  fig3 : per-depo dispatch. A host loop rasterizes ONE depo per jit call and
         accumulates on the host. This reproduces the paper's initial port:
         tiny kernels, per-item host round-trips, concurrency ~ patch size.
         Kept as the faithful *bad* baseline (paper F1).

  fig4 : batched device-resident. One jit'd program: rasterize ALL depos,
         fluctuate, scatter-add, FFT-convolve, add noise, digitize. One H2D
         (the depo arrays), one D2H (the ADC grid). The paper's proposed fix,
         implemented fully.

The stage chain itself — ``drift -> charge_grid -> convolve -> noise ->
digitize`` — lives in ``repro.core.stages`` as a ``SimGraph``; this module
contributes the fig4 *executor* (``make_sim_fn`` = jit over the graph), the
registered ``charge_grid`` strategy candidates, and the deliberately naive
fig3 host loop. ``make_sim_fn`` resolves any ``"auto"`` strategy fields
*before* jit so the traced program is fixed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import LArTPCConfig, concat_planes
from repro.core import fluctuate as fl
from repro.core.depo import DepoSet, depo_patch_origin
from repro.core.fft_conv import digitize, fft_convolve
from repro.core.noise import simulate_noise
from repro.core.rasterize import rasterize, rasterize_one
from repro.core.response import DetectorResponse, make_response
from repro.core.scatter import scatter_add
from repro.core.stages import (SimOutput, build_sim_graph,
                               compute_charge_grid, jit_executor)
from repro.tune.registry import register_strategy, set_default

__all__ = [
    "SimOutput", "compute_charge_grid", "simulate_fig3", "simulate_fig4",
    "make_sim_fn", "simulate", "charge_grid_unfused",
]


def _fluctuate(key, patches, charge, cfg: LArTPCConfig, pool=None):
    if not cfg.fluctuate or cfg.rng_strategy == "none":
        return patches
    if cfg.rng_strategy == "pool":
        assert pool is not None, "pool strategy requires a pre-computed pool"
        return fl.fluctuate_pool(pool, patches, charge)
    if cfg.rng_strategy == "relaxed":
        return fl.fluctuate_counter_relaxed(key, patches, charge)
    return fl.fluctuate_counter(key, patches, charge)


# ---------------------------------------------------------------------------
# Charge-grid strategies (depos -> S(t,x)) — the registry's second hot op
# ---------------------------------------------------------------------------


def charge_patches(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
                   pool: Optional[jax.Array] = None):
    """The unfused chain up to its scatter: fluctuated patches (N, pw, pt)
    in ``cfg.patch_dtype`` and their (N,) wire and tick origins."""
    patches, w0, t0 = rasterize(depos, cfg)
    return _fluctuate(key, patches, depos.charge, cfg, pool), w0, t0


def unfused_cfg(strategy: str, cfg: LArTPCConfig) -> LArTPCConfig:
    """``cfg`` as the unfused charge-grid ``strategy`` runs its chain
    (``charge_patches`` and ``scatter_add``): ``unfused_bf16`` in
    bfloat16 patches, ``unfused`` as configured."""
    if strategy == "unfused_bf16":
        return dataclasses.replace(cfg, patch_dtype="bfloat16")
    if strategy != "unfused":
        raise ValueError(f"{strategy!r} is not an unfused charge_grid "
                         "strategy ('unfused', 'unfused_bf16')")
    return cfg


@register_strategy("charge_grid", "unfused",
                   note="rasterize -> fluctuate -> scatter_add")
def charge_grid_unfused(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
                        pool: Optional[jax.Array] = None) -> jax.Array:
    return scatter_add(*charge_patches(key, depos, cfg, pool), cfg)


@register_strategy("charge_grid", "unfused_bf16",
                   note="unfused chain with bfloat16 patches (f32 accumulate)")
def charge_grid_unfused_bf16(key: jax.Array, depos: DepoSet,
                             cfg: LArTPCConfig,
                             pool: Optional[jax.Array] = None) -> jax.Array:
    return charge_grid_unfused(key, depos, unfused_cfg("unfused_bf16", cfg),
                               pool)


#: Mosaic (JAX 0.9.0) refuses every fused kernel at lowering:
#: "Unimplemented primitive in Pallas TPU lowering for KernelType.TC: erf".
#: Past that, the kernels put each per-tile depo-id list and the seven (N,)
#: depo-parameter arrays in scalar prefetch (SMEM): ~28 MB at the full
#: config against an SMEM of well under 1 MB. Until the kernels are
#: rewritten they are never selectable on a TPU.
FUSED_TPU_REFUSAL = ("Unimplemented primitive in Pallas TPU lowering for "
                     "KernelType.TC: erf")


def _fused_viable(ctx) -> bool:
    # the fused kernel draws counter-style fluctuation randomness in kernel,
    # so it competes in the physics-default config; the paper-faithful
    # pre-computed "pool" stream cannot be reproduced in kernel, and off-TPU
    # the Pallas interpreter makes production grids prohibitive
    cfg = ctx.cfg
    if cfg is None or (cfg.fluctuate and cfg.rng_strategy in ("pool", "relaxed")):
        return False
    if ctx.backend == "tpu" or concat_planes(cfg):
        return False  # FUSED_TPU_REFUSAL; planes of their own wire counts
    cells = ctx.shape.get("num_wires", 0) * ctx.shape.get("num_ticks", 0)
    return cells <= (1 << 21)


def _fused_key(key: jax.Array, cfg: LArTPCConfig) -> Optional[jax.Array]:
    """The in-kernel RNG key, or None when the config wants no fluctuation."""
    if cfg.fluctuate and cfg.rng_strategy == "counter":
        return key
    if cfg.fluctuate and cfg.rng_strategy in ("pool", "relaxed"):
        raise ValueError(
            "fused charge-grid strategies draw in-kernel counter randomness "
            "and cannot reproduce the pre-computed pool/relaxed streams; use "
            "rng_strategy='counter'/'none' or charge_grid_strategy='unfused'")
    return None


@register_strategy("charge_grid", "fused_pallas", available=_fused_viable,
                   note="fused rasterize+fluctuate+scatter Pallas kernel",
                   differentiable=False)
def charge_grid_fused(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
                      pool: Optional[jax.Array] = None) -> jax.Array:
    from repro.kernels.fused_sim.ops import simulate_charge_grid

    del pool  # in-kernel counter RNG; the pool strategy is rejected above
    return simulate_charge_grid(depos, cfg, key=_fused_key(key, cfg))


@register_strategy("charge_grid", "fused_pallas_compact",
                   available=_fused_viable,
                   note="fused kernel over occupied tiles only",
                   differentiable=False)
def charge_grid_fused_compact(key: jax.Array, depos: DepoSet,
                              cfg: LArTPCConfig,
                              pool: Optional[jax.Array] = None) -> jax.Array:
    from repro.kernels.fused_sim.ops import simulate_charge_grid_compact

    del pool
    return simulate_charge_grid_compact(depos, cfg,
                                        key=_fused_key(key, cfg))


def _fused_mp_viable(ctx) -> bool:
    # the multi-plane fused kernels only make sense with a plane axis to
    # batch over; fluctuation constraints match the single-plane fused
    # kernels (in-kernel counter RNG), and off-TPU the interpreter budget
    # scales with the number of planes it rasterizes per launch
    cfg = ctx.cfg
    if cfg is None or cfg.num_planes < 2 or concat_planes(cfg):
        return False
    if cfg.fluctuate and cfg.rng_strategy in ("pool", "relaxed"):
        return False
    if ctx.backend == "tpu":
        return False  # FUSED_TPU_REFUSAL
    cells = (ctx.shape.get("num_wires", 0) * ctx.shape.get("num_ticks", 0)
             * cfg.num_planes)
    return cells <= (1 << 21)


def _plane_grid_keys(key: jax.Array, cfg: LArTPCConfig):
    """Stacked per-plane in-kernel RNG subkeys ``fold_in(key, p)``, or None
    when the config wants no fluctuation (pool/relaxed streams rejected by
    ``_fused_key``, same as the single-plane fused strategies)."""
    from repro.config import plane_specs

    if _fused_key(key, cfg) is None:
        return None
    idx = jnp.asarray([s.index for s in plane_specs(cfg)], jnp.uint32)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, idx)


def _require_plane_axis(depos: DepoSet, cfg: LArTPCConfig) -> None:
    if depos.wire.ndim < 2 or depos.wire.shape[0] != cfg.num_planes:
        raise ValueError(
            "multi-plane charge_grid strategies take the FULL stacked "
            f"(num_planes={cfg.num_planes}, N) depos of one event (got "
            f"shape {depos.wire.shape}); they are dispatched by the "
            "stacked plane-batching path, not per plane")


@register_strategy("charge_grid", "fused_pallas_multiplane",
                   available=_fused_mp_viable,
                   note="one fused kernel rasterizes ALL planes per launch",
                   differentiable=False)
def charge_grid_fused_multiplane(key: jax.Array, depos: DepoSet,
                                 cfg: LArTPCConfig,
                                 pool: Optional[jax.Array] = None
                                 ) -> jax.Array:
    from repro.kernels.fused_sim.ops import simulate_charge_grid_multiplane

    del pool
    _require_plane_axis(depos, cfg)
    return simulate_charge_grid_multiplane(depos, cfg,
                                           keys=_plane_grid_keys(key, cfg))


@register_strategy("charge_grid", "fused_pallas_multiplane_compact",
                   available=_fused_mp_viable,
                   note="multi-plane fused kernel over occupied tiles only",
                   differentiable=False)
def charge_grid_fused_multiplane_compact(key: jax.Array, depos: DepoSet,
                                         cfg: LArTPCConfig,
                                         pool: Optional[jax.Array] = None
                                         ) -> jax.Array:
    from repro.kernels.fused_sim.ops import (
        simulate_charge_grid_multiplane_compact)

    del pool
    _require_plane_axis(depos, cfg)
    return simulate_charge_grid_multiplane_compact(
        depos, cfg, keys=_plane_grid_keys(key, cfg))


def _mp_xla_viable(ctx) -> bool:
    # plane-flattened XLA chain: needs a plane axis to amortize, and its
    # fluctuation randomness is the fused kernels' counter hash, which (like
    # them) cannot reproduce the pre-computed pool/relaxed streams. No cell
    # cap — plain XLA scales to production grids on every backend. Its
    # grid is (P, W, T): planes of their own wire counts take the unfused
    # chain's one scatter over the concatenated wire axis instead.
    cfg = ctx.cfg
    if cfg is None or cfg.num_planes < 2 or concat_planes(cfg):
        return False
    return not (cfg.fluctuate and cfg.rng_strategy in ("pool", "relaxed"))


@register_strategy("charge_grid", "multiplane_xla", available=_mp_xla_viable,
                   note="plane-flattened XLA chain; counter-hash fluctuation",
                   differentiable=False)
def charge_grid_multiplane_xla(key: jax.Array, depos: DepoSet,
                               cfg: LArTPCConfig,
                               pool: Optional[jax.Array] = None) -> jax.Array:
    """All planes as ONE flat depo batch: rasterize (P*N) patches, draw
    counter-hash fluctuations, and land them with a single window scatter
    into a plane-major (P*W, T) grid.

    The plane axis never becomes a Python loop or a vmap: every stage sees
    one batch, so per-dispatch overhead and the RNG cost are paid once. The
    fluctuation draws use the fused kernels' stateless counter hash (seeded
    per plane from ``fold_in(key, plane)``, streamed per depo, countered per
    patch pixel) instead of threefry — statistically interchangeable, but a
    different bit stream than ``unfused``, so it carries its own pinned
    goldens.
    """
    del pool  # counter-hash RNG; the pool strategy is rejected above
    _require_plane_axis(depos, cfg)
    n_planes, n = depos.wire.shape[0], depos.wire.shape[-1]
    flat = jax.tree.map(
        lambda x: x.reshape((n_planes * n,) + x.shape[2:]), depos)
    patches, w0, t0 = rasterize(flat, cfg)
    keys = _plane_grid_keys(key, cfg)
    if keys is not None:
        seeds = jax.random.key_data(keys).astype(jnp.uint32)  # (P, 2)
        s0 = jnp.repeat(seeds[:, 0], n)[:, None, None]
        s1 = jnp.repeat(seeds[:, 1], n)[:, None, None]
        # per-depo stream (same odd constant as the fused kernel's depo
        # stream), per-patch-pixel counter
        d_id = jnp.tile(jnp.arange(n, dtype=jnp.uint32), n_planes)
        stream = (d_id * jnp.uint32(0x9E3779B9))[:, None, None]
        pw, pt = patches.shape[1], patches.shape[2]
        pix = (jnp.arange(pw, dtype=jnp.uint32)[:, None] * jnp.uint32(pt)
               + jnp.arange(pt, dtype=jnp.uint32)[None, :])[None]
        normals = fl.counter_normals_erfinv(s0, s1, stream, pix)
        patches = fl.binomial_normal_approx(
            patches, flat.charge, normals.astype(patches.dtype))
    # plane-major wire offsets turn P scatters into ONE window scatter over
    # a (P*W, T) grid
    off = jnp.repeat(
        jnp.arange(n_planes, dtype=w0.dtype) * cfg.num_wires, n)
    tall = dataclasses.replace(cfg, num_wires=n_planes * cfg.num_wires)
    grid = scatter_add(patches, w0 + off, t0, tall, strategy="xla")
    return grid.reshape(n_planes, cfg.num_wires, cfg.num_ticks)


set_default("charge_grid", "unfused")


# ---------------------------------------------------------------------------
# Pipelines
# ---------------------------------------------------------------------------


def simulate_fig4(key: jax.Array, depos, resp=None,
                  cfg: Optional[LArTPCConfig] = None,
                  pool: Optional[jax.Array] = None,
                  add_noise: bool = True, recon: bool = False) -> SimOutput:
    """The batched device-resident pipeline (paper Fig. 4). jit-able end to end.

    One ``SimGraph.run`` of the canonical stage chain; ``depos`` may be a
    detector-frame ``DepoSet`` or a physical ``PhysicalDepoSet`` (the drift
    stage transports the latter). ``resp`` is a single ``DetectorResponse``
    (single-plane), a per-plane sequence (multi-plane), or None for the
    config defaults; multi-plane outputs carry a leading plane axis.
    ``recon=True`` appends the deconvolve/hit_find stages and populates
    ``SimOutput.decon``/``hits``.
    """
    if cfg is None:
        # cfg defaults to None only so resp can be omitted positionally
        raise TypeError("simulate_fig4() missing required argument: 'cfg'")
    graph = build_sim_graph(cfg, resp, pool=pool, add_noise=add_noise,
                            recon=recon)
    return graph.run(key, depos)


def simulate_fig3(key: jax.Array, depos: DepoSet, resp: DetectorResponse,
                  cfg: LArTPCConfig, pool: Optional[jax.Array] = None,
                  add_noise: bool = True, max_depos: Optional[int] = None) -> SimOutput:
    """Per-depo host-loop pipeline (paper Fig. 3) — deliberately naive.

    One jit dispatch per depo; the patch returns to the host each iteration
    (``np.asarray`` forces the D2H transfer the paper's Fig. 3 shows), and the
    host accumulates into a numpy grid. Conv/noise still run on device at the
    end (the paper's port also left "scatter add" and "FT" serial).
    """
    pw, pt = cfg.patch_wires, cfg.patch_ticks

    @jax.jit
    def one(wire, tick, sw, st, q, w0, t0, normals):
        patch = rasterize_one(wire, tick, sw, st, q, w0.astype(jnp.float32),
                              t0.astype(jnp.float32), pw, pt)
        if cfg.fluctuate and cfg.rng_strategy != "none":
            qq = jnp.maximum(q, 1.0)
            p = jnp.clip(patch / qq, 0.0, 1.0)
            patch = jnp.maximum(
                patch + jnp.sqrt(jnp.maximum(patch * (1 - p), 0.0)) * normals, 0.0)
        return patch

    w0s, t0s = depo_patch_origin(depos, cfg)
    n = depos.n if max_depos is None else min(depos.n, max_depos)
    host_grid = np.zeros((cfg.num_wires, cfg.num_ticks), np.float32)
    wire, tick = np.asarray(depos.wire), np.asarray(depos.tick)
    sw, st = np.asarray(depos.sigma_w), np.asarray(depos.sigma_t)
    q = np.asarray(depos.charge)
    w0s_h, t0s_h = np.asarray(w0s), np.asarray(t0s)
    if pool is None:
        pool = fl.make_pool(jax.random.fold_in(key, 7), 1 << 16)
    pool_h = np.asarray(pool)
    for i in range(n):
        normals = jnp.asarray(
            pool_h[(i * pw * pt) % pool_h.shape[0]:][: pw * pt].reshape(pw, pt)
            if (i * pw * pt) % pool_h.shape[0] + pw * pt <= pool_h.shape[0]
            else np.resize(pool_h, (pw, pt)))
        patch = np.asarray(one(wire[i], tick[i], sw[i], st[i], q[i],
                               w0s_h[i], t0s_h[i], normals))  # D2H per depo
        host_grid[w0s_h[i]:w0s_h[i] + pw, t0s_h[i]:t0s_h[i] + pt] += patch
    grid = jnp.asarray(host_grid)  # final H2D
    signal = fft_convolve(grid, resp, cfg.fft_strategy)
    if add_noise:
        signal = signal + simulate_noise(jax.random.fold_in(key, 1), cfg) / max(
            cfg.adc_per_electron, 1e-30)
    return SimOutput(adc=digitize(signal, cfg), signal=signal, charge_grid=grid)


def make_sim_fn(cfg: LArTPCConfig, resp: Optional[DetectorResponse] = None,
                add_noise: bool = True, donate: bool = False,
                recon: bool = False):
    """Return a jit'd simulate(key, depos) closure (the production path):
    the single-event executor of the canonical ``SimGraph``.

    ``recon=True`` runs the full sim -> recon chain (deconvolve + hit_find
    appended; see ``build_sim_graph``).

    Any ``"auto"`` strategy fields resolve (tuning cache / backend default)
    here, before jit, so the traced program is fixed.

    ``donate=True`` donates the (key, depos) input buffers to the call
    (``jax.jit`` ``donate_argnums``): XLA reuses their device memory for
    outputs instead of allocating fresh buffers — the right choice for
    streaming drivers that stage fresh inputs every launch. Callers that
    re-invoke with the *same* arrays (benchmark loops) must keep the
    default.
    """
    from repro.tune import resolve_config

    cfg = resolve_config(cfg)
    # build_sim_graph supplies the standard RNG pool when cfg asks for it;
    # jit_executor the per-plane default responses when resp is None
    return jit_executor(
        cfg, resp, lambda r: build_sim_graph(cfg, r, add_noise=add_noise,
                                             recon=recon).run,
        donate_argnums=(0, 1) if donate else ())


def simulate(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
             resp=None, add_noise: bool = True, **kw) -> SimOutput:
    from repro.tune import resolve_config

    cfg = resolve_config(cfg)
    if cfg.pipeline == "fig3":
        if cfg.num_planes > 1:
            raise ValueError(
                "the fig3 per-depo host-loop baseline is single-plane only; "
                "use pipeline='fig4' for multi-plane configs")
        resp = resp if resp is not None else make_response(cfg)
        return simulate_fig3(key, depos, resp, cfg, add_noise=add_noise, **kw)
    return simulate_fig4(key, depos, resp, cfg, add_noise=add_noise, **kw)
