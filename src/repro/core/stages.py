"""Stage-graph simulation core: one composable pipeline, many executors.

The paper (and its OpenMP/SYCL follow-ups, arXiv:2203.02479 / 2304.01841)
treats the LArTPC sim as a *chain of stages* — drift, rasterize/scatter
("charge grid"), convolve, noise, digitize — whose per-stage cost profile
drives every porting decision. This module makes that chain a first-class
object instead of code duplicated across entry points:

  Stage     : one named pipeline step — ``fn(SimState) -> SimState`` plus
              the strategy-registry op key it dispatches (if any).
  SimGraph  : an ordered tuple of stages with one executor (``run``), which
              runs each stage under ``jax.named_scope(stage.name)``, and
              stage overrides (``replace``) for specialized executors.
  SimState  : the pytree flowing between stages (keys, depos, grid,
              signal, adc).

All four production entry points execute the same graph object:

  make_sim_fn           : jit(graph.run)                       (single event)
  make_batched_sim_fn   : jit(graph.run_events)                (event batch:
                          each stage vmapped over the events, noise
                          drawn one event at a time)
                          (both via ``jit_executor``: the response spectra
                          are program arguments, not baked-in literals)
  make_distributed_sim  : jit(shard_map(graph.run))            (multi-device,
                          with charge_grid/convolve/noise stage overrides)
  stream_simulate       : the double-buffered driver over make_batched_sim_fn

so adding a stage or a strategy is a one-file change, and the per-stage
cost profile the papers use to find the next bottleneck comes for free: every
op of a compiled program carries its stage's scope in its HLO ``op_name``
(``jit(run)/vmap(charge_grid)/scatter-add``), so a profiler trace (TensorBoard,
Perfetto) attributes device time to stages as the fused program runs.

RNG contract (bit-for-bit with the pre-graph code): the executor splits the
event key once — ``kf, kn = split(key)`` — exactly as ``simulate_fig4``
always did; stages draw from their assigned subkey. ``SimState.key`` keeps
the *unsplit* event key for executors with their own derivation schedule
(the distributed pipeline folds in a per-device index).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig, concat_planes, plane_specs
from repro.core.depo import DepoSet
from repro.core.fft_conv import digitize, fft_convolve, resolve_fft_strategy
from repro.core.noise import noise_spectrum, sample_noise_rows
from repro.core.response import DetectorResponse

#: canonical stage order of the simulation chain
STAGE_ORDER = ("drift", "charge_grid", "convolve", "noise", "digitize")
#: the recon stages ``build_sim_graph(..., recon=True)`` appends
RECON_STAGE_ORDER = ("deconvolve", "hit_find")
#: the full sim -> recon chain
FULL_STAGE_ORDER = STAGE_ORDER + RECON_STAGE_ORDER


class SimOutput(NamedTuple):
    """Simulation result. Single-plane configs (``num_planes == 1``) keep
    the seed 2-D layout; multi-plane configs carry a leading plane axis on
    every leaf: adc (P, num_wires, num_ticks), etc. — except those given
    per-plane wire counts (``cfg.plane_wires``), whose planes stack along
    one wire axis in plane order: adc (sum of plane_wires, num_ticks), with
    hits still (P, max_hits).

    ``decon``/``hits`` are populated only by recon graphs
    (``build_sim_graph(..., recon=True)``) and stay None — an empty pytree
    node, invisible to jit/vmap — on the default sim-only graph."""

    adc: jax.Array        # (num_wires, num_ticks) int16
    signal: jax.Array     # (num_wires, num_ticks) float32 pre-digitization
    charge_grid: jax.Array  # S(t,x) after scatter-add
    decon: Optional[jax.Array] = None  # deconvolved charge estimate Ŝ(t,x)
    hits: Optional[Any] = None         # HitSet (repro.core.hitfind)
    #: () bool — True when every float stage output was finite; populated
    #: only when ``cfg.check_finite`` (None otherwise: an empty pytree
    #: node, so the default graph's structure/output is untouched)
    finite_ok: Optional[jax.Array] = None


class SimState(NamedTuple):
    """The pytree a SimGraph threads through its stages.

    ``depos`` may be a ``PhysicalDepoSet`` (drift transports it) or an
    already-drifted ``DepoSet`` (drift passes it through) — the branch is
    on pytree *structure*, resolved at trace time.
    """

    key: jax.Array                     # unsplit event key
    kf: jax.Array                      # charge-grid subkey (fig4 schedule)
    kn: jax.Array                      # noise subkey (fig4 schedule)
    depos: Any                         # PhysicalDepoSet | DepoSet
    grid: Optional[jax.Array] = None   # S(t,x) after charge_grid
    signal: Optional[jax.Array] = None  # M(t,x) after convolve (+ noise)
    adc: Optional[jax.Array] = None    # int16 after digitize
    decon: Optional[jax.Array] = None  # Ŝ(t,x) after deconvolve (recon)
    hits: Optional[Any] = None         # HitSet after hit_find (recon)
    finite_ok: Optional[jax.Array] = None  # check_finite sentinel accumulator


@dataclasses.dataclass(frozen=True)
class Stage:
    """One named pipeline step.

    name : the stage's ``jax.named_scope``, which names its ops in a trace
    fn   : ``SimState -> SimState`` — reads its inputs from the state,
           writes its outputs back
    op   : strategy-registry hot-op key this stage dispatches through
           (``repro.tune``), or None for fixed-function stages
    """

    name: str
    fn: Callable[[SimState], SimState]
    op: Optional[str] = None
    #: under the batched executor (``SimGraph.run_events``) run one event
    #: at a time (``lax.map``) instead of vmapped over the event axis
    per_event: bool = False


@dataclasses.dataclass(frozen=True)
class SimGraph:
    """An ordered stage chain with one executor for every launch mode."""

    stages: Tuple[Stage, ...]

    @property
    def stage_names(self) -> Tuple[str, ...]:
        return tuple(s.name for s in self.stages)

    def stage(self, name: str) -> Stage:
        for s in self.stages:
            if s.name == name:
                return s
        raise KeyError(f"no stage {name!r}; graph has {self.stage_names}")

    def replace(self, **overrides: Callable[[SimState], SimState] | Stage
                ) -> "SimGraph":
        """A new graph with named stages overridden (the specialization
        hook: the distributed executor swaps in collective-aware
        charge_grid/convolve/noise implementations, scenario configs can
        swap any stage without touching the executor)."""
        unknown = set(overrides) - set(self.stage_names)
        if unknown:
            raise KeyError(f"unknown stages {sorted(unknown)}; "
                           f"graph has {self.stage_names}")
        stages = tuple(
            (overrides[s.name] if isinstance(overrides.get(s.name), Stage)
             else dataclasses.replace(s, fn=overrides[s.name]))
            if s.name in overrides else s
            for s in self.stages)
        return SimGraph(stages=stages)

    # -- execution ----------------------------------------------------------

    def init_state(self, key: jax.Array, depos) -> SimState:
        kf, kn = jax.random.split(key)
        return SimState(key=key, kf=kf, kn=kn, depos=depos)

    def output(self, state: SimState) -> SimOutput:
        return SimOutput(adc=state.adc, signal=state.signal,
                         charge_grid=state.grid, decon=state.decon,
                         hits=state.hits, finite_ok=state.finite_ok)

    def run_state(self, state: SimState) -> SimState:
        for stage in self.stages:
            with jax.named_scope(stage.name):
                state = stage.fn(state)
        return state

    def run(self, key: jax.Array, depos) -> SimOutput:
        """Execute the full chain for one event. jit/vmap/shard_map-able."""
        return self.output(self.run_state(self.init_state(key, depos)))

    def run_events(self, keys: jax.Array, depos) -> SimOutput:
        """Execute the chain for a batch of events (a leading event axis on
        ``keys`` and every depo leaf): each stage vmapped over the events,
        as ``vmap(run)`` would, except ``per_event`` stages, which map over
        them one at a time. Per-event results equal ``run``'s."""
        state = jax.vmap(self.init_state)(keys, depos)
        for s in self.stages:
            if s.per_event:
                with jax.named_scope(s.name):
                    state = jax.lax.map(s.fn, state)
            else:
                state = jax.vmap(_in_scope(s))(state)
        return self.output(state)


def _in_scope(stage: Stage) -> Callable[[SimState], SimState]:
    """``stage.fn`` under the stage's named scope."""

    def fn(state: SimState) -> SimState:
        with jax.named_scope(stage.name):
            return stage.fn(state)

    return fn


# ---------------------------------------------------------------------------
# Stage factories — the default (single-device fig4) implementations
#
# Multi-plane configs (``cfg.num_planes > 1``) run every readout stage once
# per plane inside ONE stage fn — a static Python loop over ``plane_specs``
# with stacked (P, ...) state leaves — so the graph shape and the executors
# stay plane-count agnostic. ``planes`` restricts a multi-plane graph to a
# subset of plane indices (a one-plane graph of a multi-plane config); it
# has no effect on single-plane configs, whose stages are byte-for-byte the
# seed implementations.
#
# Planes of their own wire counts (``concat_planes(cfg)``) stack along one
# wire axis instead, plane p's rows at ``plane_rows`` — the charge grid is
# ONE scatter over every plane's patches; convolve, noise, deconvolve and
# hit_find run per plane (their transform shapes differ), each plane's ops
# under ``jax.named_scope(<plane kind>)`` inside the stage's scope.
# ---------------------------------------------------------------------------


#: charge_grid strategies that rasterize ALL planes in one kernel launch —
#: they receive the unsplit charge-grid subkey plus the full (P, N) depos
#: and fold the per-plane ``fold_in(kf, index)`` subkeys internally, so
#: their output is bit-identical to the per-plane loop
MULTIPLANE_CHARGE_GRID = ("fused_pallas_multiplane",
                          "fused_pallas_multiplane_compact",
                          "multiplane_xla")
#: charge_grid strategies safe to vmap over the plane axis (pure-XLA
#: rasterize/fluctuate/scatter chains). The single-plane Pallas kernels are
#: excluded — their multi-plane form is the dedicated strategies above —
#: so anything else falls back to the per-plane loop.
PLANE_VMAP_CHARGE_GRID = ("unfused", "unfused_bf16")


def resolve_plane_batching(cfg: LArTPCConfig) -> str:
    """Resolve ``cfg.plane_batching`` to a concrete "loop" | "stacked"."""
    mode = cfg.plane_batching
    if mode not in ("auto", "loop", "stacked"):
        raise ValueError(f"unknown plane_batching {mode!r}; expected 'auto', "
                         "'loop' or 'stacked'")
    if mode == "auto":
        return "stacked" if cfg.num_planes > 1 else "loop"
    return mode


def plane_fold_keys(key: jax.Array, specs) -> jax.Array:
    """Stacked per-plane subkeys ``fold_in(key, spec.index)``.

    The vmapped form of the loop's per-plane fold — bit-identical per row
    (same derivation as ``batch.event_keys`` uses for the event axis)."""
    idx = jnp.asarray([s.index for s in specs], dtype=jnp.uint32)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, idx)


def _selected_specs(cfg: LArTPCConfig, planes: Optional[Tuple[int, ...]]):
    specs = plane_specs(cfg)
    if planes is None:
        return specs
    return tuple(specs[p] for p in planes)


def plane_rows(specs) -> Tuple[Tuple[int, int], ...]:
    """(first row, wire count) of each plane on the concatenated wire axis,
    in the order of ``specs``."""
    rows, start = [], 0
    for spec in specs:
        rows.append((start, spec.num_wires))
        start += spec.num_wires
    return tuple(rows)


def _per_plane(specs, grid: jax.Array, fn) -> jax.Array:
    """``fn(i, spec, rows)`` on each plane's rows of a concatenated (sum W,
    T) array, under the plane kind's scope, concatenated back."""
    parts = []
    for i, (spec, (start, n)) in enumerate(zip(specs, plane_rows(specs))):
        with jax.named_scope(spec.kind):
            parts.append(fn(i, spec, grid[start:start + n]))
    return jnp.concatenate(parts)


def _as_plane_responses(cfg: LArTPCConfig, resp,
                        planes: Optional[Tuple[int, ...]]):
    """Normalize ``resp`` to one response per selected plane.

    None builds the defaults (``make_response`` per plane type); a single
    ``DetectorResponse`` is accepted only for single-plane configs — a lone
    transform cannot cover induction *and* collection planes, so passing
    one to a multi-plane graph is an error, not a silent broadcast.
    """
    from repro.core.response import make_response

    specs = _selected_specs(cfg, planes)
    if resp is None:
        return tuple(make_response(cfg, plane=s.kind, num_wires=s.num_wires)
                     for s in specs)
    if isinstance(resp, DetectorResponse):
        if len(specs) != 1:
            raise ValueError(
                f"config has {len(specs)} selected planes but got a single "
                "DetectorResponse; pass make_plane_responses(cfg) (or None "
                "to build the per-plane defaults)")
        return (resp,)
    resps = tuple(resp)
    if len(resps) != len(specs):
        raise ValueError(f"got {len(resps)} responses for {len(specs)} "
                         "selected planes")
    return resps


def drift_stage(cfg: LArTPCConfig,
                planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """Transport physical depos to the readout plane(s); pass through depos
    that already arrived (an input DepoSet), so every executor accepts both
    physical- and detector-frame input. Multi-plane configs project each
    physical depo onto every selected plane (leading plane axis on the
    output DepoSet); pre-drifted input must already carry that axis."""
    from repro.core.drift import PhysicalDepoSet, transport, transport_planes

    multi = cfg.num_planes > 1

    def fn(state: SimState) -> SimState:
        if isinstance(state.depos, PhysicalDepoSet):
            depos = (transport_planes(state.depos, cfg, planes=planes)
                     if multi else transport(state.depos, cfg))
            return state._replace(depos=depos)
        if multi:
            if state.depos.wire.ndim < 2:
                raise ValueError(
                    "multi-plane config fed a planeless DepoSet; pass a "
                    "PhysicalDepoSet (the drift stage projects it onto "
                    "every plane) or a DepoSet with a leading plane axis "
                    "(e.g. generate_plane_depos)")
            n_in = state.depos.wire.shape[-2]
            if n_in != cfg.num_planes:
                raise ValueError(
                    f"pre-drifted depos carry {n_in} planes but the config "
                    f"has num_planes={cfg.num_planes}; pre-drifted input "
                    "always carries the FULL plane axis (a plane-restricted "
                    "graph selects from it here)")
            if planes is not None:
                # select the restricted planes so downstream stages'
                # positional plane loop lines up with the selected specs
                sel = jnp.asarray(planes)
                return state._replace(depos=jax.tree.map(
                    lambda x: x[..., sel, :], state.depos))
        return state

    return Stage("drift", fn, op="drift")


def compute_charge_grid(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
                        pool: Optional[jax.Array] = None) -> jax.Array:
    """Dispatch depos -> S(t,x) through the registered strategy."""
    from repro.tune import autotune, registry

    strategy = cfg.charge_grid_strategy
    if strategy == "auto":
        strategy = autotune.resolve("charge_grid", cfg).strategy
    return registry.get_strategy("charge_grid", strategy).fn(
        key, depos, cfg, pool)


def charge_grid_stage(cfg: LArTPCConfig,
                      pool: Optional[jax.Array] = None,
                      planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """depos -> S(t,x): rasterize + fluctuate + scatter-add (or the fused
    kernel), dispatched through the ``charge_grid`` strategy registry.

    Multi-plane: each plane draws from a plane-folded subkey
    (``fold_in(kf, plane_index)``) so electron fluctuations are independent
    per plane; grids stack to (P, W, T). ``plane_batching="loop"`` runs one
    dispatch per plane (the original static Python loop); "stacked" runs the
    plane axis as ONE batched dispatch — a dedicated multi-plane kernel
    strategy when resolved, otherwise a plane vmap of the XLA chain — with
    bit-identical output (same per-plane subkeys, same per-plane math).
    (The paper-faithful ``pool`` stream reuses the one pool per plane,
    matching its fixed-pool design across events.)"""
    specs = _selected_specs(cfg, planes)
    multi = cfg.num_planes > 1
    concat = concat_planes(cfg)
    stacked = multi and resolve_plane_batching(cfg) == "stacked"

    def loop_fn(state: SimState) -> SimState:
        grids = []
        for i, spec in enumerate(specs):
            kf = jax.random.fold_in(state.kf, spec.index)
            depos_p = jax.tree.map(lambda x, i=i: x[i], state.depos)
            grids.append(compute_charge_grid(kf, depos_p, cfg, pool=pool))
        return state._replace(grid=jnp.stack(grids))

    def concat_fn(state: SimState) -> SimState:
        from repro.core.pipeline import charge_patches, unfused_cfg
        from repro.core.scatter import scatter_add
        from repro.tune import autotune

        strategy = cfg.charge_grid_strategy
        if strategy == "auto":
            strategy = autotune.resolve("charge_grid", cfg).strategy
        if strategy not in PLANE_VMAP_CHARGE_GRID:
            raise ValueError(
                f"charge_grid strategy {strategy!r} fills a (P, W, T) grid; "
                "planes of their own wire counts (plane_wires) take "
                f"{PLANE_VMAP_CHARGE_GRID}")
        scfg = unfused_cfg(strategy, cfg)
        patches, w0s, t0s = [], [], []
        for i, (spec, (start, n)) in enumerate(zip(specs, plane_rows(specs))):
            p, w0, t0 = charge_patches(
                jax.random.fold_in(state.kf, spec.index),
                jax.tree.map(lambda x, i=i: x[i], state.depos),
                dataclasses.replace(scfg, num_wires=n), pool)
            patches.append(p)
            w0s.append(w0 + start)
            t0s.append(t0)
        # one scatter over every plane's patches: plane p's wire origins
        # are offset to its rows of the concatenated axis
        tall = dataclasses.replace(
            scfg, num_wires=sum(s.num_wires for s in specs))
        return state._replace(grid=scatter_add(
            jnp.concatenate(patches), jnp.concatenate(w0s),
            jnp.concatenate(t0s), tall))

    def fn(state: SimState) -> SimState:
        if not multi:
            return state._replace(grid=compute_charge_grid(
                state.kf, state.depos, cfg, pool=pool))
        if concat:
            return concat_fn(state)
        if not stacked:
            return loop_fn(state)
        from repro.tune import autotune, registry

        strategy = cfg.charge_grid_strategy
        if strategy == "auto":
            strategy = autotune.resolve("charge_grid", cfg).strategy
        if (strategy in MULTIPLANE_CHARGE_GRID
                and len(specs) == cfg.num_planes):
            # one kernel launch rasterizes every plane; it folds the
            # per-plane subkeys from the unsplit kf internally
            return state._replace(grid=registry.get_strategy(
                "charge_grid", strategy).fn(state.kf, state.depos, cfg, pool))
        if strategy in PLANE_VMAP_CHARGE_GRID:
            f = registry.get_strategy("charge_grid", strategy).fn
            grid = jax.vmap(lambda k, d: f(k, d, cfg, pool))(
                plane_fold_keys(state.kf, specs), state.depos)
            return state._replace(grid=grid)
        return loop_fn(state)

    return Stage("charge_grid", fn, op="charge_grid")


def convolve_stage(cfg: LArTPCConfig, resp,
                   planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """S(t,x) -> M(t,x): frequency-domain convolution with the detector
    response, dispatched through the ``fft_convolve`` strategy registry.

    Multi-plane: ``resp`` is a per-plane sequence (bipolar induction /
    unipolar collection transforms). ``plane_batching="loop"`` runs one
    convolution per plane; "stacked" runs ONE batched rfft2 over the
    (P, W, T) grid with the per-plane response spectra stacked to
    (P, wp, tf) — bit-identical (batched FFTs compute each plane with the
    same per-plane program) — falling back to the loop when the per-plane
    resolved strategies are not uniformly "rfft2" or the responses disagree
    on padded shape."""
    multi = cfg.num_planes > 1
    concat = concat_planes(cfg)
    specs = _selected_specs(cfg, planes)
    resps = _as_plane_responses(cfg, resp, planes)
    stacked = (multi and resolve_plane_batching(cfg) == "stacked"
               and len({r.pad_shape for r in resps}) == 1)

    def loop_fn(state: SimState) -> SimState:
        signal = jnp.stack([
            fft_convolve(state.grid[i], r, cfg.fft_strategy)
            for i, r in enumerate(resps)])
        return state._replace(signal=signal)

    def fn(state: SimState) -> SimState:
        if not multi:
            return state._replace(
                signal=fft_convolve(state.grid, resps[0], cfg.fft_strategy))
        if concat:
            return state._replace(signal=_per_plane(
                specs, state.grid, lambda i, spec, g: fft_convolve(
                    g, resps[i], cfg.fft_strategy)))
        if not stacked:
            return loop_fn(state)
        w, t = state.grid.shape[-2:]
        if any(resolve_fft_strategy(cfg.fft_strategy, (w, t), r) != "rfft2"
               for r in resps):
            return loop_fn(state)
        from repro.core.fft_conv import _pad_grid

        wp, tp = resps[0].pad_shape
        padded = jnp.stack([_pad_grid(state.grid[i], r)
                            for i, r in enumerate(resps)])
        rfreq = jnp.stack([r.freq for r in resps])
        out = jnp.fft.irfft2(jnp.fft.rfft2(padded) * rfreq, s=(wp, tp))
        return state._replace(signal=out[:, :w, :t])

    return Stage("convolve", fn, op="fft_convolve")


def noise_stage(cfg: LArTPCConfig,
                planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """Add frequency-shaped electronics noise to the signal (multi-plane:
    an independent realization per plane via plane-folded subkeys —
    ``plane_batching="stacked"`` draws every plane's spectrum in ONE
    batched dispatch over the stacked subkeys, bit-identical to the
    per-plane loop)."""
    specs = _selected_specs(cfg, planes)
    multi = cfg.num_planes > 1
    concat = concat_planes(cfg)
    stacked = multi and resolve_plane_batching(cfg) == "stacked"
    # the spectrum and the gain are built with the graph, outside the
    # batched executor's per-event map, so that every executor computes
    # them, and divides by the gain, alike
    amp = noise_spectrum(cfg)
    denom = jnp.maximum(cfg.adc_per_electron, 1e-30)

    def draw(key: jax.Array, rows: int) -> jax.Array:
        return sample_noise_rows(key, rows, amp, cfg.num_ticks)

    def fn(state: SimState) -> SimState:
        if not multi:
            return state._replace(
                signal=state.signal + draw(state.kn, cfg.num_wires) / denom)
        if concat:
            return state._replace(signal=_per_plane(
                specs, state.signal, lambda i, spec, sig: sig + draw(
                    jax.random.fold_in(state.kn, spec.index),
                    spec.num_wires) / denom))
        if stacked:
            noise = jax.vmap(lambda k: draw(k, cfg.num_wires))(
                plane_fold_keys(state.kn, specs))
        else:
            noise = jnp.stack([
                draw(jax.random.fold_in(state.kn, spec.index), cfg.num_wires)
                for spec in specs])
        return state._replace(signal=state.signal + noise / denom)

    # a batch draws its events' noise one at a time: batched over events,
    # the TPU compiler returns wrong inverse transforms for some shapes
    # (0.35 relative error at 2 x 3,456 or 4 x 2,400 rows x 9,600 ticks,
    # PERF.md), the event-at-a-time draw is right at every shape measured
    return Stage("noise", fn, per_event=True)


def digitize_stage(cfg: LArTPCConfig) -> Stage:
    """M(t,x) -> int16 ADC counts."""

    def fn(state: SimState) -> SimState:
        return state._replace(adc=digitize(state.signal, cfg))

    return Stage("digitize", fn)


def deconvolve_stage(cfg: LArTPCConfig, resp=None,
                     planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """ADC -> Ŝ(t,x): invert the response with the config's regularized
    filter, dispatched through the ``deconvolve`` strategy registry.

    The per-plane inverse filters are precomputed here from the SAME
    responses the convolve stage applied (bipolar induction planes get the
    bipolar inverse, unipolar collection the unipolar one)."""
    from repro.core.deconvolve import (deconvolve, make_deconv_filter,
                                       measured_signal)

    multi = cfg.num_planes > 1
    concat = concat_planes(cfg)
    specs = _selected_specs(cfg, planes)
    filts = tuple(make_deconv_filter(r, cfg)
                  for r in _as_plane_responses(cfg, resp, planes))

    def fn(state: SimState) -> SimState:
        meas = measured_signal(state.adc, cfg)
        if not multi:
            return state._replace(
                decon=deconvolve(meas, filts[0], cfg.deconv_strategy))
        if concat:
            return state._replace(decon=_per_plane(
                specs, meas, lambda i, spec, m: deconvolve(
                    m, filts[i], cfg.deconv_strategy)))
        decon = jnp.stack([
            deconvolve(meas[i], f, cfg.deconv_strategy)
            for i, f in enumerate(filts)])
        return state._replace(decon=decon)

    return Stage("deconvolve", fn, op="deconvolve")


def hit_find_stage(cfg: LArTPCConfig,
                   planes: Optional[Tuple[int, ...]] = None) -> Stage:
    """Ŝ(t,x) -> HitSet: threshold-scan runs on every deconvolved wire,
    dispatched through the ``hit_find`` strategy registry. Multi-plane:
    one scan per plane, HitSet leaves stacked to (P, max_hits)."""
    from repro.core.hitfind import find_hits

    specs = _selected_specs(cfg, planes)
    multi = cfg.num_planes > 1
    concat = concat_planes(cfg)

    def fn(state: SimState) -> SimState:
        if not multi:
            return state._replace(
                hits=find_hits(state.decon, cfg, cfg.hitfind_strategy))
        if concat:
            per_plane = []
            for spec, (start, n) in zip(specs, plane_rows(specs)):
                with jax.named_scope(spec.kind):
                    per_plane.append(find_hits(state.decon[start:start + n],
                                               cfg, cfg.hitfind_strategy))
        else:
            per_plane = [find_hits(state.decon[i], cfg, cfg.hitfind_strategy)
                         for i in range(len(specs))]
        hits = jax.tree.map(lambda *xs: jnp.stack(xs), *per_plane)
        return state._replace(hits=hits)

    return Stage("hit_find", fn, op="hit_find")


#: which SimState field each stage's finite sentinel inspects (stages that
#: only produce integers — digitize — have nothing to check; hit_find's
#: float leaves derive from decon, checked one stage earlier, but its
#: charge/tick can still overflow so it is checked too)
_FINITE_CHECK_FIELDS = {
    "drift": "depos",
    "charge_grid": "grid",
    "convolve": "signal",
    "noise": "signal",
    "deconvolve": "decon",
    "hit_find": "hits",
}


def _finite_checked(stage: Stage) -> Stage:
    """Wrap a stage with the ``cfg.check_finite`` sentinel: after the stage
    runs, AND ``all(isfinite(...))`` over the float leaves it wrote into the
    state's ``finite_ok`` flag. One fused reduction per stage — jit-cheap —
    and never a branch, so vmap/shard_map see the same program shape."""
    field = _FINITE_CHECK_FIELDS.get(stage.name)
    if field is None:
        return stage

    def fn(state: SimState) -> SimState:
        state = stage.fn(state)
        ok = (state.finite_ok if state.finite_ok is not None
              else jnp.asarray(True))
        for leaf in jax.tree.leaves(getattr(state, field)):
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
                ok = ok & jnp.all(jnp.isfinite(leaf))
        return state._replace(finite_ok=ok)

    return dataclasses.replace(stage, fn=fn)


def build_sim_graph(cfg: LArTPCConfig, resp=None,
                    pool: Optional[jax.Array] = None, add_noise: bool = True,
                    overrides: Optional[Dict[str, Callable | Stage]] = None,
                    planes: Optional[Tuple[int, ...]] = None,
                    recon: bool = False) -> SimGraph:
    """Assemble the canonical ``drift -> charge_grid -> convolve -> noise ->
    digitize`` chain. This is the ONLY place the stage order is written down;
    every executor (single / batched / distributed / streaming) runs the
    graph this returns.

    ``recon=True`` appends the reconstruction stages ``deconvolve ->
    hit_find`` after digitize (closing the sim -> recon loop); the default
    graph stays bit-identical to the sim-only chain — no recon stage, no
    ``decon``/``hits`` output leaves.

    ``resp`` is the detector response: a single ``DetectorResponse`` for
    single-plane configs, a per-plane sequence for multi-plane configs, or
    None to build the per-plane-type defaults. Multi-plane configs
    (``cfg.num_planes > 1``) run each readout stage per plane and stack a
    leading plane axis onto every ``SimOutput`` leaf; ``planes`` restricts
    the graph to a subset of plane indices.

    ``add_noise=False`` drops the noise stage (rather than running it as an
    identity), so traced programs only contain real work.
    ``overrides`` maps stage names to replacement fns/Stages (see
    ``SimGraph.replace``).

    When the config asks for the paper-faithful ``pool`` fluctuation stream
    and no pool is passed, the standard pre-computed pool is built here —
    every executor gets it without its own wiring.
    (Skipped when ``overrides`` replaces the charge_grid stage: the
    replacement owns its fluctuation scheme, e.g. the distributed
    executor's counter RNG.)
    """
    if (pool is None and cfg.fluctuate and cfg.rng_strategy == "pool"
            and not (overrides and "charge_grid" in overrides)):
        from repro.core import fluctuate as fl

        pool = fl.make_pool(jax.random.key(1234))
    stages = [
        drift_stage(cfg, planes=planes),
        charge_grid_stage(cfg, pool=pool, planes=planes),
        convolve_stage(cfg, resp, planes=planes),
    ]
    if add_noise:
        stages.append(noise_stage(cfg, planes=planes))
    stages.append(digitize_stage(cfg))
    if recon:
        stages.append(deconvolve_stage(cfg, resp, planes=planes))
        stages.append(hit_find_stage(cfg, planes=planes))
    if cfg.check_finite:
        # the numeric sentinel wraps the standard stages only; ``overrides``
        # below replace whole (wrapped) stages, so a specialized executor
        # owns its own checking if it wants any
        stages = [_finite_checked(s) for s in stages]
    graph = SimGraph(stages=tuple(stages))
    if overrides:
        graph = graph.replace(**overrides)
    return graph


class _Bound:
    """A jitted ``fn(consts, *args)`` (or its ``Lowered``/``Compiled``
    stage) called as ``f(*args)``: ``consts`` stay bound through
    ``lower``/``compile``; every other attribute is the wrapped object's."""

    def __init__(self, inner, consts):
        self._inner, self._consts = inner, consts

    def __call__(self, *args):
        return self._inner(self._consts, *args)

    def lower(self, *args):
        return _Bound(self._inner.lower(self._consts, *args), self._consts)

    def compile(self):
        return _Bound(self._inner.compile(), self._consts)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def jit_executor(cfg: LArTPCConfig, resp, make_fn: Callable,
                 donate_argnums: Tuple[int, ...] = ()):
    """``jax.jit`` an executor whose detector responses are program
    ARGUMENTS, not literals baked into the compiled program.

    A jitted function embeds every array it closes over. The response
    spectra are ~100 MB per plane at the full config (more with recon's
    inverse filters, which derive from them), so an executor closing over
    its graph compiles slowly into a program of hundreds of MB that a
    size-capped persistent compilation cache refuses. Here
    ``make_fn(resp)`` builds the executor from the responses inside the
    trace; the result is called (and ``lower``-ed) with the executor's own
    arguments, ``donate_argnums`` counting those.
    """
    resps = _as_plane_responses(cfg, resp, None)

    def run(freqs, *args):
        live = tuple(r._replace(freq=f) for r, f in zip(resps, freqs))
        return make_fn(live if cfg.num_planes > 1 else live[0])(*args)

    jitted = jax.jit(run, donate_argnums=tuple(i + 1 for i in donate_argnums))
    return _Bound(jitted, tuple(r.freq for r in resps))
