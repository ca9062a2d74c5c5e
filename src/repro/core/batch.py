"""Multi-event batching: pack E ragged events into one device-resident launch.

The paper's Fig. 3 -> Fig. 4 lesson is that throughput comes from batching
work into few large kernels instead of many small dispatches. The seed repo
applied that *within* one event but still looped events on the host — the
same serialization one level up. This module closes the loop at the event
level:

  pack_events      : E ragged DepoSets -> one padded (E, N_max) EventBatch
                     (structure of arrays; padding rows carry zero charge so
                     they rasterize to zero and scatter-add is a no-op).
  simulate_events  : the full fig4 pipeline under ``jax.vmap`` over the event
                     axis, one jit'd program for all E events, with per-event
                     RNG keys so events remain statistically independent
                     under the default ``counter`` strategy. Caveat: with
                     ``rng_strategy="pool"`` every event reuses the same
                     normal pool from offset 0 — fluctuations are then
                     identical across events, exactly as they are between
                     per-event calls of ``simulate_fig4`` (the paper's fixed
                     pre-computed pool design; only the additive noise stage
                     differs per event).
  shard_events     : place the event axis across devices via the mesh rules
                     in ``repro.core.sharding`` (logical axis "events").

Per-event results are bit-identical to calling ``simulate_fig4`` on the same
padded row (asserted in ``tests/test_event_batch.py``): vmap changes the
batching, not the math, and zero-charge padding contributes exactly 0.0 to
every accumulation.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.depo import DepoSet
from repro.core.response import DetectorResponse
from repro.core.sharding import current_mesh, logical, named_sharding
from repro.core.stages import (SimGraph, SimOutput, build_sim_graph,
                               jit_executor)


class EventBatch(NamedTuple):
    """Padded structure-of-arrays container for E events of <= N_max depos.

    wire/tick/sigma_w/sigma_t/charge : (E, N_max) float32, rows past
    ``n_depos[e]`` are padding (charge 0, sigma 1) that contributes nothing.
    Multi-plane events (``generate_plane_depos``) carry a plane axis
    between the event and depo axes: (E, P, N_max).
    n_depos : (E,) int32 — valid depo count per event (per plane).
    """

    wire: jax.Array
    tick: jax.Array
    sigma_w: jax.Array
    sigma_t: jax.Array
    charge: jax.Array
    n_depos: jax.Array

    @property
    def num_events(self) -> int:
        return self.wire.shape[0]

    @property
    def max_depos(self) -> int:
        return self.wire.shape[-1]

    @property
    def total_depos(self) -> int:
        """Total number of *valid* (non-padding) depos across events."""
        return int(jax.device_get(self.n_depos).sum())

    def depo_set(self) -> DepoSet:
        """View as a DepoSet of (E, N_max) leaves — the vmap operand."""
        return DepoSet(wire=self.wire, tick=self.tick, sigma_w=self.sigma_w,
                       sigma_t=self.sigma_t, charge=self.charge)

    def event(self, e: int) -> DepoSet:
        """The padded per-event slice (keeps the (N_max,) padded length, so
        ``simulate_fig4`` on it reproduces the batched row bit-for-bit)."""
        return DepoSet(wire=self.wire[e], tick=self.tick[e],
                       sigma_w=self.sigma_w[e], sigma_t=self.sigma_t[e],
                       charge=self.charge[e])


class PhysicalEventBatch(NamedTuple):
    """Padded structure-of-arrays container for E *physical* events.

    The calibration path (``repro.core.fit``) batches events upstream of the
    drift stage — gradients must flow through transport — so it packs
    ``PhysicalDepoSet``s rather than drifted ``DepoSet``s. Leaves are
    (E, N_max) float32; padding rows carry q = 0 (a zero-charge depo drifts
    to a zero-charge depo and rasterizes to nothing).
    """

    x: jax.Array
    y: jax.Array
    z: jax.Array
    t: jax.Array
    q: jax.Array
    n_depos: jax.Array

    @property
    def num_events(self) -> int:
        return self.x.shape[0]

    @property
    def max_depos(self) -> int:
        return self.x.shape[-1]

    def physical_set(self):
        """View as a PhysicalDepoSet of (E, N_max) leaves — the vmap operand."""
        from repro.core.drift import PhysicalDepoSet

        return PhysicalDepoSet(x=self.x, y=self.y, z=self.z, t=self.t,
                               q=self.q)

    def event(self, e: int):
        """The padded per-event slice (keeps the (N_max,) padded length)."""
        from repro.core.drift import PhysicalDepoSet

        return PhysicalDepoSet(x=self.x[e], y=self.y[e], z=self.z[e],
                               t=self.t[e], q=self.q[e])


def pack_physical_events(events, pad_to: Optional[int] = None,
                         pad_multiple: int = 1) -> PhysicalEventBatch:
    """Pack E ragged PhysicalDepoSets into one padded (E, N_max) batch.

    The physical-frame sibling of ``pack_events``: all leaves pad with 0 —
    a q = 0 depo at the frame origin is inert through drift (charge 0 after
    recombination/lifetime scaling) and through rasterization (all-zero
    patch, fluctuation variance 0). Caveat: the *RNG realization* of the
    sampling strategies still depends on the padded length (threefry draws
    pair counter i with i + n/2 over the flattened patch block), so runs are
    bit-comparable only at equal ``N_max`` — which is why fit targets and
    the fit loss share one batch (``repro.core.fit``).
    """
    if not events:
        raise ValueError("pack_physical_events needs at least one event")
    n_max = max(max(ev.n for ev in events), 1)
    if pad_to is not None:
        n_max = max(n_max, pad_to)
    n_max = -(-n_max // pad_multiple) * pad_multiple

    def padf(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_max - x.shape[-1])])

    stacked = {f: jnp.stack([padf(getattr(ev, f)) for ev in events])
               for f in ("x", "y", "z", "t", "q")}
    n_depos = jnp.asarray([ev.n for ev in events], jnp.int32)
    return PhysicalEventBatch(n_depos=n_depos, **stacked)


def empty_event(planes: int = 1) -> DepoSet:
    """A zero-depo event (used to pad the *event* axis of a short batch).

    ``planes > 1`` shapes the leaves (planes, 0) so the empty event stacks
    with multi-plane events from ``generate_plane_depos``.
    """
    shape = (0,) if planes == 1 else (planes, 0)
    z = jnp.zeros(shape, jnp.float32)
    return DepoSet(wire=z, tick=z, sigma_w=z, sigma_t=z, charge=z)


def pad_depos(depos: DepoSet, n_max: int) -> DepoSet:
    """Pad one event's depo axis (the LAST leaf axis — a plane axis may
    lead it) to ``n_max`` with inert depos.

    Padding rows have charge 0 (rasterizes to an all-zero patch, fluctuation
    variance 0, scatter-add of zeros) and sigma 1 (any positive value —
    avoids 0/0 in the Gaussian edges).
    """
    n = depos.n
    if n > n_max:
        raise ValueError(f"event has {n} depos > pad target {n_max}")
    pad = n_max - n

    def padf(x, fill=0.0):
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        return jnp.pad(x, widths, constant_values=fill)

    return DepoSet(
        wire=padf(depos.wire), tick=padf(depos.tick),
        sigma_w=padf(depos.sigma_w, 1.0), sigma_t=padf(depos.sigma_t, 1.0),
        charge=padf(depos.charge),
    )


def pack_events(events: Sequence[DepoSet], pad_to: Optional[int] = None,
                pad_multiple: int = 1) -> EventBatch:
    """Pack E ragged DepoSets into one padded (E, N_max) EventBatch.

    N_max = max event size, rounded up to ``pad_multiple`` (pick a fixed
    ``pad_to`` across batches to avoid re-jitting per batch shape).
    """
    if not events:
        raise ValueError("pack_events needs at least one event")
    n_max = max(max(ev.n for ev in events), 1)
    if pad_to is not None:
        n_max = max(n_max, pad_to)
    n_max = -(-n_max // pad_multiple) * pad_multiple
    padded = [pad_depos(ev, n_max) for ev in events]
    stacked = {f: jnp.stack([getattr(p, f) for p in padded])
               for f in DepoSet._fields}
    n_depos = jnp.asarray([ev.n for ev in events], jnp.int32)
    return EventBatch(n_depos=n_depos, **stacked)


def screen_events(events, ids: Sequence[int], cfg: LArTPCConfig, *,
                  pad_to: Optional[int] = None, batch: int = 0,
                  health=None):
    """Ingest validation gate: keep clean events, quarantine the rest.

    Runs ``repro.core.validate.check_depos`` on every (event, id) pair and
    returns ``(kept_events, kept_ids, dead_letters)`` — kept events preserve
    their ids (and hence their ``fold_in`` keys), so their simulated ADCs
    are bit-identical to a run that never saw the quarantined events.
    ``pad_to`` enforces the padded-batch capacity (an event larger than the
    pad target would crash ``pack_events`` mid-stream); ``health`` (a
    ``RunHealth``) collects the counters when given.
    """
    from repro.core.validate import check_depos, dead_letter

    kept_events, kept_ids, letters = [], [], []
    for ev, depos in zip(ids, events):
        reasons = check_depos(depos, cfg, max_depos=pad_to)
        if reasons:
            letters.append(dead_letter(ev, batch, reasons, depos))
        else:
            kept_events.append(depos)
            kept_ids.append(ev)
    if health is not None and letters:
        health.quarantined += len(letters)
        health.dead_letters.extend(letters)
    return kept_events, kept_ids, letters


def event_keys(key: jax.Array, event_ids: Sequence[int]) -> jax.Array:
    """Stacked per-event keys, (E,) — fold_in(key, ev) for each event id.

    Matches the per-event key derivation of the single-event launcher, so a
    batched run reproduces a serial run of the same event ids exactly.
    """
    ids = jnp.asarray(list(event_ids), jnp.uint32)
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, ids)


# ---------------------------------------------------------------------------
# Batched pipeline
# ---------------------------------------------------------------------------


def simulate_events(keys: jax.Array, batch: EventBatch, resp: DetectorResponse,
                    cfg: LArTPCConfig, pool: Optional[jax.Array] = None,
                    add_noise: bool = True, recon: bool = False,
                    graph: Optional[SimGraph] = None) -> SimOutput:
    """The canonical SimGraph for all E events in one program: vmap over the
    event axis (the batched executor of ``repro.core.stages``,
    ``SimGraph.run_events``, which draws the noise one event at a time).

    keys : (E,) PRNG keys (one per event — events stay independent).
    Returns a SimOutput whose leaves carry a leading event axis:
    adc (E, num_wires, num_ticks), etc. With ``recon=True`` the graph ends
    in deconvolve + hit_find and ``decon``/``hits`` gain the event axis too
    (HitSet leaves become (E, max_hits), n_hits (E,)).
    """
    if graph is None:
        graph = build_sim_graph(cfg, resp, pool=pool, add_noise=add_noise,
                                recon=recon)
    depos = batch.depo_set()

    def ev_names(x):
        return ("events",) + (None,) * (x.ndim - 1)

    depos = jax.tree.map(lambda x: logical(x, ev_names(x)), depos)
    keys = logical(keys, ("events",))
    out = graph.run_events(keys, depos)
    # tree.map (not per-field) so nested recon leaves (the HitSet) get the
    # event-axis constraint too and absent (None) fields pass through
    return jax.tree.map(lambda x: logical(x, ev_names(x)), out)


def make_batched_sim_fn(cfg: LArTPCConfig,
                        resp: Optional[DetectorResponse] = None,
                        add_noise: bool = True, donate: bool = False,
                        recon: bool = False):
    """jit'd ``sim(keys, batch) -> SimOutput`` closure (batched production
    path — the vmap executor over the same ``SimGraph`` ``make_sim_fn``
    runs single-event). ``recon=True`` appends deconvolve + hit_find.

    ``"auto"`` strategy fields resolve here, before jit, so one fixed traced
    program serves the whole stream (see ``repro.tune``).

    ``donate=True`` donates the (keys, batch) buffers (``donate_argnums``):
    the streaming launcher stages a fresh batch every launch, so its input
    memory can be recycled for outputs instead of growing the footprint by
    a full (E, N_max) batch. Keep the default when re-invoking with the
    same arrays (e.g. benchmark sweeps)."""
    from repro.tune import resolve_config

    cfg = resolve_config(cfg)

    def make_fn(r):
        # build_sim_graph supplies the standard RNG pool when cfg asks for
        # it; jit_executor the per-plane default responses when resp is None
        graph = build_sim_graph(cfg, r, add_noise=add_noise, recon=recon)

        def sim(keys, batch: EventBatch) -> SimOutput:
            return simulate_events(keys, batch, r, cfg, graph=graph)

        return sim

    return jit_executor(cfg, resp, make_fn,
                        donate_argnums=(0, 1) if donate else ())


def shard_events(batch: EventBatch, mesh=None) -> EventBatch:
    """Stage an EventBatch onto devices, event axis sharded per mesh rules.

    This is the explicit H2D step of the streaming launcher: with a mesh
    active the event axis spreads over the data axes; without one it is a
    plain (async) device_put.
    """
    mesh = mesh or current_mesh()

    def put(x, names):
        s = named_sharding(x.shape, names, mesh=mesh)
        return jax.device_put(x, s) if s is not None else jax.device_put(x)

    arrs = {f: put(getattr(batch, f),
                   ("events",) + (None,) * (getattr(batch, f).ndim - 1))
            for f in DepoSet._fields}
    return EventBatch(n_depos=put(batch.n_depos, ("events",)), **arrs)
