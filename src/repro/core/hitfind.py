"""Threshold-scan hit finding over deconvolved wires -> fixed-capacity HitSet.

The recon follow-ups to the source paper (arXiv:2107.00812 "Optimizing the
Hit Finding Algorithm...") make this the workload after deconvolution: walk
each wire's deconvolved waveform, and turn every run of consecutive
above-threshold ticks into one *hit* — summed charge, charge-weighted mean
tick, peak sample. The algorithm is sequential in time per wire but
embarrassingly parallel over wires, which is exactly the portability
trade-off the registry exists to measure:

  scan   : one ``lax.fori_loop`` over ticks with every wire on its own
           lane — each per-tick step is a few vector ops over all wires.
  pallas : the same scanner as a Pallas kernel, 128 wires per grid step
           (``repro.kernels.hitfind``) — both call the SAME ``scan_runs``
           body, so their outputs are bit-identical by construction.

Output contract (``HitSet``): a fixed-capacity (``cfg.max_hits``), mask-
padded pytree, so jit/vmap/shard_map see static shapes whatever the event
occupancy. Hits are compacted wire-major (ascending wire, then time);
``n_hits`` counts every candidate run found — ``n_hits > mask.sum()`` means
capacity truncation (per-wire ``max_hits_per_wire`` or global ``max_hits``),
detectable instead of silent.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.tune.registry import register_strategy, set_default


class HitSet(NamedTuple):
    """Fixed-capacity, mask-padded hits of one readout plane.

    Leaves are (max_hits,); multi-plane outputs stack a leading plane axis,
    batched executors a leading event axis. Padding rows have mask False and
    zeroed values.
    """

    wire: jax.Array    # int32 global wire index of the hit's wire
    tick: jax.Array    # float32 charge-weighted mean tick of the run
    charge: jax.Array  # float32 summed deconvolved charge (electrons)
    peak: jax.Array    # float32 max deconvolved sample in the run
    mask: jax.Array    # bool — True for real hits, False for padding
    n_hits: jax.Array  # () int32 total candidate runs found; > mask.sum()
    #                    signals capacity truncation


# ---------------------------------------------------------------------------
# The shared run scanner (both strategies execute this exact body)
# ---------------------------------------------------------------------------


def scan_runs(load, t_len: int, lanes: int, threshold, cap: int):
    """Scan ``lanes`` waveforms in lockstep for runs of samples > threshold.

    ``load(t)`` returns the (1, lanes) float32 samples of tick ``t``: one
    wire per lane, so every per-tick step is a handful of vector ops over
    all lanes. Returns (count, charge, tsum, peak): count (1, lanes) int32
    is the TOTAL number of runs found per wire (may exceed ``cap``);
    charge/tsum/peak (cap, lanes) float32 hold the first ``cap`` runs in
    time order, with ``tsum`` the charge-weighted tick sum (``finish_scan``
    divides it into the mean tick). Runs are closed by masked selects over
    the ``cap`` slots — no dynamic indexing — so the body lowers unchanged
    in XLA and in a Mosaic kernel.
    """
    slot = jax.lax.broadcasted_iota(jnp.int32, (cap, lanes), 0)

    def emit(fire, n, csum, tsum, pk, hq, hts, hp):
        # a wire that closes its n-th run stores it in slot n, if n < cap;
        # n counts every run, stored or not, so truncation stays visible
        put = fire & (slot == n)
        hq = jnp.where(put, csum, hq)
        hts = jnp.where(put, tsum, hts)
        hp = jnp.where(put, pk, hp)
        return n + fire.astype(jnp.int32), hq, hts, hp

    def step(t, carry):
        n, active, csum, tsum, pk, hq, hts, hp = carry
        v = load(t)
        above = v > threshold
        run = active != 0
        # a run ends when the previous tick was in-run and this one is not
        n, hq, hts, hp = emit(run & ~above, n, csum, tsum, pk, hq, hts, hp)
        tf = t.astype(jnp.float32)
        csum = jnp.where(above, jnp.where(run, csum + v, v), 0.0)
        tsum = jnp.where(above, jnp.where(run, tsum + v * tf, v * tf), 0.0)
        pk = jnp.where(above, jnp.where(run, jnp.maximum(pk, v), v), 0.0)
        return n, above.astype(jnp.int32), csum, tsum, pk, hq, hts, hp

    row = jnp.zeros((1, lanes), jnp.float32)
    cand = jnp.zeros((cap, lanes), jnp.float32)
    carry = (jnp.zeros((1, lanes), jnp.int32), jnp.zeros((1, lanes), jnp.int32),
             row, row, row, cand, cand, cand)
    n, active, csum, tsum, pk, hq, hts, hp = jax.lax.fori_loop(
        0, t_len, step, carry)
    # flush a run still open at the readout edge
    return emit(active != 0, n, csum, tsum, pk, hq, hts, hp)


def finish_scan(n, hq, hts, hp):
    """Lane-major scan outputs -> the per-wire candidate layout: counts
    (W,) and charge/tick/peak (W, cap), tick = tsum / charge (empty slots
    stay 0)."""
    tick = hts / jnp.maximum(hq, 1e-30)
    return n[0], hq.T, tick.T, hp.T


# ---------------------------------------------------------------------------
# Strategies — the registry's ``hit_find`` op
# ---------------------------------------------------------------------------
#
# A strategy maps (decon (W, T), cfg) -> per-wire candidates:
#   counts (W,) int32, charge/tick/peak (W, max_hits_per_wire) float32
# ``find_hits`` compacts them into the global HitSet.


@register_strategy("hit_find", "scan",
                   note="fori_loop run scanner over ticks, wires on lanes",
                   differentiable=False)
def hit_find_scan(decon: jax.Array, cfg: LArTPCConfig):
    w, t_len = decon.shape
    q = decon.astype(jnp.float32).T                      # (T, W)
    return finish_scan(*scan_runs(
        lambda t: jax.lax.dynamic_slice_in_dim(q, t, 1, axis=0), t_len, w,
        jnp.float32(cfg.hit_threshold), int(cfg.max_hits_per_wire)))


def _pallas_viable(ctx) -> bool:
    # compiled on TPU; elsewhere the Pallas interpreter walks the wire grid
    # in Python, so cap it to smoke-scale grids (same bound as fused_pallas)
    if ctx.backend == "tpu":
        return True
    cells = ctx.shape.get("num_wires", 0) * ctx.shape.get("num_ticks", 0)
    return cells <= (1 << 21)


@register_strategy("hit_find", "pallas", available=_pallas_viable,
                   note="Pallas kernel, 128 wires per grid step (same body)",
                   differentiable=False)
def hit_find_pallas(decon: jax.Array, cfg: LArTPCConfig):
    from repro.kernels.hitfind.ops import find_wire_hits_pallas

    return find_wire_hits_pallas(decon, threshold=float(cfg.hit_threshold),
                                 cap=int(cfg.max_hits_per_wire))


set_default("hit_find", "scan")


# ---------------------------------------------------------------------------
# Compaction + dispatch
# ---------------------------------------------------------------------------


def compact_hits(counts: jax.Array, charge: jax.Array, tick: jax.Array,
                 peak: jax.Array, cfg: LArTPCConfig, *,
                 wire_offset=0, max_hits: Optional[int] = None) -> HitSet:
    """Flatten per-wire candidate arrays into one wire-major HitSet.

    Stored hits keep (wire, time) order; candidates past the global
    ``max_hits`` capacity fall into a dump slot that is dropped. ``n_hits``
    sums the *found* counts, so truncation (per-wire or global) shows as
    ``n_hits > mask.sum()``. ``wire_offset`` shifts the reported wire index
    (the distributed executor passes its shard's first global wire).
    """
    w, cap = charge.shape
    m = int(max_hits if max_hits is not None else cfg.max_hits)
    stored = jnp.minimum(counts, cap)                    # (W,)
    starts = jnp.cumsum(stored) - stored                 # exclusive prefix
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = j < stored[:, None]                          # (W, cap)
    # invalid and overflow candidates both target the dump slot m
    tgt = jnp.where(valid, jnp.minimum(starts[:, None] + j, m), m).reshape(-1)
    wires = jnp.broadcast_to(
        (jnp.arange(w, dtype=jnp.int32) + wire_offset)[:, None], (w, cap))

    def place(vals, dtype):
        out = jnp.zeros((m + 1,), dtype)
        return out.at[tgt].set(vals.reshape(-1).astype(dtype))[:m]

    nstored = jnp.zeros((m + 1,), jnp.int32).at[tgt].add(
        valid.reshape(-1).astype(jnp.int32))[:m]
    return HitSet(
        wire=place(wires, jnp.int32),
        tick=place(tick, jnp.float32),
        charge=place(charge, jnp.float32),
        peak=place(peak, jnp.float32),
        mask=nstored > 0,
        n_hits=jnp.sum(counts).astype(jnp.int32),
    )


def find_hits(decon: jax.Array, cfg: LArTPCConfig,
              strategy: Optional[str] = None, *, wire_offset=0,
              max_hits: Optional[int] = None) -> HitSet:
    """Threshold-scan one plane's deconvolved (W, T) grid into a HitSet.

    ``strategy`` may be None (registry default), ``"auto"`` (tuning cache /
    default, keyed by the grid shape and per-wire capacity), or a registered
    candidate name; unknown names fail here with the valid list.
    ``wire_offset``/``max_hits`` override the global wire numbering and the
    HitSet capacity (the distributed executor scans per-shard slices).
    """
    from repro.tune import autotune, registry

    if strategy is None:
        strategy = registry.default_strategy("hit_find")
    elif strategy == "auto":
        shape = {"num_wires": decon.shape[0], "num_ticks": decon.shape[1],
                 "max_hits_per_wire": cfg.max_hits_per_wire}
        strategy = autotune.resolve("hit_find", None, shape=shape).strategy
    try:
        strat = registry.get_strategy("hit_find", strategy)
    except KeyError:
        valid = sorted(registry.strategies("hit_find")) + ["auto"]
        raise ValueError(
            f"unknown hit_find strategy {strategy!r}; valid: {valid}"
        ) from None
    counts, charge, tick, peak = strat.fn(decon, cfg)
    return compact_hits(counts, charge, tick, peak, cfg,
                        wire_offset=wire_offset, max_hits=max_hits)


def hits_to_tuples(hits: HitSet) -> Tuple[Tuple[int, float, float], ...]:
    """Host-side view of the real hits as sorted (wire, tick, charge)
    tuples — the executor-equivalence tests compare hit SETS this way
    (compaction *positions* differ between the single-device and sharded
    layouts; the hits themselves must not)."""
    import numpy as np

    mask = np.asarray(hits.mask)
    rows = zip(np.asarray(hits.wire)[mask].tolist(),
               np.asarray(hits.tick)[mask].tolist(),
               np.asarray(hits.charge)[mask].tolist())
    return tuple(sorted(rows))
