"""Scatter-add: accumulate all depo patches into the readout grid S(t, x).

The paper's Kokkos port uses ``Kokkos::atomic_add`` (Fig. 5). TPUs/XLA expose
no device atomics; we implement five deterministic strategies:

  xla           : one ``scatter-add`` HLO with a (pw, pt) window update per
                  depo. Fast on the CPU; the TPU compiler keeps no window
                  scatter, it expands it into a ``while`` loop with one trip
                  (bounds check, add, dynamic-update-slice) per depo, about
                  5 us each on a v5e: 0.5 s per 100k-depo event.
  lane_rows     : the TPU's default. The grid is viewed as a table of
                  128-tick rows; each patch row is placed exactly into a
                  256-lane strip at its tick offset and added as two table
                  rows. A scatter whose updates are whole rows of the
                  operand's minor dimension (or single elements) is one the
                  TPU compiler sorts and applies natively, with no loop per
                  update. Depos go through in chunks that keep the strips
                  near 0.5 GB.
  sort_segment  : sort pixel contributions by destination index with one
                  fused ``lax.sort_key_val``, segment-reduce the equal-
                  destination runs, then scatter the run totals with
                  ``indices_are_sorted=True`` — the sorted stream turns
                  random-access HBM traffic into sequential traffic, the TPU
                  analogue of coalesced atomics.
  pallas        : owner-computes tile binning (``repro.kernels.scatter_add``):
                  the output grid is cut into VMEM tiles; depos are binned to
                  the tiles they touch; each tile *gathers* its contributions.
                  Scatter inverted into gather = canonical TPU formulation,
                  bitwise deterministic (atomics are not).
  pallas_compact: the same owner-computes kernel launched over OCCUPIED
                  tiles only — kernel work scales with occupied readout
                  area instead of detector area (track-like depo sets leave
                  most tiles empty).

All strategies accumulate in float32 (patches may arrive narrower, see
``cfg.patch_dtype``) and produce identical results up to float addition
order where patches overlap, asserted in tests. Each registers itself as a
``scatter_add`` candidate in the kernel-strategy registry (``repro.tune``);
set ``cfg.scatter_strategy="auto"`` to pick per backend from the tuning
cache or the backend's default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.tune.registry import register_strategy, set_default


def _flat_pixel_indices(w0: jax.Array, t0: jax.Array, pw: int, pt: int, num_ticks: int):
    """Flat destination index for every patch pixel: (N, pw, pt) int32."""
    dw = jnp.arange(pw, dtype=jnp.int32)[None, :, None]
    dt = jnp.arange(pt, dtype=jnp.int32)[None, None, :]
    return (w0[:, None, None] + dw) * num_ticks + (t0[:, None, None] + dt)


def flat_pixel_contribs(patches: jax.Array, w0: jax.Array, t0: jax.Array,
                        num_ticks: int):
    """Flattened (idx, vals) contribution stream, built ONCE and shared by
    the HLO-scatter strategies.

    idx  : (N*pw*pt,) int32 flat destination pixel of every patch pixel
    vals : (N*pw*pt,) float32 values (upcast from ``cfg.patch_dtype`` —
           narrow patches halve the HBM read; accumulation stays f32)
    """
    n, pw, pt = patches.shape
    idx = _flat_pixel_indices(w0, t0, pw, pt, num_ticks).reshape(-1)
    vals = patches.reshape(-1).astype(jnp.float32)
    return idx, vals


@register_strategy("scatter_add", "xla", note="one scatter-add HLO")
def scatter_xla(patches: jax.Array, w0: jax.Array, t0: jax.Array, cfg: LArTPCConfig):
    n, pw, pt = patches.shape
    if pw > cfg.num_wires or pt > cfg.num_ticks:
        # degenerate grids (patch larger than the readout): per-pixel
        # updates keep the in-range pixels a clipped window start cannot
        # express — correctness path only, never hit at detector shapes
        idx, vals = flat_pixel_contribs(patches, w0, t0, cfg.num_ticks)
        grid = jnp.zeros((cfg.num_wires * cfg.num_ticks,), jnp.float32)
        grid = grid.at[idx].add(vals, mode="drop")
        return grid.reshape(cfg.num_wires, cfg.num_ticks)
    # ONE update per PATCH (a (pw, pt) window at (w0, t0)) instead of one
    # per pixel: N window adds replace N*pw*pt scalar adds, so the scatter
    # stops paying per-element index arithmetic. ``depo_patch_origin``
    # clips every origin to [0, dim - patch], so no window is ever out of
    # bounds and the update stream visits pixels in the same (n, dw, dt)
    # order as the per-pixel form — bit-identical output, ~50x faster on
    # CPU at smoke shapes.
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1))
    starts = jnp.stack([w0, t0], axis=-1)
    return jax.lax.scatter_add(
        jnp.zeros((cfg.num_wires, cfg.num_ticks), jnp.float32), starts,
        patches.astype(jnp.float32), dnums,
        indices_are_sorted=False, unique_indices=False)


#: lanes of one TPU vector register row: the minor width of the row table
LANES = 128
#: bytes of placed strips ``lane_rows`` holds at once; the depos are cut
#: into as many chunks as this takes (100k 20x20 patches: 4 chunks)
STRIP_CHUNK_BYTES = 1 << 29


@register_strategy("scatter_add", "lane_rows",
                   note="patch rows placed in 128-lane rows, one sorted row "
                        "scatter per chunk")
def scatter_lane_rows(patches: jax.Array, w0: jax.Array, t0: jax.Array,
                      cfg: LArTPCConfig):
    n, pw, pt = patches.shape
    if pw > cfg.num_wires or pt > cfg.num_ticks:
        return scatter_xla(patches, w0, t0, cfg)  # degenerate grids
    # the grid as a table of 128-tick rows: row w * nb + b holds wire w's
    # ticks [128 b, 128 b + 128), padded past num_ticks and cropped below
    nb = -(-cfg.num_ticks // LANES)
    drop = cfg.num_wires * nb
    k = -(-(LANES - 1 + pt) // LANES)  # table rows one patch row can touch
    chunks = max(1, -(-n * pw * k * LANES * 4 // STRIP_CHUNK_BYTES))
    size = -(-n // chunks)
    pad = chunks * size - n
    # padded depos start past the last block, so all their rows drop
    vals = jnp.pad(patches.astype(jnp.float32), ((0, pad), (0, 0), (0, 0)))
    w0 = jnp.pad(w0, (0, pad))
    t0 = jnp.pad(t0, (0, pad), constant_values=nb * LANES)
    # (wire, block) offset of each of a patch's pw * k table rows, as one
    # minor axis: a (pw, k) pair of axes costs the TPU compiler minutes
    dw, dk = jnp.divmod(jnp.arange(pw * k, dtype=jnp.int32), k)
    tick = jnp.arange(pt, dtype=jnp.int32)
    lane = jnp.arange(k * LANES, dtype=jnp.int32)

    def add_chunk(table, xs):
        v, w, t = xs
        b, r = t // LANES, t % LANES
        span = b[:, None] + dk  # (size, pw * k)
        rows = jnp.where(span < nb, (w[:, None] + dw) * nb + span, drop)
        # exact placement: each strip lane takes at most one patch value,
        # so the one-hot contraction at HIGHEST reproduces it bit for bit
        onehot = lane[None, None, :] == (r[:, None] + tick)[:, :, None]
        strips = jnp.einsum("nwt,ntl->nwl", v, onehot.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        table = table.at[rows.reshape(-1)].add(
            strips.reshape(-1, LANES), mode="drop")
        return table, None

    table, _ = jax.lax.scan(
        add_chunk, jnp.zeros((drop, LANES), jnp.float32),
        (vals.reshape(chunks, size, pw, pt), w0.reshape(chunks, size),
         t0.reshape(chunks, size)))
    return table.reshape(cfg.num_wires, nb * LANES)[:, :cfg.num_ticks]


@register_strategy("scatter_add", "sort_segment",
                   note="fused sort by destination, segment-sum, sorted scatter")
def scatter_sort_segment(patches: jax.Array, w0: jax.Array, t0: jax.Array,
                         cfg: LArTPCConfig):
    idx, vals = flat_pixel_contribs(patches, w0, t0, cfg.num_ticks)
    # ONE fused sort carries the values with the keys (no argsort + two
    # gathers: half the sort-stage memory traffic)
    idx_s, vals_s = jax.lax.sort_key_val(idx, vals)
    # collapse runs of equal destination before the scatter: after sorting,
    # segment-reduce by run id, then one sorted scatter of the run totals.
    new_run = jnp.concatenate(
        [jnp.array([0], jnp.int32), (idx_s[1:] != idx_s[:-1]).astype(jnp.int32)])
    seg_id = jnp.cumsum(new_run)
    nseg = vals_s.shape[0]  # static upper bound on number of runs
    totals = jax.ops.segment_sum(vals_s, seg_id, num_segments=nseg,
                                 indices_are_sorted=True)
    # each run's destination: a segment-max of the (constant-per-run) sorted
    # indices — replaces the old jnp.nonzero first-position pass + gather
    seg_dest = jax.ops.segment_max(idx_s, seg_id, num_segments=nseg,
                                   indices_are_sorted=True)
    valid = jnp.arange(nseg) <= seg_id[-1]
    grid = jnp.zeros((cfg.num_wires * cfg.num_ticks,), jnp.float32)
    grid = grid.at[jnp.where(valid, seg_dest, cfg.num_wires * cfg.num_ticks)].add(
        jnp.where(valid, totals, 0.0), mode="drop", indices_are_sorted=True,
        unique_indices=False)
    return grid.reshape(cfg.num_wires, cfg.num_ticks)


#: Mosaic (JAX 0.9.0) refuses both owner-computes kernels at lowering:
#: "Unimplemented primitive in Pallas TPU lowering for KernelType.TC:
#: dynamic_update_slice" (the patch placement). Past that, the per-tile
#: depo-id list rides in scalar prefetch (SMEM): 25 MB at the full config.
#: Until the kernels are rewritten they are never selectable on a TPU.
SCATTER_TPU_REFUSAL = ("Unimplemented primitive in Pallas TPU lowering for "
                       "KernelType.TC: dynamic_update_slice")


def _pallas_viable(ctx) -> bool:
    # Refused by the TPU compiler (SCATTER_TPU_REFUSAL). Anywhere else the
    # kernel runs in the Pallas interpreter, which is a correctness tool —
    # keep it out of the tuner's candidate set once the grid is big enough
    # that interpret-mode tile loops dominate (it would never win, only
    # slow tuning down).
    if ctx.backend == "tpu":
        return False
    cells = ctx.shape.get("num_wires", 0) * ctx.shape.get("num_ticks", 0)
    return cells <= (1 << 21)


@register_strategy("scatter_add", "pallas", available=_pallas_viable,
                   note="owner-computes tile kernel; interpret only",
                   differentiable=False)
def scatter_pallas(patches: jax.Array, w0: jax.Array, t0: jax.Array,
                   cfg: LArTPCConfig, interpret: bool | None = None):
    from repro.kernels.scatter_add.ops import scatter_add_tiles

    return scatter_add_tiles(
        patches, w0, t0,
        num_wires=cfg.num_wires, num_ticks=cfg.num_ticks,
        interpret=interpret,
    )


@register_strategy("scatter_add", "pallas_compact", available=_pallas_viable,
                   note="owner-computes kernel over occupied tiles only",
                   differentiable=False)
def scatter_pallas_compact(patches: jax.Array, w0: jax.Array, t0: jax.Array,
                           cfg: LArTPCConfig, interpret: bool | None = None):
    from repro.kernels.scatter_add.ops import scatter_add_tiles_compact

    return scatter_add_tiles_compact(
        patches, w0, t0,
        num_wires=cfg.num_wires, num_ticks=cfg.num_ticks,
        interpret=interpret,
    )


set_default("scatter_add", "xla")
set_default("scatter_add", "lane_rows", backend="tpu")

#: name -> fn view of the registered candidates (back-compat surface)
STRATEGIES = {
    "xla": scatter_xla,
    "sort_segment": scatter_sort_segment,
    "lane_rows": scatter_lane_rows,
    "pallas": scatter_pallas,
    "pallas_compact": scatter_pallas_compact,
}


def scatter_add(patches, w0, t0, cfg: LArTPCConfig, strategy: str | None = None):
    """Dispatch to a scatter strategy.

    ``strategy`` (or ``cfg.scatter_strategy``) may be a concrete name or
    ``"auto"``: auto resolves through the tuning cache / backend default at
    trace time, so the traced program is fixed (see ``repro.tune``).
    """
    from repro.tune import autotune, registry

    strategy = strategy or cfg.scatter_strategy
    if strategy == "auto":
        shape = autotune.op_shape("scatter_add", cfg)
        shape["num_depos"] = int(patches.shape[0])
        strategy = autotune.resolve("scatter_add", cfg, shape=shape).strategy
    return registry.get_strategy("scatter_add", strategy).fn(
        patches, w0, t0, cfg)
