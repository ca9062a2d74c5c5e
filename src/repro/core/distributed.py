"""Multi-device LArTPC simulation: shard_map pipeline + pencil-decomposed FFT.

Production layout (mesh axes combined into one logical "shard" group):

  depos         : sharded over all devices (pure DP — rasterization is
                  embarrassingly parallel).
  scatter-add   : each device accumulates a *partial* grid from its depo
                  shard, then one ``psum_scatter`` along the wire axis leaves
                  the summed grid wire-sharded. (TPU analogue of the paper's
                  cross-GPU atomic-add: a single reduce-scatter collective.)
  FFT           : pencil decomposition — tick-axis rFFT is wire-local;
                  an ``all_to_all`` transposes to frequency-sharding so the
                  wire-axis FFT is local; multiply by R(ω); inverse the chain.
  output        : ADC grid wire-sharded (stays distributed for downstream
                  consumers, e.g. signal processing).

Two scatter-reduction strategies for §Perf:
  psum_scatter : partial full-size grids + one reduce-scatter (simple; moves
                 W_pad*T bytes per device through ICI).
  halo         : depos are pre-binned to their owner wire-shard on the host
                 (data pipeline does this for free); each device scatter-adds
                 only its own wire range + a halo margin, then exchanges halo
                 strips with neighbours via ``ppermute``. Moves only
                 O(halo*T) bytes — collective-bytes drop by ~W_shard/halo.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import LArTPCConfig
from repro.core import fluctuate as fl
from repro.core.depo import DepoSet
from repro.core.noise import noise_spectrum, sample_noise_rows
from repro.core.rasterize import rasterize
from repro.core.scatter import scatter_add
from repro.core.stages import (SimState, build_sim_graph,
                               resolve_plane_batching)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def padded_grid_shape(cfg: LArTPCConfig, nshards: int):
    """(W_pad, T, F_pad): wire axis divisible by nshards, freq axis too."""
    w_pad = _round_up(cfg.num_wires, nshards)
    nfreq = cfg.num_ticks // 2 + 1
    f_pad = _round_up(nfreq, nshards)
    return w_pad, cfg.num_ticks, f_pad


def make_distributed_sim(mesh: Mesh, cfg: LArTPCConfig, resp,
                         axes: Sequence[str] = ("data", "model"),
                         scatter_reduction: str = "psum_scatter",
                         add_noise: bool = True, recon: bool = False):
    """Build the jit'd distributed sim: (key, depos sharded over `axes`) -> ADC.

    `resp` is the response at the *distributed* (W_pad, T) grid shape —
    build it with ``make_distributed_response`` (single plane) or
    ``make_distributed_plane_responses`` (one per plane, multi-plane
    configs). Multi-plane configs take *physical* depos (the stock drift
    stage projects them onto every plane in-graph) and return a
    (num_planes, W_pad, T) ADC grid, plane axis replicated, wire axis
    sharded.

    ``recon=True`` appends the deconvolve/hit_find stages with
    collective-aware overrides and returns ``(adc, decon, hits)`` instead
    of the bare ADC grid: deconvolve rides the SAME pencil-FFT path as the
    forward convolve (the inverse filter is just another frequency-domain
    multiply, at the distributed cyclic shape); hit finding is wire-local
    per shard — each shard scans its own wires with a per-shard HitSet
    capacity of ceil(max_hits / nshards) and its global wire offset, and
    the shards' hits concatenate along the capacity axis (hit *positions*
    therefore differ from the single-device compaction; the masked hit set
    is what matches). ``hits.n_hits`` is summed over shards to the global
    candidate count, () single-plane / (P,) multi-plane.

    scatter_reduction:
      psum_scatter : each device scatter-adds its depos into a full-size
                     partial grid; one reduce-scatter leaves it wire-sharded
                     over ALL axes. Moves O(W_pad*T) bytes per device.
      halo         : depos must arrive pre-binned by wire strip over the LAST
                     axis (the data pipeline sorts by wire — free); each
                     device accumulates only its strip + halo margins and
                     exchanges the margins with ring neighbours, partials
                     psum'd over the other axes. Moves O(W_pad*T/nshards)
                     bytes — the paper's atomic-add turned into a
                     neighbour exchange. Single-plane only: each plane has
                     its own wire coordinate, so one host-side wire binning
                     cannot serve them all.
    """
    from repro.config import concat_planes, plane_specs

    if concat_planes(cfg):
        raise ValueError(
            "make_distributed_sim shards one (num_planes, W_pad, T) grid, "
            "every plane num_wires wide; this config gives its planes their "
            f"own wire counts (plane_wires={tuple(cfg.plane_wires)}), which "
            "the wire-sharded executor does not support — run it on one "
            "device (make_batched_sim_fn / stream_simulate)")
    axes = tuple(axes)
    specs = plane_specs(cfg)
    multi = cfg.num_planes > 1
    n_planes = len(specs)
    # "stacked" folds the plane axis into the shard_map body as a real
    # array axis: ONE reduce-scatter chain, ONE pencil-FFT all_to_all
    # chain, and one halo ppermute pair per step regardless of P (the
    # "loop" mode preserves the per-plane collectives)
    stacked = multi and resolve_plane_batching(cfg) == "stacked"
    if multi and scatter_reduction == "halo" and not stacked:
        raise ValueError(
            "multi-plane scatter_reduction='halo' requires "
            "plane_batching='stacked': the loop path pre-bins depos by ONE "
            "wire coordinate, but every plane projects its own; the "
            "stacked path takes a (num_planes, N) DepoSet pre-binned per "
            "plane-projected wire (bin_depos_by_wire)")
    if multi:
        resps = tuple(resp)
        if len(resps) != len(specs):
            raise ValueError(f"got {len(resps)} responses for "
                             f"{len(specs)} planes")
        rfreqs = [r.freq for r in resps]
    else:
        rfreqs = [resp.freq]  # (w_pad, nfreq) complex64, precomputed
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    # strips live on the FIRST axis so strip-major wire ownership matches the
    # flat (axes-major) ownership the pencil FFT uses
    halo_axis = axes[0]
    n_halo = mesh.shape[halo_axis]
    if scatter_reduction == "halo":
        w_pad, t_len, f_pad = padded_grid_shape(cfg, max(nshards, n_halo))
        w_strip = w_pad // n_halo
        halo = cfg.patch_wires
        assert w_strip >= halo, (
            f"halo strategy needs strip {w_strip} >= patch {halo}")
    else:
        w_pad, t_len, f_pad = padded_grid_shape(cfg, nshards)
    nfreq = t_len // 2 + 1
    w_shard = w_pad // nshards
    f_shard = f_pad // nshards

    namp = noise_spectrum(cfg)  # (nfreq,)

    # The distributed executor runs the SAME SimGraph as the single-event
    # and batched paths; only the collective-aware stages are overridden
    # (charge_grid reduces across devices, convolve is the pencil FFT,
    # noise draws per-device wire-local realizations). Drift and digitize
    # are the stock stages — drift is elementwise over the (sharded) depo
    # axis and digitize over the grid, so both shard freely, including the
    # multi-plane per-plane projection.

    def _rasterize_fluct(depos, base_key):
        """One plane's depo shard -> fluctuated patches (no collectives)."""
        patches, w0, t0 = rasterize(depos, cfg)
        if cfg.fluctuate and cfg.rng_strategy != "none":
            kf = jax.random.fold_in(base_key, _flat_index(axes, mesh))
            patches = fl.fluctuate_counter(kf, patches, depos.charge)
        return patches, w0, t0

    def _local_strip(patches, w0, t0):
        """Local halo-margined strip for one plane (no collectives)."""
        me = jax.lax.axis_index(halo_axis)
        lo = me * w_strip
        # local strip with halo margin on both sides (depos pre-binned
        # so every patch lands within [lo-halo, lo+w_strip+halo))
        return _scatter_local_strip(patches, w0, t0, lo, w_strip, halo,
                                    t_len, cfg)

    def _reduce_strips(strip):
        """Halo collectives for (..., strip_w, T) strips: one psum over the
        non-halo axes, one ppermute ring exchange, one sub-shard slice —
        the SAME collective count whether a plane axis leads or not."""
        for a in axes[1:]:
            strip = jax.lax.psum(strip, a)
        strip_own = _halo_exchange(strip, w_strip, halo, halo_axis)
        if w_shard == w_strip:
            return strip_own
        # slice my (finer) w_shard piece out of the strip for the FFT
        sub = _flat_index(axes[1:], mesh)
        start = (0,) * (strip_own.ndim - 2) + (sub * w_shard, 0)
        sizes = strip_own.shape[:-2] + (w_shard, t_len)
        return jax.lax.dynamic_slice(strip_own, start, sizes)

    def _reduce_partials(partial):
        """Reduce-scatter the wire axis (axis -2) of (..., W_pad, T)
        partials across every shard: one psum_scatter per mesh axis, the
        SAME collective count whether a plane axis leads or not."""
        lead = partial.shape[:-2]
        for a in axes:
            na = mesh.shape[a]
            partial = jnp.moveaxis(
                partial.reshape(*lead, na, partial.shape[-2] // na, t_len),
                -3, 0)
            partial = jax.lax.psum_scatter(
                partial, a, scatter_dimension=0, tiled=False)
        return partial

    def _charge_grid_one(depos, base_key):
        """One plane's depo shard -> its wire-sharded grid piece."""
        patches, w0, t0 = _rasterize_fluct(depos, base_key)
        if scatter_reduction == "halo":
            return _reduce_strips(_local_strip(patches, w0, t0))
        return _reduce_partials(
            _scatter_partial_full(patches, w0, t0, w_pad, t_len, cfg))

    def dist_charge_grid(state: SimState) -> SimState:
        if not multi:
            return state._replace(
                grid=_charge_grid_one(state.depos, state.key))
        # per-plane rasterize + fluctuate (local work, plane-folded keys,
        # bit-identical to the loop); ONLY the collectives batch over P
        locals_ = []
        for i, spec in enumerate(specs):
            depos_p = jax.tree.map(lambda x, i=i: x[i], state.depos)
            base = jax.random.fold_in(state.key, spec.index)
            locals_.append(_rasterize_fluct(depos_p, base))
        if not stacked:
            return state._replace(grid=jnp.stack([
                (_reduce_strips(_local_strip(p, w0, t0))
                 if scatter_reduction == "halo" else
                 _reduce_partials(_scatter_partial_full(p, w0, t0, w_pad,
                                                        t_len, cfg)))
                for p, w0, t0 in locals_]))
        if scatter_reduction == "halo":
            strip = jnp.stack([_local_strip(p, w0, t0)
                               for p, w0, t0 in locals_])
            return state._replace(grid=_reduce_strips(strip))
        partial = jnp.stack([
            _scatter_partial_full(p, w0, t0, w_pad, t_len, cfg)
            for p, w0, t0 in locals_])
        return state._replace(grid=_reduce_partials(partial))

    def _convolve_one(grid_local, rfreq):
        # ---- pencil FFT: tick rFFT local -> transpose -> wire FFT ----
        freq_t = jnp.fft.rfft(grid_local, axis=-1)          # (w_shard, nfreq)
        freq_t = jnp.pad(freq_t, ((0, 0), (0, f_pad - nfreq)))
        # transpose: (w_shard, f_pad) -> gather wires / scatter freq
        blk = freq_t.reshape(w_shard, nshards, f_shard)
        blk = jnp.swapaxes(blk, 0, 1)                        # (nshards, w_shard, f_shard)
        blk = _all_to_all_chain(blk, axes, mesh)             # (nshards, w_shard, f_shard)
        cols = blk.reshape(w_pad, f_shard)                   # all wires, my freqs
        freq_wt = jnp.fft.fft(cols, axis=0)                  # wire-axis FFT

        # ---- multiply by response in frequency domain ----
        me = _flat_index(axes, mesh)
        rcols = jax.lax.dynamic_slice(
            jnp.pad(rfreq, ((0, 0), (0, f_pad - nfreq))),
            (0, me * f_shard), (w_pad, f_shard))
        out_wt = freq_wt * rcols

        # ---- inverse chain ----
        cols = jnp.fft.ifft(out_wt, axis=0)                  # (w_pad, f_shard)
        blk = cols.reshape(nshards, w_shard, f_shard)
        blk = _all_to_all_chain(blk, axes, mesh)
        freq_t = jnp.swapaxes(blk, 0, 1).reshape(w_shard, f_pad)[:, :nfreq]
        return jnp.fft.irfft(freq_t, n=t_len, axis=-1).real.astype(jnp.float32)

    def _convolve_planes(grid_local, rfreq_pad):
        """All P planes through ONE pencil-FFT all_to_all chain.

        grid_local (P, w_shard, t_len); rfreq_pad (P, w_pad, f_pad) —
        plane p's output bit-identical to ``_convolve_one`` on plane p
        (the all_to_all is pure data movement, the FFTs batch per row).
        """
        freq_t = jnp.fft.rfft(grid_local, axis=-1)      # (P, w_shard, nfreq)
        freq_t = jnp.pad(freq_t, ((0, 0), (0, 0), (0, f_pad - nfreq)))
        blk = freq_t.reshape(n_planes, w_shard, nshards, f_shard)
        blk = jnp.moveaxis(blk, 2, 0)            # (nshards, P, w_shard, f_sh)
        blk = _all_to_all_chain(blk, axes, mesh)
        cols = jnp.swapaxes(blk, 0, 1).reshape(n_planes, w_pad, f_shard)
        freq_wt = jnp.fft.fft(cols, axis=-2)             # wire-axis FFT

        me = _flat_index(axes, mesh)
        rcols = jax.lax.dynamic_slice(
            rfreq_pad, (0, 0, me * f_shard), (n_planes, w_pad, f_shard))
        out_wt = freq_wt * rcols

        cols = jnp.fft.ifft(out_wt, axis=-2)             # (P, w_pad, f_shard)
        blk = jnp.swapaxes(cols.reshape(n_planes, nshards, w_shard, f_shard),
                           0, 1)                 # (nshards, P, w_shard, f_sh)
        blk = _all_to_all_chain(blk, axes, mesh)
        freq_t = jnp.moveaxis(blk, 0, 2).reshape(
            n_planes, w_shard, f_pad)[..., :nfreq]
        return jnp.fft.irfft(freq_t, n=t_len, axis=-1).real.astype(
            jnp.float32)

    if multi and stacked:
        rfreq_pad = jnp.stack([
            jnp.pad(rf, ((0, 0), (0, f_pad - nfreq))) for rf in rfreqs])

    def dist_convolve(state: SimState) -> SimState:
        if not multi:
            return state._replace(signal=_convolve_one(state.grid, rfreqs[0]))
        if stacked:
            return state._replace(
                signal=_convolve_planes(state.grid, rfreq_pad))
        return state._replace(signal=jnp.stack([
            _convolve_one(state.grid[i], rfreqs[i])
            for i in range(len(rfreqs))]))

    def _noise_one(kn):
        # wire-local noise realization for one plane: the shared draw, so
        # the Parseval normalization lives in exactly one place
        return sample_noise_rows(kn, w_shard, namp, t_len)

    def dist_noise(state: SimState) -> SimState:
        # per-device key schedule
        kn = jax.random.fold_in(state.key, 77 + _flat_index(axes, mesh))
        if not multi:
            noise = _noise_one(kn)
        elif stacked:
            # ONE batched spectrum draw over the stacked per-plane subkeys
            # (same fold_in derivation as the loop, vmapped)
            idx = jnp.asarray([s.index for s in specs], jnp.uint32)
            kns = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(kn, idx)
            noise = jax.vmap(_noise_one)(kns)
        else:
            noise = jnp.stack([
                _noise_one(jax.random.fold_in(kn, spec.index))
                for spec in specs])
        return state._replace(
            signal=state.signal + noise / max(cfg.adc_per_electron, 1e-30))

    overrides = {"charge_grid": dist_charge_grid, "convolve": dist_convolve}
    if add_noise:
        overrides["noise"] = dist_noise

    if recon:
        from repro.core.deconvolve import make_deconv_filter, measured_signal
        from repro.core.hitfind import find_hits

        # per-plane inverse filters at the distributed cyclic shape: the
        # resp(s) passed in ARE that shape, so the filters inherit it
        gfreqs = [make_deconv_filter(r, cfg).freq
                  for r in (resps if multi else [resp])]
        cap_shard = -(-cfg.max_hits // nshards)

        def _deconv_one(adc_local, gfreq):
            # the inverse filter is just another frequency-domain multiply:
            # reuse the forward pencil-FFT chain verbatim
            return _convolve_one(measured_signal(adc_local, cfg), gfreq)

        if multi and stacked:
            gfreq_pad = jnp.stack([
                jnp.pad(g, ((0, 0), (0, f_pad - nfreq))) for g in gfreqs])

        def dist_deconvolve(state: SimState) -> SimState:
            if not multi:
                return state._replace(
                    decon=_deconv_one(state.adc, gfreqs[0]))
            if stacked:
                # the inverse filter rides the same single-shot pencil chain
                return state._replace(decon=_convolve_planes(
                    measured_signal(state.adc, cfg), gfreq_pad))
            return state._replace(decon=jnp.stack([
                _deconv_one(state.adc[i], gfreqs[i])
                for i in range(len(gfreqs))]))

        def _hits_one(decon_local):
            me = _flat_index(axes, mesh)
            off = me * w_shard
            gw = off + jnp.arange(w_shard)
            # the wire axis is padded to w_pad: zero the padding wires so
            # their (noise-only) waveforms cannot fire hits
            masked = jnp.where((gw < cfg.num_wires)[:, None],
                               decon_local, 0.0)
            return find_hits(masked, cfg, cfg.hitfind_strategy,
                             wire_offset=off, max_hits=cap_shard)

        def dist_hit_find(state: SimState) -> SimState:
            if not multi:
                h = _hits_one(state.decon)
                # n_hits -> (1,) so every HitSet leaf concatenates over the
                # shard axis under one out_spec; the wrapper sums it back
                return state._replace(hits=h._replace(n_hits=h.n_hits[None]))
            per = [_hits_one(state.decon[i]) for i in range(len(specs))]
            h = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
            return state._replace(hits=h._replace(n_hits=h.n_hits[:, None]))

        overrides["deconvolve"] = dist_deconvolve
        overrides["hit_find"] = dist_hit_find

    graph = build_sim_graph(cfg, resp, add_noise=add_noise,
                            overrides=overrides, recon=recon)
    grid_spec = P(None, axes, None) if multi else P(axes, None)

    def local_run(key, depos):
        out = graph.run(key, depos)
        if not recon:
            return out.adc
        return out.adc, out.decon, out.hits

    # multi-plane halo takes a pre-drifted, per-plane-binned (P, N) DepoSet:
    # shard the depo axis, replicate the plane axis. Everything else takes
    # 1-D depo leaves (physical depos for multi-plane psum_scatter; the
    # in-graph drift stage projects them per plane).
    depo_spec = (P(None, axes) if multi and scatter_reduction == "halo"
                 else P(axes))
    fn = shard_map(
        local_run, mesh=mesh,
        # the depo spec is a pytree prefix: every leaf of the depos arg
        # (DepoSet or PhysicalDepoSet) shards its depo axis over `axes`
        in_specs=(P(), depo_spec),
        # the HitSet spec is a prefix too: every hit leaf concatenates its
        # leading (capacity / plane) axis over the shard group
        out_specs=(grid_spec if not recon else
                   (grid_spec, grid_spec,
                    P(None, axes) if multi else P(axes))),
        check_vma=False,
    )
    if not recon:
        return jax.jit(fn)

    def run(key, depos):
        adc, decon, hits = fn(key, depos)
        # per-shard candidate counts -> one global count per plane
        n = jnp.sum(hits.n_hits, axis=-1).astype(jnp.int32)
        return adc, decon, hits._replace(n_hits=n)

    return jax.jit(run)


def _flat_index(axes, mesh):
    """Flattened linear index of this device within the `axes` group."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _all_to_all_chain(blk, axes, mesh):
    """all_to_all over possibly-multiple mesh axes treated as one group.

    blk: (nshards, ...) — the leading axis is split/concat across the group.
    """
    if len(axes) == 1:
        return jax.lax.all_to_all(blk, axes[0], split_axis=0, concat_axis=0,
                                  tiled=True)
    # factor the group: reshape leading dim (A*B) -> A blocks of B
    a, b = axes[0], axes[1]
    na, nb = mesh.shape[a], mesh.shape[b]
    n = blk.shape[0]
    assert n == na * nb
    x = blk.reshape(na, nb, *blk.shape[1:])
    x = jax.lax.all_to_all(x, a, split_axis=0, concat_axis=0, tiled=False)
    # now (na, nb, ...): leading na is local block from each a-peer
    x = jnp.swapaxes(x, 0, 1).reshape(nb, na, *blk.shape[1:])
    x = jax.lax.all_to_all(x, b, split_axis=0, concat_axis=0, tiled=False)
    x = jnp.swapaxes(x, 0, 1)  # (na, nb, ...)
    return x.reshape(n, *blk.shape[1:])


def _scatter_partial_full(patches, w0, t0, w_pad, t_len, cfg: LArTPCConfig):
    """Local scatter-add into a full-size (padded) grid."""
    import dataclasses

    cfg2 = dataclasses.replace(cfg, num_wires=w_pad, num_ticks=t_len)
    return scatter_add(patches, w0, t0, cfg2, strategy="xla")


def _scatter_local_strip(patches, w0, t0, lo, w_shard, halo, t_len,
                         cfg: LArTPCConfig):
    """Scatter-add into my wire strip [lo-halo, lo+w_shard+halo)."""
    import dataclasses

    strip_w = w_shard + 2 * halo
    # shift into strip coordinates; out-of-range pixels get dropped by the
    # scatter's bounds mode.
    w0s = w0 - (lo - halo)
    n, pw, pt = patches.shape
    dw = jnp.arange(pw, dtype=jnp.int32)[None, :, None]
    dt = jnp.arange(pt, dtype=jnp.int32)[None, None, :]
    wi = w0s[:, None, None] + dw
    ti = t0[:, None, None] + dt
    inb = (wi >= 0) & (wi < strip_w)
    flat = jnp.where(inb, wi, 0) * t_len + ti
    grid = jnp.zeros((strip_w * t_len,), patches.dtype)
    grid = grid.at[flat.reshape(-1)].add(
        jnp.where(inb, patches, 0.0).reshape(-1), mode="drop")
    return grid.reshape(strip_w, t_len)


def _halo_exchange(strip, w_shard, halo, axis: str):
    """Add my halo overhangs into my neighbours' strips (ring ppermute).

    strip: (..., w_shard + 2*halo, T) — the wire axis is axis -2, so a
    stacked plane axis rides along through ONE ppermute pair; returns the
    owned (..., w_shard, T) region.
    """
    lo_halo = strip[..., :halo, :]    # belongs to left neighbour
    hi_halo = strip[..., -halo:, :]   # belongs to right neighbour
    n = jax.lax.psum(1, axis)
    right = [(i, (i + 1) % n) for i in range(n)]
    left = [(i, (i - 1) % n) for i in range(n)]
    from_left = jax.lax.ppermute(hi_halo, axis, right)   # left nbr's overhang
    from_right = jax.lax.ppermute(lo_halo, axis, left)   # right nbr's overhang
    own = strip[..., halo:halo + w_shard, :]
    own = own.at[..., :halo, :].add(from_left)
    own = own.at[..., -halo:, :].add(from_right)
    return own


def bin_depos_by_wire(depos: DepoSet, n_strips: int, w_pad: int) -> DepoSet:
    """Host-side pre-binning for the halo strategy: sort depos by wire and
    pad each strip's bucket to equal count (zero-charge filler), so strip i
    of the first mesh axis receives exactly the depos that touch it.

    Also accepts a multi-plane ``DepoSet`` with (P, N) leaves: each plane's
    row is binned by ITS OWN projected wire coordinate, and every plane
    shares one bucket capacity (the max over plane x strip) so strip s
    occupies the same column range in every plane — a single depo-axis
    shard then carries strip s of ALL planes.
    """
    import numpy as np

    wires = np.asarray(depos.wire)
    multi = wires.ndim == 2
    wires = np.atleast_2d(wires)
    strip_w = w_pad // n_strips
    plane_buckets = []
    cap = 1
    for wrow in wires:
        strip = np.clip((wrow // strip_w).astype(np.int64), 0, n_strips - 1)
        buckets = [np.nonzero(strip == s)[0] for s in range(n_strips)]
        cap = max(cap, max(len(b) for b in buckets))
        plane_buckets.append(buckets)
    n_out = cap * n_strips
    rows = []
    for buckets in plane_buckets:
        idx = np.zeros(n_out, np.int64)
        valid = np.zeros(n_out, bool)
        for s, b in enumerate(buckets):
            idx[s * cap:s * cap + len(b)] = b
            valid[s * cap:s * cap + len(b)] = True
        rows.append((idx, valid))
    center = np.array([(s * strip_w + strip_w // 2)
                       for s in range(n_strips)], np.float32)
    fill_wire = np.repeat(center, cap)

    def take(x, fill):
        arr = np.atleast_2d(np.asarray(x))
        out = np.stack([np.where(valid, arr[p][idx], fill).astype(np.float32)
                        for p, (idx, valid) in enumerate(rows)])
        return jnp.asarray(out if multi else out[0])

    return DepoSet(
        wire=take(depos.wire, fill_wire),
        tick=take(depos.tick, 100.0),
        sigma_w=take(depos.sigma_w, 1.0),
        sigma_t=take(depos.sigma_t, 1.0),
        charge=take(depos.charge, 0.0),
    )


def shard_depos(depos, mesh: Mesh, axes=("data", "model")):
    """Pad depo count to shard evenly and device_put with the DP sharding.

    Accepts a detector-frame ``DepoSet`` or a physical ``PhysicalDepoSet``
    (the input of multi-plane distributed runs — the in-graph drift stage
    projects it per plane). Padding depos carry zero charge, so they
    contribute nothing to any plane.
    """
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    # multi-plane halo inputs carry (P, N) leaves: pad/shard the LAST
    # (depo) axis only and replicate the plane axis
    planed = isinstance(depos, DepoSet) and depos.wire.ndim == 2
    n = depos.wire.shape[-1] if planed else depos.n
    n_pad = _round_up(n, nshards)
    pad = n_pad - n

    def padf(x):
        if planed:
            return jnp.pad(x, ((0, 0), (0, pad)))
        return jnp.pad(x, (0, pad))

    padded = type(depos)(*(padf(x) for x in depos))
    if isinstance(depos, DepoSet):
        # zero-charge padding; positive sigmas avoid 0/0 in Gaussian edges
        padded = padded._replace(charge=padded.charge.at[..., n:].set(0.0),
                                 sigma_w=padded.sigma_w.at[..., n:].set(1.0),
                                 sigma_t=padded.sigma_t.at[..., n:].set(1.0))
    # physical depos pad with zeros: q=0 is inert, and the drift stage's
    # sigma floors keep zero-drift-time widths positive
    sh = NamedSharding(mesh, P(None, tuple(axes)) if planed
                       else P(tuple(axes)))
    return type(depos)(*(jax.device_put(x, sh) for x in padded))
