"""Every strategy the registry offers on the CPU, checked against a plain
float64 NumPy form of its op at the deployments' own plane shapes.

The strategy-equivalence tests (``test_tune.py``) hold the candidates to
one another; here each is held to an independent reference of the op it
implements, at the smoke ``lartpc-uboone`` U plane (128 wires, bipolar
response) and at ``lartpc-uboone3p``'s planes (96-wire induction, 128-wire
collection; the charge grid over all three planes stacked on one
320-wire axis, as the deployment scatters it). The reference reads only
the kernel a ``DetectorResponse`` carries; its padding, spectra and run
scanning are written out here.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, plane_specs
from repro.core.deconvolve import deconvolve, make_deconv_filter
from repro.core.depo import generate_depos, generate_plane_depos
from repro.core.fft_conv import fft_convolve
from repro.core.rasterize import rasterize
from repro.core.response import make_response
from repro.core.scatter import scatter_add
from repro.core.stages import plane_rows
from repro.tune.registry import (available_strategies, get_strategy,
                                 make_context)

U = get_config("lartpc-uboone", smoke=True)
U3P = get_config("lartpc-uboone3p", smoke=True)

#: (name, config, plane kind, wires) of each distinct plane shape
PLANES = {
    "uboone-U": (U, "induction", U.num_wires),
    "uboone3p-U": (U3P, "induction", plane_specs(U3P)[0].num_wires),
    "uboone3p-Y": (U3P, "collection", plane_specs(U3P)[2].num_wires),
}


def _offered(op, cfg, **shape):
    """The strategy names the registry offers for ``op`` on the CPU."""
    ctx = make_context(cfg, dict(shape), backend="cpu")
    return sorted(available_strategies(op, ctx))


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# ---------------------------------------------------------------------------
# Plain float64 references
# ---------------------------------------------------------------------------


def ref_scatter(patches, w0, t0, shape):
    """Add each (pw, pt) patch at its (w0, t0) origin; pixels off the grid
    are dropped."""
    grid = np.zeros(shape, np.float64)
    patches = np.asarray(patches, np.float64)
    for p, w, t in zip(patches, np.asarray(w0), np.asarray(t0)):
        pw, pt = p.shape
        w1, t1 = min(w + pw, shape[0]), min(t + pt, shape[1])
        grid[max(w, 0):w1, max(t, 0):t1] += p[max(-w, 0):w1 - w,
                                              max(-t, 0):t1 - t]
    return grid


def ref_convolve(grid, kernel):
    """Linear convolution: wire offset ``dw - rw // 2``, tick offset ``dt``
    (the response is causal in time and centred on its middle wire), cropped
    to the grid."""
    grid, kernel = np.asarray(grid, np.float64), np.asarray(kernel, np.float64)
    w, t = grid.shape
    rw, rt = kernel.shape
    out = np.zeros_like(grid)
    for dw in range(rw):
        s = dw - rw // 2
        src = grid[max(-s, 0):w - max(s, 0)]
        for dt in range(rt):
            out[max(s, 0):w - max(-s, 0), dt:] += kernel[dw, dt] * src[:, :t - dt]
    return out


def ref_deconvolve(meas, kernel, pad_shape, cfg, kind):
    """The bounded inverse of the response's spectrum at ``pad_shape``,
    conj(R) / (|R|^2 + lambda max|R|^2), times the Gaussian time window for
    ``kind == "gaussian"``, applied on the zero-padded grid."""
    wp, tp = pad_shape
    rw, rt = kernel.shape
    kpad = np.zeros(pad_shape)
    kpad[:rw, :rt] = np.asarray(kernel, np.float64)
    resp = np.fft.fft2(np.roll(kpad, -(rw // 2), axis=0))
    power = np.abs(resp) ** 2
    filt = np.conj(resp) / (power + cfg.deconv_wiener_lambda * power.max())
    if kind == "gaussian":
        k = np.abs(np.fft.fftfreq(tp) * tp)  # |time-frequency index|
        nyq = max(tp // 2, 1)
        filt = filt * np.exp(-0.5 * (k / (cfg.deconv_gauss_cut * nyq)) ** 2)
    w, t = meas.shape
    mpad = np.zeros(pad_shape)
    mpad[:w, :t] = np.asarray(meas, np.float64)
    return np.real(np.fft.ifft2(np.fft.fft2(mpad) * filt))[:w, :t]


def ref_wire_hits(decon, threshold, cap):
    """Runs of consecutive samples above ``threshold`` on each wire: the
    count of all runs, and the charge sum, charge-weighted mean tick and
    peak of the first ``cap``."""
    decon = np.asarray(decon, np.float64)
    w = decon.shape[0]
    counts = np.zeros(w, np.int64)
    charge, tick, peak = (np.zeros((w, cap)) for _ in range(3))
    for i, row in enumerate(decon):
        above = np.concatenate([[False], row > threshold, [False]])
        starts = np.flatnonzero(~above[:-1] & above[1:])
        ends = np.flatnonzero(above[:-1] & ~above[1:])
        counts[i] = len(starts)
        for j, (a, b) in enumerate(zip(starts[:cap], ends[:cap])):
            v = row[a:b]
            charge[i, j] = v.sum()
            tick[i, j] = (v * np.arange(a, b)).sum() / v.sum()
            peak[i, j] = v.max()
    return counts, charge, tick, peak


# ---------------------------------------------------------------------------
# Inputs: each deployment's own smoke event
# ---------------------------------------------------------------------------


def _patches(name):
    """(patches, w0, t0, grid shape) of the deployment's charge-grid
    scatter: the U plane alone, or the three planes' patches with their
    wire origins offset to the plane's rows of the stacked axis."""
    key = jax.random.key(3)
    if name == "uboone":
        p, w0, t0 = rasterize(generate_depos(key, U), U)
        return p, w0, t0, (U.num_wires, U.num_ticks)
    depos = generate_plane_depos(key, U3P)
    specs = plane_specs(U3P)
    parts = []
    for i, (start, n) in enumerate(plane_rows(specs)):
        p, w0, t0 = rasterize(jax.tree.map(lambda x, i=i: x[i], depos),
                              dataclasses.replace(U3P, num_wires=n))
        parts.append((p, w0 + start, t0))
    p, w0, t0 = (jnp.concatenate(x) for x in zip(*parts))
    return p, w0, t0, (sum(s.num_wires for s in specs), U3P.num_ticks)


_PLANE_CACHE = {}


def _plane(name):
    """(cfg, response, reference charge grid, reference signal) of one
    plane: the plane's own depos scattered and convolved in float64."""
    if name not in _PLANE_CACHE:
        cfg, kind, wires = PLANES[name]
        pcfg = dataclasses.replace(cfg, num_wires=wires, num_planes=1,
                                   plane_wires=())
        resp = make_response(cfg, plane=kind, num_wires=wires)
        p, w0, t0 = rasterize(generate_depos(jax.random.key(5), pcfg), pcfg)
        grid = ref_scatter(p, w0, t0, (wires, cfg.num_ticks))
        _PLANE_CACHE[name] = (pcfg, resp, grid, ref_convolve(grid,
                                                              resp.kernel))
    return _PLANE_CACHE[name]


SCATTER_CASES = [(d, s) for d, cfg in (("uboone", U), ("uboone3p", U3P))
                 for s in _offered("scatter_add", cfg, num_depos=256,
                                   num_wires=cfg.num_wires,
                                   num_ticks=cfg.num_ticks)]
FFT_CASES = [(p, s) for p, (cfg, _, w) in PLANES.items()
             for s in _offered("fft_convolve", cfg, num_wires=w,
                               num_ticks=cfg.num_ticks,
                               response_wires=cfg.response_wires,
                               response_ticks=cfg.response_ticks)]
DECON_CASES = [(p, s, f) for p in PLANES for s in ("rfft2", "fft_reuse")
               for f in ("wiener", "gaussian")]
HIT_CASES = [(p, s) for p, (cfg, _, w) in PLANES.items()
             for s in _offered("hit_find", cfg, num_wires=w,
                               num_ticks=cfg.num_ticks)]


def test_the_cpu_offers_the_strategies_checked_here():
    """The cases below are the registry's own CPU offer at these shapes;
    a strategy that stops being offered, or a new one, shows here."""
    assert sorted({s for _, s in SCATTER_CASES}) == [
        "lane_rows", "pallas", "pallas_compact", "sort_segment", "xla"]
    assert sorted({s for _, s in FFT_CASES}) == ["direct", "fft2", "rfft2"]
    assert sorted({s for _, s in HIT_CASES}) == ["pallas", "scan"]


@pytest.mark.parametrize("deployment,strategy", SCATTER_CASES)
def test_scatter_add_matches_float64(deployment, strategy):
    p, w0, t0, shape = _patches(deployment)
    cfg = dataclasses.replace(U if deployment == "uboone" else U3P,
                              num_wires=shape[0])
    got = scatter_add(p, w0, t0, cfg, strategy=strategy)
    want = ref_scatter(p, w0, t0, shape)
    assert got.shape == shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("plane,strategy", FFT_CASES)
def test_fft_convolve_matches_float64(plane, strategy):
    _, resp, grid, want = _plane(plane)
    got = fft_convolve(jnp.asarray(grid, jnp.float32), resp, strategy=strategy)
    assert got.shape == grid.shape
    assert _rel_l2(got, want) < 1e-5


@pytest.mark.parametrize("plane,strategy,kind", DECON_CASES)
def test_deconvolve_matches_float64(plane, strategy, kind):
    cfg, resp, _, signal = _plane(plane)
    filt = make_deconv_filter(resp, cfg, kind=kind)
    got = deconvolve(jnp.asarray(signal, jnp.float32), filt, strategy=strategy)
    want = ref_deconvolve(signal, resp.kernel, resp.pad_shape, cfg, kind)
    assert got.shape == signal.shape
    assert _rel_l2(got, want) < 1e-5


@pytest.mark.parametrize("plane,strategy", HIT_CASES)
def test_hit_find_matches_float64(plane, strategy):
    cfg, resp, _, signal = _plane(plane)
    decon = ref_deconvolve(signal, resp.kernel, resp.pad_shape, cfg, "wiener")
    decon32 = np.asarray(decon, np.float32)
    counts, charge, tick, peak = (np.asarray(x) for x in get_strategy(
        "hit_find", strategy).fn(jnp.asarray(decon32), cfg))
    want = ref_wire_hits(decon32, cfg.hit_threshold, cfg.max_hits_per_wire)
    assert want[0].sum() > 0, "the plane's event leaves no hits to find"
    np.testing.assert_array_equal(counts, want[0])
    for got_x, want_x in zip((charge, tick, peak), want[1:]):
        np.testing.assert_allclose(got_x, want_x, rtol=1e-5, atol=1e-3)
