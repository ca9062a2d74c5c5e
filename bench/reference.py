"""Plain reference of the simulation chain, in NumPy and SciPy float64.

It imports nothing of the program. Every constant comes from the
configuration's file under ``bench/configs`` (its ``sizes``), and every
table (detector response, noise spectrum, inverse filter) is built here
from the formulas the configuration names. The only shared parts are the
event's depos, drawn by the frozen generator in ``bench/depogen.py``, and
the random normals, drawn by ``jax.random`` from the event key by the key
schedule the configuration states (the ``counter`` fluctuation strategy).
Where a configuration names no reference module of its own
(``bench/references``), that schedule is ``check.reference_event``'s:

    event key   fold_in(key(seed), event_id)
    kf, kn      split(event key)                 charge grid, noise
    plane p     fold_in(kf, p), fold_in(kn, p)   (multi-plane only)
    fluctuation normal(kf_p, (depos, patch_wires, patch_ticks), float32)
    noise       k1, k2 = split(kn_p); normal(k1 | k2, (wires, ticks//2+1))

Stages: drift, charge grid (bin-integrated Gaussian patches, binomial
fluctuation by its normal approximation, scatter-add), convolution with the
field x electronics response, frequency-shaped noise, digitization, and for
recon configurations the Wiener deconvolution and the threshold hit finder.
Each plane runs the chain on its own (``plane_chain``), at its own wire
count, so a deployment's module can reuse it for planes that differ.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, NamedTuple, Optional

import numpy as np
import scipy.fft
import scipy.signal
from scipy.special import erf

WORKERS = os.cpu_count() or 1


class EventRef(NamedTuple):
    """Reference outputs of one event: lists with one array per plane, each
    (wires of that plane, T), so planes may differ in wire count."""

    grid: List[np.ndarray]  # charge after fluctuation, electrons
    signal: List[np.ndarray]  # after convolution and noise, electrons
    adc: List[np.ndarray]  # int16 counts
    decon: Optional[List[np.ndarray]] = None  # recon only
    hits: Optional[list] = None  # per plane: Hits


class Hits(NamedTuple):
    """The hits one plane stores, wire-major, and the count found."""

    wire: np.ndarray
    tick: np.ndarray
    charge: np.ndarray
    n_found: int


# ---------------------------------------------------------------------------
# Geometry and drift
# ---------------------------------------------------------------------------


def planes(sizes: dict):
    """(kind, angle_deg, pitch_mm) of each readout plane."""
    n = sizes["num_planes"]
    if n == 1:
        return [("induction", 0.0, sizes["wire_pitch_mm"])]
    pitches = sizes["plane_pitches_mm"] or [sizes["wire_pitch_mm"]] * n
    return [(sizes["plane_types"][p], sizes["plane_angles_deg"][p], pitches[p])
            for p in range(n)]


def project(y, z, angle_deg: float, pitch_mm: float, sizes: dict):
    """Wire coordinate on a plane whose wires lie ``angle_deg`` from
    vertical, centred on the detector, in float32 as the configuration
    computes it (a rounded wire coordinate picks a depo's patch origin)."""
    rad = math.radians(angle_deg)
    cos_, sin_ = math.cos(rad), math.sin(rad)
    cw = cos_ * sizes["wire_pitch_mm"] / pitch_mm
    cz = sin_ / pitch_mm
    nw = sizes["num_wires"]
    y_max = (nw - 1.0) * sizes["wire_pitch_mm"]
    z_max = nw * sizes["wire_pitch_mm"]
    lo = min(0.0, y_max * cos_) + min(0.0, z_max * sin_)
    hi = max(0.0, y_max * cos_) + max(0.0, z_max * sin_)
    off = (nw - 1.0) / 2.0 - (lo + hi) / (2.0 * pitch_mm)
    if abs(off) < 1e-6:
        off = 0.0
    if cw == 1.0 and cz == 0.0 and off == 0.0:
        return y
    w = y * np.float32(cw)
    if cz != 0.0:
        w = w + z * np.float32(cz)
    if off != 0.0:
        w = w + np.float32(off)
    return w


def drift(phys: dict, pitch_mm: float, sizes: dict) -> dict:
    """Physical depos (float32 arrays x, y, t, q) on one plane -> detector
    depos: arrival tick, diffusion widths, charge after recombination and
    electron lifetime."""
    x = phys["x"].astype(np.float64)
    tick = (phys["t"] + phys["x"]) / np.float32(sizes["tick_us"])
    scale = sizes["diffusion_scale"]
    sigma_t = (np.sqrt(2.0 * sizes["diffusion_long"] * x)
               / (sizes["drift_speed_mm_us"] * sizes["tick_us"]) * scale
               + sizes["sigma_t_floor"])
    sigma_w = (np.sqrt(2.0 * sizes["diffusion_tran"] * x) / pitch_mm * scale
               + sizes["sigma_w_floor"])
    ns = sizes["nsigma"]
    sigma_w = np.clip(sigma_w, min(0.3, sizes["sigma_w_floor"]),
                      (sizes["patch_wires"] / 2 - 1) / ns)
    sigma_t = np.clip(sigma_t, min(0.3, sizes["sigma_t_floor"]),
                      (sizes["patch_ticks"] / 2 - 1) / ns)
    q = phys["q"].astype(np.float64) * sizes["recombination"]
    if sizes["electron_lifetime_us"] > 0.0:
        q = q * np.exp(-x / sizes["electron_lifetime_us"])
    return {"wire": phys["wire"], "tick": tick.astype(np.float32),
            "sigma_w": sigma_w, "sigma_t": sigma_t, "charge": q}


# ---------------------------------------------------------------------------
# Charge grid
# ---------------------------------------------------------------------------


def _axis_weights(center, sigma, origin, npix: int):
    edges = origin[:, None] + np.arange(npix + 1)[None, :]
    cdf = erf((edges - center.astype(np.float64)[:, None])
              / (sigma[:, None] * math.sqrt(2.0)))
    return np.maximum(0.5 * (cdf[:, 1:] - cdf[:, :-1]), 0.0)


def charge_grid(depos: dict, normals: np.ndarray, num_wires: int,
                sizes: dict, chunk: int = 4096) -> np.ndarray:
    """Bin-integrated Gaussian patches, fluctuated, summed into a grid of
    ``num_wires`` wires (in chunks of depos, so each chunk's arrays stay in
    cache)."""
    nw, nt = num_wires, sizes["num_ticks"]
    pw, pt = sizes["patch_wires"], sizes["patch_ticks"]
    w0 = np.clip(np.rint(depos["wire"]).astype(np.int64) - pw // 2, 0, nw - pw)
    t0 = np.clip(np.rint(depos["tick"]).astype(np.int64) - pt // 2, 0, nt - pt)
    ww = _axis_weights(depos["wire"], depos["sigma_w"], w0, pw)
    wt = _axis_weights(depos["tick"], depos["sigma_t"], t0, pt)
    q = depos["charge"]
    offsets = (np.arange(pw)[:, None] * nt + np.arange(pt)[None, :]).ravel()
    n = len(q)
    idx = np.empty((n, pw * pt), np.int64)
    vals = np.empty((n, pw * pt))
    for a in range(0, n, chunk):
        b = min(a + chunk, n)
        patch = (q[a:b, None, None] * ww[a:b, :, None]
                 * wt[a:b, None, :]).reshape(b - a, -1)
        if sizes["fluctuate"]:
            # Binomial(q, w) by its normal approximation: mean q w and
            # variance q w (1 - w), clipped at zero
            p = np.clip(patch / np.maximum(q[a:b], 1.0)[:, None], 0.0, 1.0)
            var = np.maximum(patch * (1.0 - p), 0.0)
            patch = np.maximum(
                patch + np.sqrt(var) * normals[a:b].reshape(b - a, -1), 0.0)
        vals[a:b] = patch
        idx[a:b] = (w0[a:b] * nt + t0[a:b])[:, None] + offsets[None, :]
    grid = np.bincount(idx.ravel(), weights=vals.ravel(), minlength=nw * nt)
    return grid.reshape(nw, nt)


# ---------------------------------------------------------------------------
# Response, convolution, noise, digitization
# ---------------------------------------------------------------------------


def response_kernel(kind: str, sizes: dict) -> np.ndarray:
    """(response_wires, response_ticks) field x electronics response."""
    rw, rt = sizes["response_wires"], sizes["response_ticks"]
    t = np.arange(rt) * sizes["tick_us"]
    if kind == "collection":
        field = np.exp(-0.5 * ((t - 1.0) / 0.5) ** 2)
    else:  # induction: bipolar, a derivative of a Gaussian
        field = -(t - 1.5) * np.exp(-0.5 * ((t - 1.5) / 0.6) ** 2)
    x = np.clip(t / sizes["response_shaping_us"], 0.0, None)
    elec = x**4 * np.exp(-4 * x)  # CR-(RC)^4 semi-Gaussian shaper
    elec = elec / (elec.max() + 1e-30)
    tr = np.convolve(field, elec)[:rt]
    tr = tr / (np.abs(tr).max() + 1e-30)
    dw = np.arange(rw) - (rw - 1) / 2.0
    prof = np.exp(-0.5 * (dw / (rw / 6.0)) ** 2)
    prof = prof / prof.sum()
    return prof[:, None] * tr[None, :] * sizes["response_gain"]


def convolve(grid: np.ndarray, kernel: np.ndarray) -> np.ndarray:
    """Linear convolution, the kernel centred on its middle wire and
    starting at tick 0."""
    nw, nt = grid.shape
    with scipy.fft.set_workers(WORKERS):
        full = scipy.signal.fftconvolve(grid, kernel, mode="full")
    c = kernel.shape[0] // 2
    return full[c:c + nw, :nt]


def noise(key_pair, sizes: dict) -> np.ndarray:
    """Frequency-shaped noise with expected rms ``noise_rms_adc`` counts:
    an amplitude spectrum 1/sqrt(f) + 0.3 with a Gaussian roll-off, random
    phases from two normal draws, and an inverse real FFT per wire."""
    re, im = key_pair
    n = sizes["num_ticks"]
    nf = n // 2 + 1
    f = np.arange(nf) + 1.0
    amp = (1.0 / np.sqrt(f) + 0.3) * np.exp(-((f / nf) ** 2) * 2.0)
    w = np.full(nf, 2.0)
    w[0] = 0.5
    if n % 2 == 0:
        w[-1] = 0.5
    amp = amp * sizes["noise_rms_adc"] * n / np.sqrt(np.sum(w * amp**2) + 1e-30)
    im = im.astype(np.float64).copy()
    im[:, 0] = 0.0
    if n % 2 == 0:
        im[:, -1] = 0.0
    spec = (re + 1j * im) * amp[None, :] * math.sqrt(0.5)
    return scipy.fft.irfft(spec, n=n, axis=-1, workers=WORKERS)


def digitize(signal: np.ndarray, sizes: dict) -> np.ndarray:
    adc = sizes["adc_baseline"] + sizes["adc_per_electron"] * signal
    return np.clip(np.rint(adc), 0, 4095).astype(np.int16)


# ---------------------------------------------------------------------------
# Recon: deconvolution and hit finding
# ---------------------------------------------------------------------------


def fast_len(n: int) -> int:
    """Smallest 2^a 3^b 5^c >= n."""
    best = 1 << max(n - 1, 0).bit_length()
    m5 = 1
    while m5 < best:
        m53 = m5
        while m53 < best:
            m = m53
            while m < n:
                m *= 2
            best = min(best, m)
            m53 *= 3
        m5 *= 5
    return best


def deconvolve(adc: np.ndarray, kernel: np.ndarray, sizes: dict) -> np.ndarray:
    """Wiener inverse of the response on the linear-convolution grid
    (5-smooth padded sizes): G = conj(R) / (|R|^2 + lambda max|R|^2)."""
    if sizes["deconv_filter"] != "wiener":
        raise ValueError(f"no reference for filter {sizes['deconv_filter']!r}")
    nw, nt = adc.shape
    rw, rt = kernel.shape
    wp, tp = fast_len(nw + rw - 1), fast_len(nt + rt - 1)
    kpad = np.zeros((wp, tp))
    kpad[:rw, :rt] = kernel
    kpad = np.roll(kpad, -(rw // 2), axis=0)
    r = scipy.fft.rfft2(kpad, workers=WORKERS)
    power = np.abs(r) ** 2
    g = np.conj(r) / (power + sizes["deconv_wiener_lambda"] * power.max())
    meas = np.zeros((wp, tp))
    meas[:nw, :nt] = ((adc.astype(np.float64) - sizes["adc_baseline"])
                      / sizes["adc_per_electron"])
    spec = scipy.fft.rfft2(meas, workers=WORKERS) * g
    return scipy.fft.irfft2(spec, s=(wp, tp), workers=WORKERS)[:nw, :nt]


def find_hits(decon: np.ndarray, sizes: dict) -> Hits:
    """Runs of consecutive ticks above threshold on each wire: summed
    charge and charge-weighted mean tick. Each wire keeps its first
    ``max_hits_per_wire`` runs; the plane stores the first ``max_hits`` of
    those in wire-major order and counts every run found."""
    nw, nt = decon.shape
    above = decon > sizes["hit_threshold"]
    edge = np.diff(np.pad(above, ((0, 0), (1, 1))).astype(np.int8), axis=1)
    sw, st = np.nonzero(edge == 1)  # run starts, wire-major
    ew, et = np.nonzero(edge == -1)  # one past each run's end
    assert np.array_equal(sw, ew)
    n_found = len(sw)
    first = np.searchsorted(sw, sw, side="left")
    rank = np.arange(n_found) - first
    keep = np.nonzero(rank < sizes["max_hits_per_wire"])[0][: sizes["max_hits"]]
    wire = sw[keep]
    charge = np.empty(len(keep))
    tsum = np.empty(len(keep))
    ticks = np.arange(nt, dtype=np.float64)
    for i, k in enumerate(keep):
        v = decon[sw[k], st[k]:et[k]]
        charge[i] = v.sum()
        tsum[i] = (v * ticks[st[k]:et[k]]).sum()
    return Hits(wire=wire, tick=tsum / np.maximum(charge, 1e-30),
                charge=charge, n_found=n_found)


# ---------------------------------------------------------------------------
# One event
# ---------------------------------------------------------------------------


def plane_chain(depos: dict, kind: str, num_wires: int, normals,
                noise_draws, sizes: dict, recon: bool) -> dict:
    """One plane's chain from its drifted depos (``drift``) to its outputs
    ("grid", "signal", "adc", and in recon "decon", "hits"), on a plane of
    ``num_wires`` wires with a ``kind`` response. ``normals``: its
    fluctuation normals, (depos, patch_wires, patch_ticks); ``noise_draws``:
    its (re, im) noise normals, each (num_wires, ticks // 2 + 1)."""
    grid = charge_grid(depos, normals, num_wires, sizes)
    kernel = response_kernel(kind, sizes)
    signal = convolve(grid, kernel) + noise(noise_draws, sizes) / max(
        sizes["adc_per_electron"], 1e-30)
    adc = digitize(signal, sizes)
    out = {"grid": grid, "signal": signal, "adc": adc}
    if recon:
        out["decon"] = deconvolve(adc, kernel, sizes)
        out["hits"] = find_hits(out["decon"], sizes)
    return out


def simulate_plane(phys: dict, p: int, draws, sizes: dict, recon: bool):
    """Plane p of the default geometry (``planes``, ``project``), every
    plane ``num_wires`` wires; ``draws(p)`` gives its fluctuation normals
    and its (re, im) noise normals."""
    kind, angle, pitch = planes(sizes)[p]
    normals, noise_draws = draws(p)
    wire = project(phys["y"], phys["z"], angle, pitch, sizes)
    depos = drift(dict(phys, wire=wire), pitch, sizes)
    return plane_chain(depos, kind, sizes["num_wires"], normals, noise_draws,
                       sizes, recon)


def event_ref(outs: list, recon: bool) -> EventRef:
    """The per-plane lists of ``plane_chain`` outputs, in plane order."""

    def per_plane(key):
        return [o[key] for o in outs]

    return EventRef(grid=per_plane("grid"), signal=per_plane("signal"),
                    adc=per_plane("adc"),
                    decon=per_plane("decon") if recon else None,
                    hits=per_plane("hits") if recon else None)


def simulate_event(phys: dict, draws, sizes: dict, recon: bool) -> EventRef:
    """The whole chain for one event of the default geometry, its planes on
    threads of their own (NumPy and SciPy release the interpreter lock in
    the array work).

    ``phys``: float32 arrays x, y, z, t, q of the physical depos;
    ``draws(p)``: plane p's normals, as ``simulate_plane`` takes them.
    """
    n = sizes["num_planes"]
    with ThreadPoolExecutor(max_workers=n) as pool:
        outs = list(pool.map(
            lambda p: simulate_plane(phys, p, draws, sizes, recon), range(n)))
    return event_ref(outs, recon)
