"""Detector response R(t, x): field response × electronics shaping.

The paper uses the pre-computed MicroBooNE 2-D response (refs [9,10]): bipolar
for induction planes, unipolar for collection. We synthesize a response with the
same structure: a wire-direction induction profile spanning ±(response_wires//2)
wires convolved with a time-direction shaping (semi-Gaussian electronics) and a
plane-dependent field-response time shape.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig


class DetectorResponse(NamedTuple):
    """A frequency-domain transfer function over the padded readout grid.

    The FORWARD response (``make_response``) is the canonical instance, but
    the container is direction-agnostic: the recon chain's inverse filters
    (``repro.core.deconvolve.make_deconv_filter``) are DetectorResponses
    too — same ``pad_shape``/``plane``, ``freq`` holding the regularized
    inverse — so the spectral ``fft_convolve`` layouts and the plane-keyed
    tuning bucket apply to deconvolution unchanged. ``kernel`` stays the
    forward kernel, which the real-space ``direct`` strategy reads: it
    applies to forward responses only.
    """

    kernel: jax.Array       # (response_wires, response_ticks) real-space response
    freq: jax.Array         # rfft2 of the kernel at padded grid shape (complex64)
    pad_shape: tuple        # (W_pad, T_pad) padded grid shape for linear conv
    plane: str = "induction"  # field-response type this transform encodes
    #                          ("induction" | "collection") — part of the
    #                          fft_convolve tuning key (repro.tune)


def _semigaussian(t_us: jax.Array, shaping_us: float = 2.0, order: int = 4) -> jax.Array:
    """CR-(RC)^n semi-Gaussian electronics shaping response."""
    x = jnp.clip(t_us / shaping_us, 0.0, None)
    h = (x ** order) * jnp.exp(-order * x)
    return h / (jnp.max(h) + 1e-30)


def _field_time(t_us: jax.Array, plane: str) -> jax.Array:
    """Field-response time shape: bipolar (induction) or unipolar (collection)."""
    if plane == "collection":
        return jnp.exp(-0.5 * ((t_us - 1.0) / 0.5) ** 2)
    # induction: derivative-of-Gaussian -> bipolar
    return -(t_us - 1.5) * jnp.exp(-0.5 * ((t_us - 1.5) / 0.6) ** 2)


def next_fast_len(n: int) -> int:
    """Smallest 2^a * 3^b * 5^c >= n (FFT-friendly size)."""
    if n <= 1:
        return 1
    best = 1 << (n - 1).bit_length()
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


def make_response(cfg: LArTPCConfig, plane: str = "induction",
                  num_wires: int | None = None) -> DetectorResponse:
    """The response of a ``plane``-kind readout of ``num_wires`` wires
    (default ``cfg.num_wires``), transformed at its padded grid shape."""
    rw, rt = cfg.response_wires, cfg.response_ticks
    t_us = jnp.arange(rt, dtype=jnp.float32) * cfg.tick_us
    time_resp = _field_time(t_us, plane)
    elec = _semigaussian(t_us, shaping_us=cfg.response_shaping_us)
    # time response = field (x) electronics, linear convolution cropped to rt
    tr = jnp.convolve(time_resp, elec, mode="full")[:rt]
    tr = tr / (jnp.max(jnp.abs(tr)) + 1e-30)

    # wire-direction induction profile: falls off with wire distance
    dw = jnp.arange(rw, dtype=jnp.float32) - (rw - 1) / 2.0
    wire_prof = jnp.exp(-0.5 * (dw / (rw / 6.0)) ** 2)
    wire_prof = wire_prof / jnp.sum(wire_prof)

    kernel = wire_prof[:, None] * tr[None, :]
    # overall amplitude: a calibration degree of freedom. A *python* 1.0
    # skips the multiply so the default traced program is unchanged; a
    # traced gain (repro.core.fit differentiating the response) always
    # applies (multiplying by exactly 1.0 is value-exact anyway).
    gain = cfg.response_gain
    if isinstance(gain, jax.Array) or gain != 1.0:
        kernel = kernel * gain

    w_pad = next_fast_len((num_wires or cfg.num_wires) + rw - 1)
    t_pad = next_fast_len(cfg.num_ticks + rt - 1)
    kpad = jnp.zeros((w_pad, t_pad), jnp.float32)
    kpad = kpad.at[:rw, :rt].set(kernel)
    # center the wire axis so output is aligned (roll by half the wire span)
    kpad = jnp.roll(kpad, shift=-(rw // 2), axis=0)
    freq = jnp.fft.rfft2(kpad)
    return DetectorResponse(kernel=kernel, freq=freq, pad_shape=(w_pad, t_pad),
                            plane=plane)


def make_plane_responses(cfg: LArTPCConfig):
    """One ``DetectorResponse`` per readout plane of ``cfg``, in plane order
    (bipolar for induction planes, unipolar for the collection plane), each
    padded for its plane's wire count."""
    from repro.config import plane_specs

    return tuple(make_response(cfg, plane=s.kind, num_wires=s.num_wires)
                 for s in plane_specs(cfg))


def make_distributed_response(cfg: LArTPCConfig, w_pad: int,
                              plane: str = "induction") -> DetectorResponse:
    """Response transform at the *distributed* grid shape (w_pad, num_ticks).

    The distributed pipeline uses cyclic convolution at the readout size
    (Wire-Cell's own convention — the response support (~200 ticks) is tiny
    compared to the readout window, and wrap-around lands in the pre-trigger
    padding), so freq is evaluated at exactly (w_pad, num_ticks).
    """
    base = make_response(cfg, plane)
    rw, rt = base.kernel.shape
    kpad = jnp.zeros((w_pad, cfg.num_ticks), jnp.float32)
    kpad = kpad.at[:rw, :rt].set(base.kernel)
    kpad = jnp.roll(kpad, shift=-(rw // 2), axis=0)
    freq = jnp.fft.rfft2(kpad)  # (w_pad, num_ticks//2+1)
    return DetectorResponse(kernel=base.kernel, freq=freq,
                            pad_shape=(w_pad, cfg.num_ticks), plane=plane)


def make_distributed_plane_responses(cfg: LArTPCConfig, w_pad: int):
    """Per-plane responses at the distributed grid shape, in plane order."""
    from repro.config import plane_specs

    return tuple(make_distributed_response(cfg, w_pad, plane=s.kind)
                 for s in plane_specs(cfg))
