"""Signal-processing deconvolution — the recon half of the sim->recon loop.

The follow-up papers to the source paper (arXiv:2002.06291, 2107.00812) make
per-plane deconvolution the first reconstruction workload on the same
detectors: invert the field+electronics response the convolve stage applied,
recovering charge-vs-wire-vs-time from the ADC waveforms.

    M(ω) = R(ω)·S(ω)  +  N(ω)          (convolve stage + noise stage)
    Ŝ(ω) = G(ω)·M(ω)                    (this module)

A bare inverse 1/R blows up where |R| -> 0 (the induction transform has a
near-zero DC line: a bipolar response integrates to ~0, so per-wire total
charge is unrecoverable — Wire-Cell's own signal processing has the same
hole). Both filters here regularize that inversion:

  wiener   : G = conj(R) / (|R|² + λ·max|R|²) — the Wiener form with a flat
             noise-to-signal prior λ (relative to the response peak power),
             gain bounded by 1/(2·sqrt(λ·max|R|²)) however small |R| gets.
  gaussian : the same bounded inversion times a Gaussian low-pass along the
             time-frequency axis (Wire-Cell's default filter family); the
             window's DC gain is exactly 1.

A filter is *represented as* a ``DetectorResponse`` (freq = G at the same
``pad_shape``), so applying it is literally the convolve stage's math and
both ``fft_convolve`` spectral layouts (``SPECTRAL_LAYOUTS``) work on it
unchanged. The filter keeps the FORWARD kernel as its ``kernel``, so the
real-space ``direct`` convolve strategy would apply the forward response:
it is never used here. Two candidates register under the ``deconvolve`` op:

  rfft2     : direct half-spectrum multiply (the rfft2 convolve layout).
  fft_reuse : dispatch through ``fft_convolve``'s own tuned strategy table —
              whatever spectral layout won the convolve tuning wins here
              too; a winner that is not a spectral layout maps to rfft2.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.fft_conv import (fft_convolve, fft_convolve_rfft2,
                                 fft_shape, resolve_fft_strategy)
from repro.core.response import DetectorResponse
from repro.tune.registry import register_strategy, set_default

#: filter families ``make_deconv_filter`` accepts
DECONV_FILTERS = ("wiener", "gaussian")
#: the ``fft_convolve`` strategies that multiply by ``freq``, and so apply
#: an inverse filter; ``fft_reuse`` resolves among these alone
SPECTRAL_LAYOUTS = ("rfft2", "fft2")


def measured_signal(adc: jax.Array, cfg: LArTPCConfig) -> jax.Array:
    """ADC counts -> measured signal in electron units.

    Inverts the affine map of ``digitize`` (baseline shift + gain); the
    round/clip quantization is irrecoverable — at the default gain one ADC
    count is 1/adc_per_electron = 100 electrons, which is why hit thresholds
    sit well above a single count.
    """
    gain = cfg.adc_per_electron
    if isinstance(gain, jax.Array):
        # traced gain (gradient-based calibration, repro.core.fit)
        denom = jnp.maximum(gain, 1e-30)
    else:
        denom = max(float(gain), 1e-30)
    return (adc.astype(jnp.float32) - cfg.adc_baseline) / denom


def _bounded_inverse(freq: jax.Array, lam: float) -> jax.Array:
    """conj(R)/(|R|² + λ·max|R|²): the regularized inverse both filters share.

    λ is *relative* to the response peak power, so the gain bound
    1/(2·sqrt(λ·max|R|²)) holds whatever the response normalization, and
    |R| = 0 maps to gain 0 instead of a 1/ε blow-up.
    """
    power = jnp.real(freq * jnp.conj(freq))
    floor = lam * jnp.max(power)
    return jnp.conj(freq) / (power + floor)


def make_deconv_filter(resp: DetectorResponse, cfg: LArTPCConfig,
                       kind: Optional[str] = None,
                       wiener_lambda: Optional[float] = None,
                       gauss_cut: Optional[float] = None,
                       ) -> DetectorResponse:
    """Build the inverse filter G for ``resp`` as a ``DetectorResponse``.

    The returned transform has ``freq = G`` at ``resp.pad_shape`` and keeps
    ``resp``'s kernel and plane kind, so it drops into the same dispatch
    (and the same plane-keyed tuning bucket) as the forward convolve.
    ``kind``/``wiener_lambda``/``gauss_cut`` default to the config fields.
    """
    kind = kind if kind is not None else cfg.deconv_filter
    lam = (wiener_lambda if wiener_lambda is not None
           else cfg.deconv_wiener_lambda)
    if kind not in DECONV_FILTERS:
        raise ValueError(
            f"unknown deconv filter {kind!r}; valid: {list(DECONV_FILTERS)}")
    g = _bounded_inverse(resp.freq, lam)
    if kind == "gaussian":
        cut = gauss_cut if gauss_cut is not None else cfg.deconv_gauss_cut
        # rfft half-spectrum: column k is time-frequency index k in
        # [0, T_pad//2]; the window is exp(-½ (k/(cut·Nyquist))²) — real,
        # wire-independent, and exactly 1 at k = 0 (DC gain preserved)
        nyq = max(resp.pad_shape[1] // 2, 1)
        k = jnp.arange(g.shape[1], dtype=jnp.float32)
        window = jnp.exp(-0.5 * (k / (cut * nyq)) ** 2)
        g = g * window[None, :]
    return DetectorResponse(kernel=resp.kernel, freq=g.astype(jnp.complex64),
                            pad_shape=resp.pad_shape, plane=resp.plane)


def make_plane_deconv_filters(cfg: LArTPCConfig, resps=None):
    """One inverse filter per readout plane, in plane order.

    ``resps`` is the per-plane forward responses (defaults to
    ``make_plane_responses(cfg)``); filters inherit each plane's transform
    shape, so they work at the distributed grid shape too when built from
    ``make_distributed_plane_responses``.
    """
    from repro.core.response import make_plane_responses

    if resps is None:
        resps = make_plane_responses(cfg)
    return tuple(make_deconv_filter(r, cfg) for r in resps)


# ---------------------------------------------------------------------------
# Strategies — the registry's ``deconvolve`` op
# ---------------------------------------------------------------------------


@register_strategy("deconvolve", "rfft2",
                   note="direct half-spectrum inverse-filter multiply")
def deconvolve_rfft2(meas: jax.Array, filt: DetectorResponse) -> jax.Array:
    # the filter is a DetectorResponse, so the rfft2 convolve layout IS the
    # deconvolution: pad -> rfft2 -> multiply G -> irfft2 -> crop
    return fft_convolve_rfft2(meas, filt)


@register_strategy("deconvolve", "fft_reuse",
                   note="reuse the tuned fft_convolve layout for the "
                        "inverse multiply")
def deconvolve_fft_reuse(meas: jax.Array, filt: DetectorResponse) -> jax.Array:
    # "auto" resolves from the fft_convolve tuning cache (plane-keyed) at
    # trace time — the layout that won the forward convolve wins here too,
    # if it is spectral: ``direct`` would convolve with the forward kernel
    name = resolve_fft_strategy("auto", meas.shape, filt)
    if name not in SPECTRAL_LAYOUTS:
        name = "rfft2"
    return fft_convolve(meas, filt, strategy=name)


set_default("deconvolve", "rfft2")


def deconvolve(meas: jax.Array, filt: DetectorResponse,
               strategy: Optional[str] = None) -> jax.Array:
    """Apply the inverse filter: measured signal (electrons) -> charge
    estimate Ŝ(t,x), same (num_wires, num_ticks) layout as the charge grid.

    ``meas`` is the measured signal in electron units — ``SimOutput.signal``
    directly, or ``measured_signal(adc, cfg)`` for the full ADC chain.
    ``strategy`` may be None (registry default), ``"auto"`` (tuning cache,
    keyed by shape AND plane kind like the forward convolve), or any
    registered candidate name; unknown names fail here with the valid list.
    """
    from repro.tune import autotune, registry

    if strategy is None:
        strategy = registry.default_strategy("deconvolve")
    elif strategy == "auto":
        strategy = autotune.resolve("deconvolve", None,
                                    shape=fft_shape(meas.shape, filt)).strategy
    try:
        strat = registry.get_strategy("deconvolve", strategy)
    except KeyError:
        valid = sorted(registry.strategies("deconvolve")) + ["auto"]
        raise ValueError(
            f"unknown deconvolve strategy {strategy!r}; valid: {valid}"
        ) from None
    return strat.fn(meas, filt)
