"""Frequency-domain convolution — the paper's "FT" step (Eq. 2).

    S(t,x) --rfft2--> S(ω) ; M(ω) = R(ω)·S(ω) ; M(ω) --irfft2--> M(t,x)

Zero-padding to the response's linear-convolution size avoids circular wrap
(``make_response`` picks FFT-friendly padded sizes). The whole chain
(pad → rfft2 → complex multiply → irfft2 → crop) fuses into one program —
the paper's §5 "hand-write vendor FFT wrappers" problem is XLA's job here.

Three strategies register as ``fft_convolve`` candidates in the
kernel-strategy registry (``repro.tune``):

  rfft2  : real-input FFT over the half-spectrum — half the frequency-domain
           memory traffic; the natural choice when the backend's rfft is
           native. The default off the TPU.
  fft2   : full complex FFT — same math (the full spectrum is reconstructed
           from the stored half-spectrum via Hermitian symmetry); some
           backends lower complex FFTs better than real ones.
  direct : no transform. The same linear convolution computed from the
           real-space kernel as ONE convolution on the matrix unit: ticks
           are blocked into 128-lane channels and the response becomes
           block-Toeplitz weights (``_toeplitz_weights``), at f32 precision
           (``Precision.HIGHEST``). The response is short (21 wires x 200
           ticks), so this is about 1.9x the direct multiply-adds and far
           cheaper on a TPU than the 2D FFT at the padded size. The
           default on the TPU; offered only where the response is padded to
           the linear-convolution size and spans at most
           ``MAX_TICK_BLOCKS`` tick blocks. It reads ``resp.kernel``, so it
           applies to forward responses only, never to an inverse filter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import LArTPCConfig
from repro.core.response import DetectorResponse
from repro.tune.registry import TuneContext, register_strategy, set_default

#: ticks per block of the ``direct`` strategy: one block is the 128 lanes of
#: a TPU vector register and the contraction width of its matrix unit
TICK_BLOCK = 128
#: the most tick blocks a kernel may span for ``direct`` to be offered; the
#: multiply-adds grow with the span (1.92x the direct count at 200 ticks)
MAX_TICK_BLOCKS = 4


def _widen(grid: jax.Array) -> jax.Array:
    """The single upcast of the convolve stage: FFT kernels only accept
    f32/f64 inputs (``rfft2`` rejects bfloat16 outright), so narrow grids
    (``cfg.patch_dtype="bfloat16"`` paths) widen to float32 before the
    convolution and EVERY strategy returns the widened dtype — identical
    math, identical output dtype, whatever the input precision."""
    if grid.dtype not in (jnp.float32, jnp.float64):
        return grid.astype(jnp.float32)
    return grid


def _pad_grid(grid: jax.Array, resp: DetectorResponse) -> jax.Array:
    """Zero-pad the (widened) grid to the response's linear-convolution
    size."""
    w, t = grid.shape
    wp, tp = resp.pad_shape
    grid = _widen(grid)
    return jnp.zeros((wp, tp), grid.dtype).at[:w, :t].set(grid)


@register_strategy("fft_convolve", "rfft2",
                   note="real-input half-spectrum FFT")
def fft_convolve_rfft2(grid: jax.Array, resp: DetectorResponse) -> jax.Array:
    w, t = grid.shape
    wp, tp = resp.pad_shape
    freq = jnp.fft.rfft2(_pad_grid(grid, resp))
    out = jnp.fft.irfft2(freq * resp.freq, s=(wp, tp))
    return out[:w, :t]


def _full_spectrum(half: jax.Array, tp: int) -> jax.Array:
    """Full complex spectrum of a real signal from its rfft2 half-spectrum.

    Hermitian symmetry: F[k1, k2] = conj(F[-k1 mod W, tp - k2]).
    """
    wp = half.shape[0]
    ncopy = tp - half.shape[1]
    rows = (-jnp.arange(wp)) % wp
    cols = ncopy - jnp.arange(ncopy)
    tail = jnp.conj(half[rows][:, cols])
    return jnp.concatenate([half, tail], axis=1)


@register_strategy("fft_convolve", "fft2",
                   note="full complex FFT; identical math, different layout")
def fft_convolve_fft2(grid: jax.Array, resp: DetectorResponse) -> jax.Array:
    w, t = grid.shape
    wp, tp = resp.pad_shape
    padded = _pad_grid(grid, resp)  # upcasts narrow grids, same as rfft2
    freq = jnp.fft.fft2(padded.astype(jnp.complex64))
    rfreq = _full_spectrum(resp.freq, tp)
    out = jnp.real(jnp.fft.ifft2(freq * rfreq))
    # return the PADDED dtype (f32 for narrow inputs), matching rfft2 —
    # downcasting back to e.g. bfloat16 here made the two strategies
    # disagree on output dtype for the same input
    return out[:w, :t].astype(padded.dtype)


def tick_blocks(response_ticks: int) -> int:
    """Tick blocks a kernel of ``response_ticks`` ticks reaches back over:
    an output block sees its own block and ``ceil((rt - 1) / 128)`` before
    it (3 for 200 ticks)."""
    return -(-(response_ticks - 1) // TICK_BLOCK) + 1


def _direct_available(ctx: TuneContext) -> bool:
    """``direct`` computes the LINEAR convolution, so it stands in for the
    transform only where the response is padded to at least the linear size
    (a cyclic response, as ``make_distributed_response`` builds, never takes
    it) and the kernel spans at most ``MAX_TICK_BLOCKS`` tick blocks. A
    shape without pads (``autotune.op_shape``) describes ``make_response``'s
    own transform, which is always padded to the linear size."""
    s = ctx.shape
    rw, rt = s["response_wires"], s["response_ticks"]
    linear = (s["num_wires"] + rw - 1, s["num_ticks"] + rt - 1)
    pads = (s.get("pad_wires", linear[0]), s.get("pad_ticks", linear[1]))
    return (tick_blocks(rt) <= MAX_TICK_BLOCKS
            and pads[0] >= linear[0] and pads[1] >= linear[1])


def _toeplitz_weights(kernel: jax.Array) -> jax.Array:
    """HWIO weights ``(rw, nm, 128, 128)`` of the blocked convolution:
    ``W[rw-1-dw, m, j_in, j_out] = K[dw, (nm-1-m)*128 + j_out - j_in]``
    where that tick lies in ``[0, rt)``, else 0. Built with ``jnp`` ops from
    the traced kernel, so the response stays differentiable (the fit's
    ``response_gain`` and ``response_shaping_us``)."""
    rt = kernel.shape[1]
    nm = tick_blocks(rt)
    m = jnp.arange(nm)[:, None, None]
    j_in = jnp.arange(TICK_BLOCK)[None, :, None]
    j_out = jnp.arange(TICK_BLOCK)[None, None, :]
    dt = (nm - 1 - m) * TICK_BLOCK + j_out - j_in          # (nm, 128, 128)
    taps = kernel[:, jnp.clip(dt, 0, rt - 1)]          # (rw, nm, 128, 128)
    return jnp.where((dt >= 0) & (dt < rt), taps, 0.0)[::-1]


@register_strategy("fft_convolve", "direct", available=_direct_available,
                   note="block-Toeplitz convolution on the matrix unit at "
                        "f32 precision; no transform")
def fft_convolve_direct(grid: jax.Array, resp: DetectorResponse) -> jax.Array:
    w, t = grid.shape
    rw, rt = resp.kernel.shape
    nb, nm = -(-t // TICK_BLOCK), tick_blocks(rt)
    grid = _widen(grid)
    # NHWC (1, W, nb, 128): wire is the height, the tick block the width and
    # the tick within its block the channel
    x = jnp.pad(grid, ((0, 0), (0, nb * TICK_BLOCK - t)))
    x = x.reshape(1, w, nb, TICK_BLOCK)
    kern = _toeplitz_weights(resp.kernel.astype(grid.dtype))
    out = lax.conv_general_dilated(
        x, kern, window_strides=(1, 1),
        # the kernel is centred on its middle wire and starts at tick 0
        padding=((rw - 1 - rw // 2, rw // 2), (nm - 1, 0)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return out.reshape(w, nb * TICK_BLOCK)[:, :t]


set_default("fft_convolve", "rfft2")
# the TPU's 2D FFT at these padded sizes runs near 1% of its bandwidth
# roofline, the matrix unit does the short response far faster
set_default("fft_convolve", "direct", backend="tpu")


def fft_shape(grid_shape, resp: DetectorResponse) -> dict:
    """The ``fft_convolve`` tuning shape of a ``grid_shape`` grid convolved
    with ``resp`` (the dims ``autotune.op_shape`` gives from a config)."""
    return {"num_wires": grid_shape[0], "num_ticks": grid_shape[1],
            "response_wires": resp.kernel.shape[0],
            "response_ticks": resp.kernel.shape[1],
            # plane kind keys the decision: induction and collection
            # transforms are different problems to the tuner
            "plane": resp.plane}


def resolve_fft_strategy(strategy: str | None, grid_shape,
                         resp: DetectorResponse) -> str:
    """The concrete ``fft_convolve`` strategy name a dispatch runs.

    ``strategy`` may be None (the registry's backend default), ``"auto"``
    (tuning cache / default), or any registered candidate name; an unknown
    name fails here with the valid candidates, not deep inside the
    registry. A candidate whose availability predicate refuses this grid
    and response (``direct`` on a cyclic response) resolves to ``rfft2``.
    """
    from repro.tune import autotune, registry

    shape = fft_shape(grid_shape, resp)
    if strategy is None:
        strategy = registry.default_strategy("fft_convolve")
    elif strategy == "auto":
        strategy = autotune.resolve("fft_convolve", None,
                                    shape=shape).strategy
    try:
        strat = registry.get_strategy("fft_convolve", strategy)
    except KeyError:
        valid = sorted(registry.strategies("fft_convolve")) + ["auto"]
        raise ValueError(
            f"unknown fft_convolve strategy {strategy!r}; valid: {valid}"
        ) from None
    wp, tp = resp.pad_shape
    ctx = registry.make_context(None, dict(shape, pad_wires=wp, pad_ticks=tp))
    return strategy if strat.is_available(ctx) else "rfft2"


def fft_convolve(grid: jax.Array, resp: DetectorResponse,
                 strategy: str | None = None) -> jax.Array:
    """Linear 2-D convolution of the charge grid with the detector response.

    ``strategy`` is resolved by ``resolve_fft_strategy``. EVERY concrete
    name dispatches through the registry — a strategy registered by an
    extension is honored even if it shadows a built-in.
    """
    from repro.tune import registry

    name = resolve_fft_strategy(strategy, grid.shape, resp)
    return registry.get_strategy("fft_convolve", name).fn(grid, resp)


def digitize(signal: jax.Array, cfg: LArTPCConfig) -> jax.Array:
    """Voltage -> ADC counts (12-bit), paper's M(t,x) measurement.

    ``cfg.digitize_ste`` selects a straight-through estimator for the
    round/clip quantization: the forward VALUES are identical (round and
    clip commute when the rails are integers, so ``round(clip(x)) ==
    clip(round(x))``) but the result stays float32 and the backward pass
    treats rounding as identity while the clip still zeroes gradients
    outside the ADC rails — the standard STE for quantizers. The default
    (``False``) is the bit-identical int16 seed path.
    """
    adc = cfg.adc_baseline + cfg.adc_per_electron * signal
    if cfg.digitize_ste:
        clipped = jnp.clip(adc, 0.0, 4095.0)
        return clipped + jax.lax.stop_gradient(jnp.round(clipped) - clipped)
    return jnp.clip(jnp.round(adc), 0, 4095).astype(jnp.int16)
