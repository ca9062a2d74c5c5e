"""FFT-convolution and response-transform unit/property tests.

Covers the ISSUE 5 satellite fixes:
  * every ``fft_convolve`` strategy runs on narrow (bfloat16) charge grids
    and returns one identical dtype (the bf16 path used to crash rfft2 and
    return bf16 from fft2);
  * ``response.next_fast_len`` is provably minimal 5-smooth >= n;
  * ``fft_conv._full_spectrum`` reconstructs the exact Hermitian tail at
    odd and even padded widths.

No hypothesis dependency: the property sweeps are deterministic
enumerations, so these tests always run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LArTPCConfig
from repro.core.fft_conv import _full_spectrum, fft_convolve
from repro.core.response import make_response, next_fast_len

CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=64,
                   response_wires=11, response_ticks=48)

STRATEGIES = ("rfft2", "fft2", "direct")
PATCH_DTYPES = ("float32", "bfloat16")


class TestConvolveDtypes:
    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("patch_dtype", PATCH_DTYPES)
    def test_strategy_runs_on_patch_dtype(self, strategy, patch_dtype):
        """Every (strategy, patch dtype) pair runs and returns float32 —
        the single upcast lives in ``_widen``."""
        resp = make_response(CFG)
        grid = jax.random.uniform(
            jax.random.key(1), (CFG.num_wires, CFG.num_ticks),
            dtype=jnp.float32).astype(jnp.dtype(patch_dtype))
        out = fft_convolve(grid, resp, strategy)
        assert out.dtype == jnp.float32
        assert out.shape == (CFG.num_wires, CFG.num_ticks)

    @pytest.mark.parametrize("patch_dtype", PATCH_DTYPES)
    def test_strategies_agree_per_dtype(self, patch_dtype):
        """Every strategy sees the same upcast input, so they agree with
        rfft2 to roundoff and share its output dtype."""
        resp = make_response(CFG)
        grid = jax.random.uniform(
            jax.random.key(2), (CFG.num_wires, CFG.num_ticks),
            dtype=jnp.float32).astype(jnp.dtype(patch_dtype))
        outs = [fft_convolve(grid, resp, s) for s in STRATEGIES]
        scale = float(jnp.abs(outs[0]).max()) + 1e-30
        for out in outs[1:]:
            assert out.dtype == outs[0].dtype
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(out),
                                       atol=1e-4 * scale)

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_full_chain_runs_bf16_patches(self, strategy):
        """End-to-end: the registry-default convolve no longer crashes a
        ``patch_dtype="bfloat16"`` / ``unfused_bf16`` simulation."""
        from repro.core.depo import generate_depos
        from repro.core.pipeline import make_sim_fn

        cfg = dataclasses.replace(CFG, patch_dtype="bfloat16",
                                  fft_strategy=strategy)
        key = jax.random.key(0)
        out = make_sim_fn(cfg)(key, generate_depos(key, cfg))
        assert out.adc.dtype == jnp.int16
        assert out.signal.dtype == jnp.float32


def _five_smooth_up_to(limit: int):
    vals = set()
    p2 = 1
    while p2 <= limit:
        p23 = p2
        while p23 <= limit:
            v = p23
            while v <= limit:
                vals.add(v)
                v *= 5
            p23 *= 3
        p2 *= 2
    return sorted(vals)


class TestNextFastLen:
    def test_five_smooth_at_least_n_and_minimal(self):
        """For every n <= 2048: the result divides into 2/3/5 factors only,
        is >= n, and equals the brute-force minimal 5-smooth value."""
        smooth = _five_smooth_up_to(1 << 12)
        for n in range(1, 2049):
            m = next_fast_len(n)
            assert m >= n, (n, m)
            r = m
            for p in (2, 3, 5):
                while r % p == 0:
                    r //= p
            assert r == 1, f"next_fast_len({n}) = {m} is not 5-smooth"
            expect = next(v for v in smooth if v >= n)
            assert m == expect, (n, m, expect)

    def test_spot_values(self):
        assert next_fast_len(1) == 1
        assert next_fast_len(2561) == 2592      # 2^5 * 3^4
        assert next_fast_len(9791) == 10000     # 2^4 * 5^4


class TestFullSpectrum:
    @pytest.mark.parametrize("tp", [40, 41])   # even and odd padded widths
    def test_hermitian_tail_exact(self, tp):
        """The reconstructed tail bins equal conj(half[-k1 % W, tp - k2])
        exactly — pure gather/conj, no transform roundoff allowed."""
        rng = np.random.default_rng(7)
        x = rng.standard_normal((16, tp)).astype(np.float32)
        half = np.asarray(jnp.fft.rfft2(jnp.asarray(x)))
        full = np.asarray(_full_spectrum(jnp.asarray(half), tp))
        nfreq = half.shape[1]
        assert full.shape == (16, tp)
        np.testing.assert_array_equal(full[:, :nfreq], half)
        for k2 in range(nfreq, tp):
            for k1 in range(16):
                expect = np.conj(half[(-k1) % 16, tp - k2])
                assert full[k1, k2] == expect, (k1, k2)

    @pytest.mark.parametrize("tp", [40, 41])
    def test_reconstruction_matches_fft2(self, tp):
        """fft2 of the real grid and the Hermitian reconstruction of its
        rfft2 half-spectrum are the same spectrum (to FFT roundoff)."""
        rng = np.random.default_rng(11)
        x = rng.standard_normal((12, tp)).astype(np.float32)
        full = np.asarray(_full_spectrum(jnp.fft.rfft2(jnp.asarray(x)), tp))
        ref = np.asarray(jnp.fft.fft2(jnp.asarray(x)))
        scale = np.abs(ref).max()
        np.testing.assert_allclose(full, ref, atol=1e-5 * scale)
