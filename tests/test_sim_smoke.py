"""Fixed-seed smoke variants of the hypothesis-gated physics tests.

``test_core_sim``, ``test_kernels`` and ``test_fused_kernel`` guard
their property sweeps with a module-level
``pytest.importorskip("hypothesis")`` — which skips the WHOLE module,
including their plain statistical tests, on boxes without hypothesis
installed. These fixed-seed variants keep the load-bearing invariants
(noise calibration, charge conservation, strategy equivalence) exercised
everywhere, with a handful of pinned seeds standing in for each random
sweep. No hypothesis import anywhere in this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LArTPCConfig
from repro.core.depo import generate_depos
from repro.core.fft_conv import digitize
from repro.core.noise import simulate_noise
from repro.core.pipeline import simulate_fig3, simulate_fig4
from repro.core.rasterize import rasterize
from repro.core.response import make_response
from repro.core.scatter import scatter_sort_segment, scatter_xla

CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=128,
                   response_wires=11, response_ticks=48)


class TestNoiseCalibrationSmoke:
    """Fixed-seed stand-ins for TestNoise in test_core_sim."""

    @pytest.mark.parametrize("num_ticks", [256, 257])
    def test_rms_matches_config_target(self, num_ticks):
        """Realized time-domain RMS hits the configured target within 5%
        with and without a Nyquist bin (Parseval normalization)."""
        cfg = dataclasses.replace(CFG, num_ticks=num_ticks, num_wires=128)
        noise = simulate_noise(jax.random.key(3), cfg)
        rms = float(jnp.sqrt(jnp.mean(noise ** 2)))
        assert abs(rms - cfg.noise_rms_adc) < 0.05 * cfg.noise_rms_adc, rms

    @pytest.mark.parametrize("seed", [0, 1, 7])
    def test_zero_mean_across_seeds(self, seed):
        noise = simulate_noise(jax.random.key(seed), CFG)
        assert abs(float(noise.mean())) < 0.1

    def test_spectrum_dc_and_nyquist_real(self):
        cfg = dataclasses.replace(CFG, num_ticks=256, num_wires=8)
        spec = jnp.fft.rfft(simulate_noise(jax.random.key(4), cfg), axis=-1)
        np.testing.assert_allclose(np.asarray(spec[:, 0].imag), 0.0,
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(spec[:, -1].imag), 0.0,
                                   atol=1e-3)


class TestRasterizeSmoke:
    """Fixed-seed stand-ins for the rasterize property sweeps."""

    @pytest.mark.parametrize("seed,n", [(0, 64), (11, 17), (42, 100)])
    def test_nonneg_bounded_mass(self, seed, n):
        """Rasterized mass is non-negative and never exceeds the depo
        charge (3-sigma truncation only loses mass)."""
        depos = generate_depos(jax.random.key(seed), CFG, n)
        patches, _, _ = rasterize(depos, CFG)
        p = np.asarray(patches)
        assert (p >= -1e-4).all()
        sums = p.sum(axis=(1, 2))
        assert (sums <= np.asarray(depos.charge) * 1.01).all()


class TestScatterSmoke:
    """Fixed-seed stand-ins for the scatter strategy-equivalence sweep."""

    @pytest.mark.parametrize("seed,n", [(0, 128), (5, 1), (123, 77)])
    def test_strategies_agree(self, seed, n):
        depos = generate_depos(jax.random.key(seed), CFG, n)
        patches, w0, t0 = rasterize(depos, CFG)
        g1 = scatter_xla(patches, w0, t0, CFG)
        g2 = scatter_sort_segment(patches, w0, t0, CFG)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=5e-2)

    def test_total_charge_preserved(self):
        depos = generate_depos(jax.random.key(0), CFG, 64)
        patches, w0, t0 = rasterize(depos, CFG)
        grid = scatter_xla(patches, w0, t0, CFG)
        np.testing.assert_allclose(float(grid.sum()), float(patches.sum()),
                                   rtol=1e-5)


class TestFusedKernelSmoke:
    """Fixed-seed stand-ins for the fused rasterize+scatter oracle sweep."""

    @pytest.mark.parametrize("seed,n", [(0, 64), (17, 9)])
    def test_fused_equals_oracle(self, seed, n):
        from repro.kernels.fused_sim.ops import simulate_charge_grid
        from repro.kernels.fused_sim.ref import simulate_charge_grid_ref

        cfg = LArTPCConfig(num_wires=96, num_ticks=768, num_depos=128)
        depos = generate_depos(jax.random.key(seed), cfg, n)
        g = simulate_charge_grid(depos, cfg)
        r = simulate_charge_grid_ref(depos, cfg)
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=5e-2)


class TestPipelineSmoke:
    """Fixed-seed stand-ins for TestPipelines/TestFFTConv in test_core_sim."""

    def test_fig3_equals_fig4_no_rng(self):
        cfg = dataclasses.replace(CFG, fluctuate=False, num_depos=24)
        depos = generate_depos(jax.random.key(0), cfg, 24)
        resp = make_response(cfg)
        key = jax.random.key(0)
        out3 = simulate_fig3(key, depos, resp, cfg, add_noise=False)
        out4 = simulate_fig4(key, depos, resp, cfg, add_noise=False)
        np.testing.assert_allclose(np.asarray(out3.charge_grid),
                                   np.asarray(out4.charge_grid),
                                   rtol=1e-4, atol=1e-2)
        assert (np.asarray(out3.adc) == np.asarray(out4.adc)).mean() > 0.999

    def test_digitize_range_and_dtype(self):
        sig = jnp.asarray(np.random.default_rng(0).standard_normal(
            (8, 32)).astype(np.float32)) * 1e6
        adc = digitize(sig, CFG)
        assert adc.dtype == jnp.int16
        assert int(adc.min()) >= 0 and int(adc.max()) <= 4095
