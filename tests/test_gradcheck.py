"""Differentiable-sim gradient verification (ISSUE 7).

Three layers of protection for the calibration path:

  * the per-stage finite-difference matrix (``repro.core.gradcheck``): each
    stage's analytic gradient against central differences, per plane kind
    and with/without the recon chain — the same suite CI gates via
    ``launch/fit.py --gradcheck``;
  * exact STE/relaxed contracts asserted analytically (pass-through
    gradients inside the ADC rails, zero outside; NaN-free gradients at
    zero fluctuation variance), where finite differences of a quantized
    forward would be meaningless;
  * forward bit-identity: the differentiable graph's float32 forward equals
    the default graph's quantized int16 ADC exactly, and the default graph
    still reproduces the pinned golden SHA-256 digests — calibration
    machinery must not move the physics by one ulp.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core import fluctuate as fl
from repro.core.fft_conv import digitize
from repro.core.fit import fit_config, make_fit_loss, make_fit_targets
from repro.core.fit import FitParam, FitSpec
from repro.core.gradcheck import (finite_difference_grad, gradcheck,
                                  stage_gradcheck_cases,
                                  stage_gradcheck_suite)
from repro.core.stages import build_sim_graph

CFG = get_config("lartpc-uboone", smoke=True)


# ---------------------------------------------------------------------------
# The FD matrix
# ---------------------------------------------------------------------------


class TestStageMatrix:
    @pytest.mark.parametrize("case", stage_gradcheck_cases(),
                             ids=lambda c: c.name)
    def test_stage_gradient_matches_fd(self, case):
        """Every stage's analytic gradient agrees with central differences
        (per-case step/tolerance — see the gradcheck module docstring)."""
        (res,) = stage_gradcheck_suite(cases=[case])
        assert res.ok, (f"{res.name}: analytic {res.analytic} vs numeric "
                        f"{res.numeric} (rel_err {res.max_rel_err:.3e})")

    @pytest.mark.parametrize("plane", ["induction", "collection"])
    def test_response_gradient_per_plane_kind(self, plane):
        """The convolve-stage gradient holds for BOTH field-response
        families (bipolar induction / unipolar collection)."""
        from repro.core.depo import generate_depos
        from repro.core.fft_conv import fft_convolve
        from repro.core.response import make_response
        from repro.core.stages import compute_charge_grid

        cfg = fit_config(CFG)
        # key 3 (partitionable threefry, JAX 0.9) draws a weight field that
        # leaves the objective nearly flat in the shaping time (|grad| ~
        # 8e-4): an f32 central difference is then round-off, not slope
        key = jax.random.key(4)
        depos = generate_depos(key, cfg)
        grid = compute_charge_grid(jax.random.fold_in(key, 2), depos, cfg)
        w = jax.random.normal(jax.random.fold_in(key, 1), grid.shape)

        def f(theta):
            tcfg = dataclasses.replace(cfg, response_gain=theta[0],
                                       response_shaping_us=theta[1])
            resp = make_response(tcfg, plane=plane)
            return jnp.sum(fft_convolve(grid, resp, tcfg.fft_strategy) * w
                           ) / grid.size

        res = gradcheck(f, jnp.asarray([1.3, 1.7]), name=f"convolve/{plane}",
                        eps=1e-3, rtol=3e-2)
        assert res.ok, res

    def test_fit_loss_gradcheck_with_recon_chain(self):
        """The full fit loss with the deconvolved-charge term is in the
        matrix; this pins that WITHOUT it the same loss still gradchecks
        (recon stages absent from the traced graph entirely)."""
        cfg = dataclasses.replace(fit_config(CFG),
                                  electrons_per_depo=150_000.0)
        spec = FitSpec(params=(FitParam("recombination"),))
        targets = make_fit_targets(cfg, jax.random.key(5), num_events=1)
        loss = make_fit_loss(cfg, spec, targets)

        def f(theta):
            return loss(theta * cfg.recombination)

        res = gradcheck(f, jnp.asarray([0.9]), name="e2e/no-recon",
                        eps=2e-2, rtol=2e-1, atol=1e-3)
        assert res.ok, res

    def test_finite_difference_grad_on_quadratic(self):
        """The FD helper itself: exact on a quadratic (central differences
        have no truncation error there)."""
        c = jnp.asarray([1.0, -2.0, 0.5])

        def f(x):
            return jnp.sum((x - c) ** 2)

        x0 = jnp.asarray([0.3, 0.1, -0.2])
        g = finite_difference_grad(f, x0, eps=1e-2)
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x0 - c),
                                   rtol=1e-3, atol=1e-4)

    def test_gradcheck_flags_wrong_gradient(self):
        """A deliberately wrong custom gradient must FAIL the check — the
        suite's assertions are only meaningful if it can."""

        @jax.custom_vjp
        def bad_square(x):
            return jnp.sum(x * x)

        def fwd(x):
            return bad_square(x), x

        def bwd(x, g):
            return (3.0 * g * x,)  # wrong: should be 2 g x

        bad_square.defvjp(fwd, bwd)
        res = gradcheck(bad_square, jnp.asarray([1.5]), name="bad")
        assert not res.ok

    def test_nan_analytic_gradient_fails(self):
        """A NaN gradient path is an automatic failure (not a tolerance
        comparison against FD noise)."""

        def f(x):
            return jnp.sum(jnp.sqrt(x))  # d/dx sqrt at 0 -> inf/nan

        res = gradcheck(f, jnp.asarray([0.0]), name="nan")
        assert not res.ok


# ---------------------------------------------------------------------------
# Exact contracts: relaxed fluctuation and the STE digitizer
# ---------------------------------------------------------------------------


class TestRelaxedFluctuation:
    def test_forward_bit_identical_to_counter(self, rng_key):
        """The relaxed draw IS the counter draw forward: same key, same
        threefry normals, value-identical masking — bit-for-bit equal."""
        n = 64
        charge = jnp.abs(jax.random.normal(rng_key, (n,))) * 5000.0
        charge = charge.at[:4].set(0.0)  # zero-charge (padding) depos
        patches = jnp.abs(jax.random.normal(
            jax.random.fold_in(rng_key, 1),
            (n, CFG.patch_wires, CFG.patch_ticks))) * charge[:, None, None] / 50.0
        key = jax.random.fold_in(rng_key, 2)
        a = fl.fluctuate_counter(key, patches, charge)
        b = fl.fluctuate_counter_relaxed(key, patches, charge)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gradient_finite_at_zero_variance(self, rng_key):
        """jax.grad through the relaxed draw is NaN-free even where the
        binomial variance is exactly 0 (zero-charge padding depos, p=1
        saturated pixels) — the masked-sqrt reparameterization's reason to
        exist. The plain counter draw produces NaN there."""
        charge = jnp.asarray([0.0, 5000.0])
        patches = jnp.stack([jnp.zeros((4, 4)),
                             jnp.full((4, 4), 100.0)])
        key = jax.random.key(0)

        def loss_relaxed(scale):
            return jnp.sum(fl.fluctuate_counter_relaxed(
                key, patches * scale, charge * scale))

        g = jax.grad(loss_relaxed)(1.0)
        assert bool(jnp.isfinite(g))

        def loss_counter(scale):
            return jnp.sum(fl.fluctuate_counter(
                key, patches * scale, charge * scale))

        assert not bool(jnp.isfinite(jax.grad(loss_counter)(1.0)))


class TestDigitizeSTE:
    def test_forward_equals_quantized(self, rng_key):
        """STE forward values equal the int16 path exactly (round and clip
        commute on the integer rails), including above/below the rails."""
        sig = jax.random.uniform(rng_key, (64, 64), minval=-2e5,
                                 maxval=6e5)
        hard = digitize(sig, CFG)
        assert hard.dtype == jnp.int16
        soft = digitize(sig, dataclasses.replace(CFG, digitize_ste=True))
        assert soft.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(hard, np.float32),
                                      np.asarray(soft))

    def test_gradient_is_passthrough_inside_rails(self):
        """d(adc)/d(signal) is adc_per_electron inside the rails and 0
        outside — the straight-through contract, asserted analytically
        (FD over a staircase measures nothing)."""
        cfg = dataclasses.replace(CFG, digitize_ste=True)
        # baseline 900, gain 0.01: signal -2e5 -> adc -1100 (below rail 0),
        # 1e4 -> 1000 (inside), 5e5 -> 5900 (above rail 4095)
        sig = jnp.asarray([-2e5, 1e4, 5e5])
        g = jax.grad(lambda s: jnp.sum(digitize(s, cfg)))(sig)
        np.testing.assert_allclose(np.asarray(g),
                                   [0.0, cfg.adc_per_electron, 0.0],
                                   atol=1e-9)


# ---------------------------------------------------------------------------
# Forward bit-identity: calibration machinery must not move the defaults
# ---------------------------------------------------------------------------


class TestForwardIdentity:
    def test_default_graph_still_matches_golden_pins(self):
        """The default (non-STE, counter-sampling) graph reproduces the
        pinned ADC digests — the new config fields and traced-config
        branches left the bit-exact path untouched."""
        from test_stages import GOLDEN_ADC_SHA256, _sha
        from repro.core.depo import generate_depos

        key = jax.random.key(0)
        depos = generate_depos(key, CFG)
        adc = jax.jit(build_sim_graph(CFG, None).run)(key, depos).adc
        assert _sha(adc) == GOLDEN_ADC_SHA256["unfused"]

    def test_fit_graph_forward_equals_default_quantized(self):
        """fit_config's graph (relaxed + STE, float32) produces EXACTLY the
        default graph's int16 ADC values on the same event/key."""
        from repro.core.depo import generate_depos

        key = jax.random.key(7)
        depos = generate_depos(key, CFG)
        hard = jax.jit(build_sim_graph(CFG, None).run)(key, depos).adc
        soft = jax.jit(build_sim_graph(fit_config(CFG), None).run)(
            key, depos).adc
        assert soft.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(hard, np.float32),
                                      np.asarray(soft))

    def test_fit_loss_exactly_zero_at_truth(self):
        """The self-calibration contract: same keys -> same noise and
        fluctuation realizations -> loss exactly 0 at the true params."""
        cfg = dataclasses.replace(CFG, electron_lifetime_us=60.0,
                                  recombination=0.75)
        spec = FitSpec(params=(FitParam("electron_lifetime_us", lo=5.0,
                                        hi=500.0),
                               FitParam("recombination", lo=0.2, hi=1.0)))
        targets = make_fit_targets(cfg, jax.random.key(11), num_events=2)
        loss = jax.jit(make_fit_loss(cfg, spec, targets))
        assert float(loss(spec.true_theta(cfg))) == 0.0
        # and strictly positive away from truth (the minimum is real)
        off = spec.true_theta(dataclasses.replace(
            cfg, electron_lifetime_us=90.0, recombination=0.6))
        assert float(loss(off)) > 0.0
