"""Kernel-strategy registry + autotuner tests.

Covers the ISSUE-2 contract: cache round-trip (second call hits disk),
deterministic winner under a fake timer, and bit-for-bit strategy
equivalence on a fixed non-overlapping DepoSet.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.config import LArTPCConfig
from repro.core.depo import DepoSet, generate_depos
from repro.core.fft_conv import (fft_convolve, fft_convolve_fft2,
                                 fft_convolve_rfft2)
from repro.core.pipeline import (charge_grid_fused, charge_grid_unfused,
                                 make_sim_fn, simulate_fig4)
from repro.core.rasterize import rasterize
from repro.core.response import make_response
from repro.core.scatter import scatter_add

CFG = LArTPCConfig(num_wires=96, num_ticks=768, num_depos=64)

#: fake timings (seconds) — pallas / fused_pallas are made the deterministic
#: winners on purpose: the wall clock must play no part under an injected timer
FAKE_TIMES = {"xla": 3.0, "sort_segment": 2.0, "lane_rows": 2.5,
              "pallas": 1.0, "pallas_compact": 1.5,
              "unfused": 2.0, "unfused_bf16": 2.5, "fused_pallas": 1.0,
              "fused_pallas_compact": 1.5, "rfft2": 1.0, "fft2": 2.0,
              "direct": 3.0,
              "scan": 2.0}  # hit_find: "pallas" (1.0) fake-wins over "scan"


def fake_timer(calls):
    def timer(name, thunk):
        calls.append(name)
        return FAKE_TIMES[name]

    return timer


def lattice_depos(cfg=CFG) -> DepoSet:
    """Depos whose patches cannot overlap (and sit fully inside the grid):
    every output pixel receives at most one contribution, so all scatter
    strategies must agree *bit for bit* — no addition-order slack."""
    pw, pt = cfg.patch_wires, cfg.patch_ticks
    wires = np.arange(pw, cfg.num_wires - pw, pw + 8, dtype=np.float32)
    ticks = np.arange(pt, cfg.num_ticks - pt, pt + 12, dtype=np.float32)
    ww, tt = np.meshgrid(wires, ticks, indexing="ij")
    n = ww.size
    return DepoSet(
        wire=jnp.asarray(ww.ravel()), tick=jnp.asarray(tt.ravel()),
        sigma_w=jnp.full((n,), 1.0), sigma_t=jnp.full((n,), 1.2),
        charge=jnp.linspace(500.0, 5000.0, n, dtype=np.float32))


class TestRegistry:
    def test_ops_and_candidates_registered(self):
        assert set(tune.list_ops()) >= {"scatter_add", "charge_grid",
                                        "fft_convolve"}
        assert set(tune.strategies("scatter_add")) == {
            "xla", "sort_segment", "lane_rows", "pallas", "pallas_compact"}
        assert set(tune.strategies("charge_grid")) == {
            "unfused", "unfused_bf16", "fused_pallas",
            "fused_pallas_compact", "fused_pallas_multiplane",
            "fused_pallas_multiplane_compact", "multiplane_xla"}
        assert set(tune.strategies("fft_convolve")) == {"rfft2", "fft2",
                                                       "direct"}

    def test_unknown_names_raise_with_known_list(self):
        with pytest.raises(KeyError, match="scatter_add"):
            tune.get_strategy("scatter_add", "atomics")
        with pytest.raises(KeyError, match="known"):
            tune.strategies("matmul")

    def test_availability_fused_competes_in_default_physics_config(self):
        """In-kernel counter RNG lifts the old fluctuate=False restriction:
        fused candidates are available under the default (counter) config and
        only the irreproducible pre-computed pool stream excludes them."""
        shape = tune.op_shape("charge_grid", CFG)
        ctx = tune.make_context(CFG, shape)  # fluctuate=True, counter RNG
        avail = tune.available_strategies("charge_grid", ctx)
        assert {"fused_pallas", "fused_pallas_compact"} <= set(avail)
        pooled = dataclasses.replace(CFG, rng_strategy="pool")
        ctx = tune.make_context(pooled, shape)
        avail = tune.available_strategies("charge_grid", ctx)
        assert "fused_pallas" not in avail
        assert "fused_pallas_compact" not in avail
        quiet = dataclasses.replace(CFG, fluctuate=False)
        ctx = tune.make_context(quiet, shape)
        assert "fused_pallas" in tune.available_strategies("charge_grid", ctx)

    def test_availability_pallas_excluded_at_production_grids_off_tpu(self):
        big = LArTPCConfig()  # 2560 x 9592: interpret-prohibitive on CPU
        ctx = tune.make_context(big, tune.op_shape("scatter_add", big),
                                backend="cpu")
        assert "pallas" not in tune.available_strategies("scatter_add", ctx)
        # on TPU the scatter and fused charge-grid kernels are refused by
        # Mosaic and excluded; the hit-finder kernel compiles and competes
        ctx_tpu = tune.make_context(big, tune.op_shape("scatter_add", big),
                                    backend="tpu")
        assert "pallas" not in tune.available_strategies("scatter_add",
                                                         ctx_tpu)
        cg = tune.available_strategies(
            "charge_grid", tune.make_context(
                big, tune.op_shape("charge_grid", big), backend="tpu"))
        assert not any(name.startswith("fused") for name in cg)
        hf = tune.make_context(big, tune.op_shape("hit_find", big),
                               backend="tpu")
        assert "pallas" in tune.available_strategies("hit_find", hf)

    def test_backend_defaults(self):
        assert tune.default_strategy("scatter_add", "cpu") == "xla"
        assert tune.default_strategy("scatter_add", "tpu") == "lane_rows"
        assert tune.default_strategy("fft_convolve", "cpu") == "rfft2"
        assert tune.default_strategy("fft_convolve", "tpu") == "direct"


class TestAutotuner:
    def test_deterministic_winner_under_fake_timer(self, tmp_path):
        calls = []
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        d = tune.tune_op("scatter_add", CFG, cache=cache,
                         timer=fake_timer(calls))
        assert d.strategy == "pallas"      # smallest fake time, not wall time
        assert d.source == "tuned"
        assert set(calls) == {"xla", "sort_segment", "lane_rows", "pallas",
                              "pallas_compact"}

    def test_cache_roundtrip_second_call_hits_disk(self, tmp_path):
        path = str(tmp_path / "cache.json")
        calls = []
        d1 = tune.tune_op("scatter_add", CFG, cache=tune.TuneCache(path),
                          timer=fake_timer(calls))
        n_timed = len(calls)
        assert n_timed > 0 and d1.source == "tuned"
        # a FRESH TuneCache instance must find the decision on disk
        d2 = tune.tune_op("scatter_add", CFG, cache=tune.TuneCache(path),
                          timer=fake_timer(calls))
        assert d2.cache_hit and d2.strategy == d1.strategy
        assert len(calls) == n_timed, "cache hit must not re-time candidates"

    def test_force_retunes_past_the_cache(self, tmp_path):
        path = str(tmp_path / "cache.json")
        calls = []
        tune.tune_op("scatter_add", CFG, cache=tune.TuneCache(path),
                     timer=fake_timer(calls))
        n = len(calls)
        d = tune.tune_op("scatter_add", CFG, cache=tune.TuneCache(path),
                         timer=fake_timer(calls), force=True)
        assert d.source == "tuned" and len(calls) > n

    def test_shape_bucketing_shares_and_splits_keys(self):
        a = tune.cache_key("scatter_add", "cpu", "cpu", {"num_depos": 100_000})
        b = tune.cache_key("scatter_add", "cpu", "cpu", {"num_depos": 120_000})
        c = tune.cache_key("scatter_add", "cpu", "cpu", {"num_depos": 1_000})
        assert a == b and a != c

    def test_resolve_explicit_wins_over_cache(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        tune.tune_op("scatter_add", CFG, cache=cache, timer=fake_timer([]))
        d = tune.resolve("scatter_add", CFG, cache=cache)  # cfg names "xla"
        assert d.source == "explicit" and d.strategy == "xla"

    def test_resolve_config_replaces_auto_fields(self, tmp_path):
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        cfg = dataclasses.replace(CFG, scatter_strategy="auto",
                                  fft_strategy="auto",
                                  charge_grid_strategy="auto")
        resolved = tune.resolve_config(cfg, tune=True, cache=cache,
                                       timer=fake_timer([]))
        assert resolved.scatter_strategy == "pallas"   # fake-timer winner
        assert resolved.fft_strategy == "rfft2"
        # fused competes (and fake-wins) even with fluctuate=True: the
        # in-kernel counter RNG lifted the old exclusion
        assert resolved.charge_grid_strategy == "fused_pallas"
        assert resolved.hitfind_strategy == "pallas"   # fake-timer winner
        # defaults-only resolution (no tuning, no cache entry)
        resolved2 = tune.resolve_config(
            cfg, cache=tune.TuneCache(str(tmp_path / "empty.json")))
        assert resolved2.scatter_strategy == tune.default_strategy(
            "scatter_add")

    def test_scatter_add_auto_uses_cached_winner(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "cache.json"))
        tuned = tune.tune_op("scatter_add", CFG, timer=fake_timer([]))
        assert tuned.strategy == "pallas"                # fake-timer winner
        cfg = dataclasses.replace(CFG, scatter_strategy="auto")
        # the auto path must resolve to the cached winner, from the cache
        d = tune.resolve("scatter_add", cfg)
        assert d.strategy == "pallas" and d.source == "cache"
        # and the dispatch itself must run that winner without error
        depos = lattice_depos(cfg)
        patches, w0, t0 = rasterize(depos, cfg)
        out = scatter_add(patches, w0, t0, cfg)
        ref = tune.get_strategy("scatter_add", "pallas").fn(
            patches, w0, t0, cfg)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    def test_cached_winner_ignored_when_predicate_fails(self, tmp_path):
        """A fused_pallas charge_grid winner tuned under the counter-RNG
        config must NOT be served from cache to a pool-RNG config (whose
        pre-computed stream the kernel cannot reproduce) — the cache key
        omits predicate inputs like `rng_strategy`."""
        cache = tune.TuneCache(str(tmp_path / "cache.json"))
        counter = dataclasses.replace(CFG, charge_grid_strategy="auto")
        d = tune.tune_op("charge_grid", counter, cache=cache,
                         timer=fake_timer([]))
        assert d.strategy == "fused_pallas"              # fake-timer winner
        pooled = dataclasses.replace(CFG, rng_strategy="pool",
                                     charge_grid_strategy="auto")
        d2 = tune.resolve("charge_grid", pooled, cache=cache)
        assert d2.strategy == "unfused"                  # not the stale hit
        assert d2.source == "default"
        # the counter-RNG config still gets its cached winner
        d3 = tune.resolve("charge_grid", counter, cache=cache)
        assert d3.strategy == "fused_pallas" and d3.cache_hit


class TestStrategyEquivalence:
    def test_scatter_strategies_bit_for_bit_on_fixed_deposet(self):
        """Every registered scatter strategy produces the IDENTICAL grid on a
        DepoSet whose patches never overlap (no addition-order freedom)."""
        depos = lattice_depos()
        patches, w0, t0 = rasterize(depos, CFG)
        grids = {name: np.asarray(strat.fn(patches, w0, t0, CFG))
                 for name, strat in tune.strategies("scatter_add").items()}
        assert "lane_rows" in grids
        ref_name, ref = next(iter(grids.items()))
        assert float(np.abs(ref).sum()) > 0.0
        for name, grid in grids.items():
            assert np.array_equal(ref, grid), (
                f"strategy {name!r} diverged bitwise from {ref_name!r}")

    def test_scatter_strategies_allclose_with_overlap(self):
        depos = generate_depos(jax.random.key(0), CFG, 128)
        patches, w0, t0 = rasterize(depos, CFG)
        grids = {name: np.asarray(strat.fn(patches, w0, t0, CFG))
                 for name, strat in tune.strategies("scatter_add").items()}
        ref = grids.pop("xla")
        assert "lane_rows" in grids
        for name, grid in grids.items():
            np.testing.assert_allclose(grid, ref, rtol=1e-4, atol=5e-2,
                                       err_msg=name)

    def test_fft_strategies_agree(self):
        resp = make_response(CFG)
        grid = jax.random.uniform(jax.random.key(1),
                                  (CFG.num_wires, CFG.num_ticks))
        a = np.asarray(fft_convolve_rfft2(grid, resp))
        b = np.asarray(fft_convolve_fft2(grid, resp))
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)

    def test_charge_grid_strategies_agree_without_fluctuation(self):
        cfg = dataclasses.replace(CFG, fluctuate=False)
        depos = generate_depos(jax.random.key(2), cfg, 96)
        key = jax.random.key(3)
        a = np.asarray(charge_grid_unfused(key, depos, cfg))
        b = np.asarray(charge_grid_fused(key, depos, cfg))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-2)

    def test_all_charge_grid_strategies_agree_without_fluctuation(self):
        """Every registered candidate (incl. compact and bf16 variants)
        produces the same grid when fluctuation is off."""
        cfg = dataclasses.replace(CFG, fluctuate=False)
        depos = generate_depos(jax.random.key(7), cfg, 96)
        key = jax.random.key(8)
        ref = np.asarray(charge_grid_unfused(key, depos, cfg))
        ctx = tune.registry.make_context(
            cfg, tune.autotune.op_shape("charge_grid", cfg))
        for name, strat in tune.strategies("charge_grid").items():
            if not strat.is_available(ctx):
                continue  # e.g. multi-plane strategies at num_planes=1
            got = np.asarray(strat.fn(key, depos, cfg, None))
            tol = dict(rtol=1e-2, atol=2e1) if "bf16" in name else dict(
                rtol=1e-5, atol=5e-2)
            np.testing.assert_allclose(got, ref, err_msg=name, **tol)

    def test_fused_compact_matches_dense_bitwise_with_fluctuation(self):
        """Compaction preserves global tile ids, hence RNG streams: the
        compacted fused grid equals the dense fused grid BIT FOR BIT even
        with in-kernel fluctuation enabled."""
        from repro.core.pipeline import charge_grid_fused_compact

        depos = generate_depos(jax.random.key(9), CFG, 128)
        key = jax.random.key(10)
        dense = np.asarray(charge_grid_fused(key, depos, CFG))
        compact = np.asarray(charge_grid_fused_compact(key, depos, CFG))
        assert np.array_equal(dense, compact)

    def test_fused_raises_only_for_pool_rng(self):
        """The in-kernel RNG covers counter fluctuation; only the paper's
        pre-computed pool stream is irreproducible in kernel and rejected."""
        depos = generate_depos(jax.random.key(4), CFG, 8)
        pooled = dataclasses.replace(CFG, rng_strategy="pool")
        with pytest.raises(ValueError, match="pool"):
            charge_grid_fused(jax.random.key(0), depos, pooled)
        # the default counter config runs (and fluctuates: grid != mean grid)
        quiet = dataclasses.replace(CFG, fluctuate=False)
        mean = np.asarray(charge_grid_fused(jax.random.key(0), depos, quiet))
        fluct = np.asarray(charge_grid_fused(jax.random.key(0), depos, CFG))
        assert not np.array_equal(mean, fluct)
        assert abs(fluct.sum() - mean.sum()) / mean.sum() < 0.05


class TestFFTDispatch:
    """ISSUE-3 satellite: every concrete name routes through the registry."""

    def test_unknown_strategy_raises_value_error_with_candidates(self):
        resp = make_response(CFG)
        grid = jnp.zeros((CFG.num_wires, CFG.num_ticks))
        with pytest.raises(ValueError, match=r"fftw.*rfft2"):
            fft_convolve(grid, resp, "fftw")

    @pytest.mark.parametrize("name", ["rfft2", "fft2"])
    def test_concrete_names_route_through_registry(self, name, monkeypatch):
        """The old dispatch short-circuited 'rfft2' past the registry; now a
        registry override is honored for every concrete name."""
        from repro.tune import registry as reg

        calls = []
        orig = reg.get_strategy("fft_convolve", name)

        def spy(grid, resp):
            calls.append(name)
            return orig.fn(grid, resp)

        monkeypatch.setitem(reg._OPS["fft_convolve"], name,
                            dataclasses.replace(orig, fn=spy))
        resp = make_response(CFG)
        grid = jax.random.uniform(jax.random.key(0),
                                  (CFG.num_wires, CFG.num_ticks))
        out = fft_convolve(grid, resp, name)
        assert calls == [name]
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(orig.fn(grid, resp)),
                                   rtol=1e-6)


class TestPipelineIntegration:
    def test_fused_strategy_through_fig4(self):
        """The fused kernel is a first-class pipeline citizen: fig4 with
        charge_grid_strategy='fused_pallas' matches the unfused pipeline."""
        cfg = dataclasses.replace(CFG, fluctuate=False)
        fused = dataclasses.replace(cfg, charge_grid_strategy="fused_pallas")
        depos = generate_depos(jax.random.key(5), cfg, 64)
        resp = make_response(cfg)
        key = jax.random.key(6)
        a = simulate_fig4(key, depos, resp, cfg, add_noise=False)
        b = simulate_fig4(key, depos, resp, fused, add_noise=False)
        np.testing.assert_allclose(np.asarray(a.charge_grid),
                                   np.asarray(b.charge_grid),
                                   rtol=1e-5, atol=5e-2)
        assert (np.asarray(a.adc) == np.asarray(b.adc)).mean() > 0.999

    def test_make_sim_fn_resolves_auto_before_jit(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "cache.json"))
        cfg = dataclasses.replace(CFG, scatter_strategy="auto",
                                  fft_strategy="auto")
        sim = make_sim_fn(cfg)
        out = sim(jax.random.key(0), generate_depos(jax.random.key(1), cfg,
                                                    cfg.num_depos))
        ref = make_sim_fn(dataclasses.replace(cfg, scatter_strategy="xla",
                                              fft_strategy="rfft2"))(
            jax.random.key(0), generate_depos(jax.random.key(1), cfg,
                                              cfg.num_depos))
        assert np.array_equal(np.asarray(out.adc), np.asarray(ref.adc))
