"""Multi-plane (U/V/W) readout tests — the ISSUE 5 tentpole contract.

Three families:
  * geometry: the drift stage projects each depo's transverse position onto
    every plane's pitch direction (hand-checked coefficients), and the
    identity plane is bit-for-bit the single-plane drift;
  * executors: single / batched / streaming runs of a 3-plane config agree
    with each other and carry the leading plane axis (the distributed
    executor is covered by examples/sim_distributed.py --planes 3 in CI);
  * physics shape: induction planes produce bipolar waveforms, the
    collection plane unipolar ones — the paper's Fig. 2 signature.

Single-plane bit-identity with the pre-multi-plane revision is pinned
separately by the golden digests in tests/test_stages.py.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, plane_specs
from repro.core.batch import (empty_event, event_keys, make_batched_sim_fn,
                              pack_events)
from repro.core.depo import (generate_depos, generate_physical_depos,
                             generate_plane_depos)
from repro.core.drift import (PhysicalDepoSet, project_to_plane, transport,
                              transport_planes)
from repro.core.pipeline import make_sim_fn
from repro.core.response import make_plane_responses, make_response
from repro.core.stages import build_sim_graph

CFG = get_config("lartpc-uboone", smoke=True)
CFG3 = dataclasses.replace(CFG, num_planes=3)
#: deterministic physics for bitwise cross-checks
CFG3_QUIET = dataclasses.replace(CFG3, fluctuate=False)


class TestPlaneSpecs:
    def test_single_plane_is_seed_geometry(self):
        (spec,) = plane_specs(CFG)
        assert spec.kind == "induction"
        assert spec.angle_deg == 0.0
        assert spec.pitch_mm == CFG.wire_pitch_mm

    def test_default_triple_is_uvw(self):
        specs = plane_specs(CFG3)
        assert [s.kind for s in specs] == ["induction", "induction",
                                           "collection"]
        assert [s.angle_deg for s in specs] == [60.0, -60.0, 0.0]
        assert all(s.pitch_mm == CFG.wire_pitch_mm for s in specs)

    def test_validation(self):
        with pytest.raises(ValueError, match="num_planes"):
            plane_specs(dataclasses.replace(CFG, num_planes=0))
        with pytest.raises(ValueError, match="plane_angles_deg"):
            plane_specs(dataclasses.replace(CFG, num_planes=4))
        with pytest.raises(ValueError, match="plane type"):
            plane_specs(dataclasses.replace(
                CFG, num_planes=2, plane_types=("induction", "bogus")))


class TestProjection:
    def test_projection_coefficients(self):
        """Relative wire coordinates follow
        Δwire_p = (Δy_mm cos(angle) + Δz_mm sin(angle)) / pitch_p
        (the per-plane centering offset cancels in the difference)."""
        pd = PhysicalDepoSet(
            x=jnp.array([10.0, 10.0]), y=jnp.array([7.0, 12.0]),
            z=jnp.array([33.0, 20.0]), t=jnp.zeros(2), q=jnp.full(2, 1e3))
        for spec in plane_specs(CFG3):
            proj = project_to_plane(pd, spec, CFG3)
            rad = math.radians(spec.angle_deg)
            expect = ((12.0 - 7.0) * CFG3.wire_pitch_mm * math.cos(rad)
                      + (20.0 - 33.0) * math.sin(rad)) / spec.pitch_mm
            got = float(proj.y[1] - proj.y[0])
            np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)

    def test_projection_centered_on_grid(self):
        """Rotated planes are centered: the bulk of generated events lands
        inside [0, num_wires) on EVERY plane (only the ±60° corner
        overhangs that num_wires wires cannot cover may clip).

        Pooled over 16 events: a smoke event is ONE track, and a single
        track lands anywhere from 10% to 100% in bounds on a ±60° plane
        depending on the RNG realization."""
        wires = [transport_planes(
            generate_physical_depos(jax.random.key(k), CFG3), CFG3).wire
            for k in range(16)]
        for p in range(3):
            w = np.concatenate([np.asarray(x[p]) for x in wires])
            inb = ((w >= 0) & (w <= CFG3.num_wires - 1)).mean()
            assert inb > 0.8, (p, inb)
            # centered: the event's midpoint sits near the grid center
            mid = 0.5 * (w.min() + w.max())
            assert 0.2 * CFG3.num_wires < mid < 0.8 * CFG3.num_wires, (p, mid)

    def test_identity_plane_projection_is_bitwise_noop(self):
        """The angle-0, reference-pitch plane must not round-trip through
        unit constants: its projection returns the input leaves unchanged."""
        pd = generate_physical_depos(jax.random.key(0), CFG3)
        spec = plane_specs(CFG3)[2]
        proj = project_to_plane(pd, spec, CFG3)
        assert proj.y is pd.y

    def test_collection_plane_drift_equals_single_plane_drift(self):
        """Plane W (identity geometry) of the multi-plane transport is
        bit-for-bit the seed single-plane transport."""
        pd = generate_physical_depos(jax.random.key(1), CFG3)
        multi = transport_planes(pd, CFG3)
        single = transport(pd, CFG)
        for f in multi._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(multi, f)[2]),
                np.asarray(getattr(single, f)), err_msg=f)

    def test_plane_restriction_matches_full_transport(self):
        pd = generate_physical_depos(jax.random.key(2), CFG3)
        full = transport_planes(pd, CFG3)
        only1 = transport_planes(pd, CFG3, planes=(1,))
        np.testing.assert_array_equal(np.asarray(only1.wire[0]),
                                      np.asarray(full.wire[1]))

    def test_restricted_graph_selects_plane_from_predrifted_input(self):
        """A planes=(p,)-restricted graph fed FULL pre-drifted (P, N) depos
        selects plane p's rows — same output as feeding physical depos."""
        key = jax.random.key(8)
        pd = generate_physical_depos(key, CFG3_QUIET)
        graph = build_sim_graph(CFG3_QUIET, add_noise=False, planes=(2,))
        from_physical = graph.run(key, pd)
        from_predrifted = graph.run(key, transport_planes(pd, CFG3_QUIET))
        np.testing.assert_array_equal(np.asarray(from_physical.adc),
                                      np.asarray(from_predrifted.adc))

    def test_predrifted_wrong_plane_count_rejected(self):
        two_plane_depos = transport_planes(
            generate_physical_depos(jax.random.key(0), CFG3), CFG3,
            planes=(0, 1))
        with pytest.raises(ValueError, match="carry 2 planes"):
            build_sim_graph(CFG3).run(jax.random.key(0), two_plane_depos)

    def test_rotated_planes_differ(self):
        """U/V see genuinely different wire coordinates (z extent is real)."""
        pd = generate_physical_depos(jax.random.key(3), CFG3)
        d = transport_planes(pd, CFG3)
        assert float(jnp.abs(d.wire[0] - d.wire[1]).max()) > 1.0
        assert float(jnp.abs(d.wire[0] - d.wire[2]).max()) > 1.0


class TestExecutors:
    def test_single_event_shapes_and_dtype(self):
        key = jax.random.key(0)
        out = make_sim_fn(CFG3)(key, generate_physical_depos(key, CFG3))
        shape3 = (3, CFG3.num_wires, CFG3.num_ticks)
        assert out.adc.shape == shape3 and out.adc.dtype == jnp.int16
        assert out.signal.shape == shape3
        assert out.charge_grid.shape == shape3

    def test_physical_and_predrifted_inputs_agree(self):
        if jax.default_backend() != "cpu":
            pytest.skip("bitwise jit-vs-eager drift is CPU-specific")
        key = jax.random.key(4)
        sim = make_sim_fn(CFG3)
        a = sim(key, generate_physical_depos(key, CFG3))
        b = sim(key, generate_plane_depos(key, CFG3))
        np.testing.assert_array_equal(np.asarray(a.adc), np.asarray(b.adc))

    def test_planeless_depos_rejected(self):
        with pytest.raises(ValueError, match="planeless"):
            make_sim_fn(CFG3)(jax.random.key(0),
                              generate_depos(jax.random.key(0), CFG))

    def test_single_response_rejected(self):
        with pytest.raises(ValueError, match="single"):
            build_sim_graph(CFG3, make_response(CFG3))

    def test_batched_rows_equal_single_event_runs(self):
        """The vmap executor over 3-plane events matches per-event runs of
        the same graph — the multi-plane analogue of the single-plane
        equivalence pinned in test_stages."""
        key = jax.random.key(5)
        events = [generate_plane_depos(jax.random.fold_in(key, e), CFG3)
                  for e in range(2)]
        batch = pack_events(events)
        assert batch.wire.shape == (2, 3, CFG3.num_depos)
        keys = event_keys(key, range(2))
        out = make_batched_sim_fn(CFG3)(keys, batch)
        assert out.adc.shape == (2, 3, CFG3.num_wires, CFG3.num_ticks)
        sim = make_sim_fn(CFG3)
        for e in range(2):
            ref = sim(keys[e], batch.event(e))
            np.testing.assert_array_equal(np.asarray(out.adc[e]),
                                          np.asarray(ref.adc))

    def test_streaming_multi_plane(self):
        from repro.launch.sim import stream_simulate

        seen = {}

        def on_batch(b, n_valid, n_depos, dt, out):
            seen[b] = (n_valid, tuple(out.adc.shape))

        stats = stream_simulate(CFG3, num_events=3, batch_events=2,
                                on_batch=on_batch)
        assert stats["events"] == 3
        assert seen[0] == (2, (2, 3, CFG3.num_wires, CFG3.num_ticks))
        assert seen[1][0] == 1  # padded final batch reports 1 valid event

    def test_empty_event_padding_is_inert(self):
        """A short 3-plane batch pads with (P, 0)-shaped empty events whose
        rows produce a baseline-only readout."""
        key = jax.random.key(6)
        events = [generate_plane_depos(key, CFG3), empty_event(planes=3)]
        cfg = dataclasses.replace(CFG3_QUIET)
        out = make_batched_sim_fn(cfg, add_noise=False)(
            event_keys(key, range(2)), pack_events(events))
        pad_adc = np.asarray(out.adc[1])
        assert (pad_adc == int(cfg.adc_baseline)).all()


class TestPhysicsShape:
    """Bipolar induction / unipolar collection — the acceptance-criterion
    waveform check, on the noise-free deterministic chain."""

    @pytest.fixture(scope="class")
    def signal(self):
        key = jax.random.key(0)
        out = make_sim_fn(CFG3_QUIET, add_noise=False)(
            key, generate_physical_depos(key, CFG3_QUIET))
        return np.asarray(out.signal)

    def test_induction_planes_bipolar(self, signal):
        for p in (0, 1):
            pos, neg = signal[p].max(), -signal[p].min()
            assert pos > 0 and neg > 0.25 * pos, (p, pos, neg)

    def test_collection_plane_unipolar(self, signal):
        pos, neg = signal[2].max(), -signal[2].min()
        assert pos > 0
        assert neg <= 1e-3 * pos, (pos, neg)

    def test_adc_swings_both_ways_on_induction_only(self):
        key = jax.random.key(0)
        out = make_sim_fn(CFG3_QUIET, add_noise=False)(
            key, generate_physical_depos(key, CFG3_QUIET))
        adc = np.asarray(out.adc).astype(int) - int(CFG3_QUIET.adc_baseline)
        assert adc[0].min() < -5 and adc[0].max() > 5
        assert adc[1].min() < -5 and adc[1].max() > 5
        assert adc[2].min() >= -1 and adc[2].max() > 5

    def test_collection_plane_equals_single_plane_collection_run(self):
        """Plane W shares the seed geometry, so a 3-plane quiet run's third
        plane is bit-identical to a single-plane run with the collection
        response — multi-plane machinery adds no numeric drift."""
        key = jax.random.key(7)
        pd = generate_physical_depos(key, CFG3_QUIET)
        out3 = jax.jit(build_sim_graph(CFG3_QUIET, add_noise=False).run)(
            key, pd)
        cfg1 = dataclasses.replace(CFG3_QUIET, num_planes=1)
        resp = make_response(cfg1, plane="collection")
        out1 = jax.jit(build_sim_graph(cfg1, resp, add_noise=False).run)(
            key, pd)
        np.testing.assert_array_equal(np.asarray(out3.adc[2]),
                                      np.asarray(out1.adc))


class TestPlaneResponses:
    def test_make_plane_responses_kinds(self):
        resps = make_plane_responses(CFG3)
        assert [r.plane for r in resps] == ["induction", "induction",
                                            "collection"]
        # collection kernel is non-negative, induction kernel is bipolar
        assert float(resps[2].kernel.min()) >= 0.0
        assert float(resps[0].kernel.min()) < 0.0

    def test_fft_tuning_keyed_by_plane(self):
        """The fft_convolve tuning key carries the plane kind, so induction
        and collection decisions cannot alias (the autotune satellite)."""
        from repro.tune import autotune

        shape_i = autotune.op_shape("fft_convolve", CFG)
        assert shape_i["plane"] == "induction"
        shape_c = dict(shape_i, plane="collection")
        key_i = autotune.cache_key("fft_convolve", "cpu", "cpu", shape_i)
        key_c = autotune.cache_key("fft_convolve", "cpu", "cpu", shape_c)
        assert key_i != key_c
        assert "plane=induction" in key_i and "plane=collection" in key_c

    def test_multi_plane_auto_fft_stays_per_plane(self, tmp_path):
        """resolve_config on a multi-plane config must NOT bake one concrete
        fft strategy into the field (that would key every plane to the
        plane-0 decision): the field stays "auto" — resolved per dispatch
        with plane=resp.plane — and tuning produces one decision (and one
        cache key) per distinct plane kind."""
        import os

        from repro.tune import autotune

        cfg = dataclasses.replace(CFG3, fft_strategy="auto")
        cache = autotune.TuneCache(str(tmp_path / "cache.json"))
        os.environ.pop("REPRO_TUNE_CACHE", None)
        resolved, decisions = autotune.resolve_config_with_decisions(
            cfg, cache=cache)
        assert resolved.fft_strategy == "auto"
        fft_d = [d for d in decisions if d.op == "fft_convolve"]
        assert len(fft_d) == 2  # induction + collection
        planes = {d.cache_key.split("plane=")[1].split(";")[0]
                  for d in fft_d if "plane=" in d.cache_key}
        assert planes == {"collection", "induction"}
        # tuning measures each kind and persists per-kind cache entries
        # (other "auto" ops — e.g. hit_find — also reach the timer; give
        # their candidates a flat score so only the fft ranking is forced)
        fake = lambda name, thunk: {"rfft2": 1.0, "fft2": 2.0}.get(name, 1.0)  # noqa: E731
        _, tuned = autotune.resolve_config_with_decisions(
            cfg, tune=True, cache=cache, timer=fake)
        tuned_fft = [d for d in tuned if d.op == "fft_convolve"]
        assert {d.source for d in tuned_fft} == {"tuned"}
        keys = {d.cache_key for d in tuned_fft}
        assert len(keys) == 2
        for k in keys:
            assert cache.get(k)["strategy"] == "rfft2"
