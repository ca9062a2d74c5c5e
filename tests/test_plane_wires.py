"""Planes of their own wire counts on one wire axis (``cfg.plane_wires``).

MicroBooNE's readout has 2,400 + 2,400 induction and 3,456 collection
wires; its registry entry ``lartpc-uboone3p`` runs them at those counts.
Here its smoke size, 96 / 96 / 128 wires x 512 ticks, checks:

  * geometry: the face is the one the wires cover (``face_extent_mm``),
    each plane is centred on it with its own wire count — hand-checked coefficients and offsets — and every depo the
    generator draws over the face lands on each plane's wires;
  * layout: an event's planes stack along one wire axis, plane 0 first,
    (E, sum W, T), with hits (E, P, max_hits);
  * executors: single, batched and streaming runs agree bit for bit, and
    each plane's rows are the plane's own chain;
  * refusals: the wire-sharded executor and the launcher's ``--planes``
    take planes of ``num_wires`` wires and refuse any config that gives
    its planes their own counts (``concat_planes``).
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (apply_overrides, concat_planes, face_extent_mm,
                          get_config, plane_specs)
from repro.core.batch import event_keys, make_batched_sim_fn, pack_events
from repro.core.depo import generate_physical_depos, generate_plane_depos
from repro.core.drift import PhysicalDepoSet, project_to_plane
from repro.core.noise import noise_spectrum, sample_noise_rows
from repro.core.pipeline import make_sim_fn
from repro.core.response import make_plane_responses, next_fast_len
from repro.core.stages import build_sim_graph, plane_rows

CFG = get_config("lartpc-uboone3p", smoke=True)
WIRES = (96, 96, 128)
TOTAL = sum(WIRES)


def test_smoke_geometry_is_the_unequal_triple():
    assert concat_planes(CFG)
    specs = plane_specs(CFG)
    assert [s.num_wires for s in specs] == list(WIRES)
    assert [s.kind for s in specs] == ["induction", "induction",
                                       "collection"]
    assert plane_rows(specs) == ((0, 96), (96, 96), (192, 128))


def test_full_geometry_is_microboone():
    """2,400 / 2,400 / 3,456 wires at 3 mm; the face is the 10,368 mm the
    collection wires span by the height at which the +-60 degree wires
    cover it, (7,200 - 10,368 cos 60) / sin 60 = 2,327.88 mm."""
    full = get_config("lartpc-uboone3p")
    assert [s.num_wires for s in plane_specs(full)] == [2400, 2400, 3456]
    assert [s.angle_deg for s in plane_specs(full)] == [60.0, -60.0, 0.0]
    y, z = face_extent_mm(full)
    assert y == 10368.0
    assert z == pytest.approx(2016.0 / math.sin(math.radians(60.0)))
    assert (full.num_ticks, full.scatter_strategy) == (9600, "auto")
    assert [next_fast_len(w + full.response_wires - 1)
            for w in (2400, 3456)] == [2430, 3600]


def test_equal_planes_keep_their_plane_axis():
    cfg3 = dataclasses.replace(get_config("lartpc-uboone", smoke=True),
                               num_planes=3)
    assert not concat_planes(cfg3)
    assert [s.num_wires for s in plane_specs(cfg3)] == [cfg3.num_wires] * 3


def test_plane_wires_shorter_than_the_planes_is_refused():
    with pytest.raises(ValueError, match="plane_wires"):
        plane_specs(dataclasses.replace(CFG, plane_wires=(96, 96)))


def test_json_lists_become_tuples():
    """A configuration file's lists override tuple fields as tuples: the
    frozen config stays hashable."""
    cfg = apply_overrides(CFG, {"plane_wires": [64, 64, 80],
                                "plane_pitches_mm": [3.0, 3.0, 3.0]})
    assert cfg.plane_wires == (64, 64, 80)
    assert cfg.plane_pitches_mm == (3.0, 3.0, 3.0)
    hash(cfg)


def test_the_face_is_the_one_the_wires_cover():
    """y spans the 0-degree plane's 128 wires; z is the height at which
    96 wires at +-60 degrees span the face's projection."""
    y, z = face_extent_mm(CFG)
    assert y == 128 * 3.0
    assert z == pytest.approx((96 * 3.0 - y * 0.5) / math.sin(math.pi / 3))
    # the narrower angled plane fixes the height
    narrow = dataclasses.replace(CFG, plane_wires=(96, 90, 128))
    assert face_extent_mm(narrow)[1] == pytest.approx(
        (90 * 3.0 - y * 0.5) / math.sin(math.pi / 3))


@pytest.mark.parametrize("angles,match", [
    ((60.0, -60.0, 30.0), "0-degree"),
    ((0.0, 0.0, 0.0), "0-degree"),
])
def test_a_face_the_wires_cannot_fix_is_refused(angles, match):
    with pytest.raises(ValueError, match=match):
        face_extent_mm(dataclasses.replace(CFG, plane_angles_deg=angles))


def test_angled_planes_too_narrow_for_the_face_are_refused():
    with pytest.raises(ValueError, match="do not span"):
        face_extent_mm(dataclasses.replace(CFG, plane_wires=(60, 60, 128)))


class TestFaceProjection:
    @pytest.mark.parametrize("p", range(3))
    def test_coefficients_and_offset(self, p):
        """wire_p = (y pitch cos a + z sin a) / pitch + off_p, with off_p
        putting the middle wire at the face's projected midpoint."""
        spec = plane_specs(CFG)[p]
        y_face, z_face = face_extent_mm(CFG)
        pitch = CFG.wire_pitch_mm
        pd = PhysicalDepoSet(x=jnp.zeros(3), y=jnp.array([0.0, 10.0, 50.0]),
                             z=jnp.array([0.0, 30.0, 80.0]), t=jnp.zeros(3),
                             q=jnp.ones(3))
        got = np.asarray(project_to_plane(pd, spec, CFG).y)
        a = math.radians(spec.angle_deg)
        corners = [yc * math.cos(a) + zc * math.sin(a)
                   for yc in (0.0, y_face) for zc in (0.0, z_face)]
        off = (spec.num_wires - 1) / 2 - (min(corners) + max(corners)) / (
            2 * pitch)
        want = (np.array([0.0, 10.0, 50.0]) * pitch * math.cos(a)
                + np.array([0.0, 30.0, 80.0]) * math.sin(a)) / pitch + off
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
        # the face's corners span exactly the plane's wires, edge to edge
        lo = min(corners) / pitch + off
        hi = max(corners) / pitch + off
        assert lo == pytest.approx(-0.5) and hi == pytest.approx(
            spec.num_wires - 0.5)

    def test_every_depo_lands_on_every_plane(self):
        wires = [generate_plane_depos(jax.random.key(k), CFG).wire
                 for k in range(16)]
        for p, n in enumerate(WIRES):
            w = np.concatenate([np.asarray(x[p]) for x in wires])
            assert w.min() >= -0.5 - 1e-3 and w.max() <= n - 0.5 + 1e-3, p

    def test_generator_draws_over_the_face(self):
        y_face, z_face = face_extent_mm(CFG)
        pd = generate_physical_depos(jax.random.key(3), CFG, n=4096)
        y, z = np.asarray(pd.y), np.asarray(pd.z)
        assert 0 <= y.min() and y.max() <= y_face / CFG.wire_pitch_mm
        assert 0 <= z.min() and z.max() <= z_face
        assert y.max() > 96  # past the induction planes' wire count


def _events(n, key=jax.random.key(7)):
    return [generate_plane_depos(jax.random.fold_in(key, e), CFG)
            for e in range(n)]


class TestLayoutAndExecutors:
    @pytest.fixture(scope="class")
    def batched(self):
        key = jax.random.key(7)
        batch = pack_events(_events(2, key))
        keys = event_keys(key, range(2))
        out = make_batched_sim_fn(CFG, recon=True)(keys, batch)
        return keys, batch, out

    def test_layout(self, batched):
        _, batch, out = batched
        assert batch.wire.shape == (2, 3, CFG.num_depos)
        for leaf in (out.adc, out.signal, out.charge_grid, out.decon):
            assert leaf.shape == (2, TOTAL, CFG.num_ticks)
        assert out.adc.dtype == jnp.int16
        assert out.hits.wire.shape == (2, 3, CFG.max_hits)
        assert out.hits.n_hits.shape == (2, 3)
        assert int(np.asarray(out.hits.mask).sum()) > 0

    def test_single_event_runs_equal_batched_rows(self, batched):
        keys, batch, out = batched
        sim = make_sim_fn(CFG, recon=True)
        for e in range(2):
            ref = sim(keys[e], batch.event(e))
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(
                    jax.tree.map(lambda x, e=e: x[e], out))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_streaming_equals_batched(self, batched):
        from repro.launch.sim import stream_simulate

        keys, batch, out = batched
        seen = {}

        def on_batch(b, n_valid, n_depos, dt, o):
            seen[b] = jax.tree.map(np.asarray, o)

        key = jax.random.key(11)
        stats = stream_simulate(CFG, num_events=2, batch_events=2, seed=11,
                                on_batch=on_batch, recon=True)
        assert stats["events"] == 2
        events = [generate_plane_depos(jax.random.fold_in(key, e), CFG)
                  for e in range(2)]
        want = make_batched_sim_fn(CFG, recon=True)(
            event_keys(key, range(2)), pack_events(events))
        for a, b in zip(jax.tree.leaves(seen[0]), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_each_plane_is_its_own_chain(self):
        """Plane p's rows: its charge grid from fold_in(kf, p) at its own
        wire count, its noise from fold_in(kn, p) with its own rows."""
        from repro.core.pipeline import charge_grid_unfused

        key = jax.random.key(9)
        depos = generate_plane_depos(key, CFG)
        graph = build_sim_graph(CFG)
        state = graph.run_state(graph.init_state(key, depos))
        amp = noise_spectrum(CFG)
        for p, (spec, (start, n)) in enumerate(
                zip(plane_specs(CFG), plane_rows(plane_specs(CFG)))):
            pcfg = dataclasses.replace(CFG, num_wires=n)
            grid = charge_grid_unfused(
                jax.random.fold_in(state.kf, p),
                jax.tree.map(lambda x, p=p: x[p], depos), pcfg)
            np.testing.assert_array_equal(
                np.asarray(state.grid[start:start + n]), np.asarray(grid))
            noise = sample_noise_rows(jax.random.fold_in(state.kn, p), n,
                                      amp, CFG.num_ticks)
            assert noise.shape == (n, CFG.num_ticks)

    def test_responses_pad_per_plane(self):
        shapes = [r.pad_shape for r in make_plane_responses(CFG)]
        rw, rt = CFG.response_wires, CFG.response_ticks
        assert shapes == [(next_fast_len(w + rw - 1),
                           next_fast_len(CFG.num_ticks + rt - 1))
                          for w in WIRES]
        assert shapes[0] != shapes[2]

    def test_plane_kind_scopes_name_the_ops(self):
        """Each plane's convolve, noise, deconvolve and hit_find ops carry
        its kind as a scope inside the stage's."""
        key = jax.random.key(0)
        batch = pack_events(_events(2))
        names = re.findall(r'op_name="([^"]*)"', make_batched_sim_fn(
            CFG, recon=True).lower(event_keys(key, range(2)), batch)
            .compile().as_text())
        for stage in ("convolve", "noise", "deconvolve", "hit_find"):
            for kind in ("induction", "collection"):
                # vmap(convolve)/collection/...; the noise stage maps over
                # the events: noise/while/body/closed_call/collection/...
                pat = rf"[/(]{stage}[/)](.*/)?{kind}/"
                assert any(re.search(pat, n) for n in names), (stage, kind)

    def test_the_tuner_offers_only_the_unfused_chains(self):
        """Candidates that fill a (P, W, T) grid are not available here, so
        ``"auto"`` never picks one."""
        import repro.core.pipeline  # noqa: F401  (registers the candidates)
        from repro.tune.registry import TuneContext, available_strategies

        for backend in ("cpu", "tpu"):
            ctx = TuneContext(cfg=CFG, backend=backend, device_kind=backend,
                              shape={"num_wires": TOTAL,
                                     "num_ticks": CFG.num_ticks})
            assert set(available_strategies("charge_grid", ctx)) == {
                "unfused", "unfused_bf16"}, backend

    def test_fused_charge_grids_are_refused(self):
        cfg = dataclasses.replace(CFG, charge_grid_strategy="multiplane_xla")
        with pytest.raises(ValueError, match="plane_wires"):
            make_sim_fn(cfg)(jax.random.key(0), _events(1)[0])


#: planes given their own counts, unequal as MicroBooNE's or equal: both
#: take the concatenated wire axis, and both refusals use that one test
OWN_COUNTS = [CFG, dataclasses.replace(CFG, plane_wires=(128, 128, 128))]


@pytest.mark.parametrize("cfg", OWN_COUNTS, ids=["unequal", "equal"])
def test_distributed_executor_refuses_unequal_planes(cfg):
    from repro.core.distributed import make_distributed_sim
    from repro.launch.mesh import make_mesh

    assert concat_planes(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="plane_wires"):
        make_distributed_sim(mesh, cfg, None)


@pytest.mark.parametrize("cfg", OWN_COUNTS, ids=["unequal", "equal"])
def test_launcher_planes_flag_refuses_unequal_planes(cfg):
    from repro.launch.sim import with_planes

    with pytest.raises(ValueError, match="their own wire counts"):
        with_planes(cfg, 2)
    equal = get_config("lartpc-uboone", smoke=True)
    assert with_planes(equal, 3).num_planes == 3
