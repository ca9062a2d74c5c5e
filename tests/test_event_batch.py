"""Multi-event batched engine (repro.core.batch) vs the per-event pipeline.

The contract under test: packing E ragged events into one padded (E, N_max)
EventBatch and running ``simulate_events`` (vmap'd fig4) is *bit-for-bit*
identical to a Python loop of per-event ``simulate_fig4`` calls on the same
padded rows, and zero-charge padding is exactly inert.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.config import LArTPCConfig
from repro.core.batch import (EventBatch, empty_event, event_keys,
                              make_batched_sim_fn, pack_events, pad_depos,
                              shard_events, simulate_events)
from repro.core.depo import DepoSet, generate_depos
from repro.core.pipeline import simulate_fig4
from repro.core.response import make_response
from repro.launch.mesh import make_mesh
from repro.launch.sim import stream_simulate

CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=48,
                   response_wires=11, response_ticks=48)
RAGGED = [7, 16, 3, 12]


def _events(sizes, seed=100):
    key = jax.random.key(0)
    return [generate_depos(jax.random.fold_in(key, seed + i), CFG, n)
            for i, n in enumerate(sizes)]


class TestPackEvents:
    def test_shapes_and_counts(self):
        batch = pack_events(_events(RAGGED))
        assert batch.num_events == len(RAGGED)
        assert batch.max_depos == max(RAGGED)
        assert batch.wire.shape == (len(RAGGED), max(RAGGED))
        np.testing.assert_array_equal(np.asarray(batch.n_depos), RAGGED)
        assert batch.total_depos == sum(RAGGED)

    def test_padding_is_inert_rows(self):
        """Rows past n_depos[e] carry zero charge and positive sigma."""
        batch = pack_events(_events(RAGGED))
        for e, n in enumerate(RAGGED):
            assert np.all(np.asarray(batch.charge[e, n:]) == 0.0)
            assert np.all(np.asarray(batch.sigma_w[e, n:]) > 0.0)

    def test_pad_to_and_multiple(self):
        batch = pack_events(_events([5, 3]), pad_to=20)
        assert batch.max_depos == 20
        batch = pack_events(_events([5, 3]), pad_multiple=8)
        assert batch.max_depos == 8

    def test_event_roundtrip_exact(self):
        """Valid region of event(e) is the original depo data, bitwise."""
        events = _events(RAGGED)
        batch = pack_events(events)
        for e, ev in enumerate(events):
            got = batch.event(e)
            for f in DepoSet._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, f))[:ev.n],
                    np.asarray(getattr(ev, f)))

    def test_empty_event_and_oversize(self):
        batch = pack_events([empty_event(), _events([4])[0]])
        assert int(batch.n_depos[0]) == 0 and int(batch.n_depos[1]) == 4
        with pytest.raises(ValueError):
            pad_depos(_events([8])[0], 4)


class TestBatchedEqualsLoop:
    def test_bit_for_bit_ragged(self):
        """vmap'd batch == loop of simulate_fig4 on the padded rows,
        bit-for-bit, with fluctuation AND noise on (per-event keys)."""
        batch = pack_events(_events(RAGGED))
        keys = event_keys(jax.random.key(0), range(len(RAGGED)))
        resp = make_response(CFG)
        out = simulate_events(keys, batch, resp, CFG)
        for e in range(len(RAGGED)):
            ref = simulate_fig4(keys[e], batch.event(e), resp, CFG)
            np.testing.assert_array_equal(np.asarray(out.adc[e]),
                                          np.asarray(ref.adc))
            np.testing.assert_array_equal(np.asarray(out.signal[e]),
                                          np.asarray(ref.signal))
            np.testing.assert_array_equal(np.asarray(out.charge_grid[e]),
                                          np.asarray(ref.charge_grid))

    def test_bit_for_bit_jitted(self):
        """The jit'd production closure matches a jit'd per-event fig4."""
        batch = pack_events(_events(RAGGED))
        keys = event_keys(jax.random.key(0), range(len(RAGGED)))
        resp = make_response(CFG)
        sim = make_batched_sim_fn(CFG, resp=resp)
        out = sim(keys, batch)
        one = jax.jit(lambda k, d: simulate_fig4(k, d, resp, CFG))
        for e in range(len(RAGGED)):
            ref = one(keys[e], batch.event(e))
            np.testing.assert_array_equal(np.asarray(out.adc[e]),
                                          np.asarray(ref.adc))

    def test_padding_does_not_change_physics(self):
        """With deterministic physics (no fluctuation/noise), the padded row
        gives the same grid as the unpadded event — padding is exactly 0."""
        cfg = dataclasses.replace(CFG, fluctuate=False)
        events = _events([7])
        batch = pack_events(events, pad_to=32)
        resp = make_response(cfg)
        key = jax.random.key(3)
        ref = simulate_fig4(key, events[0], resp, cfg, add_noise=False)
        padded = simulate_fig4(key, batch.event(0), resp, cfg, add_noise=False)
        np.testing.assert_array_equal(np.asarray(ref.charge_grid),
                                      np.asarray(padded.charge_grid))
        np.testing.assert_array_equal(np.asarray(ref.adc),
                                      np.asarray(padded.adc))

    def test_pool_strategy_batched(self):
        """The paper-faithful pool RNG strategy also survives vmap."""
        cfg = dataclasses.replace(CFG, rng_strategy="pool")
        from repro.core.fluctuate import make_pool
        pool = make_pool(jax.random.key(9), 1 << 14)
        batch = pack_events(_events([5, 9]))
        keys = event_keys(jax.random.key(1), range(2))
        resp = make_response(cfg)
        out = simulate_events(keys, batch, resp, cfg, pool=pool)
        ref = simulate_fig4(keys[1], batch.event(1), resp, cfg, pool=pool)
        np.testing.assert_array_equal(np.asarray(out.adc[1]),
                                      np.asarray(ref.adc))


class TestRNGIndependence:
    def test_events_get_independent_randomness(self):
        """Identical depos under different per-event keys -> different ADC;
        identical keys -> identical ADC."""
        ev = _events([16])[0]
        batch = pack_events([ev, ev])
        resp = make_response(CFG)
        k_diff = event_keys(jax.random.key(0), [0, 1])
        out = simulate_events(k_diff, batch, resp, CFG)
        assert not np.array_equal(np.asarray(out.adc[0]),
                                  np.asarray(out.adc[1]))
        k_same = event_keys(jax.random.key(0), [5, 5])
        out = simulate_events(k_same, batch, resp, CFG)
        np.testing.assert_array_equal(np.asarray(out.adc[0]),
                                      np.asarray(out.adc[1]))

    def test_keys_match_serial_launcher(self):
        """event_keys(key, ids) == [fold_in(key, id) for id in ids], so a
        batched run replays the serial per-event key schedule."""
        key = jax.random.key(7)
        keys = event_keys(key, [0, 3, 11])
        for i, ev in enumerate([0, 3, 11]):
            np.testing.assert_array_equal(
                jax.random.key_data(keys[i]),
                jax.random.key_data(jax.random.fold_in(key, ev)))


class TestStreaming:
    def test_stream_counts_and_batches(self):
        stats = stream_simulate(CFG, num_events=5, batch_events=2, seed=0)
        assert stats["events"] == 5
        assert stats["depos"] == 5 * CFG.num_depos
        assert len(stats["batches"]) == 3
        # the ragged final batch reports only its real event
        assert stats["batches"][-1]["events"] == 1
        assert stats["wall_s"] > 0

    def test_stream_matches_direct_batch(self):
        """Streamed results equal a direct simulate_events call on the same
        event ids (same fold_in key schedule)."""
        got = {}
        stats = stream_simulate(
            CFG, num_events=2, batch_events=2, seed=0,
            on_batch=lambda b, nv, nd, dt, out: got.update({b: out}))
        assert stats["events"] == 2
        key = jax.random.key(0)
        events = [generate_depos(jax.random.fold_in(key, ev), CFG)
                  for ev in range(2)]
        batch = pack_events(events, pad_to=CFG.num_depos)
        ref = simulate_events(event_keys(key, range(2)), batch,
                              make_response(CFG), CFG)
        np.testing.assert_array_equal(np.asarray(got[0].adc),
                                      np.asarray(ref.adc))


class TestSharding:
    def test_shard_events_places_on_device(self):
        batch = shard_events(pack_events(_events([4, 4])))
        assert isinstance(batch, EventBatch)
        assert batch.wire.devices() == {jax.devices()[0]}

    def test_event_axis_rule_registered(self):
        from repro.core.sharding import ACT_RULES, build_spec
        assert "events" in ACT_RULES
        mesh = make_mesh((1,), ("data",))
        spec = build_spec((4, 8), ("events", None), mesh, ACT_RULES)
        assert spec[0] == "data"

    def test_simulate_under_mesh(self):
        """The batched engine runs (and matches) under an active 1-device
        mesh — the sharding constraints are exercised, not just no-ops."""
        from repro.core.sharding import use_mesh
        batch = pack_events(_events([6, 6]))
        keys = event_keys(jax.random.key(0), range(2))
        resp = make_response(CFG)
        ref = simulate_events(keys, batch, resp, CFG)
        mesh = make_mesh((1, 1), ("data", "model"))
        with use_mesh(mesh):
            sim = make_batched_sim_fn(CFG, resp=resp)
            out = sim(event_keys(jax.random.key(0), range(2)),
                      shard_events(batch))
        np.testing.assert_array_equal(np.asarray(out.adc),
                                      np.asarray(ref.adc))


def test_batched_executor_draws_noise_one_event_at_a_time():
    """The batch's noise stage is one loop over its events (``lax.map``),
    not a draw batched over them: batched over events, the TPU compiler
    returns wrong inverse transforms for some shapes (PERF.md)."""
    import re

    keys = event_keys(jax.random.key(0), range(2))
    batch = pack_events(_events(RAGGED[:2]))
    text = make_batched_sim_fn(CFG).lower(keys, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert "jit(run)/noise/while" in names
    assert not any(n.startswith("jit(run)/vmap(noise)") for n in names)


def test_only_what_the_noise_writes_leaves_its_event_loop():
    """The noise loop returns the signal alone: the fields of the state it
    leaves as they are (the grid, the depos, the keys) are forwarded past
    the loop by ``lax.map``, not copied through it event by event."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    keys = event_keys(jax.random.key(0), range(2))
    batch = pack_events(_events(RAGGED[:2]))
    top = jax.make_jaxpr(make_batched_sim_fn(CFG))(keys, batch)

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "scan"
                    and "noise" in str(eqn.source_info.name_stack)):
                yield eqn
            for p in eqn.params.values():
                sub = p.jaxpr if isinstance(p, ClosedJaxpr) else p
                if isinstance(sub, Jaxpr):
                    yield from scans(sub)

    (loop,) = scans(top.jaxpr)
    assert [v.aval.shape for v in loop.outvars] == [
        (2, CFG.num_wires, CFG.num_ticks)]
