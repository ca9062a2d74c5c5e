"""Fault-tolerance layer tests (ISSUE 8): the streaming executor must
survive poison events, transient OOMs, kills, and corrupt caches — WITHOUT
perturbing a single bit of any healthy event's ADC.

The contracts under test:

  * ingest validation quarantines invalid events; survivors are bit-identical
    to a run that never saw the poison (ids/keys preserved)
  * the batch journal makes a killed run resumable, and the resumed run's
    per-batch ADC SHA-256 digests equal a clean uninterrupted run's
  * OOM-class dispatch failures retry with halved batches — bit-identical
    (vmap row independence + fixed pad_to); non-OOM failures fail fast with
    a structured SimBatchError
  * the default path (validation on, clean input, no journal, check_finite
    off) is bit-identical to the pre-ISSUE-8 code — the pinned golden digest
    from tests/test_stages.py must still hold, with and without the sentinel
  * the autotune cache survives torn writes, garbage bytes, foreign schemas,
    and concurrent writers
"""
import dataclasses
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from repro.config import LArTPCConfig, get_config
from repro.core.batch import (empty_event, event_keys, make_batched_sim_fn,
                              pack_events, screen_events)
from repro.core.depo import DepoSet, generate_depos
from repro.core.drift import PhysicalDepoSet
from repro.core.validate import (RunHealth, SimBatchError, check_depos,
                                 dead_letter, is_oom_error)
from repro.launch.journal import (JournalError, RunJournal,
                                  load_journal_records, run_fingerprint)
from repro.launch.sim import stream_simulate
from repro.testing.faults import (FaultPlan, InjectedDispatchError,
                                  InjectedOOM, corrupt_tune_cache)

# small config (test_event_batch conventions) — fast on CPU
CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=48,
                   response_wires=11, response_ticks=48)

# the pinned ``unfused`` digest from tests/test_stages.py (smoke config, CPU,
# key 0; refreshed there for JAX 0.9.0's threefry): the default path with
# this module's layer present must still hit it
GOLDEN_UNFUSED_SHA = (
    "03c6fc7cfdd6b839cb75778937a1c57657e8eea37347b2139b506da36c3105e2")


def _depos(ev: int, cfg: LArTPCConfig = CFG, seed: int = 0) -> DepoSet:
    return generate_depos(jax.random.fold_in(jax.random.key(seed), ev), cfg)


def _nan_depos(ev: int) -> DepoSet:
    d = _depos(ev)
    q = np.array(np.asarray(d.charge))
    q[0] = np.nan
    return d._replace(charge=q)


# ---------------------------------------------------------------------------
# Validation rules
# ---------------------------------------------------------------------------


class TestValidation:
    def test_clean_event_passes(self):
        assert check_depos(_depos(0), CFG) == []

    def test_nan_charge_rejected(self):
        reasons = check_depos(_nan_depos(0), CFG)
        assert any("nonfinite charge" in r for r in reasons)

    def test_inf_position_rejected(self):
        d = _depos(0)
        w = np.array(np.asarray(d.wire))
        w[3] = np.inf
        reasons = check_depos(d._replace(wire=w), CFG)
        assert any("nonfinite wire" in r for r in reasons)

    def test_negative_charge_rejected(self):
        d = _depos(0)
        q = np.array(np.asarray(d.charge))
        q[1] = -5.0
        reasons = check_depos(d._replace(charge=q), CFG)
        assert any("negative charge" in r for r in reasons)

    def test_zero_sigma_rejected(self):
        d = _depos(0)
        s = np.zeros_like(np.asarray(d.sigma_w))
        reasons = check_depos(d._replace(sigma_w=s), CFG)
        assert any("non-positive sigma_w" in r for r in reasons)

    def test_far_out_of_frame_rejected_mild_overhang_ok(self):
        d = _depos(0)
        w = np.array(np.asarray(d.wire))
        w[0] = -1.5  # mild overhang: the rasterizer clips this — fine
        assert check_depos(d._replace(wire=w), CFG) == []
        w[0] = 1e7   # corruption-scale: reject
        reasons = check_depos(d._replace(wire=w), CFG)
        assert any("wire outside" in r for r in reasons)

    def test_oversize_rejected(self):
        d = _depos(0)
        assert check_depos(d, CFG, max_depos=d.n) == []
        reasons = check_depos(d, CFG, max_depos=d.n - 1)
        assert any("oversized" in r for r in reasons)

    def test_inconsistent_shapes_rejected(self):
        d = _depos(0)
        reasons = check_depos(
            d._replace(charge=np.asarray(d.charge)[:-1]), CFG)
        assert any("inconsistent leaf shapes" in r for r in reasons)

    def test_plane_axis_mismatch_rejected(self):
        d = _depos(0)
        stacked = type(d)(*[np.stack([np.asarray(a)] * 2)
                            for a in d])  # (2, N) leaves
        cfg3 = dataclasses.replace(CFG, num_planes=3)
        reasons = check_depos(stacked, cfg3)
        assert any("plane axis 2 != num_planes 3" in r for r in reasons)

    def test_physical_frame_rules(self):
        n = 16
        ok = PhysicalDepoSet(
            x=np.full(n, 5.0, np.float32), y=np.zeros(n, np.float32),
            z=np.zeros(n, np.float32), t=np.zeros(n, np.float32),
            q=np.full(n, 100.0, np.float32))
        assert check_depos(ok, CFG) == []
        bad_x = ok._replace(x=np.full(n, -3.0, np.float32))
        assert any("negative drift time" in r for r in check_depos(bad_x, CFG))
        bad_q = ok._replace(q=np.full(n, -1.0, np.float32))
        assert any("negative charge" in r for r in check_depos(bad_q, CFG))

    def test_screen_events_quarantines_and_counts(self):
        health = RunHealth()
        events = [_depos(0), _nan_depos(1), _depos(2)]
        kept, ids, letters = screen_events(events, [0, 1, 2], CFG,
                                           batch=7, health=health)
        assert ids == [0, 2] and len(kept) == 2
        assert health.quarantined == 1
        (letter,) = letters
        assert letter["event"] == 1 and letter["batch"] == 7
        assert letter["reasons"]
        json.dumps(letter)  # must be JSON-serializable as-is

    def test_dead_letter_shape(self):
        d = _depos(0)
        rec = dead_letter(3, 1, ["r"], d)
        assert rec == {"event": 3, "batch": 1, "reasons": ["r"],
                       "n_depos": d.n}


class TestOOMClassification:
    def test_injected_oom_is_oom(self):
        assert is_oom_error(InjectedOOM("RESOURCE_EXHAUSTED: boom"))

    def test_message_variants(self):
        assert is_oom_error(RuntimeError("CUDA out of memory"))
        assert is_oom_error(RuntimeError("OUT_OF_MEMORY while allocating"))

    def test_ordinary_errors_are_not(self):
        assert not is_oom_error(InjectedDispatchError("nope"))
        assert not is_oom_error(ValueError("shape mismatch"))


# ---------------------------------------------------------------------------
# Fault plan
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse(self):
        p = FaultPlan.parse("nan@0, neg@3,oversize@2,oom@1,oom@4x2,error@5")
        assert p.nan_events == {0} and p.negative_events == {3}
        assert p.oversized_events == {2}
        assert p.oom_batches == {1: 1, 4: 2}
        assert p.error_batches == {5}

    def test_parse_rejects_bad_specs(self):
        with pytest.raises(ValueError):
            FaultPlan.parse("explode@1")
        with pytest.raises(ValueError):
            FaultPlan.parse("nan@1x2")  # xN is oom-only

    def test_corrupt_event_nan_and_oversize(self):
        p = FaultPlan.parse("nan@0,oversize@1")
        d0 = p.corrupt_event(0, _depos(0))
        assert not np.isfinite(np.asarray(d0.charge)).all()
        d1 = p.corrupt_event(1, _depos(1))
        assert d1.n == 2 * _depos(1).n
        # unscheduled events pass through untouched (same object)
        d2 = _depos(2)
        assert p.corrupt_event(2, d2) is d2

    def test_oom_countdown(self):
        p = FaultPlan.parse("oom@0x2")
        for _ in range(2):
            with pytest.raises(InjectedOOM):
                p.before_dispatch(0)
        p.before_dispatch(0)  # budget spent: no raise

    def test_error_batch_always_raises(self):
        p = FaultPlan.parse("error@1")
        p.before_dispatch(0)
        for _ in range(2):
            with pytest.raises(InjectedDispatchError):
                p.before_dispatch(1)


# ---------------------------------------------------------------------------
# Journal
# ---------------------------------------------------------------------------


class TestJournal:
    def test_create_append_reload(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        with RunJournal(path, fingerprint="abc") as j:
            j.append_batch({"batch": 0, "events": 2})
            j.append_batch({"batch": 1, "events": 1})
        j2 = RunJournal(path, fingerprint="abc", resume=True)
        assert sorted(j2.completed) == [0, 1]
        assert j2.completed[1]["events"] == 1
        j2.close()

    def test_fingerprint_mismatch_rejected(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        RunJournal(path, fingerprint="abc").close()
        with pytest.raises(JournalError, match="fingerprint"):
            RunJournal(path, fingerprint="DIFFERENT", resume=True)

    def test_torn_final_line_tolerated(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        with RunJournal(path, fingerprint="abc") as j:
            j.append_batch({"batch": 0, "events": 2})
            j.append_batch({"batch": 1, "events": 2})
        with open(path, "a") as f:
            f.write('{"kind": "batch", "batch": 2, "eve')  # torn write
        j2 = RunJournal(path, fingerprint="abc", resume=True)
        assert sorted(j2.completed) == [0, 1]  # torn record dropped
        j2.close()
        # and the journal is APPENDABLE again after the torn line
        recs = load_journal_records(path)
        assert [r["batch"] for r in recs] == [0, 1]

    def test_garbage_file_rejected(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        with open(path, "w") as f:
            f.write("not a journal\n")
        with pytest.raises(JournalError):
            RunJournal(path, fingerprint="abc", resume=True)

    def test_fingerprint_covers_cfg_and_params(self):
        a = run_fingerprint(CFG, seed=0, batch_events=2)
        assert a == run_fingerprint(CFG, seed=0, batch_events=2)
        assert a != run_fingerprint(CFG, seed=1, batch_events=2)
        cfg2 = dataclasses.replace(CFG, num_wires=128)
        assert a != run_fingerprint(cfg2, seed=0, batch_events=2)


# ---------------------------------------------------------------------------
# Streaming fault tolerance (shared compiled sim via module fixture)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sim_fn():
    # one jit'd program shared by every streaming test (shape-polymorphic:
    # E=2 and E=4 launches each compile once)
    return make_batched_sim_fn(CFG, donate=False)


def _stream_rows(sim, cfg=CFG, num_events=4, batch_events=2, **kw):
    """stream_simulate + per-batch valid-region ADC capture."""
    rows = {}

    def grab(b, n_valid, n_depos, dt, out):
        rows[b] = np.array(np.asarray(out.adc)[:n_valid])

    stats = stream_simulate(cfg, num_events, batch_events, sim=sim,
                            on_batch=grab, **kw)
    return rows, stats


class TestStreamFaultTolerance:
    def test_clean_run_health(self, sim_fn):
        rows, stats = _stream_rows(sim_fn)
        assert stats["events"] == 4
        h = stats["health"]
        assert h["events_ok"] == 4 and h["quarantined"] == 0
        assert h["retries"] == 0 and h["resumed"] == 0

    def test_quarantine_preserves_survivors_bitwise(self, sim_fn):
        clean, _ = _stream_rows(sim_fn)
        rows, stats = _stream_rows(sim_fn, faults=FaultPlan.parse("nan@1"))
        h = stats["health"]
        assert h["quarantined"] == 1 and h["events_ok"] == 3
        assert stats["events"] == 3
        (letter,) = h["dead_letters"]
        assert letter["event"] == 1 and letter["batch"] == 0
        # batch 0 survivor (event 0) bit-identical to the clean run's row
        np.testing.assert_array_equal(rows[0][0], clean[0][0])
        # batch 1 untouched entirely
        np.testing.assert_array_equal(rows[1], clean[1])

    def test_validation_off_is_bit_identical_on_clean_input(self, sim_fn):
        on, _ = _stream_rows(sim_fn)
        off, _ = _stream_rows(sim_fn, validate=False)
        for b in on:
            np.testing.assert_array_equal(on[b], off[b])

    def test_oversized_event_quarantined_not_crash(self, sim_fn):
        rows, stats = _stream_rows(sim_fn,
                                   faults=FaultPlan.parse("oversize@2"))
        assert stats["health"]["quarantined"] == 1
        assert any("oversized" in r
                   for r in stats["health"]["dead_letters"][0]["reasons"])

    def test_retry_halving_is_bit_identical(self, sim_fn):
        clean, _ = _stream_rows(sim_fn, num_events=4, batch_events=4)
        rows, stats = _stream_rows(sim_fn, num_events=4, batch_events=4,
                                   faults=FaultPlan.parse("oom@0"))
        h = stats["health"]
        assert h["retries"] == 1 and h["halvings"] == 1
        np.testing.assert_array_equal(rows[0], clean[0])

    def test_nonretryable_fails_fast_with_context(self, sim_fn):
        with pytest.raises(SimBatchError) as ei:
            _stream_rows(sim_fn, faults=FaultPlan.parse("error@1"))
        e = ei.value
        assert e.batch == 1 and e.attempts == 1
        assert isinstance(e.cause, InjectedDispatchError)
        assert isinstance(e.__cause__, InjectedDispatchError)

    def test_retry_budget_exhausted_raises(self, sim_fn):
        with pytest.raises(SimBatchError) as ei:
            _stream_rows(sim_fn, faults=FaultPlan.parse("oom@0x9"),
                         max_retries=2)
        assert ei.value.attempts == 3  # initial + 2 retries
        assert is_oom_error(ei.value.cause)

    def test_resume_is_bit_identical(self, sim_fn, tmp_path):
        jpath = str(tmp_path / "run.jsonl")
        cpath = str(tmp_path / "clean.jsonl")
        _stream_rows(sim_fn, num_events=6, batch_events=2, journal=cpath)
        shas = {r["batch"]: r["adc_sha"]
                for r in load_journal_records(cpath)}
        # killed run: batch 1 dies permanently; batch 0 must be salvaged
        with pytest.raises(SimBatchError):
            _stream_rows(sim_fn, num_events=6, batch_events=2,
                         journal=jpath, faults=FaultPlan.parse("error@1"))
        done = {r["batch"] for r in load_journal_records(jpath)}
        assert done == {0}
        # resume: only batches 1..2 run; digests equal the clean run's
        rows, stats = _stream_rows(sim_fn, num_events=6, batch_events=2,
                                   journal=jpath, resume=True)
        assert sorted(rows) == [1, 2]  # batch 0 skipped, not re-run
        assert stats["health"]["resumed"] == 2
        assert stats["events"] == 6
        resumed = {r["batch"]: r["adc_sha"]
                   for r in load_journal_records(jpath)}
        assert resumed == shas

    def test_resume_wrong_config_rejected(self, sim_fn, tmp_path):
        jpath = str(tmp_path / "run.jsonl")
        _stream_rows(sim_fn, journal=jpath)
        with pytest.raises(JournalError, match="fingerprint"):
            _stream_rows(sim_fn, seed=99, journal=jpath, resume=True)

    def test_resume_without_journal_rejected(self, sim_fn):
        with pytest.raises(ValueError, match="journal"):
            stream_simulate(CFG, 2, sim=sim_fn, resume=True)

    def test_callback_error_does_not_lose_stats(self, sim_fn):
        def bad_callback(b, n_valid, n_depos, dt, out):
            raise KeyError("user bug")

        with pytest.warns(RuntimeWarning) as rec:
            stats = stream_simulate(CFG, 4, 2, sim=sim_fn,
                                    on_batch=bad_callback)
        assert sum("callback failed for batch" in str(w.message)
                   for w in rec) == 2
        assert stats["events"] == 4  # every batch still recorded
        assert len(stats["batches"]) == 2
        assert stats["health"]["callback_errors"] == 2

    def test_zero_events(self, sim_fn):
        stats = stream_simulate(CFG, 0, 2, sim=sim_fn)
        assert stats["events"] == 0 and stats["batches"] == []
        assert stats["health"]["events_ok"] == 0

    def test_negative_events_rejected(self, sim_fn):
        with pytest.raises(ValueError, match="num_events"):
            stream_simulate(CFG, -1, sim=sim_fn)

    def test_all_quarantined_batch_still_streams(self, sim_fn):
        rows, stats = _stream_rows(sim_fn,
                                   faults=FaultPlan.parse("nan@0,nan@1"))
        assert stats["health"]["quarantined"] == 2
        assert stats["events"] == 2  # batch 1's events survive
        assert rows[0].shape[0] == 0  # batch 0: all padding
        # batch 1 rows bit-identical to a clean run
        clean, _ = _stream_rows(sim_fn)
        np.testing.assert_array_equal(rows[1], clean[1])


# ---------------------------------------------------------------------------
# check_finite sentinel
# ---------------------------------------------------------------------------


class TestCheckFinite:
    def test_off_path_hits_seed_golden_pin(self):
        """The fault-tolerance layer must not move the default path by one
        bit: the seed-era pinned digest still holds (CPU lowering)."""
        if jax.default_backend() != "cpu":
            pytest.skip("pinned digests are CPU-lowering specific")
        from repro.core.pipeline import make_sim_fn

        cfg = get_config("lartpc-uboone", smoke=True)
        assert cfg.check_finite is False  # off by default
        key = jax.random.key(0)
        adc = np.ascontiguousarray(
            np.asarray(make_sim_fn(cfg)(key, generate_depos(key, cfg)).adc))
        assert hashlib.sha256(adc.tobytes()).hexdigest() == GOLDEN_UNFUSED_SHA

    def test_on_path_is_bitwise_identical_and_reports_ok(self):
        from repro.core.pipeline import make_sim_fn

        cfg = get_config("lartpc-uboone", smoke=True)
        key = jax.random.key(0)
        depos = generate_depos(key, cfg)
        base = make_sim_fn(cfg)(key, depos)
        checked = make_sim_fn(
            dataclasses.replace(cfg, check_finite=True))(key, depos)
        np.testing.assert_array_equal(np.asarray(base.adc),
                                      np.asarray(checked.adc))
        assert base.finite_ok is None       # off: empty pytree node
        assert bool(checked.finite_ok)      # on, clean input: True

    def test_sentinel_trips_on_nan_input(self):
        cfg = dataclasses.replace(CFG, check_finite=True)
        sim = make_batched_sim_fn(cfg, donate=False)
        events = [_depos(0), _nan_depos(1)]
        out = sim(event_keys(jax.random.key(0), [0, 1]),
                  pack_events(events, pad_to=CFG.num_depos))
        ok = np.asarray(out.finite_ok)
        assert ok.shape == (2,)
        assert bool(ok[0]) and not bool(ok[1])

    def test_stream_counts_nonfinite_events(self):
        cfg = dataclasses.replace(CFG, check_finite=True)
        sim = make_batched_sim_fn(cfg, donate=False)
        # validation OFF so the NaN reaches the device sentinel
        _, stats = _stream_rows(sim, cfg=cfg, validate=False,
                                faults=FaultPlan.parse("nan@1"))
        assert stats["health"]["nonfinite_events"] == 1
        assert stats["batches"][0]["nonfinite"] == 1
        assert stats["batches"][1]["nonfinite"] == 0


# ---------------------------------------------------------------------------
# Degenerate recon inputs
# ---------------------------------------------------------------------------


class TestDegenerateRecon:
    def test_empty_events_yield_zero_hits(self):
        """All-padding batches flow through deconvolve + hit_find: with no
        charge and no noise every mask is False and n_hits == 0."""
        cfg = dataclasses.replace(CFG, noise_rms_adc=0.0)
        sim = make_batched_sim_fn(cfg, donate=False, recon=True)
        batch = pack_events([empty_event(), empty_event()],
                            pad_to=cfg.num_depos)
        out = sim(event_keys(jax.random.key(0), [100, 101]), batch)
        assert int(np.asarray(out.hits.mask).sum()) == 0
        assert int(np.asarray(out.hits.n_hits).sum()) == 0

    def test_stream_recon_with_all_quarantined_batch(self):
        cfg = dataclasses.replace(CFG, noise_rms_adc=0.0)
        sim = make_batched_sim_fn(cfg, donate=False, recon=True)
        rows = {}

        def grab(b, n_valid, n_depos, dt, out):
            rows[b] = int(np.asarray(out.hits.mask)[:n_valid].sum())

        stats = stream_simulate(cfg, 4, 2, sim=sim, recon=True,
                                on_batch=grab,
                                faults=FaultPlan.parse("nan@0,nan@1"))
        assert stats["health"]["quarantined"] == 2
        assert rows[0] == 0          # fully-masked batch: zero hits
        assert stats["batches"][0]["hits"] == 0


# ---------------------------------------------------------------------------
# Tune-cache robustness
# ---------------------------------------------------------------------------


class TestTuneCacheRobustness:
    def _cache(self, tmp_path):
        from repro.tune.autotune import TuneCache

        return TuneCache(str(tmp_path / "tune_cache.json"))

    def test_roundtrip_stamps_schema(self, tmp_path):
        from repro.tune.autotune import SCHEMA_VERSION

        c = self._cache(tmp_path)
        c.put("k", {"strategy": "xla"})
        hit = self._cache(tmp_path).get("k")
        assert hit["strategy"] == "xla"
        assert hit["schema"] == SCHEMA_VERSION

    @pytest.mark.parametrize("mode", ["truncate", "garbage", "foreign"])
    def test_corruption_degrades_to_miss_and_recovers(self, tmp_path, mode):
        c = self._cache(tmp_path)
        c.put("op|cpu|cpu|n=1", {"strategy": "xla"})
        corrupt_tune_cache(c.path, mode)
        fresh = self._cache(tmp_path)
        assert fresh.get("op|cpu|cpu|n=1") is None  # miss, not crash
        # and a subsequent put writes a clean usable cache again
        fresh.put("op|cpu|cpu|n=1", {"strategy": "pallas"})
        assert self._cache(tmp_path).get("op|cpu|cpu|n=1")["strategy"] == \
            "pallas"

    def test_foreign_schema_entries_ignored_per_entry(self, tmp_path):
        c = self._cache(tmp_path)
        c.put("mine", {"strategy": "xla"})
        corrupt_tune_cache(c.path, "foreign")  # clobbers with foreign JSON
        fresh = self._cache(tmp_path)
        assert fresh.get("some|other|tool|key") is None
        assert fresh.get("scatter_add|cpu|cpu|num_depos=256") is None

    def test_concurrent_writers_merge_not_clobber(self, tmp_path):
        """Two cache handles (two processes, in spirit): the second writer
        re-reads disk on put, so the first writer's entry survives."""
        a = self._cache(tmp_path)
        b = self._cache(tmp_path)
        b.get("warm")  # b loads (empty) disk BEFORE a writes
        a.put("from_a", {"strategy": "xla"})
        b.put("from_b", {"strategy": "pallas"})
        final = self._cache(tmp_path)
        assert final.get("from_a")["strategy"] == "xla"
        assert final.get("from_b")["strategy"] == "pallas"

    def test_no_tmp_litter(self, tmp_path):
        c = self._cache(tmp_path)
        c.put("k", {"strategy": "xla"})
        litter = [f for f in os.listdir(tmp_path) if ".tmp" in f]
        assert litter == []

    def test_usable_hit_rejects_non_dict(self):
        from repro.tune import registry
        from repro.tune.autotune import _usable_hit, op_shape

        registry.ensure_registered()
        ctx = registry.make_context(CFG, op_shape("scatter_add", CFG))
        assert not _usable_hit("scatter_add", None, ctx)
        assert not _usable_hit("scatter_add", "just a string", ctx)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
