"""The reduction from a trace of the window to the per-layer metrics."""
import json
from pathlib import Path

import pytest

from bench import harness, tracereduce

DATA = Path(__file__).resolve().parent / "testdata"

#: a window of 100 ns: three dispatches, three copies to the host
SPANS = [("bench.window", 0, 100), ("bench.dispatch", 5, 7),
         ("bench.dispatch", 30, 31), ("bench.on_batch", 50, 55),
         ("bench.dispatch", 70, 72), ("bench.on_batch", 78, 88),
         ("bench.on_batch", 95, 98)]
#: device ops, one nested in another and one running past the window
OPS = [("fusion.1", 6, 40), ("while.2", 10, 20), ("fft.3", 45, 56),
       ("x.4", 68, 75), ("y.5", 90, 120)]
KINDS = {"while.2": "jit(run)/vmap()/scatter-add",
         "fft.3": "jit(run)/vmap(jit(fft))/jit(fft)",
         "fusion.1": "jit(run)/vmap()/erf", "x.4": "jit__uniform", "y.5": ""}


def hand_record():
    return tracereduce.Record(window=(0, 100), ops={0: OPS}, spans=SPANS,
                              kinds=KINDS, events=2)


def reader(name):
    return harness.load_reader(name)


def test_busy_union_and_idle_share():
    rec = hand_record()
    assert rec.busy(0) == [(6, 40), (45, 56), (68, 75), (90, 100)]
    assert rec.busy_s() == pytest.approx(62e-9)
    assert rec.window_s() == pytest.approx(100e-9)
    assert reader("device_idle_share").read(rec) == pytest.approx(38.0)
    assert reader("device_ms_per_event").read(rec) == pytest.approx(
        1e3 * 62e-9 / 2)


def test_sums_by_op_kind():
    rec = hand_record()
    assert reader("fft_ms_per_event").read(rec) == pytest.approx(
        1e3 * 11e-9 / 2)


def test_host_prep_from_the_benchmark_spans():
    rec = hand_record()
    assert rec.host_prep() == [(0, 5), (7, 30), (55, 70)]
    assert reader("host_prep_ms_per_event").read(rec) == pytest.approx(
        1e-6 * 43 / 2)


def test_breakdown_labels_each_gap_by_the_host_region_it_fell_in():
    b = hand_record().breakdown()
    assert [g[0] for g in b["idle_gaps"]] == [
        "on_batch:1", "host_prep:2", "host_prep:0", "wait"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [15e-9, 12e-9, 6e-9, 5e-9])
    assert b["device_ops"][0] == ["fusion.1 jit(run)/vmap()/erf",
                                 pytest.approx(34e-9)]
    assert len(b["device_ops"]) == 5


def test_a_reader_with_nothing_to_read_returns_nothing():
    rec = tracereduce.Record(window=(0, 100), ops={0: [("a", 0, 50)]},
                             spans=SPANS, kinds={"a": ""}, events=2)
    assert reader("fft_ms_per_event").read(rec) is None
    empty = tracereduce.Record(window=(0, 100), ops={}, spans=[],
                               kinds={}, events=0)
    for name in ("device_idle_share", "device_ms_per_event",
                 "host_prep_ms_per_event"):
        assert reader(name).read(empty) is None


def load_recorded(path: Path) -> tracereduce.Record:
    d = json.loads(path.read_text())
    return tracereduce.Record(
        window=tuple(d["window"]),
        ops={int(c): [tuple(o) for o in v] for c, v in d["ops"].items()},
        spans=[tuple(s) for s in d["spans"]], kinds=d["kinds"],
        events=d["events"])


def top_level(rec: tracereduce.Record) -> tracereduce.Record:
    """The record with only the ops no other op's interval holds: the same
    busy time, a fraction of the events (the recorded trace was cut down
    so)."""
    ops = {}
    for c, evs in rec.ops.items():
        keep, end = [], None
        for name, s, e in sorted(evs, key=lambda o: (o[1], -o[2])):
            if end is None or e > end:
                keep.append((name, s, e))
                end = e if end is None else max(end, e)
        ops[c] = keep
    return tracereduce.Record(window=rec.window, ops=ops, spans=rec.spans,
                              kinds=rec.kinds, events=rec.events)


def _sweep_busy(rec, chip=0):
    """Busy nanoseconds by a sweep over start (+1) and end (-1) events."""
    lo, hi = rec.window
    clipped = [(max(s, lo), min(e, hi)) for _, s, e in rec.ops[chip]]
    edges = sorted([(s, 1) for s, e in clipped if e > s]
                   + [(e, -1) for s, e in clipped if e > s])
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_trace_reduces_as_by_a_sweep():
    """A window recorded on a TPU v5e (a 10k-depo stream of one 2,560-wire
    plane; ``top_level`` of its record, re-based to the window's start at
    microsecond resolution) reduces as a sweep over its intervals does."""
    rec = load_recorded(DATA / "record_uboone1p-tracks10k.json")
    assert rec.ops and rec.spans and rec.events > 0
    assert tracereduce.length(rec.busy(0)) == _sweep_busy(rec)
    assert top_level(rec).busy(0) == rec.busy(0)
    idle = reader("device_idle_share").read(rec)
    assert 0.0 < idle < 100.0
    gaps = rec.idle_gaps()
    assert sum(e - s for _, s, e in gaps) == (
        rec.window[1] - rec.window[0] - _sweep_busy(rec))
    prep = rec.host_prep()
    assert len(prep) == sum(1 for n, _, _ in rec.spans
                            if n == "bench.dispatch")
    assert all(s <= e for s, e in prep)
    assert reader("fft_ms_per_event").read(rec) > 0
