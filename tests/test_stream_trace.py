"""The streaming executor's host spans, read back from a profiler trace.

``stream_simulate`` marks every step of every batch with a ``sim.*`` span
(``jax.profiler.TraceAnnotation``). Here a small stream runs under
``jax.profiler.trace`` on the CPU, with one event of batch 0 quarantined
and the journal on, and the ``.xplane.pb`` is read back through
``ProfileData``.
"""
from collections import defaultdict

import jax
import pytest
from jax.profiler import ProfileData

from repro.config import LArTPCConfig
from repro.core.batch import make_batched_sim_fn
from repro.launch.sim import stream_simulate
from repro.testing.faults import FaultPlan

CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=48,
                   response_wires=11, response_ticks=48)
EVENTS, BATCH = 6, 2
#: the spans every batch has exactly one of, in the order they start
PER_BATCH = ("sim.generate", "sim.screen", "sim.pack", "sim.dispatch",
             "sim.wait", "sim.journal", "sim.callback")


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """[(name, start_ns, end_ns, args)] of the run, by start."""
    tmp = tmp_path_factory.mktemp("stream_trace")
    sim = make_batched_sim_fn(CFG, donate=False)
    with jax.profiler.trace(str(tmp / "trace")):
        stats = stream_simulate(CFG, EVENTS, BATCH, sim=sim,
                                on_batch=lambda *a: None,
                                journal=str(tmp / "journal.jsonl"),
                                faults=FaultPlan.parse("nan@1"))
    assert stats["health"]["quarantined"] == 1
    (path,) = (tmp / "trace").rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sim."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda x: x[1])


def by_batch(spans):
    out = defaultdict(list)
    for name, s, e, args in spans:
        if "batch" in args:
            out[args["batch"]].append((name, s, e, args))
    return out


def test_each_batch_has_one_span_of_each_step_in_order(spans):
    batches = by_batch(spans)
    assert sorted(batches) == list(range(EVENTS // BATCH))
    for b, rows in batches.items():
        assert tuple(n for n, *_ in rows) == PER_BATCH, b
        for (_, s0, e0, _), (_, s1, _, _) in zip(rows, rows[1:]):
            assert e0 <= s1  # each step ends before the next begins


def test_fetch_spans_nest_in_screen_spans(spans):
    screens = [(s, e) for n, s, e, _ in spans if n == "sim.screen"]
    fetches = [(s, e) for n, s, e, _ in spans if n == "sim.fetch"]
    # one pull per generated event
    assert len(fetches) == EVENTS
    for s, e in fetches:
        assert any(ss <= s and e <= se for ss, se in screens)


def test_counts_sit_on_the_spans_that_count_them(spans):
    batches = by_batch(spans)
    screen = {b: rows[1][3] for b, rows in batches.items()}
    assert screen[0] == {"batch": 0, "events": 2, "quarantined": 1}
    assert screen[1]["quarantined"] == screen[2]["quarantined"] == 0
    generate = {b: rows[0][3] for b, rows in batches.items()}
    assert all(g["events"] == BATCH for g in generate.values())
    pack = {b: rows[2][3] for b, rows in batches.items()}
    # the quarantined event's row is padding: no depos, the same slots
    assert pack[0]["depos"] == CFG.num_depos
    assert pack[1]["depos"] == BATCH * CFG.num_depos
    assert all(p["slots"] == BATCH * CFG.num_depos for p in pack.values())


def _launch(monkeypatch, *argv):
    from repro.launch import sim as launcher

    monkeypatch.setattr("sys.argv", ["repro.launch.sim", *argv])
    launcher.main()


def test_launcher_trace_dir_records_the_stream(tmp_path, monkeypatch,
                                               capsys):
    _launch(monkeypatch, "--smoke", "--events", "2", "--batch-events", "2",
            "--trace-dir", str(tmp_path))
    assert "trace: " in capsys.readouterr().out
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert set(PER_BATCH) - {"sim.journal"} <= names


def test_launcher_trace_dir_needs_the_streaming_pipeline(tmp_path,
                                                         monkeypatch):
    with pytest.raises(SystemExit, match="trace-dir"):
        _launch(monkeypatch, "--smoke", "--pipeline", "fig3",
                "--trace-dir", str(tmp_path))
