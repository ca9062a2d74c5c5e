"""The harness finds what it runs by name, refuses what is not defined,
never runs on the CPU, and takes events/s over the whole window."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_configs_and_metrics_are_found_by_name(entry):
    cell = harness.load_cell(entry["name"])
    assert cell.config["name"] == entry["config"]
    assert cell.chips == entry["chips"]
    assert cell.batch_events >= 1 and cell.limits
    listed = {m["name"] for m in SPEC["per_layer"]
              if entry["name"] in m.get("workloads", [entry["name"]])}
    assert set(cell.readers) == listed
    for name, reader in cell.readers.items():
        meta = next(m for m in SPEC["per_layer"] if m["name"] == name)
        assert (reader.UNIT, reader.MOVES, reader.LAYER) == (
            meta["unit"], meta["moves"], meta["layer"])


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_hold_what_benchmark_json_says(entry):
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert conf["name"] == entry["name"]
    assert conf["reduced"] == entry["reduced"]


def _spec_with(tmp_path, **change):
    spec = json.loads(json.dumps(SPEC))
    for key, fn in change.items():
        fn(spec[key])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def test_a_cell_naming_an_unknown_config_is_refused(tmp_path):
    def rename(workloads):
        workloads[0]["config"] = "no-such-config"

    path = _spec_with(tmp_path, workloads=rename)
    with pytest.raises(harness.Refused, match="no-such-config"):
        harness.load_cell(SPEC["workloads"][0]["name"], spec_path=path)


def test_a_cell_naming_an_unknown_metric_is_refused(tmp_path):
    def add(per_layer):
        per_layer.append(dict(per_layer[0], name="no_such_metric"))

    path = _spec_with(tmp_path, per_layer=add)
    with pytest.raises(harness.Refused, match="no_such_metric"):
        harness.load_cell(SPEC["workloads"][0]["name"], spec_path=path)


def test_an_unknown_workload_is_refused():
    with pytest.raises(harness.Refused, match="no-such-cell"):
        harness.load_cell("no-such-cell")


def test_the_run_refuses_a_cpu_only_jax():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000019", "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_events_per_s_is_the_whole_call_from_its_start_to_the_last_batch(
        monkeypatch):
    """Set-up takes 100 s on the clock; each batch of 2 events completes
    1.5 s after the one before. The window opens at the call and closes
    when its last batch completes, so 8 events in 4 batches read 8 / 6."""
    import numpy as np

    import repro.launch.sim as sim_mod
    from repro.core.stages import SimOutput

    clock = FakeClock(100.0)
    out = SimOutput(adc=np.zeros((2, 1, 1), np.int16),
                    signal=np.zeros((2, 1, 1)),
                    charge_grid=np.zeros((2, 1, 1)))
    calls = []

    def fake_stream(cfg, num_events, batch_events, *, seed, sim, on_batch,
                    recon):
        calls.append((num_events, batch_events, seed))
        for b in range(num_events // batch_events):
            clock.t += 1.5
            on_batch(b, batch_events, 0, 1.5, out)
        health = {"events_ok": num_events, "quarantined": 0,
                  "nonfinite_events": 0, "callback_errors": 0}
        return {"events": num_events, "health": health}

    monkeypatch.setattr(sim_mod, "stream_simulate", fake_stream)
    cell = harness.Cell(name="fake", config={"recon": False, "sizes": {}},
                        traffic={}, batch_events=2, chips=1, limits={},
                        readers={}, trace_batches=3)
    session = harness.Session.__new__(harness.Session)
    session.cell, session.clock, session.cfg = cell, clock, None
    session.sim, session.batch_s = None, 1.5
    session.compiles = type("C", (), {"count": 0})()
    setup_s = clock() - 0.0
    win = session.window(seed=7, seconds=6.0)
    assert calls == [(8, 2, 7)]
    assert win.wall_s == pytest.approx(6.0)
    e2e = harness.end_to_end(win, setup_s)
    assert e2e["events_per_s"]["value"] == pytest.approx(8 / 6.0)
    assert e2e["setup_s"]["value"] == pytest.approx(100.0)
    assert harness.failed_events(win) == 0
