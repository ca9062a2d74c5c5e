"""The in-program metrics: the simulator's stage scopes read from a trace
record, and the ``sim.*`` host spans of a traced run on the chip."""
import json
from pathlib import Path

import pytest

from bench import harness, scopes, tracereduce
from bench.test_bench_trace import load_recorded

DATA = Path(__file__).resolve().parent / "testdata"
#: a traced run of ``uboone-u-recon.tracks100k`` on the chip, with the
#: program's ``sim.*`` host spans [(name, start_ns, end_ns)] beside the
#: Record's fields
RECORDED_FILE = DATA / "record_uboone-u-recon-tracks100k.json"

#: device ops of the program, by stage scope, and one eager generator op
OPS = [("jit_run/while.1", 100, 400), ("jit_run/dynamic-update-slice.2",
                                       150, 160),
       ("jit_run/fusion.3", 400, 450), ("jit_run/fusion.4", 450, 480),
       ("jit_run/fusion.5", 480, 490), ("jit_run/fusion.6", 490, 500),
       ("jit_run/fusion.7", 500, 560), ("jit_run/while.8", 560, 600),
       ("jit__normal/fusion", 5, 8)]
KINDS = {"jit_run/while.1": "jit(run)/vmap(charge_grid)/scatter-add",
         "jit_run/dynamic-update-slice.2": "",  # loop body: no metadata
         "jit_run/fusion.3": "jit(run)/vmap(convolve)/jit(fft)/jit(fft)/fft",
         "jit_run/fusion.4": "jit(run)/vmap(noise)/vmap()/jit(_normal)/mul",
         "jit_run/fusion.5": "jit(run)/vmap(vmap(noise))/add",
         "jit_run/fusion.6": "jit(run)/jit(simulate_noise)/irfft",
         "jit_run/fusion.7": "jit(run)/vmap(deconvolve)/jit(fft)/fft",
         "jit_run/while.8": "jit(run)/vmap(hit_find)/while",
         "jit__normal/fusion": "jit__normal"}
EVENTS = 2

STAGE = {"charge_grid_ms_per_event": 300, "convolve_ms_per_event": 50,
         "noise_ms_per_event": 30 + 10, "deconvolve_ms_per_event": 60,
         "hit_find_ms_per_event": 40}
#: the readers of the benchmark before the in-program metrics, and what
#: they read on the committed 10k-depo record
EXISTING = {"device_idle_share": 19.749984216175264,
            "host_prep_ms_per_event": 168.3155,
            "device_ms_per_event": 270.10450000000003,
            "fft_ms_per_event": 215.81150000000002}


def hand_record(kinds=KINDS):
    """A window of 1,000 ns holding one batch's device ops."""
    return tracereduce.Record(window=(0, 1000), ops={0: OPS}, spans=[],
                              kinds=kinds, events=EVENTS)


def ms(ns):
    return 1e-6 * ns / EVENTS


@pytest.mark.parametrize("name", sorted(STAGE))
def test_stage_readers_read_their_scope(name):
    assert harness.load_reader(name).read(hand_record()) == pytest.approx(
        ms(STAGE[name]))


@pytest.mark.parametrize("path,hit", [
    ("jit(run)/vmap(noise)/add", True),
    ("jit(run)/vmap(vmap(noise))/add", True),
    ("jit(run)/noise/add", True),
    ("jit(run)/vmap(noise/jit(fft))/fft", True),
    ("jit(run)/jit(simulate_noise)/irfft", False),
    ("jit(run)/vmap(noise_floor)/add", False),
    ("jit_noise", False),
])
def test_a_scope_matches_as_a_whole_path_element(path, hit):
    assert bool(scopes.scope_pattern("noise").search(path)) is hit


def test_a_program_without_spans_or_scopes_reports_nothing():
    """The program before the scopes: the readers find nothing to read
    and report nothing, without raising."""
    rec = tracereduce.Record(window=(0, 1000), ops={0: OPS}, spans=[],
                             kinds={n: "jit(run)/vmap()/scatter-add"
                                    for n in KINDS}, events=EVENTS)
    for name in STAGE:
        assert harness.load_reader(name).read(rec) is None


def test_existing_readers_read_the_same_on_the_committed_record():
    rec = load_recorded(DATA / "record_uboone1p-tracks10k.json")
    for name, value in EXISTING.items():
        assert harness.load_reader(name).read(rec) == pytest.approx(
            value, rel=1e-12)


def test_loading_every_reader_leaves_the_trace_loader_as_it_is():
    """No reader changes how a traced run is loaded: a cell's traced run
    reads the same Record whichever metrics it lists."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for metric in spec["per_layer"]:
        harness.load_reader(metric["name"])
    assert tracereduce.load_record.__code__.co_filename == \
        tracereduce.__file__


#: what the traced run read on the chip from its full trace (TPU v5e,
#: ``uboone-u-recon.tracks100k``, seed 3141592653, a window of 2 batches)
RECORDED = {"device_idle_share": 8.205236193932874,
            "host_prep_ms_per_event": 434.01598275,
            "device_ms_per_event": 791.2375957500001,
            "fft_ms_per_event": 263.14590875,
            "charge_grid_ms_per_event": 517.4083412499999,
            "convolve_ms_per_event": 138.20904975,
            "noise_ms_per_event": 5.81892475,
            "deconvolve_ms_per_event": 121.28511675,
            "hit_find_ms_per_event": 6.00835075}


@pytest.fixture(scope="module")
def recorded():
    """The record of that run, cut to the ops no other op's interval
    holds and re-based to the window's start at microsecond resolution."""
    return load_recorded(RECORDED_FILE)


def test_recorded_trace_reads_as_on_the_chip(recorded):
    for name, value in RECORDED.items():
        assert harness.load_reader(name).read(recorded) == pytest.approx(
            value, rel=1e-3), name


def test_recorded_stages_cover_the_program_and_spans_the_host(recorded):
    """The stage scopes hold at least 95% of the device's busy time, and
    the program's spans at least 95% of the host's time from the first
    generate to the last callback."""
    stages = sum(harness.load_reader(n).read(recorded) for n in STAGE)
    assert stages >= 0.95 * harness.load_reader(
        "device_ms_per_event").read(recorded)
    spans = [tuple(s) for s in json.loads(RECORDED_FILE.read_text())[
        "sim_spans"]]
    first = min(s for n, s, _ in spans if n == "sim.generate")
    last = max(e for n, _, e in spans if n == "sim.callback")
    covered = tracereduce.length(tracereduce.union(
        [(s, e) for _, s, e in spans], (first, last)))
    assert covered >= 0.95 * (last - first)
    screens = [(s, e) for n, s, e in spans if n == "sim.screen"]
    for n, s, e in spans:
        if n == "sim.fetch":
            assert any(ss <= s and e <= se for ss, se in screens)
