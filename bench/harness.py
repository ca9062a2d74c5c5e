"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything else
is found by name:

    bench/configs/<config>.json   deployment: registry name, overrides, the
                                  sizes it is run at, source, assumptions,
                                  and optionally "reference": <module>
    bench/traffic/<traffic>.json  event mix: generator, depos per event
    bench/cells/<cell>.json       stream batch, limits of the check
    bench/metrics/<metric>.py     per-layer reader (``per_layer`` entries)
    bench/references/<module>.py  the deployment's own reference: its frozen
                                  generators ``GENERATORS``, one event's
                                  per-plane outputs ``reference_event`` by
                                  its key schedule, and ``event_view``, which
                                  splits the program's batch into per-plane
                                  lists. A configuration that names none is
                                  checked by ``check.py``'s own three names

Set-up is everything from process start to the window: TPU
initialisation, the response spectra, the compile of the streaming program
(served from JAX's persistent cache after a cell's first run) and one
warm-up stream of two batches at the cell's shapes. The window is one
``stream_simulate`` call over a whole number of batches, sized from the
warm-up so that it lasts about ``--seconds``; the callback copies each
batch's ADC (and in recon cells its hits) to host NumPy, as a writer
would. ``events_per_s`` is the events that call completed over its whole
wall time. One batch of the window, drawn from the seed, is then compared
with the plain reference (``check.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Optional

from bench import tracereduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
#: the warm-up stream's seed: the same work in every run, whatever --seed
WARM_SEED = 1_000_003
#: end-to-end metrics this harness takes itself, with their units
END_TO_END = {"events_per_s": "events/s", "setup_s": "s"}


class Refused(Exception):
    """The files do not define what was asked for, or no chip is there."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding cells, configurations, traffic and metrics by name
# ---------------------------------------------------------------------------


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file for {name!r} ({path.relative_to(ROOT)})")
    return json.loads(path.read_text())


def load_module(kind: str, name: str, what: str):
    """The module ``bench/<kind>/<name>.py``, refused by ``what`` it is."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {what} {name!r} ({path.relative_to(ROOT)})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The per-layer metric module ``bench/metrics/<name>.py``."""
    return load_module("metrics", name, "reader for metric")


def load_reference(config: dict):
    """The reference a configuration is checked against: the module
    ``bench/references/<module>.py`` its ``"reference"`` names, else the
    default in ``check.py``."""
    if "reference" not in config:
        from bench import check

        return check
    return load_module("references", config["reference"], "reference module")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    batch_events: int
    chips: int
    limits: Dict[str, float]
    readers: dict  # per-layer metric name -> reader module
    #: batches a traced window holds: the profiler records every op of the
    #: program's loops, so a traced window is kept short
    trace_batches: int

    @functools.cached_property
    def reference(self):
        """The module the check compares through (``load_reference``)."""
        return load_reference(self.config)

    @property
    def recon(self) -> bool:
        return bool(self.config.get("recon", False))

    @property
    def sizes(self) -> dict:
        return self.config["sizes"]


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise Refused(f"no workload {name!r} in {Path(spec_path).name}; "
                      f"known: {sorted(entries)}")
    entry = entries[name]
    for m in spec["end_to_end"]:
        if m["name"] not in END_TO_END:
            raise Refused(f"end-to-end metric {m['name']!r} is not taken by "
                          "this harness")
    readers = {m["name"]: load_reader(m["name"]) for m in spec["per_layer"]
               if name in m.get("workloads", [name])}
    cell = load_json("cells", name)
    return Cell(name=name, config=load_json("configs", entry["config"]),
                traffic=load_json("traffic", entry["traffic"]),
                batch_events=int(cell["batch_events"]), chips=entry["chips"],
                limits=cell["limits"], readers=readers,
                trace_batches=int(cell["trace_batches"]))


def build_config(cell: Cell):
    """The program's configuration for a cell, held to the file's sizes."""
    from repro.config import apply_overrides, get_config

    gen = cell.reference.GENERATORS.get(cell.traffic["generator"])
    if gen is None:
        raise Refused(f"unknown generator {cell.traffic['generator']!r}")
    reason = gen.refusal(cell.traffic)
    if reason is not None:
        raise Refused(reason)
    if cell.sizes["rng_strategy"] != "counter":
        raise Refused("the reference draws the 'counter' fluctuation only")
    cfg = get_config(cell.config["registry"],
                     smoke=cell.config.get("smoke", False))
    over = dict(cell.config.get("overrides", {}),
                num_depos=cell.traffic["depos_per_event"])
    cfg = apply_overrides(cfg, over)
    for key, want in cell.sizes.items():
        got = getattr(cfg, key)
        got = list(got) if isinstance(got, tuple) else got
        if got != want:
            raise Refused(f"config {cell.config['name']}: {key} is {got!r} "
                          f"in the program, {want!r} in its file")
    return cfg


def require_chips(n: int):
    """The devices, when JAX finds TPUs enough; never the CPU instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX finds no TPU (platform {devs[0].platform!r}); "
                      "this benchmark never runs on the CPU in its place")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# Set-up, window, check
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts backend compiles (jax.monitoring) from its creation on."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Window:
    seed: int
    batches: int
    events: int  # completed in the window
    attempted: int
    wall_s: float
    compiles: int
    health: dict
    sample: int  # batch index compared with the reference
    kept: dict  # that batch's outputs on the host
    #: a traced window's device numbers, per-layer metrics and breakdown
    trace: Optional[dict] = None


class Session:
    """The set-up of one cell in one process; windows run on it."""

    #: the host clock every window and set-up time is read from
    clock = staticmethod(time.perf_counter)

    def __init__(self, cell: Cell, *, overrides: Optional[dict] = None,
                 sim_hook: Optional[Callable] = None):
        import jax

        from repro.launch.sim import make_streaming_sim_fn, stream_donation
        from repro.tune import resolve_config_with_decisions

        self.cell = cell
        self.cfg = build_config(cell)
        if overrides:  # a variant of the program, e.g. the control
            self.cfg = dataclasses.replace(self.cfg, **overrides)
        _, decisions = resolve_config_with_decisions(self.cfg)
        for d in decisions:
            log(f"strategy {d.describe()}")
        log(f"donation {stream_donation()}")
        self.compiles = CompileCounter()
        t0 = self.clock()
        sim = make_streaming_sim_fn(self.cfg, recon=cell.recon)
        compiled = sim.lower(*self._specs()).compile()
        mem = compiled.memory_analysis()
        log(f"compile {self.clock() - t0:.3f} s; program bytes: temp "
            f"{getattr(mem, 'temp_size_in_bytes', None)} arguments "
            f"{getattr(mem, 'argument_size_in_bytes', None)} outputs "
            f"{getattr(mem, 'output_size_in_bytes', None)}")
        text = compiled.as_text()
        #: the program's HLO module name and its ops' JAX source paths,
        #: which name the device ops of a trace
        self.program = (text.split(None, 2)[1].rstrip(","),
                        tracereduce.hlo_op_names(text))
        self.sim = sim_hook(compiled) if sim_hook else compiled
        self.device = jax.devices()[0]
        t0 = self.clock()
        warm = self._stream(WARM_SEED, 2, sample=None)
        walls = [r["wall_s"] for r in warm[0]["batches"]]
        self.batch_s = walls[-1]
        log(f"warm-up {self.clock() - t0:.3f} s, seconds per batch {walls}")

    def _specs(self):
        """The program's inputs as ``stream_simulate`` packs them: one
        batch of empty events padded to ``num_depos``, and its keys."""
        import jax

        from repro.core.batch import empty_event, event_keys, pack_events

        cfg, e = self.cfg, self.cell.batch_events

        def inputs():
            rows = [empty_event(planes=cfg.num_planes)] * e
            return (event_keys(jax.random.key(0), range(e)),
                    pack_events(rows, pad_to=cfg.num_depos))

        return jax.eval_shape(inputs)

    def _stream(self, seed: int, n_batches: int, sample: Optional[int]):
        """One ``stream_simulate`` call; returns (stats, wall_s, kept)."""
        import jax
        import numpy as np

        from repro.launch.sim import stream_simulate

        annotate = jax.profiler.TraceAnnotation
        kept: dict = {}

        def writer(b, n_valid, n_depos, dt, out):
            with annotate("bench.on_batch"):
                adc = np.asarray(out.adc)
                hits = (jax.tree.map(np.asarray, out.hits._asdict())
                        if self.cell.recon else None)
            if b == sample:
                kept.update(out=out, adc=adc, hits=hits)

        def sim(keys, batch):
            with annotate("bench.dispatch"):
                return self.sim(keys, batch)

        e = self.cell.batch_events
        t0 = self.clock()
        with annotate("bench.window"):
            stats = stream_simulate(self.cfg, n_batches * e, e, seed=seed,
                                    sim=sim, on_batch=writer,
                                    recon=self.cell.recon)
        return stats, self.clock() - t0, kept

    def window(self, seed: int, seconds: float, trace: bool = False
               ) -> Window:
        import numpy as np

        from bench import check

        n_batches = max(2, round(seconds / max(self.batch_s, 1e-9)))
        if trace:  # the profiler's per-op trace grows with every batch
            n_batches = min(n_batches, self.cell.trace_batches)
        sample = check.sample_batch(seed, n_batches)
        tdir = CACHE / "trace" / self.cell.name
        if trace:
            import jax

            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the benchmark's spans suffice
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        before = self.compiles.count
        try:
            stats, wall, kept = self._stream(seed, n_batches, sample)
        finally:
            if trace:
                jax.profiler.stop_trace()
        compiles = self.compiles.count - before
        out = kept.pop("out")
        host = {"adc": kept["adc"], "signal": np.asarray(out.signal),
                "charge_grid": np.asarray(out.charge_grid)}
        if self.cell.recon:
            host["decon"] = np.asarray(out.decon)
            host["hits"] = kept["hits"]
        del out
        attempted = n_batches * self.cell.batch_events
        win = Window(seed=seed, batches=n_batches, events=stats["events"],
                     attempted=attempted, wall_s=wall, compiles=compiles,
                     health=stats["health"], sample=sample, kept=host)
        if trace:
            rec = tracereduce.load_record(tdir, stats["events"], self.program)
            shutil.rmtree(tdir, ignore_errors=True)
            win.trace = trace_summary(rec, self.cell.readers)
            del rec  # millions of ops: let the reference run without them
            gc.collect()
        return win

    def check(self, win: Window) -> Dict[str, float]:
        """The compared numbers of the window's sampled batch."""
        from bench import check

        e = self.cell.batch_events
        ids = list(range(win.sample * e, (win.sample + 1) * e))
        return check.compare_batch(
            win.kept, ids, win.seed, self.cell.sizes,
            self.cell.traffic["depos_per_event"],
            self.cell.traffic["generator"], self.cell.recon,
            self.cell.reference)


def trace_summary(rec, readers: dict) -> dict:
    """What a traced run reports: device busy and window seconds, each
    per-layer metric its reader finds, and the breakdown."""
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    return {"busy_s": rec.busy_s(), "window_s": rec.window_s(),
            "metrics": metrics, "breakdown": rec.breakdown()}


def failed_events(win: Window) -> int:
    """Events of the window that did not come back clean."""
    h = win.health
    lost = win.attempted - win.events
    return lost + h["quarantined"] + h["nonfinite_events"] + h["callback_errors"]


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def end_to_end(win: Window, setup_s: float) -> dict:
    """The end-to-end metrics: events the window's call completed over its
    whole wall time, and the set-up before it."""
    return {
        "events_per_s": {"value": win.events / win.wall_s,
                         "unit": END_TO_END["events_per_s"]},
        "setup_s": {"value": setup_s, "unit": END_TO_END["setup_s"]},
    }


def result_line(cell: Cell, session: Session, win: Window, setup_s: float,
                trace: bool, peak: Optional[int]) -> dict:
    """The last line of standard output, and the check's lines on stderr."""
    import jax

    from bench import check

    numbers = session.check(win)
    ok, table = check.verdict(numbers, cell.limits)
    failed = failed_events(win)
    ok = ok and failed == 0
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        metrics = win.trace["metrics"]
    else:
        metrics = end_to_end(win, setup_s)
    line = {"correct": bool(ok), "attempted": win.attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = win.trace["breakdown"]
    table["failed_events"] = {"value": failed, "limit": 0}
    line["check"] = table
    return line


def own_caches() -> None:
    """Point every cache and log the run writes inside the checkout:
    JAX's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``) and a tuning cache of the
    benchmark's own, so that every ``"auto"`` strategy resolves by the
    program's defaults, as a user's first launcher run would."""
    os.environ["REPRO_TUNE_CACHE"] = str(CACHE / "tune_cache.json")
    from repro.cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, **session_kw) -> dict:
    if require_tpu:
        devs = require_chips(cell.chips)
        log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    own_caches()
    session = Session(cell, **session_kw)
    setup_s = session.clock() - t_start
    win = session.window(seed, seconds, trace)
    peak = peak_bytes(session.device)
    log(f"window: {win.events} events in {win.batches} batches, "
        f"{win.wall_s:.4f} s; compiles inside {win.compiles}; "
        f"peak_bytes_in_use {peak}; health {win.health}")
    log(f"memory_stats {session.device.memory_stats()}")
    t0 = session.clock()
    line = result_line(cell, session, win, setup_s, trace, peak)
    log(f"reference check of batch {win.sample}: "
        f"{session.clock() - t0:.3f} s")
    for name, row in line["check"].items():
        log(f"check {name} {row['value']} limit {row['limit']}")
    return line


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        cell = load_cell(args.workload)
        line = run(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0
