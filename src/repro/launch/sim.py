"""LArTPC simulation launcher (the paper's workload):

    python -m repro.launch.sim [--config ID] [--smoke] [--events N]
                               [--batch-events E]
                               [--pipeline fig3|fig4] [--tune] [--retune]
                               [--strategy <scatter>] [--trace-dir DIR]
                               [--recon] [--set key=value ...]

``--tune`` autotunes every registered hot op (drift, scatter-add,
charge-grid, FFT-convolve) on the live backend at this config's shape before
running, caching winners to disk; a repeated run reports cache hits instead
of re-measuring (see docs/tuning.md). ``--strategy`` forces the scatter-add
strategy, overriding both the config and the tuner. ``--trace-dir`` records
a profiler trace of the stream (TensorBoard/Perfetto): device time per stage
under each stage's named scope, and the host's ``sim.*`` spans per batch.
``--recon`` closes the sim->recon loop: the streamed graph also deconvolves
the ADC and finds hits, and each batch reports its hit counts.

The fig4 path streams *batches* of events through one vmap'd device program
(``repro.core.batch``): while batch b computes on device, the host prepares
batch b+1 and then copies batch b back (double buffering) — the paper's
"minimize data movement" prescription applied at the event level. The
generator's ops run eagerly on the device, so on a TPU they queue behind
batch b: the ``sim.*`` spans of a ``--trace-dir`` trace show where each
step waits. ``--batch-events 1`` degenerates to
the classic one-event-per-launch loop; fig3 keeps the faithful per-depo
host-loop baseline.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import time
import warnings
from typing import Callable, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.cache import enable_compile_cache
from repro.config import (LArTPCConfig, apply_overrides, concat_planes,
                          get_config)
from repro.core import generate_depos, simulate
from repro.core.batch import (empty_event, event_keys, make_batched_sim_fn,
                              pack_events, screen_events, shard_events)
from repro.core.depo import generate_plane_depos
from repro.core.response import make_response
from repro.core.validate import RunHealth, SimBatchError, is_oom_error
from repro.launch.journal import RunJournal, run_fingerprint


def stream_donation(backend: Optional[str] = None) -> bool:
    """The streaming executor's donation policy, as a testable predicate.

    Every launch stages a FRESH batch, so the input buffers are donated:
    XLA recycles their device memory for outputs (cuts the steady-state
    footprint by one (E, N_max) batch + keys). CPU never implements
    donation — skip it there to avoid a pointless warning per compile.
    The contract auditor pins the accelerator-side request
    (``p*/streaming`` donated_args) through this same function.
    """
    if backend is None:
        backend = jax.default_backend()
    return backend != "cpu"


def make_streaming_sim_fn(cfg: LArTPCConfig, recon: bool = False,
                          donate: Optional[bool] = None):
    """The device program ``stream_simulate`` drives: ``make_batched_sim_fn``
    with the streaming donation policy applied (``donate=None`` reads
    ``stream_donation()`` for the live backend)."""
    if donate is None:
        donate = stream_donation()
    return make_batched_sim_fn(cfg, donate=donate, recon=recon)


def stream_simulate(cfg: LArTPCConfig, num_events: int, batch_events: int = 1,
                    seed: int = 0, sim: Optional[Callable] = None,
                    pad_to: Optional[int] = None,
                    on_batch: Optional[Callable] = None,
                    recon: bool = False,
                    journal: Optional[str] = None, resume: bool = False,
                    validate: bool = True, max_retries: int = 3,
                    retry_backoff_s: float = 0.0,
                    faults=None) -> dict:
    """Double-buffered streaming driver for the batched engine — the
    streaming executor of the canonical ``SimGraph`` (its device program is
    ``make_batched_sim_fn``'s jit'd vmap over ``SimGraph.run``).

    Pipelined schedule per step b:
      1. host generates + packs batch b            (eager generator ops
                                                    queue behind batch b-1)
      2. ``shard_events`` stages batch b to device (async H2D)
      3. dispatch ``sim(keys, batch_b)``           (async — device now busy)
      4. block on batch b-1's result and report it

    The final batch is padded with zero-depo events so every launch has the
    same static (E, N_max) shape — one trace, no re-jit. Returns aggregate
    stats: events, depos, wall_s, per-batch records, plus a ``health`` dict
    (``repro.core.validate.RunHealth``) of fault-tolerance counters.

    Every step runs under a host span (``jax.profiler.TraceAnnotation``,
    free when no trace is recording), each tagged ``batch=b``:
    ``sim.generate`` (events), ``sim.screen`` (events, quarantined; the
    device-to-host pull of each event inside it is ``sim.fetch``),
    ``sim.pack`` (depos, slots), ``sim.dispatch``, ``sim.wait`` (blocking on
    the batch and its readbacks), ``sim.journal`` and ``sim.callback``.

    Fault tolerance (docs/robustness.md):

    * ``validate=True`` (default) screens every generated event through
      ``check_depos``; invalid events (NaN/negative charge, frame-bound
      violations, oversized) are quarantined into dead-letter records —
      surviving events keep their ids/keys, so their ADCs are bit-identical
      to a clean run. The checks are host-side and read-only: clean-input
      output is bit-identical with validation on or off.
    * ``journal`` names an append-only JSONL batch journal (atomic,
      fsync'd appends); ``resume=True`` skips batches it records as
      complete. Event keys derive from ``fold_in(key, event_id)``, so a
      resumed run reproduces the remaining batches bit-for-bit.
    * OOM-class dispatch failures (``is_oom_error``) retry up to
      ``max_retries`` times, halving the batch's event count each attempt
      (re-padding keeps per-event results bit-identical to the unhalved
      launch); other failures — and an exhausted retry budget — surface a
      structured ``SimBatchError`` naming the batch.
    * an ``on_batch`` callback exception can no longer lose the in-flight
      batch's stats: the batch is recorded first and callback errors become
      warnings.
    * ``faults`` (a ``repro.testing.faults.FaultPlan``) deterministically
      injects corrupt events and dispatch failures so every path above is
      exercised by tests and the CI fault-smoke — None injects nothing.
    """
    if batch_events < 1:
        raise ValueError(f"batch_events must be >= 1, got {batch_events}")
    if num_events < 0:
        raise ValueError(f"num_events must be >= 0, got {num_events}")
    if resume and journal is None:
        raise ValueError("resume=True needs a journal path")
    if sim is None:
        sim = make_streaming_sim_fn(cfg, recon=recon)
    key = jax.random.key(seed)
    num_batches = -(-num_events // batch_events)
    # fixed depo padding across batches -> a single compiled program
    pad_to = pad_to if pad_to is not None else cfg.num_depos
    health = RunHealth()

    jrn = None
    if journal is not None:
        fp = run_fingerprint(cfg, seed=seed, batch_events=batch_events,
                             pad_to=pad_to, num_events=num_events,
                             recon=recon)
        jrn = RunJournal(journal, fingerprint=fp, resume=resume)

    # multi-plane configs stream per-plane pre-drifted events (leading
    # plane axis on every leaf) through the same packed-batch machinery
    gen = (generate_plane_depos if cfg.num_planes > 1 else generate_depos)

    def make_batch(b: int):
        """Generate, (optionally) fault-corrupt, screen, and pad batch b.

        Returns the full padded row list (kept events + zero-depo padding),
        the per-row ids (kept ids keep their original ``fold_in`` keys —
        quarantine never perturbs a surviving event's ADC), and the kept
        count. Padding ids continue the same schedule as before this layer
        existed, so a clean run is bit-identical to the pre-journal code.
        """
        ids = list(range(b * batch_events,
                         min((b + 1) * batch_events, num_events)))
        with TraceAnnotation("sim.generate", batch=b, events=len(ids)):
            events = [gen(jax.random.fold_in(key, ev), cfg) for ev in ids]
        if faults is not None:
            events = [faults.corrupt_event(ev, d)
                      for ev, d in zip(ids, events)]
        if validate:
            with TraceAnnotation("sim.screen", batch=b,
                                 events=len(ids)) as span:
                events, ids, letters = screen_events(
                    events, ids, cfg, pad_to=pad_to, batch=b, health=health)
                span.set_metadata(quarantined=len(letters))
        n_valid = len(ids)
        rows = events + [empty_event(planes=cfg.num_planes)] * (
            batch_events - n_valid)
        row_ids = ids + list(range(
            num_events + b * batch_events,
            num_events + b * batch_events + batch_events - n_valid))
        return rows, row_ids, n_valid

    def launch_rows(b: int, rows, row_ids, n_depos: int):
        """One device launch over the given event rows, holding ``n_depos``
        depos (fresh keys + fresh packed buffers every time, so donation can
        never invalidate a retry's inputs)."""
        if faults is not None:
            faults.before_dispatch(b)
        with TraceAnnotation("sim.pack", batch=b, depos=n_depos,
                             slots=len(rows) * pad_to):
            keys = event_keys(key, row_ids)
            batch = shard_events(pack_events(rows, pad_to=pad_to))
        with TraceAnnotation("sim.dispatch", batch=b):
            return sim(keys, batch)

    def run_degraded(b: int, rows, row_ids, first_exc: BaseException):
        """Bounded retry with graceful degradation: halve the event count
        per OOM-class attempt and launch the sub-batches sequentially.
        Row-wise vmap independence + the fixed ``pad_to`` make the halved
        results bit-identical to the unhalved launch; non-retryable causes
        and an exhausted budget surface a structured ``SimBatchError``."""
        import jax.numpy as jnp

        exc, sub, attempts = first_exc, len(rows), 0
        while True:
            if not is_oom_error(exc):
                raise SimBatchError(b, attempts + 1, sub, exc) from exc
            attempts += 1
            if attempts > max_retries:
                raise SimBatchError(b, attempts, sub, exc) from exc
            health.retries += 1
            if sub > 1:
                sub = -(-sub // 2)
                health.halvings += 1
            if retry_backoff_s:
                time.sleep(retry_backoff_s * attempts)
            try:
                outs = []
                for s in range(0, len(rows), sub):
                    part = rows[s:s + sub]
                    o = launch_rows(b, part, row_ids[s:s + sub],
                                    sum(int(d.n) for d in part))
                    jax.block_until_ready(o.adc)
                    outs.append(o)
                if len(outs) == 1:
                    return outs[0]
                return jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *outs)
            except Exception as e:  # noqa: BLE001 — classified above
                exc = e

    stats = {"events": 0, "depos": 0, "wall_s": 0.0, "batches": []}
    t_start = time.perf_counter()
    inflight = None

    def finish(entry):
        b, rows, row_ids, n_valid, n_depos, t0, out = entry
        with TraceAnnotation("sim.wait", batch=b):
            try:
                jax.block_until_ready(out.adc)
            except Exception as e:  # noqa: BLE001 — run_degraded classifies
                out = run_degraded(b, rows, row_ids, e)
            dt = time.perf_counter() - t0
            # record the batch BEFORE the user callback runs: a callback
            # exception must not lose the batch's stats or journal entry
            health.events_ok += n_valid
            stats["events"] += n_valid
            stats["depos"] += n_depos
            rec = {"batch": b, "events": n_valid, "depos": n_depos,
                   "wall_s": dt}
            if out.finite_ok is not None:
                bad = int(np.count_nonzero(
                    ~np.asarray(out.finite_ok)[:n_valid]))
                rec["nonfinite"] = bad
                health.nonfinite_events += bad
            if recon and out.hits is not None:
                rec["hits"] = int(np.asarray(out.hits.mask[:n_valid]).sum())
        if jrn is not None:
            with TraceAnnotation("sim.journal", batch=b):
                adc = np.ascontiguousarray(np.asarray(out.adc[:n_valid]))
                jrec = dict(rec, ids=[int(i) for i in row_ids[:n_valid]],
                            adc_sha=hashlib.sha256(adc.tobytes()).hexdigest(),
                            quarantined=sum(
                                1 for d in health.dead_letters
                                if d["batch"] == b))
                jrec.pop("wall_s")
                jrn.append_batch(jrec)
        stats["batches"].append(rec)
        if on_batch is not None:
            try:
                with TraceAnnotation("sim.callback", batch=b):
                    on_batch(b, n_valid, n_depos, dt, out)
            except Exception as e:  # noqa: BLE001 — user code, not ours
                health.callback_errors += 1
                warnings.warn(
                    f"on_batch callback failed for batch {b} "
                    f"(stats already recorded): {type(e).__name__}: {e}",
                    RuntimeWarning, stacklevel=2)

    try:
        for b in range(num_batches):
            if jrn is not None and b in jrn.completed:
                done = jrn.completed[b]
                health.resumed += int(done.get("events", 0))
                stats["events"] += int(done.get("events", 0))
                stats["depos"] += int(done.get("depos", 0))
                stats["batches"].append({
                    "batch": b, "events": int(done.get("events", 0)),
                    "depos": int(done.get("depos", 0)), "wall_s": 0.0,
                    "resumed": True})
                continue
            rows, row_ids, n_valid = make_batch(b)  # queues behind b-1
            n_depos = sum(int(d.n) for d in rows[:n_valid])
            t0 = time.perf_counter()
            try:
                try:
                    # async dispatch
                    out = launch_rows(b, rows, row_ids, n_depos)
                except Exception as e:  # noqa: BLE001 — classified below
                    out = run_degraded(b, rows, row_ids, e)
            except SimBatchError:
                # batch b is lost, but b-1 already computed: record it (and
                # journal it) before surfacing the error, so a --resume run
                # only redoes the batch that actually failed
                if inflight is not None:
                    finish(inflight)
                    inflight = None
                raise
            if inflight is not None:
                finish(inflight)                     # block on batch b-1
            inflight = (b, rows, row_ids, n_valid, n_depos, t0, out)
        if inflight is not None:
            finish(inflight)
    finally:
        if jrn is not None:
            jrn.close()
    stats["wall_s"] = time.perf_counter() - t_start
    stats["health"] = health.as_dict()
    return stats


def with_planes(cfg: LArTPCConfig, num_planes: int) -> LArTPCConfig:
    """``cfg`` read out on ``num_planes`` planes (the ``--planes`` flag).

    The flag counts planes of ``num_wires`` wires each; a deployment that
    gives its planes their own wire counts (``concat_planes``) fixes its
    planes itself, and is refused rather than cut to planes it does not
    have."""
    if concat_planes(cfg):
        raise ValueError(
            f"--planes {num_planes}: config {cfg.name!r} gives its planes "
            f"their own wire counts (plane_wires={tuple(cfg.plane_wires)}); "
            "it sets its own planes, so drop --planes")
    return apply_overrides(cfg, {"num_planes": num_planes})


def _run_fig3(cfg: LArTPCConfig, num_events: int, seed: int) -> None:
    """The faithful per-depo host-loop baseline (paper Fig. 3)."""
    resp = make_response(cfg)
    key = jax.random.key(seed)
    for ev in range(num_events):
        k = jax.random.fold_in(key, ev)
        depos = generate_depos(k, cfg)
        t0 = time.perf_counter()
        out = simulate(k, depos, cfg, resp=resp)
        jax.block_until_ready(out.adc)
        dt = time.perf_counter() - t0
        adc = np.asarray(out.adc)
        print(f"event {ev}: {depos.n} depos -> {adc.shape} ADC in "
              f"{dt*1e3:.0f} ms ({depos.n/dt:.3g} depos/s), "
              f"max dev {np.abs(adc - cfg.adc_baseline).max()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="lartpc-uboone",
                    help="registry deployment to simulate, e.g. "
                         "lartpc-uboone3p (MicroBooNE's three planes)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--events", type=int, default=2)
    ap.add_argument("--batch-events", type=int, default=1,
                    help="events per device launch (vmap batch size E)")
    ap.add_argument("--depos", type=int, default=0)
    ap.add_argument("--planes", type=int, default=0,
                    help="readout planes per event (1 = seed single-plane; "
                         "3 = MicroBooNE-like U/V/W triple)")
    ap.add_argument("--pipeline", choices=["fig3", "fig4"], default=None)
    ap.add_argument("--tune", action="store_true",
                    help="autotune kernel strategies for this config/backend "
                         "(cached; repeated runs report a cache hit)")
    ap.add_argument("--retune", action="store_true",
                    help="with --tune: ignore the cache and re-measure")
    ap.add_argument("--strategy", default=None,
                    help="force the scatter-add strategy (see repro.tune; "
                         "'auto' resolves via the tuning cache)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the stream into DIR "
                         "(TensorBoard/Perfetto): device time per stage "
                         "scope and the host's sim.* spans per batch")
    ap.add_argument("--recon", action="store_true",
                    help="append the deconvolve + hit_find recon stages "
                         "and report per-batch hit counts (fig4 only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="append-only JSONL batch journal for this run "
                         "(atomic, fsync'd); enables --resume")
    ap.add_argument("--resume", action="store_true",
                    help="skip batches the --journal records as complete "
                         "(bit-identical continuation; docs/robustness.md)")
    ap.add_argument("--check-finite", action="store_true",
                    help="compile a per-event isfinite sentinel into every "
                         "float stage output (jit-cheap; off by default — "
                         "the default graph is untouched)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip host-side ingest validation / quarantine")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="OOM-class dispatch retries per batch, halving the "
                         "batch's event count each attempt")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic fault schedule, e.g. "
                         "'nan@0,oversize@2,oom@1x2,error@3' "
                         "(repro.testing.faults; exercises quarantine/"
                         "retry/fail-fast paths)")
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()

    if args.resume and not args.journal:
        raise SystemExit("--resume needs --journal PATH")
    enable_compile_cache()

    cfg = get_config(args.config, smoke=args.smoke)
    if args.depos:
        cfg = apply_overrides(cfg, {"num_depos": args.depos})
    if args.planes:
        cfg = with_planes(cfg, args.planes)
    if args.pipeline:
        cfg = apply_overrides(cfg, {"pipeline": args.pipeline})
    if args.check_finite:
        cfg = apply_overrides(cfg, {"check_finite": True})
    if args.set:
        cfg = apply_overrides(cfg, dict(kv.split("=", 1) for kv in args.set))

    if args.tune:
        from repro.tune import resolve_config_with_decisions

        cfg, decisions = resolve_config_with_decisions(
            cfg, tune=True, force=args.retune, tune_explicit=True)
        for d in decisions:
            print(d.describe())
    if args.strategy:
        from repro.tune import strategies

        known = sorted(strategies("scatter_add")) + ["auto"]
        if args.strategy not in known:
            raise SystemExit(f"unknown --strategy {args.strategy!r}; "
                             f"known: {known}")
        cfg = apply_overrides(cfg, {"scatter_strategy": args.strategy})

    faults = None
    if args.inject_faults:
        from repro.testing.faults import FaultPlan

        faults = FaultPlan.parse(args.inject_faults)

    if cfg.pipeline == "fig3":
        if args.recon:
            raise SystemExit("--recon needs the batched fig4 pipeline "
                             "(drop --pipeline fig3)")
        for flag in ("journal", "resume", "inject_faults", "trace_dir"):
            if getattr(args, flag):
                raise SystemExit(f"--{flag.replace('_', '-')} needs the "
                                 "batched fig4 pipeline (drop "
                                 "--pipeline fig3)")
        _run_fig3(cfg, args.events, args.seed)
        return

    def report(b, n_valid, n_depos, dt, out):
        if n_valid == 0:
            print(f"batch {b}: 0 events (all quarantined or padding) in "
                  f"{dt*1e3:.0f} ms")
            return
        adc = np.asarray(out.adc[:n_valid])
        line = (f"batch {b}: {n_valid} events / {n_depos} depos -> "
                f"{out.adc.shape} ADC in {dt*1e3:.0f} ms "
                f"({n_depos/dt:.3g} depos/s), "
                f"max dev {np.abs(adc - cfg.adc_baseline).max()}")
        if out.finite_ok is not None:
            bad = int(np.count_nonzero(~np.asarray(out.finite_ok)[:n_valid]))
            if bad:
                line += f", {bad} NON-FINITE"
        if args.recon:
            stored = int(np.asarray(out.hits.mask[:n_valid]).sum())
            found = int(np.asarray(out.hits.n_hits[:n_valid]).sum())
            line += (f", {stored} hits"
                     + (f" ({found} found)" if found != stored else ""))
        print(line)

    tracing = (jax.profiler.trace(args.trace_dir) if args.trace_dir
               else contextlib.nullcontext())
    try:
        with tracing:
            stats = stream_simulate(cfg, args.events, args.batch_events,
                                    seed=args.seed, on_batch=report,
                                    recon=args.recon, journal=args.journal,
                                    resume=args.resume,
                                    validate=not args.no_validate,
                                    max_retries=args.max_retries,
                                    faults=faults)
    except SimBatchError as e:
        raise SystemExit(
            f"stream failed: {e}" + ("" if not args.journal else
                                     f" — rerun with --resume to continue "
                                     f"from the journal at {args.journal}"))
    if args.trace_dir:
        print(f"trace: {args.trace_dir} (TensorBoard's profile plugin or "
              "Perfetto: stage scopes on the device, sim.* spans on the host)")
    ev_s = stats["events"] / stats["wall_s"]
    dp_s = stats["depos"] / stats["wall_s"]
    print(f"total: {stats['events']} events / {stats['depos']} depos in "
          f"{stats['wall_s']:.2f} s ({ev_s:.3g} events/s, {dp_s:.3g} depos/s)")
    health = stats["health"]
    if any(health[k] for k in ("quarantined", "retries", "halvings",
                               "resumed", "nonfinite_events",
                               "callback_errors")):
        print("health: " + ", ".join(
            f"{k}={v}" for k, v in health.items() if k != "dead_letters"))
        for d in health.get("dead_letters", []):
            print(f"  dead-letter event {d['event']} (batch {d['batch']}): "
                  + "; ".join(d["reasons"]))


if __name__ == "__main__":
    main()
