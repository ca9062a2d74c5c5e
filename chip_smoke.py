"""Bring-up check: the streaming LArTPC simulator on a TPU, at full width.

    python chip_smoke.py              # phases a-e on one chip
    python chip_smoke.py --chips 4    # the multi-chip phase alone, 4 chips

Drives the ``lartpc-uboone`` full deployment (one 2,560-wire plane x 9,592
ticks, 100k depos per event; three such planes for the multi-plane phase)
through the entry points a user calls, and checks what comes out:

  a. device  : JAX must see a TPU; nothing runs on the CPU in its place.
  b. stream  : ``stream_simulate`` on one plane, 4 events in batches of 2,
               donation on; health counters clean, every event carries
               signal, and 2 events equal the per-event ``make_sim_fn``
               loop to within 1 ADC count.
  c. recon   : the same with 3 planes and ``recon=True``, batch 1, 2
               events; hits on every plane, ``n_hits`` equal to the stored
               count unless truncation is reported.
  d. kernels : every Pallas kernel the main path can select, compiled for
               the chip (``interpret=False``) on a real full-width event and
               compared with its XLA counterpart; kernels the TPU compiler
               refuses are listed as excluded.
  e. backend : the smoke config on the host CPU and on the TPU, same depos
               and key, within 1 ADC count.

``--chips 4`` runs only the multi-chip phase: the event-sharded streaming
executor over a 4-device mesh against a one-device run, and the distributed
3-plane executor against a single-device reference.

Runs in one process and starts no other. Each phase prints its own lines
(device, shapes, compile seconds, seconds per batch, peak device bytes);
the last line of standard output is the JSON object
``{"ok": true, "device": {...}}``, printed only when every check passed —
a failed check exits non-zero before it. JAX's persistent compilation
cache is ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``,
so a second run on the same machine prints lower compile seconds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
#: largest ADC difference tolerated between two executions of the same
#: physics that may round floats differently (summation order, backend)
ADC_TOL = 1
#: signal must rise this far above the noise (in units of its rms)
SIGNAL_OVER_NOISE = 10.0


def check(cond, msg: str) -> None:
    """Exit non-zero (before the final JSON line) when ``cond`` is false."""
    if not cond:
        raise SystemExit(f"FAILED: {msg}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def adc_diff(a: np.ndarray, b: np.ndarray):
    """(bitwise equal, max |a - b| in counts, share of pixels that differ)."""
    d = np.abs(a.astype(np.int32) - b.astype(np.int32))
    return bool(np.array_equal(a, b)), int(d.max()), float((d > 0).mean())


def explicit(cfg, **over):
    """``cfg`` with every strategy named (no ``"auto"`` left to resolve)."""
    return dataclasses.replace(
        cfg, charge_grid_strategy="unfused", scatter_strategy="xla",
        fft_strategy="rfft2", deconv_strategy="rfft2", hitfind_strategy="scan",
        drift_strategy="jnp", check_finite=True, **over)


def batch_specs(cfg, events: int):
    """Shapes of one packed streaming batch: (keys, EventBatch)."""
    import jax
    import jax.numpy as jnp

    from repro.core.batch import EventBatch

    lead = (events,) if cfg.num_planes == 1 else (events, cfg.num_planes)
    f = jax.ShapeDtypeStruct(lead + (cfg.num_depos,), jnp.float32)
    return (jax.ShapeDtypeStruct((events,), jax.random.key(0).dtype),
            EventBatch(wire=f, tick=f, sigma_w=f, sigma_t=f, charge=f,
                       n_depos=jax.ShapeDtypeStruct((events,), jnp.int32)))


def compile_stream(phase: str, cfg, events: int, recon: bool):
    """Compile the streaming program ahead of the stream and report it."""
    from repro.launch.sim import make_streaming_sim_fn, stream_donation

    check(stream_donation(), "streaming donation is off on this backend")
    sim = make_streaming_sim_fn(cfg, recon=recon)
    t0 = time.perf_counter()
    compiled = sim.lower(*batch_specs(cfg, events)).compile()
    dt = time.perf_counter() - t0
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    say(phase, f"compile {dt:.2f} s; program bytes: arguments "
        f"{m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
        f"temp {m.temp_size_in_bytes} total {total}")
    return compiled


def run_stream(phase: str, cfg, sim, num_events: int, batch_events: int,
               seed: int, recon: bool, keep=None):
    """``stream_simulate`` with no retries; returns host ADCs per event."""
    from repro.launch.sim import stream_simulate

    adcs, hits = {}, []

    def on_batch(b, n_valid, n_depos, dt, out):
        adc = np.asarray(out.adc[:n_valid])
        for i in range(n_valid):
            adcs[b * batch_events + i] = adc[i]
        if recon:
            hits.append((np.asarray(out.hits.mask[:n_valid]).sum(-1),
                         np.asarray(out.hits.n_hits[:n_valid])))
        if keep is not None:
            keep(b, out)
        say(phase, f"batch {b}: {n_valid} events / {n_depos} depos -> ADC "
            f"{tuple(out.adc.shape)} {out.adc.dtype} in {dt:.4f} s")

    stats = stream_simulate(cfg, num_events, batch_events, seed=seed,
                            sim=sim, validate=True, max_retries=0,
                            recon=recon, on_batch=on_batch)
    health = stats["health"]
    walls = [r["wall_s"] for r in stats["batches"]]
    say(phase, f"{stats['events']} events in {stats['wall_s']:.3f} s; "
        f"seconds per batch {walls}; steady (after the first) "
        f"{np.median(walls[1:]) if len(walls) > 1 else walls[0]:.4f}; "
        f"peak_bytes_in_use {peak_bytes()}")
    say(phase, "health: " + ", ".join(
        f"{k}={v}" for k, v in health.items() if k != "dead_letters"))
    for k in ("quarantined", "retries", "halvings", "nonfinite_events",
              "callback_errors"):
        check(health[k] == 0, f"{phase}: health {k}={health[k]}")
    check(stats["events"] == num_events == len(adcs),
          f"{phase}: {stats['events']} of {num_events} events came back")
    floor = SIGNAL_OVER_NOISE * cfg.noise_rms_adc
    for ev, adc in sorted(adcs.items()):
        dev = float(np.abs(adc.astype(np.float32) - cfg.adc_baseline).max())
        say(phase, f"event {ev}: max deviation above baseline {dev:.0f} "
            f"counts")
        check(dev > floor, f"{phase}: event {ev} shows no signal "
              f"({dev} <= {floor} counts)")
    return adcs, hits


def phase_stream(cfg, num_events: int = 4, batch_events: int = 2,
                 loop_events: int = 2, seed: int = 0):
    """b. One plane through the streaming executor, against the loop."""
    import jax

    from repro.core.batch import pad_depos
    from repro.core.depo import generate_depos
    from repro.core.pipeline import make_sim_fn

    say("b", f"stream_simulate: {num_events} events, batch {batch_events}, "
        f"{cfg.num_planes} plane x {cfg.num_wires} wires x {cfg.num_ticks} "
        f"ticks, {cfg.num_depos} depos/event")
    compiled = compile_stream("b", cfg, batch_events, recon=False)
    adcs, _ = run_stream("b", cfg, compiled, num_events, batch_events, seed,
                         recon=False)
    sim = make_sim_fn(cfg)
    key = jax.random.key(seed)
    for ev in range(loop_events):
        k = jax.random.fold_in(key, ev)
        depos = pad_depos(generate_depos(k, cfg), cfg.num_depos)
        t0 = time.perf_counter()
        ref = np.asarray(sim(k, depos).adc)
        same, dmax, frac = adc_diff(adcs[ev], ref)
        say("b", f"event {ev}: batched vs per-event make_sim_fn loop: "
            f"bitwise_equal={same} max_diff={dmax} differing_share={frac} "
            f"(loop call {time.perf_counter() - t0:.3f} s)")
        check(dmax <= ADC_TOL, f"b: event {ev} batched vs loop differ by "
              f"{dmax} > {ADC_TOL} ADC counts")


def phase_recon(cfg, num_events: int = 2, seed: int = 0):
    """c. Three planes with recon; returns event 0's deconvolved grids."""
    say("c", f"stream_simulate recon: {num_events} events, batch 1, "
        f"{cfg.num_planes} planes x {cfg.num_wires} wires x "
        f"{cfg.num_ticks} ticks, {cfg.num_depos} depos/event")
    compiled = compile_stream("c", cfg, 1, recon=True)
    decon = {}

    def keep(b, out):
        if b == 0:
            decon["grid"] = out.decon[0]

    _, hits = run_stream("c", cfg, compiled, num_events, 1, seed,
                         recon=True, keep=keep)
    for ev, (stored, found) in enumerate(hits):
        stored, found = stored[0].tolist(), found[0].tolist()
        trunc = [f > s for s, f in zip(stored, found)]
        say("c", f"event {ev}: hits stored per plane {stored}, found "
            f"{found}, truncated {trunc}")
        check(all(s > 0 for s in stored), f"c: event {ev} has a plane "
              "without hits")
        check(all(s == f or t for s, f, t in zip(stored, found, trunc)),
              f"c: event {ev} n_hits disagrees with the stored count")
    return decon["grid"]


def phase_kernels(cfg, decon):
    """d. Pallas kernels compiled for the chip against their XLA twins."""
    import jax

    from repro.core.hitfind import hit_find_scan
    from repro.core.pipeline import FUSED_TPU_REFUSAL
    from repro.core.scatter import SCATTER_TPU_REFUSAL
    from repro.kernels import default_interpret
    from repro.kernels.hitfind.ops import find_wire_hits_pallas
    from repro.tune import autotune, registry

    check(default_interpret() is False,
          "d: Pallas kernels would run interpreted on this backend")
    scan = jax.jit(lambda d: hit_find_scan(d, cfg))
    kern = jax.jit(lambda d: find_wire_hits_pallas(
        d, threshold=float(cfg.hit_threshold),
        cap=int(cfg.max_hits_per_wire), interpret=False))
    t0 = time.perf_counter()
    compiled = kern.lower(decon[0]).compile()
    say("d", f"hit_find pallas: compile {time.perf_counter() - t0:.2f} s, "
        f"input {tuple(decon[0].shape)} {decon[0].dtype}")
    check("tpu_custom_call" in compiled.as_text(),
          "d: hit_find pallas compiled without a tpu_custom_call")
    names = ("counts", "charge", "tick", "peak")
    for p in range(decon.shape[0]):
        got, ref = compiled(decon[p]), scan(decon[p])
        same = {n: bool(np.array_equal(np.asarray(g), np.asarray(r)))
                for n, g, r in zip(names, got, ref)}
        say("d", f"hit_find plane {p}: pallas vs scan bitwise {same}, "
            f"runs found {int(np.asarray(ref[0]).sum())}")
        check(all(same.values()), f"d: hit_find pallas differs from scan "
              f"on plane {p}: {same}")
    # kernels the TPU compiler refuses must not be selectable here
    for op, refusal in (("scatter_add", SCATTER_TPU_REFUSAL),
                        ("charge_grid", FUSED_TPU_REFUSAL)):
        ctx = registry.make_context(cfg, autotune.op_shape(op, cfg))
        live = set(registry.available_strategies(op, ctx))
        for name in sorted(registry.strategies(op)):
            if "pallas" in name:
                check(name not in live, f"d: {op}/{name} is selectable on "
                      "this backend, but the TPU compiler refuses it")
                say("d", f"{op} {name}: excluded on TPU ({refusal})")
    say("d", f"peak_bytes_in_use {peak_bytes()}")


def phase_backend(cfg, seed: int = 0):
    """e. The same event on the host CPU and on the TPU."""
    import jax

    from repro.core.depo import generate_depos
    from repro.core.pipeline import make_sim_fn

    cpu, acc = jax.devices("cpu")[0], jax.devices()[0]
    key = jax.device_put(jax.random.key(seed), cpu)
    depos = generate_depos(key, cfg)  # drawn once, fed to both backends
    sim = make_sim_fn(cfg)
    out = {}
    for dev in (cpu, acc):
        t0 = time.perf_counter()
        out[dev.platform] = np.asarray(
            sim(jax.device_put(key, dev), jax.device_put(depos, dev)).adc)
        say("e", f"{dev.platform}: ADC {out[dev.platform].shape} in "
            f"{time.perf_counter() - t0:.2f} s (compile included)")
    same, dmax, frac = adc_diff(out["cpu"], out[acc.platform])
    say("e", f"{cfg.num_wires} wires x {cfg.num_ticks} ticks, "
        f"{cfg.num_depos} depos: cpu vs {acc.platform} bitwise_equal={same} "
        f"max_diff={dmax} differing_share={frac}")
    check(dmax <= ADC_TOL, f"e: cpu and {acc.platform} differ by {dmax} > "
          f"{ADC_TOL} ADC counts")


def phase_multichip(cfg, n_dev: int, num_events: int = 8,
                    batch_events: int = 4, seed: int = 0):
    """The event-sharded stream and the distributed executor on n_dev
    devices, each against a one-device run of the same events."""
    import jax
    import jax.numpy as jnp

    from repro.core.depo import generate_physical_depos
    from repro.core.distributed import (make_distributed_sim,
                                        padded_grid_shape, shard_depos)
    from repro.core.drift import transport_planes
    from repro.core.fft_conv import digitize
    from repro.core.rasterize import rasterize
    from repro.core.response import make_distributed_plane_responses
    from repro.core.scatter import scatter_xla
    from repro.launch.mesh import make_mesh
    from repro.launch.sim import make_streaming_sim_fn
    from repro.parallel.sharding import use_mesh

    devices = jax.devices()[:n_dev]
    say("m", f"event-sharded stream: {num_events} events, batch "
        f"{batch_events}, over {n_dev} devices")
    spread = []

    def keep(b, out):
        shard_devs = {s.device for s in out.adc.addressable_shards}
        shape = out.adc.sharding.shard_shape(out.adc.shape)
        spread.append(len(shard_devs))
        say("m", f"batch {b}: ADC {tuple(out.adc.shape)} over "
            f"{len(shard_devs)} devices, {shape} per device")

    with use_mesh(make_mesh((n_dev,), ("data",), devices=devices)):
        sharded, _ = run_stream("m", cfg, make_streaming_sim_fn(cfg),
                                num_events, batch_events, seed, recon=False,
                                keep=keep)
    check(spread and all(s == n_dev for s in spread),
          f"m: event-sharded outputs spread over {spread} devices, not "
          f"{n_dev}")
    say("m", f"one-device stream: {num_events} events, batch 1")
    single, _ = run_stream("m1", cfg, make_streaming_sim_fn(cfg),
                           num_events, 1, seed, recon=False)
    for ev in range(num_events):
        same, dmax, frac = adc_diff(sharded[ev], single[ev])
        say("m", f"event {ev}: sharded vs one device bitwise_equal={same} "
            f"max_diff={dmax}")
        check(dmax <= ADC_TOL, f"m: event {ev} sharded vs one device "
              f"differ by {dmax} > {ADC_TOL} ADC counts")

    # distributed executor: wires sharded, reduce-scatter + pencil FFT.
    # Its tests hold it to the cyclic single-device reference (same padded
    # grid, rfft2 multiply) with fluctuation and noise off.
    cfg3 = dataclasses.replace(cfg, num_planes=3, plane_batching="stacked",
                               fluctuate=False, check_finite=False)
    shape = (n_dev // 2, 2) if n_dev % 2 == 0 else (n_dev, 1)
    mesh = make_mesh(shape, ("data", "model"), devices=devices)
    w_pad, _, _ = padded_grid_shape(cfg3, n_dev)
    resp3 = make_distributed_plane_responses(cfg3, w_pad)
    key = jax.random.key(seed)
    pdepos = generate_physical_depos(key, cfg3)
    say("m", f"distributed: {cfg3.num_planes} planes x {w_pad} wires x "
        f"{cfg3.num_ticks} ticks, {cfg3.num_depos} depos, mesh "
        f"{dict(mesh.shape)}")
    sim = make_distributed_sim(mesh, cfg3, resp3, add_noise=False)
    t0 = time.perf_counter()
    out = sim(key, shard_depos(pdepos, mesh))
    jax.block_until_ready(out)
    say("m", f"distributed: ADC {tuple(out.shape)} over "
        f"{len(out.sharding.device_set)} devices in "
        f"{time.perf_counter() - t0:.2f} s (compile included)")
    check(len(out.sharding.device_set) == n_dev,
          "m: distributed output is not spread over every device")
    adc = np.asarray(out)[:, :cfg3.num_wires]

    @jax.jit
    def reference(pd):
        ddepos = transport_planes(pd, cfg3)
        planes = []
        for p in range(cfg3.num_planes):
            dp = jax.tree.map(lambda x: x[p], ddepos)
            grid = scatter_xla(*rasterize(dp, cfg3), cfg3)
            gpad = jnp.zeros((w_pad, cfg3.num_ticks)).at[
                :cfg3.num_wires].set(grid)
            sig = jnp.fft.irfft2(jnp.fft.rfft2(gpad) * resp3[p].freq,
                                 s=(w_pad, cfg3.num_ticks))
            planes.append(digitize(sig[:cfg3.num_wires], cfg3))
        return jnp.stack(planes)

    ref = np.asarray(reference(jax.device_put(pdepos, devices[0])))
    for p in range(cfg3.num_planes):
        same, dmax, frac = adc_diff(adc[p], ref[p])
        say("m", f"distributed plane {p} vs single-device reference: "
            f"bitwise_equal={same} max_diff={dmax} exact_share={1 - frac}")
        check(dmax <= ADC_TOL and 1 - frac > 0.999,
              f"m: distributed plane {p} differs from the reference "
              f"(max {dmax}, exact share {1 - frac})")
    say("m", f"peak_bytes_in_use {peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-e; 4: the multi-chip phase only")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    say("a", f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    check(dev.platform == "tpu", "a: JAX finds no TPU; this check never "
          "runs on the CPU in its place")
    check(len(devs) >= args.chips, f"a: {args.chips} chips asked for, "
          f"{len(devs)} present")

    sys.path.insert(0, str(ROOT / "src"))
    # nothing outside the checkout may decide what is compiled
    os.environ["REPRO_TUNE_CACHE"] = str(ROOT / ".repro_tune" /
                                         "tune_cache.json")
    from repro.cache import enable_compile_cache
    from repro.config import get_config

    say("a", f"compile cache {enable_compile_cache()}")
    full = explicit(get_config("lartpc-uboone"))
    if args.chips > 1:
        phase_multichip(full, args.chips)
    else:
        phase_stream(full)
        decon = phase_recon(dataclasses.replace(
            full, num_planes=3, plane_batching="stacked"))
        phase_kernels(full, decon)
        phase_backend(explicit(get_config("lartpc-uboone", smoke=True)))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
