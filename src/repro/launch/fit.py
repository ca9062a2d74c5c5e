"""Gradient-based detector calibration driver (docs/calibration.md):

    python -m repro.launch.fit --smoke            # CI fit gate: recover 2
                                                  # seeded params to <5%
    python -m repro.launch.fit --gradcheck        # per-stage FD gradchecks
    python -m repro.launch.fit --grad-smoke --devices 2
                                                  # distributed-grad check
    python -m repro.launch.fit --params electron_lifetime_us,recombination \
                               [--steps N] [--lr LR] [--optimizer adam|bfgs] \
                               [--events E] [--perturb F] [--set key=value ...]

Self-calibration throughout: targets are ADC waveforms generated by the
DEFAULT (bit-exact, int16) graph at the config's true physics; the fit
starts each free parameter away from truth (``--perturb``, or the explicit
smoke inits) and descends the differentiable graph's loss — same per-event
RNG keys as the target run, so the loss is exactly zero at truth and the
minimizer recovers parameters rather than fitting noise.

``--grad-smoke`` checks the loss gradient computed with the event batch
sharded over ``--devices`` forced host devices against the single-device
gradient (XLA inserts the cross-device reductions; the numbers must agree).
Exit status is the gate: 0 on success, 1 on any tolerance violation.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

# NOTE: no jax / repro.core imports at module scope — --grad-smoke must set
# XLA_FLAGS (forced host device count) before jax first loads. Everything
# jax-touching imports inside the run_* functions.
from repro.cache import enable_compile_cache
from repro.config import apply_overrides, get_config

#: the --smoke scenario: seeded truth, deliberately-wrong starting points
SMOKE_TRUTH = {"electron_lifetime_us": 60.0, "recombination": 0.75}
SMOKE_INITS = {"electron_lifetime_us": 150.0, "recombination": 0.5}
SMOKE_BOUNDS = {"electron_lifetime_us": (5.0, 500.0),
                "recombination": (0.2, 1.0)}


def _smoke_cfg(args):
    """The smoke truth config: seeded physics on the smoke grid, with
    deposits large enough that the ADC imprint of a few-percent parameter
    change clears quantization (see docs/calibration.md)."""
    cfg = get_config("lartpc-uboone", smoke=True)
    cfg = dataclasses.replace(cfg, electrons_per_depo=150_000.0,
                              **SMOKE_TRUTH)
    if args.set:
        cfg = apply_overrides(cfg, dict(kv.split("=", 1) for kv in args.set))
    return cfg


def _print_step(step, loss, values):
    vals = " ".join(f"{k}={v:.5g}" for k, v in values.items())
    print(f"  step {step:4d}  loss {loss:.6g}  {vals}", flush=True)


def run_smoke(args) -> int:
    """The CI fit gate: recover the seeded smoke parameters to < tol."""
    import jax

    from repro.core.fit import (FitParam, FitSpec, calibrate,
                                make_fit_targets)

    cfg = _smoke_cfg(args)
    t0 = time.time()
    targets = make_fit_targets(cfg, jax.random.key(args.seed),
                               num_events=args.events)
    spec = FitSpec(params=tuple(
        FitParam(name, init=SMOKE_INITS[name], lo=lo, hi=hi)
        for name, (lo, hi) in SMOKE_BOUNDS.items()))
    print(f"fit-smoke: {spec.n} free params over {args.events} events "
          f"({targets.batch.max_depos} depos each), optimizer "
          f"{args.optimizer}, {args.steps} steps")
    res = calibrate(cfg, spec, targets, steps=args.steps, lr=args.lr,
                    optimizer=args.optimizer,
                    log_every=args.log_every, callback=_print_step)
    errs = res.relative_errors(SMOKE_TRUTH)
    ok = True
    for name, err in errs.items():
        line = (f"  {name:<22s} truth {SMOKE_TRUTH[name]:<10.5g} "
                f"fit {res.values[name]:<10.5g} rel_err {err:.3%}")
        if err > args.tol:
            line += f"  EXCEEDS tol {args.tol:.0%}"
            ok = False
        print(line)
    print(f"fit-smoke: loss {res.loss:.6g} after {res.steps} steps, "
          f"{time.time() - t0:.1f} s -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def run_gradcheck(args) -> int:
    """Per-stage finite-difference gradient checks (the CI gradcheck gate)."""
    from repro.core.gradcheck import stage_gradcheck_suite

    t0 = time.time()
    results = stage_gradcheck_suite(seed=args.seed)
    for r in results:
        print(r)
    n_fail = sum(not r.ok for r in results)
    print(f"gradcheck: {len(results) - n_fail}/{len(results)} ok, "
          f"{time.time() - t0:.1f} s")
    return 0 if n_fail == 0 else 1


def run_grad_smoke(args) -> int:
    """Distributed-gradient smoke: the fit-loss gradient with the event
    batch sharded across forced host devices must match the single-device
    gradient (same math, XLA-inserted reductions)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.fit import (FitParam, FitSpec, fit_config,
                                make_fit_targets)
    from repro.core.stages import build_sim_graph

    n_dev = len(jax.devices())
    if n_dev != args.devices:
        print(f"grad-smoke: expected {args.devices} devices, jax sees "
              f"{n_dev} — XLA_FLAGS not applied before jax import?")
        return 1
    cfg = _smoke_cfg(args)
    num_events = max(args.events, n_dev)
    num_events -= num_events % n_dev  # event axis must split evenly
    targets = make_fit_targets(cfg, jax.random.key(args.seed),
                               num_events=num_events)
    spec = FitSpec(params=(FitParam("electron_lifetime_us"),
                           FitParam("recombination")))
    fcfg = fit_config(cfg)
    target_adc = targets.adc.astype(jnp.float32)

    # batch/keys/target as ARGUMENTS (not closure constants) so the same
    # traced loss accepts both replicated and event-sharded operands
    def loss(theta, keys, depos, target):
        graph = build_sim_graph(spec.apply(fcfg, theta), None)
        out = graph.run_events(keys, depos)
        return jnp.mean((out.adc - target) ** 2)

    grad_fn = jax.jit(jax.grad(loss))
    theta0 = jnp.asarray([50.0, 0.6], jnp.float32)
    depos = targets.batch.physical_set()
    g_single = grad_fn(theta0, targets.keys, depos, target_adc)

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n_dev,), ("events",))
    ev_sharded = NamedSharding(mesh, P("events"))

    def shard(x):
        return jax.device_put(x, ev_sharded)

    g_dist = grad_fn(theta0, shard(targets.keys),
                     jax.tree.map(shard, depos), shard(target_adc))
    diff = float(jnp.max(jnp.abs(g_single - g_dist)))
    scale = float(jnp.max(jnp.abs(g_single))) + 1e-12
    rel = diff / scale
    print(f"grad-smoke: {num_events} events over {n_dev} devices")
    print(f"  single-device grad {[float(x) for x in g_single]}")
    print(f"  sharded grad       {[float(x) for x in g_dist]}")
    print(f"  max abs diff {diff:.3e} (rel {rel:.3e})")
    ok = rel < 1e-3
    print(f"grad-smoke: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def run_fit(args) -> int:
    """General self-calibration: fit --params of the current config from
    inits perturbed by --perturb."""
    import jax

    from repro.core.fit import (FitParam, FitSpec, calibrate,
                                make_fit_targets)

    cfg = get_config("lartpc-uboone", smoke=True)
    if args.set:
        cfg = apply_overrides(cfg, dict(kv.split("=", 1) for kv in args.set))
    names = [n for n in args.params.split(",") if n]
    truth = {n: float(getattr(cfg, n)) for n in names}
    params = []
    for n in names:
        v = truth[n]
        if v <= 0:
            raise SystemExit(
                f"--params {n}: current value {v} is not positive; seed a "
                f"truth with --set {n}=<value> to make it fittable")
        params.append(FitParam(n, init=v * args.perturb, lo=v / 8.0,
                               hi=v * 8.0))
    spec = FitSpec(params=tuple(params))
    targets = make_fit_targets(cfg, jax.random.key(args.seed),
                               num_events=args.events)
    print(f"fit: {names} from {args.perturb}x truth over "
          f"{args.events} events")
    res = calibrate(cfg, spec, targets, steps=args.steps, lr=args.lr,
                    optimizer=args.optimizer, log_every=args.log_every,
                    callback=_print_step)
    ok = True
    for name, err in res.relative_errors(truth).items():
        print(f"  {name:<22s} truth {truth[name]:<10.5g} "
              f"fit {res.values[name]:<10.5g} rel_err {err:.3%}")
        ok = ok and err <= args.tol
    print(f"fit: loss {res.loss:.6g} -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="gradient-based detector calibration")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: recover the seeded smoke parameters")
    ap.add_argument("--gradcheck", action="store_true",
                    help="run the per-stage FD gradient checks")
    ap.add_argument("--grad-smoke", action="store_true",
                    help="distributed-vs-single-device gradient agreement")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced host device count for --grad-smoke")
    ap.add_argument("--params", default="electron_lifetime_us,recombination",
                    help="comma-separated config fields to fit")
    ap.add_argument("--events", type=int, default=2)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--optimizer", choices=["adam", "bfgs"], default="adam")
    ap.add_argument("--perturb", type=float, default=1.5,
                    help="start each param at perturb x truth")
    ap.add_argument("--tol", type=float, default=0.05,
                    help="max relative recovery error")
    ap.add_argument("--log-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--set", nargs="*", default=[],
                    help="config overrides key=value (dot paths ok)")
    args = ap.parse_args(argv)

    if args.grad_smoke:
        # must happen before ANY jax import in this process
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    enable_compile_cache()
    if args.grad_smoke:
        return run_grad_smoke(args)
    if args.gradcheck:
        return run_gradcheck(args)
    if args.smoke:
        return run_smoke(args)
    return run_fit(args)


if __name__ == "__main__":
    sys.exit(main())
