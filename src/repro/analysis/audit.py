"""Compiled-program contract auditor: the static-analysis gate for every
production entry point of the stage graph.

The paper's portability lesson is that program-level properties — kernel
fusion, memory traffic, host<->device movement — decide whether a port is
fast, and that they silently regress when code is retargeted. This module
pins them the way ADC SHA goldens pin numerics: every production executor is
traced and compiled (on fake devices, CPU backend), a *contract* is
extracted from the compiled text via ``repro.analysis.hlo``, and the result
is diffed against the committed ``AUDIT_contracts.json`` baseline.

Per-program contract fields:

  collectives       : instruction count per collective kind (nonzero only)
  dtypes            : every dtype appearing in the program (f64 = hard fail)
  scatter_dtypes    : scatter-accumulation output dtypes (bf16/f16 = fail)
  donated_args      : donation requested at the jit boundary
  realized_aliases  : input->output aliases the executable established
  host_calls        : host round-trips compiled into the program (must be 0)
  recompiles        : jit-cache misses beyond the first same-shape call

Hard policy (baseline-independent): no f64, no host calls, no bf16/f16
scatter accumulation, no recompiles, and no collective kinds outside what
the program's data-movement strategy declares (``repro.tune`` strategy
metadata for single-device programs; ``SCATTER_REDUCTION_COLLECTIVES`` for
the distributed executor). Everything else — counts drifting, donation
vanishing, a new dtype appearing — fails only against the baseline, and
``--update`` refreshes it when the change is intentional.

Usage (the CI ``audit`` job):

    PYTHONPATH=src python -m repro.analysis.audit --check            # gate
    PYTHONPATH=src python -m repro.analysis.audit --update           # re-pin
    PYTHONPATH=src python -m repro.analysis.audit --check --json out.json

``--inject`` seeds a deliberate regression (f64 cast, disabled donation,
host callback, per-plane collective chains) so the gate's failure mode is
itself testable — the fault-injection pattern of ``repro.testing.faults``.

jax is imported lazily: ``main`` forces the fake-device count and the CPU
backend *before* the first jax import, exactly like ``launch/fit.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import json
import os
import sys
import warnings
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis import hlo

#: default committed baseline, at the repo root next to BENCH_*.json
DEFAULT_BASELINE = "AUDIT_contracts.json"
SCHEMA_VERSION = 1

#: seeded-regression modes (see ``--inject``): each perturbs exactly the
#: property the auditor claims to pin, so tests can prove the gate trips
INJECT_MODES = ("f64_noise", "x64", "no_donate", "host_callback",
                "extra_collective")

#: collective kinds each distributed scatter-reduction strategy is allowed
#: to emit (the pencil FFT's all-to-all chain rides along in both).
#: ``psum_scatter`` reduces partial grids with one reduce-scatter per mesh
#: axis; ``halo`` psums strips over the non-halo axes (all-reduce) and ring-
#: exchanges margins (collective-permute).
SCATTER_REDUCTION_COLLECTIVES = {
    "psum_scatter": ("reduce-scatter", "all-to-all", "all-reduce"),
    "halo": ("all-reduce", "collective-permute", "all-to-all"),
}


@dataclasses.dataclass(frozen=True)
class AuditContext:
    """Everything a program builder needs."""

    cfg: object               # the pinned audit LArTPCConfig
    planes: int
    devices: int
    inject: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class AuditProgram:
    """One production entry point the auditor compiles.

    build   : ``ctx -> (jitfn, make_args)`` — ``make_args(i)`` builds FRESH
              operands for call ``i`` (the recompile detector re-invokes).
    planes  : plane counts this program is audited at.
    needs_devices : minimum device count (distributed programs).
    collective_source : which data-movement strategy bounds the allowed
              collective kinds — "none" means the single-device policy
              (only kinds declared by ``repro.tune`` strategy metadata).
    """

    name: str
    build: Callable[[AuditContext], Tuple[object, Callable[[int], tuple]]]
    planes: Tuple[int, ...] = (1, 3)
    needs_devices: int = 1
    collective_source: str = "none"


def audit_config(planes: int = 1):
    """The pinned audit workload: the smoke config with every ``"auto"``
    strategy field made explicit, so contracts cannot drift with the
    on-disk tuning cache (the audit is hermetic by construction)."""
    import dataclasses as dc

    from repro.config import get_config

    cfg = get_config("lartpc-uboone", smoke=True)
    repl = {"hitfind_strategy": "scan"}
    if planes > 1:
        repl["num_planes"] = planes
    return dc.replace(cfg, **repl)


# ---------------------------------------------------------------------------
# Program builders (jax imported lazily inside each)
# ---------------------------------------------------------------------------


def _x64_trace(ctx: AuditContext) -> bool:
    return ctx.inject in ("x64", "f64_noise")


def _fold_key(i: int):
    import jax

    return jax.random.fold_in(jax.random.key(0), i)


def _single_graph(ctx: AuditContext, recon: bool = False):
    """The single-event graph, with the seeded host-callback / f64-cast
    regressions spliced into the noise stage when injected."""
    import jax
    import jax.numpy as jnp

    from repro.core.stages import build_sim_graph

    graph = build_sim_graph(ctx.cfg, None, recon=recon)
    orig = graph.stage("noise").fn
    if ctx.inject == "host_callback" and not recon:

        def host_noise(state):
            state = orig(state)
            sig = jax.pure_callback(
                lambda x: x,
                jax.ShapeDtypeStruct(state.signal.shape, state.signal.dtype),
                state.signal)
            return state._replace(signal=sig)

        graph = graph.replace(noise=host_noise)
    if ctx.inject == "f64_noise" and not recon:

        def f64_noise(state):
            state = orig(state)
            # a genuine f64 compute step (the *1+eps blocks XLA from
            # eliding the convert pair); requires x64 tracing to survive.
            # repro-lint suppressions: this injection exists to PROVE the
            # auditor catches exactly this.
            sig = (state.signal.astype(jnp.float64)  # repro-lint: disable=f64-literal
                   * jnp.float64(1.0 + 1e-12)).astype(jnp.float32)  # repro-lint: disable=f64-literal
            return state._replace(signal=sig)

        graph = graph.replace(noise=f64_noise)
    return graph


def _build_single(ctx: AuditContext):
    import jax

    from repro.core.depo import generate_physical_depos

    fn = jax.jit(_single_graph(ctx).run)

    def make_args(i):
        key = _fold_key(i)
        return key, generate_physical_depos(key, ctx.cfg)

    return fn, make_args


def _build_recon(ctx: AuditContext):
    import jax

    from repro.core.depo import generate_physical_depos

    fn = jax.jit(_single_graph(ctx, recon=True).run)

    def make_args(i):
        key = _fold_key(i)
        return key, generate_physical_depos(key, ctx.cfg)

    return fn, make_args


def _batch_args(ctx: AuditContext, i: int, events: int = 2):
    import jax

    from repro.core.batch import event_keys, pack_events
    from repro.core.depo import generate_depos, generate_plane_depos

    gen = generate_plane_depos if ctx.planes > 1 else generate_depos
    key = _fold_key(i)
    evs = [gen(jax.random.fold_in(key, e), ctx.cfg) for e in range(events)]
    return event_keys(key, range(events)), pack_events(evs)


def _build_batched(ctx: AuditContext):
    from repro.core.batch import make_batched_sim_fn

    return make_batched_sim_fn(ctx.cfg), lambda i: _batch_args(ctx, i)


def _build_streaming(ctx: AuditContext):
    """The device program ``stream_simulate`` drives, with the donation the
    streaming policy requests on accelerators — the request is captured at
    the jit boundary, so it is auditable even on CPU where XLA never
    realizes an alias for these shapes."""
    from repro.launch.sim import make_streaming_sim_fn, stream_donation

    donate = False if ctx.inject == "no_donate" else stream_donation("tpu")
    return (make_streaming_sim_fn(ctx.cfg, donate=donate),
            lambda i: _batch_args(ctx, i))


def _dist_setup(ctx: AuditContext, shape: Optional[Tuple[int, int]] = None):
    from repro.core.distributed import padded_grid_shape
    from repro.core.response import (make_distributed_plane_responses,
                                     make_distributed_response)
    from repro.launch.mesh import make_mesh

    n_dev = ctx.devices
    if shape is None:  # the examples/sim_distributed.py convention
        shape = (n_dev // 2, 2) if n_dev % 2 == 0 else (n_dev, 1)
    mesh = make_mesh(shape, ("data", "model"))
    w_pad, _, _ = padded_grid_shape(ctx.cfg, n_dev)
    resp = (make_distributed_plane_responses(ctx.cfg, w_pad)
            if ctx.planes > 1 else make_distributed_response(ctx.cfg, w_pad))
    return mesh, resp, w_pad


def _build_distributed_psum(ctx: AuditContext):
    import dataclasses as dc

    from repro.core.distributed import make_distributed_sim, shard_depos
    from repro.core.depo import generate_depos, generate_physical_depos

    cfg = ctx.cfg
    if ctx.inject == "extra_collective" and ctx.planes > 1:
        # the PR 9 regression: per-plane collective chains instead of one
        cfg = dc.replace(cfg, plane_batching="loop")
    mesh, resp, _ = _dist_setup(ctx)
    fn = make_distributed_sim(mesh, cfg, resp)
    gen = generate_physical_depos if ctx.planes > 1 else generate_depos

    def make_args(i):
        key = _fold_key(i)
        return key, shard_depos(gen(key, cfg), mesh)

    return fn, make_args


def _build_distributed_halo(ctx: AuditContext):
    from repro.core.distributed import (bin_depos_by_wire,
                                       make_distributed_sim, shard_depos)
    from repro.core.depo import generate_depos

    # halo strips live on the FIRST mesh axis: put every device there so
    # the ring exchange is a real neighbour pattern, not a 1-strip no-op
    mesh, resp, w_pad = _dist_setup(ctx, shape=(ctx.devices, 1))
    fn = make_distributed_sim(mesh, ctx.cfg, resp,
                              scatter_reduction="halo")
    n_strips = mesh.shape["data"]
    # one fixed event: the binning pads each strip's bucket to a DATA-
    # dependent max, so per-call fresh events would change the depo shape
    # and read as (false) recompiles; fresh shard_depos still re-stages
    binned = bin_depos_by_wire(generate_depos(_fold_key(0), ctx.cfg),
                               n_strips=n_strips, w_pad=w_pad)

    def make_args(i):
        return _fold_key(i), shard_depos(binned, mesh)

    return fn, make_args


def _fit_pieces(ctx: AuditContext):
    import jax

    from repro.core.fit import (make_fit_loss, make_fit_targets,
                                spec_from_names)

    cfg = ctx.cfg
    spec = spec_from_names(("electron_lifetime_us", "recombination"), cfg)
    targets = make_fit_targets(cfg, jax.random.key(7), num_events=2)
    loss = make_fit_loss(cfg, spec, targets)
    theta0 = spec.init_theta(cfg)
    return loss, theta0


def _build_fit_loss(ctx: AuditContext):
    import jax

    loss, theta0 = _fit_pieces(ctx)
    return jax.jit(loss), lambda i: (theta0 + 0.0,)


def _build_fit_grad(ctx: AuditContext):
    import jax

    loss, theta0 = _fit_pieces(ctx)
    return jax.jit(jax.grad(loss)), lambda i: (theta0 + 0.0,)


#: the auditable production surface: all four executors + recon + fit.
#: (fit programs are single-plane: the calibration path's contract is
#: plane-count independent — the loss vmaps the same graph.)
PROGRAMS: Tuple[AuditProgram, ...] = (
    AuditProgram("single", _build_single),
    AuditProgram("batched", _build_batched),
    AuditProgram("streaming", _build_streaming),
    AuditProgram("recon", _build_recon),
    AuditProgram("distributed_psum", _build_distributed_psum,
                 needs_devices=2, collective_source="psum_scatter"),
    AuditProgram("distributed_halo", _build_distributed_halo, planes=(1,),
                 needs_devices=2, collective_source="halo"),
    AuditProgram("fit_loss", _build_fit_loss, planes=(1,)),
    AuditProgram("fit_grad", _build_fit_grad, planes=(1,)),
)


def program_names(planes: Tuple[int, ...] = (1, 3)) -> List[str]:
    """Every contract name ``collect_contracts`` emits for ``planes``."""
    return [f"p{p}/{prog.name}" for p in planes for prog in PROGRAMS
            if p in prog.planes]


# ---------------------------------------------------------------------------
# Contract extraction
# ---------------------------------------------------------------------------


def extract_contract(jitfn, make_args, *, x64: bool = False) -> Dict:
    """Compile ``jitfn`` on ``make_args(0)`` and distill its contract."""
    import contextlib

    import jax

    ctx = (jax.enable_x64() if x64
           else contextlib.nullcontext())
    with ctx, warnings.catch_warnings():
        # donated-but-unusable buffers warn per lowering; the *contract*
        # records that state explicitly (donated_args vs realized_aliases)
        warnings.simplefilter("ignore")
        lowered = jitfn.lower(*make_args(0))
        compiled = lowered.compile()
        txt = compiled.as_text()
        recompiles = (hlo.recompile_misses(jitfn, make_args)
                      if hasattr(jitfn, "_cache_size") else 0)
    return {
        "collectives": {k: n for k, n in hlo.collective_counts(txt).items()
                        if n},
        "dtypes": sorted(hlo.dtype_census(txt)),
        "scatter_dtypes": sorted(hlo.scatter_output_dtypes(txt)),
        "donated_args": hlo.donated_arg_count(lowered),
        "realized_aliases": hlo.realized_alias_count(txt),
        "host_calls": hlo.host_call_count(txt),
        "recompiles": recompiles,
    }


def collect_contracts(planes: Tuple[int, ...] = (1, 3), devices: int = 2,
                      patterns: Optional[List[str]] = None,
                      inject: Optional[str] = None,
                      log: Callable[[str], None] = lambda s: None) -> Dict:
    """Compile every (selected) production program and extract contracts.

    Returns ``{name: contract}`` with names ``p<planes>/<program>``.
    ``patterns`` restricts by fnmatch glob; ``inject`` seeds a deliberate
    regression (see ``INJECT_MODES``).
    """
    if inject is not None and inject not in INJECT_MODES:
        raise ValueError(f"unknown inject mode {inject!r}; "
                         f"known: {INJECT_MODES}")
    out: Dict[str, Dict] = {}
    for p in planes:
        cfg = audit_config(p)
        ctx = AuditContext(cfg=cfg, planes=p, devices=devices, inject=inject)
        for prog in PROGRAMS:
            if p not in prog.planes:
                continue
            name = f"p{p}/{prog.name}"
            if patterns and not any(fnmatch.fnmatch(name, pat)
                                    for pat in patterns):
                continue
            if devices < prog.needs_devices:
                log(f"skip {name}: needs >= {prog.needs_devices} devices "
                    f"(have {devices})")
                continue
            log(f"compile {name} ...")
            jitfn, make_args = prog.build(ctx)
            out[name] = extract_contract(jitfn, make_args,
                                         x64=_x64_trace(ctx))
    return out


# ---------------------------------------------------------------------------
# Policy (baseline-independent invariants)
# ---------------------------------------------------------------------------


def _declared_local_collectives() -> set:
    """Collective kinds any registered single-device strategy declares it
    may emit (``repro.tune`` strategy metadata) — empty today, so the local
    executors' policy is collective-free programs."""
    from repro.tune import registry

    return set(registry.declared_collectives())


def _program_for(name: str) -> Optional[AuditProgram]:
    base = name.split("/", 1)[-1]
    for prog in PROGRAMS:
        if prog.name == base:
            return prog
    return None


def policy_violations(name: str, contract: Dict) -> List[str]:
    """Hard invariants a contract must satisfy regardless of the baseline."""
    v = []
    if "f64" in contract["dtypes"]:
        v.append("f64 present (x64 leak or explicit double cast: every f64 "
                 "value doubles memory traffic on accelerator paths)")
    if contract["host_calls"]:
        v.append(f"{contract['host_calls']} host call(s) compiled into a "
                 "jitted path (python callback / infeed: a device<->host "
                 "round-trip per execution)")
    bad_acc = set(contract["scatter_dtypes"]) & {"bf16", "f16"}
    if bad_acc:
        v.append(f"scatter accumulates in {sorted(bad_acc)} — bf16 paths "
                 "must accumulate in f32 (PR 3 memory-traffic contract)")
    if contract["recompiles"]:
        v.append(f"{contract['recompiles']} jit-cache miss(es) on repeated "
                 "same-shape calls (silent recompilation)")
    prog = _program_for(name)
    observed = set(contract["collectives"])
    if prog is None or prog.collective_source == "none":
        allowed = _declared_local_collectives()
    else:
        allowed = set(SCATTER_REDUCTION_COLLECTIVES[prog.collective_source])
    extra = observed - allowed
    if extra:
        v.append(f"collective kind(s) {sorted(extra)} outside the declared "
                 f"set {sorted(allowed)} for this program's data-movement "
                 "strategy")
    return v


# ---------------------------------------------------------------------------
# Baseline diff (the check_regression glob-gating machinery, for contracts)
# ---------------------------------------------------------------------------


def expand_contract_names(patterns: List[str], baseline: Dict,
                          fresh: Dict) -> List[str]:
    """Expand ``--programs`` globs against baseline+fresh contract names.

    Same semantics as ``benchmarks/check_regression.expand_records``: a glob
    matching no *baseline* contract gates nothing run after run, so it
    returns [] (the caller fails loudly); plain names pass through so a
    fully missing contract still reports as MISSING.
    """
    known = sorted(set(baseline) | set(fresh))
    names: List[str] = []
    for pat in patterns:
        if any(c in pat for c in "*?["):
            hits = [n for n in known if fnmatch.fnmatch(n, pat)]
            if not hits:
                print(f"error: --programs pattern {pat!r} matched no "
                      "contracts", file=sys.stderr)
                return []
            if not any(h in baseline for h in hits):
                print(f"error: --programs pattern {pat!r} matched no "
                      "BASELINE contracts — commit the baseline "
                      "(--update) or fix the pattern", file=sys.stderr)
                return []
            names.extend(h for h in hits if h not in names)
        elif pat not in names:
            names.append(pat)
    return names


def diff_contracts(baseline: Dict, fresh: Dict,
                   patterns: Optional[List[str]] = None) -> int:
    """Print a per-contract diff table; return 1 on drift or policy
    violation, 0 when every gated contract matches."""
    patterns = patterns or sorted(
        {n.split("/", 1)[0] + "/*" for n in fresh})
    names = expand_contract_names(patterns, baseline, fresh)
    if not names:
        return 1
    failed = False
    for name in names:
        b, f = baseline.get(name), fresh.get(name)
        if f is None:
            print(f"{name}: MISSING from fresh run (program vanished or "
                  "was skipped)  FAIL")
            failed = True
            continue
        problems = []
        if b is None:
            print(f"{name}: (new — not in baseline; --update to pin)")
        else:
            for field in sorted(set(b) | set(f)):
                if b.get(field) != f.get(field):
                    problems.append(
                        f"  {field}: {b.get(field)!r} -> {f.get(field)!r}")
        for viol in policy_violations(name, f):
            problems.append(f"  policy: {viol}")
        if problems:
            print(f"{name}: FAIL")
            for line in problems:
                print(line)
            failed = True
        elif b is not None:
            print(f"{name}: ok")
    print(f"gated {len(names)} contract(s)")
    if failed:
        print("\ncontract drift: the compiled-program contract changed — "
              "if intentional, refresh with "
              "`python -m repro.analysis.audit --update` (docs/analysis.md)",
              file=sys.stderr)
    return 1 if failed else 0


def load_baseline(path: str) -> Dict:
    with open(path) as fh:
        data = json.load(fh)
    if data.get("schema") != SCHEMA_VERSION:
        raise ValueError(f"baseline {path!r} has schema "
                         f"{data.get('schema')!r}, expected {SCHEMA_VERSION}")
    return data["contracts"]


def write_baseline(path: str, contracts: Dict, devices: int,
                   merge_into: Optional[str] = None) -> None:
    merged: Dict[str, Dict] = {}
    if merge_into and os.path.exists(merge_into):
        try:
            merged = load_baseline(merge_into)
        except (ValueError, KeyError, json.JSONDecodeError):
            merged = {}
    merged.update(contracts)
    import jax

    data = {
        "schema": SCHEMA_VERSION,
        "devices": devices,
        "backend": jax.default_backend(),
        "note": "compiled-program contracts; refresh with "
                "`python -m repro.analysis.audit --update` "
                "(see docs/analysis.md)",
        "contracts": {k: merged[k] for k in sorted(merged)},
    }
    with open(path, "w") as fh:
        json.dump(data, fh, indent=2, sort_keys=False)
        fh.write("\n")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _parse_planes(text: str) -> Tuple[int, ...]:
    try:
        planes = tuple(int(p) for p in text.split(",") if p)
    except ValueError:
        raise SystemExit(f"--planes expects e.g. '1,3', got {text!r}")
    if not planes:
        raise SystemExit("--planes expects at least one plane count")
    return planes


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis.audit",
        description="compile every production entry point and check its "
                    "program contract against the committed baseline")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="diff fresh contracts against --baseline "
                           "(default mode)")
    mode.add_argument("--update", action="store_true",
                      help="regenerate and (re)write --baseline")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"contract baseline path (default {DEFAULT_BASELINE})")
    ap.add_argument("--planes", default="1,3",
                    help="comma-separated plane counts to audit (default 1,3)")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced fake host device count (default 2; "
                         "distributed contracts need >= 2)")
    ap.add_argument("--programs", action="append", default=None,
                    help="contract name or fnmatch glob to gate (repeatable; "
                         "default: every program of the selected planes)")
    ap.add_argument("--json", default=None,
                    help="also write the fresh contracts to this path "
                         "(the CI artifact)")
    ap.add_argument("--inject", default=None, choices=INJECT_MODES,
                    help="seed a deliberate contract regression (test the "
                         "gate itself)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-program compile progress")
    args = ap.parse_args(argv)

    if "jax" not in sys.modules:
        # force the fake-device fleet and a deterministic backend BEFORE
        # the first jax import (the launch/fit.py lazy-import pattern)
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    planes = _parse_planes(args.planes)
    log = (lambda s: None) if args.quiet else (
        lambda s: print(f"[audit] {s}", file=sys.stderr))
    fresh = collect_contracts(planes=planes, devices=args.devices,
                              patterns=args.programs, inject=args.inject,
                              log=log)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"schema": SCHEMA_VERSION, "contracts": fresh}, fh,
                      indent=2)
            fh.write("\n")
    if args.update:
        write_baseline(args.baseline, fresh, args.devices,
                       merge_into=args.baseline)
        print(f"wrote {len(fresh)} contract(s) to {args.baseline}")
        return 0
    if not os.path.exists(args.baseline):
        print(f"error: no contract baseline {args.baseline!r} — generate "
              "one with `python -m repro.analysis.audit --update` and "
              "commit it (the audit gate needs a committed baseline, "
              "unlike the bench gate)", file=sys.stderr)
        return 1
    baseline = load_baseline(args.baseline)
    return diff_contracts(baseline, fresh, patterns=args.programs)


if __name__ == "__main__":
    sys.exit(main())
