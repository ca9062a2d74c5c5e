import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Perf hillclimbing driver (§Perf): measure a cell's roofline terms under
config variants, on the single-pod production mesh.

  PYTHONPATH=src python -m benchmarks.hillclimb --cell qwen3-32b/train_4k \
      --variant baseline --variant bf16_params ...
  PYTHONPATH=src python -m benchmarks.hillclimb --cell lartpc/sim

Variants are named config mutations defined in VARIANTS below; each run
prints the three roofline terms + temp memory so iterations are comparable.
"""
import argparse
import dataclasses
import time

import jax

from repro.config import SHAPES, get_config
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import build_decode, build_prefill, build_train
from repro.parallel.sharding import act_rules_for, use_mesh

PEAK, HBM, LINK = 197e12, 819e9, 50e9


def _measure(compiled):
    acc = analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "compute_ms": acc["flops"] / PEAK * 1e3,
        "memory_ms": acc["hbm_bytes"] / HBM * 1e3,
        "collective_ms": acc["collective_bytes"] / LINK * 1e3,
        "temp_gib": mem.temp_size_in_bytes / 2**30,
        "flops": acc["flops"],
        "coll_by_kind": {k: round(v / 1e9, 3)
                         for k, v in acc["collectives"].items()},
        "top_coll": [(round(b / 1e9, 1), n[:90])
                     for b, n in acc.get("top_collectives", [])[:6]],
    }


# ---------------------------------------------------------------------------
# LM cell variants
# ---------------------------------------------------------------------------

def v_baseline(cfg):
    return cfg, None, None


def v_bf16_params(cfg):
    """bf16 params + f32 master in optimizer: halves param-gather bytes."""
    return dataclasses.replace(cfg, param_dtype="bfloat16"), None, None


def v_capacity_1_0(cfg):
    if cfg.moe is None:
        return cfg, None, None
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0),
        param_dtype="bfloat16"), None, None


def v_remat_full(cfg):
    return (dataclasses.replace(cfg, remat="full",
                                param_dtype="bfloat16"), None, None)


def v_tp_microbatch(n):
    """Drop sequence parallelism (its per-layer weight-grad all-reduce over
    the model axis dominates); recover activation memory with gradient
    accumulation over n microbatches instead."""

    def f(cfg):
        from repro.config import ParallelConfig
        from repro.parallel import sharding as shd

        rules = dict(shd.ACT_RULES, seq=None)
        return (cfg, ParallelConfig(microbatches=n), rules)

    return f


def v_tp_mb_bf16(n):
    def f(cfg):
        cfg2, par, rules = v_tp_microbatch(n)(cfg)
        return dataclasses.replace(cfg2, param_dtype="bfloat16"), par, rules

    return f


def v_zero1(cfg, mb=0, bf16=True, sp=True, cap=None):
    """ZeRO-1: TP-only params (replicated over data), fully-sharded optimizer
    state; grads reduce-scatter + params all-gather once per step."""
    from repro.config import ParallelConfig
    from repro.parallel import sharding as shd

    if bf16:
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if cap is not None and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=cap))
    rules = None if sp else dict(shd.ACT_RULES, seq=None)
    par = ParallelConfig(microbatches=mb) if mb else None
    return cfg, par, rules


VARIANTS = {
    "baseline": v_baseline,
    "bf16_params": v_bf16_params,
    "bf16+cap1.0": v_capacity_1_0,
    "bf16+remat_full": v_remat_full,
    "tp_mb4": v_tp_microbatch(4),
    "tp_mb8": v_tp_microbatch(8),
    "tp_mb8_bf16": v_tp_mb_bf16(8),
    "tp_mb16_bf16": v_tp_mb_bf16(16),
    "zero1_sp": lambda c: v_zero1(c),
    "zero1_sp_f32": lambda c: v_zero1(c, bf16=False),
    "zero1_mb4": lambda c: v_zero1(c, mb=4, sp=False),
    "zero1_sp_mb2": lambda c: v_zero1(c, mb=2),
    "zero1_sp_cap1": lambda c: v_zero1(c, cap=1.0),
    "zero1_sp_mb2_cap1": lambda c: v_zero1(c, mb=2, cap=1.0),
    "bf16_cap1_mb2": lambda c: (
        dataclasses.replace(
            v_capacity_1_0(c)[0], param_dtype="bfloat16"),
        __import__("repro.config", fromlist=["ParallelConfig"]
                   ).ParallelConfig(microbatches=2),
        None),
    "bf16_cap1_mb4": lambda c: (
        dataclasses.replace(
            v_capacity_1_0(c)[0], param_dtype="bfloat16"),
        __import__("repro.config", fromlist=["ParallelConfig"]
                   ).ParallelConfig(microbatches=4),
        None),
}

ZERO1 = {"zero1_sp", "zero1_sp_f32", "zero1_mb4", "zero1_sp_mb2",
         "zero1_sp_cap1", "zero1_sp_mb2_cap1"}


def run_lm_cell(arch_id: str, shape_name: str, variants):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh()
    for vname in variants:
        cfg, parallel, rules = VARIANTS[vname](get_config(arch_id))
        t0 = time.time()
        with use_mesh(mesh, rules or act_rules_for(cfg, mesh)):
            if shape.kind == "train":
                fn, args, sh, kw = build_train(cfg, shape, mesh,
                                               parallel=parallel,
                                               zero1=vname in ZERO1)
            elif shape.kind == "prefill":
                fn, args, sh, kw = build_prefill(cfg, shape, mesh)
            else:
                fn, args, sh, kw = build_decode(cfg, shape, mesh)
            compiled = (jax.jit(fn, in_shardings=sh, **kw)
                        .lower(*args).compile())
        m = _measure(compiled)
        m["compile_s"] = round(time.time() - t0, 1)
        dom = max(["compute_ms", "memory_ms", "collective_ms"],
                  key=lambda k: m[k])
        print(f"{arch_id}/{shape_name} [{vname}] "
              f"compute={m['compute_ms']:.1f}ms memory={m['memory_ms']:.1f}ms "
              f"collective={m['collective_ms']:.1f}ms (dom={dom.split('_')[0]}) "
              f"temp={m['temp_gib']:.1f}GiB coll_GB={m['coll_by_kind']}",
              flush=True)
        for b, n in m["top_coll"]:
            print(f"    {b:>8.1f} GB  {n}", flush=True)


# ---------------------------------------------------------------------------
# LArTPC sim cell (the paper's own technique on the production mesh)
# ---------------------------------------------------------------------------

def run_sim_cell(variants):
    import jax.numpy as jnp

    from repro.core.depo import DepoSet
    from repro.core.distributed import make_distributed_sim, padded_grid_shape
    from repro.core.response import make_distributed_response

    cfg = get_config("lartpc-uboone")  # full MicroBooNE scale, 100k depos
    mesh = make_mesh((16, 16), ("data", "model"))
    nsh = 256
    w_pad, _, _ = padded_grid_shape(cfg, nsh)
    resp = make_distributed_response(cfg, w_pad)
    n = (cfg.num_depos + nsh - 1) // nsh * nsh
    depo_sds = DepoSet(*(jax.ShapeDtypeStruct((n,), jnp.float32)
                         for _ in range(5)))

    for strat in variants:
        sim = make_distributed_sim(mesh, cfg, resp, axes=("data", "model"),
                                   scatter_reduction=strat)
        t0 = time.time()
        key_abstract = jax.eval_shape(lambda: jax.random.key(0))
        compiled = sim.lower(key_abstract, depo_sds).compile()
        m = _measure(compiled)
        m["compile_s"] = round(time.time() - t0, 1)
        dom = max(["compute_ms", "memory_ms", "collective_ms"],
                  key=lambda k: m[k])
        print(f"lartpc/sim [{strat}] "
              f"compute={m['compute_ms']:.2f}ms memory={m['memory_ms']:.2f}ms "
              f"collective={m['collective_ms']:.2f}ms (dom={dom.split('_')[0]}) "
              f"temp={m['temp_gib']:.2f}GiB coll_GB={m['coll_by_kind']}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    help="<arch>/<shape> or lartpc/sim")
    ap.add_argument("--variant", action="append", default=[])
    args = ap.parse_args()
    if args.cell == "lartpc/sim":
        run_sim_cell(args.variant or ["psum_scatter", "halo"])
        return
    arch, shape = args.cell.split("/")
    run_lm_cell(arch, shape, args.variant or ["baseline"])


if __name__ == "__main__":
    main()
