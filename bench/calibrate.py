"""Readings the check's limits are set from, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--variant program|bf16] [--seconds 4]

Sets the cell up once, then for each seed runs a short window at the cell's
own sizes and load and compares its drawn batch with the reference, as a
benchmark run does; prints one JSON line per seed with the compared
numbers. ``--variant bf16`` runs the program's own lower-precision path
(bfloat16 patches), the control every limit has to fail. The first seed
also shows that the program's generator and the frozen one in
``depogen.py`` give the same depos, bit for bit. Needs the chip; the
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import depogen, harness  # noqa: E402

VARIANTS = {"program": None, "bf16": {"charge_grid_strategy": "unfused_bf16"}}


def same_depos(cell: harness.Cell, cfg, seed: int, event_id: int) -> dict:
    """Share of depo coordinates on which the program's generator (with
    its drift) and the frozen generator (with the reference's) agree."""
    import numpy as np

    from bench import reference
    from repro.core.depo import generate_depos, generate_plane_depos

    key = depogen.event_key(seed, event_id)
    gen = generate_plane_depos if cfg.num_planes > 1 else generate_depos
    prog = gen(key, cfg)
    phys = depogen.tracks(key, cfg.num_depos, cell.sizes)
    phys = {f: np.asarray(getattr(phys, f)) for f in phys._fields}
    out = {}
    for p, (_, angle, pitch) in enumerate(reference.planes(cell.sizes)):
        wire = reference.project(phys["y"], phys["z"], angle, pitch,
                                 cell.sizes)
        ref = reference.drift(dict(phys, wire=wire), pitch, cell.sizes)
        pw = np.asarray(prog.wire if cfg.num_planes == 1 else prog.wire[p])
        pt = np.asarray(prog.tick if cfg.num_planes == 1 else prog.tick[p])
        out[f"plane{p}"] = {"wire_equal": float(np.mean(pw == ref["wire"])),
                            "tick_equal": float(np.mean(pt == ref["tick"]))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", choices=sorted(VARIANTS), default="program")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.own_caches()
    session = harness.Session(cell, overrides=VARIANTS[args.variant])
    seeds = [int(s) for s in args.seeds.split(",")]
    print(json.dumps({"depos": same_depos(cell, session.cfg, seeds[0], 0)}),
          flush=True)
    for seed in seeds:
        t0 = time.perf_counter()
        win = session.window(seed, args.seconds)
        t1 = time.perf_counter()
        numbers = session.check(win)
        print(json.dumps({
            "workload": cell.name, "variant": args.variant, "seed": seed,
            "events": win.events, "window_s": win.wall_s,
            "events_per_s": win.events / win.wall_s,
            "failed": harness.failed_events(win), "batch": win.sample,
            "reference_s": time.perf_counter() - t1, "numbers": numbers,
            "compiles": win.compiles, "loop_s": t1 - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
