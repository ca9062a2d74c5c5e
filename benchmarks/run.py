"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Mapping to the paper:
  rasterization -> Table 2 (+ Table 3 portability note)
  scatter       -> Fig. 5 (scatter-add strategy scaling)
  pipeline      -> Fig. 3 vs Fig. 4 strategies (the headline comparison)
  fft           -> §5 "FT" stage
  tune          -> per-backend strategy board (registry + autotuner winners)
  lm_step       -> host-framework sanity timings for the 10 assigned archs
  fit           -> calibration path: loss/grad eval + per-step fit cost
  roofline      -> §Roofline report from the dry-run artifacts (if present)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (fft, fit, lm_step, pipeline, rasterization,
                            scatter, tune)
    from benchmarks.common import write_json
    from repro.cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for mod in [rasterization, scatter, pipeline, fft, tune, lm_step, fit]:
        try:
            mod.main()
        except Exception:  # noqa: BLE001 — keep the harness going
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()

    print(f"wrote {write_json('BENCH_all.json')}", file=sys.stderr)

    # roofline summary (reads cached dry-run artifacts; skipped if absent)
    try:
        from benchmarks import roofline

        rows = roofline.load_all("pod1")
        ok = [r for r in rows if "skipped" not in r]
        if ok:
            worst = min(ok, key=lambda r: r["roofline_frac"])
            best = max(ok, key=lambda r: r["roofline_frac"])
            print(f"roofline/cells_analysed,{len(ok)},"
                  f"worst={worst['cell']}:{worst['roofline_frac']:.3f};"
                  f"best={best['cell']}:{best['roofline_frac']:.3f}")
    except Exception:  # noqa: BLE001
        traceback.print_exc()


if __name__ == "__main__":
    main()
