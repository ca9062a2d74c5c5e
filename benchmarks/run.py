"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Mapping to the paper:
  rasterization -> Table 2 (+ Table 3 portability note)
  scatter       -> Fig. 5 (scatter-add strategy scaling)
  pipeline      -> Fig. 3 vs Fig. 4 strategies (the headline comparison)
  fft           -> §5 "FT" stage
  tune          -> per-backend strategy board (registry + autotuner winners)
  fit           -> calibration path: loss/grad eval + per-step fit cost
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import fft, fit, pipeline, rasterization, scatter, tune
    from benchmarks.common import write_json
    from repro.cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for mod in [rasterization, scatter, pipeline, fft, tune, fit]:
        try:
            mod.main()
        except Exception:  # noqa: BLE001 — keep the harness going
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()

    print(f"wrote {write_json('BENCH_all.json')}", file=sys.stderr)


if __name__ == "__main__":
    main()
