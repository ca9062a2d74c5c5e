"""Run one benchmark cell once on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers the correctness check compared, each beside its limit, are the
last lines of standard error. Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for, or when the cell, its
configuration, traffic or a metric is not defined under ``bench/``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs inside the checkout, not at a fixed path in /tmp
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".bench_cache" / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
