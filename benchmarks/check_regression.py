"""Bench regression gate: diff a fresh BENCH_*.json against the committed
baseline and fail on a large slowdown of a named record.

CI runs the pipeline benchmark into a scratch file and compares it to the
repo's committed ``BENCH_pipeline.json``:

    python benchmarks/check_regression.py \
        --baseline BENCH_pipeline.json --fresh BENCH_pipeline_fresh.json \
        --record pipeline/fig4_batched --max-ratio 2.0

``--record`` values may be shell-style globs (fnmatch): a pattern expands
against the union of baseline and fresh record names, so families of rows —
e.g. the calibration rows ``'fit/*'`` — are gated without enumerating
each one. A glob must match at least one
*committed baseline* record, else the gate fails loudly: a glob that only
matches fresh rows is gating nothing (the committed family vanished — or
was never committed — and every run would silently pass as "(new)").

The diff table ends with a per-``--record`` summary of how many rows each
selector matched (``gated N record(s) — 'fit/*': 4, …``), so a family
glob that quietly shrank is visible in the CI log even when every surviving
row passes.

Exit status 1 (with a diff table) when fresh/baseline exceeds the ratio for
any watched record; records missing from the fresh run also fail (a silently
vanished benchmark is a regression too). A plain (non-glob) record name
found in *neither* file fails — a watched name that matches nothing is a
typo or a removed benchmark, not a gate. Names missing from the baseline
but present in fresh only warn — new benchmarks land before their baseline
numbers do.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys


def load_records(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    return {r["name"]: float(r["us_per_call"]) for r in data["records"]}


def expand_records(patterns: list, baseline: dict, fresh: dict,
                   counts: dict | None = None) -> list:
    """Expand glob patterns against all known record names (plain names
    pass through so a fully missing record still reports as MISSING).

    Returns [] — which the caller treats as failure — when a glob matches
    no *baseline* record: fresh-only matches would render as warn-only
    "(new)" rows, so such a glob gates nothing run after run.

    When ``counts`` is given it is filled with {pattern: matched count} —
    the gate summary prints it so a glob that quietly shrank from 12 rows
    to 1 is visible in the CI log."""
    known = sorted(set(baseline) | set(fresh))
    names: list = []
    for pat in patterns:
        if any(c in pat for c in "*?["):
            hits = [n for n in known if fnmatch.fnmatch(n, pat)]
            if counts is not None:
                counts[pat] = len(hits)
            if not hits:
                print(f"error: --record pattern {pat!r} matched no records",
                      file=sys.stderr)
                return []
            if not any(h in baseline for h in hits):
                print(f"error: --record pattern {pat!r} matched no "
                      "BASELINE records (fresh-only matches warn instead "
                      "of gating) — commit the baseline rows or fix the "
                      "pattern", file=sys.stderr)
                return []
            names.extend(h for h in hits if h not in names)
        else:
            if counts is not None:
                counts[pat] = 1
            if pat not in names:
                names.append(pat)
    return names


def check(baseline_path: str, fresh_path: str, records: list,
          max_ratio: float) -> int:
    baseline = load_records(baseline_path)
    fresh = load_records(fresh_path)
    counts: dict = {}
    records = expand_records(records, baseline, fresh, counts=counts)
    if not records:
        return 1
    failed = False
    print(f"{'record':<40} {'baseline_us':>12} {'fresh_us':>12} {'ratio':>7}")
    for name in records:
        if name not in baseline:
            if name not in fresh:
                # a plain name in NEITHER file: nothing is being gated —
                # typo or removed benchmark, either way fail loudly
                print(f"{name:<40} {'MISSING':>12} {'MISSING':>12} "
                      f"{'--':>7}  FAIL")
                failed = True
                continue
            print(f"{name:<40} {'(new)':>12} {fresh[name]:>12.1f} {'--':>7}")
            continue
        if name not in fresh:
            print(f"{name:<40} {baseline[name]:>12.1f} {'MISSING':>12} "
                  f"{'--':>7}  FAIL")
            failed = True
            continue
        ratio = fresh[name] / baseline[name] if baseline[name] > 0 else 0.0
        verdict = "FAIL" if ratio > max_ratio else "ok"
        print(f"{name:<40} {baseline[name]:>12.1f} {fresh[name]:>12.1f} "
              f"{ratio:>6.2f}x  {verdict}")
        failed = failed or ratio > max_ratio
    per_glob = ", ".join(f"{pat!r}: {n}" for pat, n in counts.items())
    print(f"gated {len(records)} record(s) — {per_glob}")
    if failed:
        print(f"\nregression: ratio exceeded {max_ratio:.1f}x "
              f"(or a watched record vanished)", file=sys.stderr)
    return 1 if failed else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_*.json to compare against")
    ap.add_argument("--fresh", required=True,
                    help="freshly produced BENCH_*.json")
    ap.add_argument("--record", action="append", required=True,
                    help="record name or fnmatch glob to gate (repeatable); "
                         "globs expand against baseline+fresh record names")
    ap.add_argument("--max-ratio", type=float, default=2.0,
                    help="fail when fresh/baseline exceeds this (default 2x)")
    args = ap.parse_args()
    if not os.path.exists(args.baseline):
        # a branch without a committed baseline shouldn't hard-fail the
        # bench job — the gate simply has nothing to compare against yet.
        # (a missing FRESH file still fails loudly: the benchmark broke.)
        print(f"warning: no baseline {args.baseline!r} to gate against; "
              "skipping", file=sys.stderr)
        return 0
    return check(args.baseline, args.fresh, args.record, args.max_ratio)


if __name__ == "__main__":
    sys.exit(main())
