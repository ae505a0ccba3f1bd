"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only exp1,...]

Prints a per-experiment summary plus a ``name,value`` derived-metrics CSV,
and writes benchmarks/results.json.  Each experiment also appends one
JSONL line — timestamp, scale, wall seconds, derived metrics — to
``benchmarks/history/<name>.jsonl`` so runs accumulate a machine-readable
timing history (``--history-dir`` to relocate, ``--no-history`` to skip).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

from benchmarks import (
    exp1_runtime_imputations,
    exp2_quality,
    exp3_selectivity,
    exp4_bloom,
    exp5_plans,
    exp6_minmax,
    exp7_query_baseline,
    exp8_serving,
    exp9_result_cache,
    exp10_qos,
    exp11_workers,
    exp12_compiled,
    exp13_obs,
    exp14_ivm,
    kernels_micro,
)
from repro.compile_cache import use_compile_cache

MODULES = [
    exp1_runtime_imputations,
    exp2_quality,
    exp3_selectivity,
    exp4_bloom,
    exp5_plans,
    exp6_minmax,
    exp7_query_baseline,
    exp8_serving,
    exp9_result_cache,
    exp10_qos,
    exp11_workers,
    exp12_compiled,
    exp13_obs,
    exp14_ivm,
    kernels_micro,
]


def _append_history(history_dir: str, name: str, entry: dict) -> None:
    """One JSONL line per run per experiment — append-only, best-effort
    (a read-only checkout must not fail the benchmark)."""
    try:
        os.makedirs(history_dir, exist_ok=True)
        path = os.path.join(history_dir, f"{name}.jsonl")
        with open(path, "a") as fh:
            fh.write(json.dumps(entry, default=str) + "\n")
    except OSError as e:  # pragma: no cover - exotic fs only
        print(f"history append failed for {name}: {e}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale workloads (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="benchmarks/results.json")
    ap.add_argument("--history-dir", default="benchmarks/history",
                    help="where per-experiment timing history accumulates")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append to the timing history")
    args = ap.parse_args(argv)
    use_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    all_results = {}
    failures = []
    for mod in MODULES:
        if only and mod.NAME not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.run(fast=not args.full)
            der = mod.derived(rows)
            wall = time.time() - t0
            all_results[mod.NAME] = {"rows": rows, "derived": der}
            print(f"\n=== {mod.NAME} ({wall:.1f}s) ===")
            for k, v in der.items():
                print(f"{mod.NAME}/{k},{v}")
            if not args.no_history:
                _append_history(args.history_dir, mod.NAME, {
                    "ts": datetime.datetime.now(
                        datetime.timezone.utc
                    ).isoformat(timespec="seconds"),
                    "fast": not args.full,
                    "wall_s": round(wall, 3),
                    "derived": der,
                })
        except Exception as e:  # noqa: BLE001
            failures.append((mod.NAME, repr(e)))
            print(f"\n=== {mod.NAME} FAILED: {e!r} ===")
            import traceback

            traceback.print_exc()
    try:
        with open(args.out, "w") as f:
            json.dump(all_results, f, indent=2, default=str)
        print(f"\nwrote {args.out}")
    except OSError:
        pass
    print(f"{len(all_results)} experiments ok, {len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
