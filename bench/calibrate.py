#!/usr/bin/env python3
"""Readings for a cell's comparison limits, many seeds in one process.

    python3 bench/calibrate.py --workload cdc_nhanes.adhoc_loose \\
        --seeds 101,102,103 --seconds 51
    python3 bench/calibrate.py --workload cdc_nhanes.adhoc_loose \\
        --seeds 201,202,203 --control 1 --queries 110

For each seed it prints one JSON line with the compared numbers of a run of
the program (a window at the cell's own load, then the usual check), or,
with ``--control 1``, of the control: the imputer kind's control imputation
(``imputers/<kind>.py``, ``reference(..., control=True)``; for k-NN the
reference's distances in the expanded form in three bfloat16 passes) put in
the program's place, over the first ``--queries`` queries of the same
stream, as many as a run of the cell completes; a kind with no control
exits with a message.  A limit lies at or above the largest program
reading and below the smallest control reading (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
from reference import Reference


def control_checks(spec: dict, seed: int, queries: int, log) -> tuple:
    tables = spec["generator"].make(np.random.default_rng(seed),
                                    spec["config"]["params"])
    spec = dict(spec, tables=tables)
    stream = run.querygen.QueryStream(tables, spec["config"]["joins"],
                                      spec["traffic"], seed)
    records = [{"query": q, "shape": run._shape(q)}
               for q in (next(stream) for _ in range(queries))]
    try:
        control = spec["imputer"].reference(
            tables, spec["config"]["imputer"], control=True)
    except NotImplementedError as e:
        raise SystemExit(f"bench: imputer kind "
                         f"{spec['config']['imputer']['kind']!r} has no "
                         f"control: {e}") from e
    low = Reference(tables, control)
    return run._checks(spec, records,
                       lambda rs: [low.answer(r["query"]) for r in rs],
                       log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--queries", type=int,
                    help="queries the control answers (with --control 1)")
    args = ap.parse_args(argv)
    if args.control and not args.queries:
        ap.error("--control 1 needs --queries")
    spec = run.load_spec(args.workload)
    run.sut.import_program(run.ROOT)
    try:
        run.check_device(spec["chips"])
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return run.NO_CHIP

    def log(s):
        print(s, file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.control:
            checks, compared = control_checks(spec, seed, args.queries, log)
            out = {"seed": seed, "kind": "control", "compared": compared,
                   "checks": checks}
        else:
            res = run.run_cell(spec, seed, args.seconds, False, log=log)
            out = {"seed": seed, "kind": "program",
                   "attempted": res["attempted"], "metrics": res["metrics"],
                   "compared": res["compared"], "checks": res["checks"]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
