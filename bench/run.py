#!/usr/bin/env python3
"""QUIP's chip benchmark: one run of one cell.

    python3 bench/run.py --workload wifi_uci.adhoc_selective --seed 7 \\
        --seconds 51 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration (a data set
and the service's settings, ``configs/``) and a traffic mix (``traffic/``);
``cells/<workload>.json`` holds its comparison limits.  A run:

1. set-up (``setup_s``, from process start): generates the tables from the
   seed, builds ``QuipService`` with the imputer of the configuration's
   kind (``imputers/<kind>.py``), and runs every device program the window
   can call once (``sut.warm_up``);
2. the window: one client in a closed loop, no think time, submits the
   next query of the stream, waits for its result, and submits the next,
   for ``--seconds``; the query in flight at the close is waited for and
   counts.  ``qps`` is completed queries over the window, the latencies run
   from ``submit`` to ``result`` on this process's clock;
3. with ``--trace 1``: the program's span tracer is on for the whole
   window and the JAX profiler for its first ``TRACE_SECONDS``; the
   per-layer metrics (``metrics/<name>.py``) read them;
4. the check: after the device's peak memory is read and the service is
   freed, the plain reference (``reference.py`` over the kind's reference
   imputation) evaluates every query completed in the window, and
   ``compare.items_off`` counts the answer items that no admissible
   completion of the tables gives.  ``correct`` holds when every compared
   number is within its limit (both are exact: 0).

The last line on stdout is the result as JSON; the last lines on stderr are
the compared numbers beside their limits.  Without an accelerator, or with
fewer chips than the cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import compare  # noqa: E402
import devtrace  # noqa: E402
import sut  # noqa: E402
import querygen  # noqa: E402
from reference import Reference  # noqa: E402

NO_CHIP = 3
#: seconds of a ``--trace 1`` window that the JAX profiler records: a few
#: seconds of steady serving, a trace the run can still read in time
TRACE_SECONDS = 6.0


class NoChip(RuntimeError):
    pass


# --------------------------------------------------------------------------- #
# the benchmark's files, found by name
# --------------------------------------------------------------------------- #
def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def imputer(kind: str, root: str = ROOT):
    """The imputer kind ``bench/imputers/<kind>.py``: ``factory``,
    ``warm_up``, ``context`` and ``reference``."""
    path = os.path.join(root, "bench", "imputers", kind + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no imputer kind {kind!r}: add bench/imputers/{kind}.py with "
            f"factory, warm_up, context and reference (bench/README.md)")
    return _module(path, "imputer_" + kind)


def load_spec(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, from ``BENCHMARK.json`` and the files its
    entries name: the configuration, the traffic mix, the cell's limits, the
    dataset generator, the imputer kind and the metrics it reports."""
    here = os.path.join(root, "bench")
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "workload": workload,
        "chips": cell["chips"],
        "config": config,
        "traffic": _json(os.path.join(here, "traffic",
                                      cell["traffic"] + ".json")),
        "cell": _json(os.path.join(here, "cells", workload + ".json")),
        "generator": _module(os.path.join(
            here, "datagen", config["generator"] + ".py"),
            "datagen_" + config["generator"]),
        "imputer": imputer(config["imputer"]["kind"], root),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(name: str, root: str = ROOT):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    return _module(os.path.join(root, "bench", "metrics", name + ".py"),
                   "metric_" + name.replace(".", "_"))


# --------------------------------------------------------------------------- #
# device and compile bookkeeping
# --------------------------------------------------------------------------- #
def check_device(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"JAX found no accelerator (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept, so that
    a second run compiles nothing."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Counts backend compilations, persistent-cache loads included."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _device() -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def _no_mark(_name: str):
    return contextlib.nullcontext()


def _shape(q: dict) -> str:
    agg = q["aggregate"]
    kind = "proj" if agg is None else agg[0] + ("-grouped" if agg[2] else "")
    return f"{len(q['tables'])}t-{kind}"


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def _window(svc, stream, seconds: float, trace: bool, compiles):
    """The closed loop.  Returns the per-query records, the window's
    seconds and, when traced, the profiler's directory and span range."""
    import jax

    records = []
    prof = None
    t0 = time.perf_counter()
    deadline = t0 + seconds
    if trace:
        prof = {"dir": tempfile.mkdtemp(prefix="bench-trace-")}
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only: harness and JAX
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(prof["dir"], profiler_options=opts)
        prof["span0"] = svc.tracer.now()
        window = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        window.__enter__()
        t0 = time.perf_counter()
        deadline = t0 + seconds
    c0 = compiles.n
    while time.perf_counter() < deadline:
        mark = (jax.profiler.TraceAnnotation if prof and "span1" not in prof
                else _no_mark)
        q = next(stream)
        rec = {"query": q, "shape": _shape(q)}
        ts = time.perf_counter()
        with mark("harness:submit"):
            ticket = svc.submit(sut.to_query(q))
        with mark("harness:run " + rec["shape"]):
            try:
                rec["answer"] = sut.answer_of(svc.result(ticket),
                                              q["aggregate"] is None)
            except Exception as e:  # a failed query is counted, not fatal
                rec["error"] = f"{type(e).__name__}: {e}"
        rec["latency_s"] = time.perf_counter() - ts
        svc.release(ticket)
        records.append(rec)
        if (prof is not None and "span1" not in prof
                and time.perf_counter() - t0 >= TRACE_SECONDS):
            _stop_trace(svc, window, prof, len(records))
    elapsed = time.perf_counter() - t0
    if prof is not None and "span1" not in prof:
        _stop_trace(svc, window, prof, len(records))
    return records, elapsed, prof, compiles.n - c0


def _stop_trace(svc, window, prof: dict, queries: int) -> None:
    import jax

    window.__exit__(None, None, None)
    prof["span1"] = svc.tracer.now()
    prof["traced_queries"] = queries
    jax.profiler.stop_trace()


CHECKS = ("failed_queries", "answer_items_off")


def _checks(spec: dict, records: list, answers_of, log) -> tuple:
    """The compared numbers, and what they were read from: failed queries,
    and the answer items of every completed query that no admissible
    completion of the tables gives (``compare.items_off``).
    ``answers_of(records) -> answers`` gives what is checked."""
    done = [r for r in records if "error" not in r]
    t0 = time.perf_counter()
    imputation = spec["imputer"].reference(spec["tables"],
                                           spec["config"]["imputer"])
    ref = Reference(spec["tables"], imputation)
    items = 0
    off = {"aggregates": 0, "projections": 0}
    for i, (r, answer) in enumerate(zip(done, answers_of(done))):
        n, bad = compare.items_off(answer, ref.expect(r["query"]))
        items += n
        off["projections" if r["query"]["aggregate"] is None
            else "aggregates"] += bad
        if bad:
            log(f"answer off: query {i} ({r['shape']}) {bad} of {n} items: "
                f"{json.dumps(r['query'])[:300]}")
    amb, n_open, imputed = imputation.ambiguous()
    detail = {"queries": len(done), "items": items,
              "items_off_aggregates": off["aggregates"],
              "items_off_projections": off["projections"],
              "imputed_cells": imputed, "ambiguous_cells": amb,
              "open_cells": n_open,
              "seconds": time.perf_counter() - t0}
    log(f"reference: {len(done)} queries, {items} answer items compared in "
        f"{detail['seconds']:.1f} s; {amb} of {imputed} imputed cells "
        f"ambiguous at the stated precision, {n_open} of them open; items "
        f"off: {off['aggregates']} in aggregates, {off['projections']} in "
        f"projections")
    values = {"failed_queries": len(records) - len(done),
              "answer_items_off": off["aggregates"] + off["projections"]}
    checks = {name: {"value": values[name],
                     "limit": spec["cell"]["limits"][name]}
              for name in CHECKS}
    return checks, detail


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """One run on the current JAX backend; returns the result dict."""
    import jax

    config, mix = spec["config"], spec["traffic"]
    use_compile_cache()
    compiles = Compiles()
    try:
        tables = spec["generator"].make(np.random.default_rng(seed),
                                        config["params"])
        spec = dict(spec, tables=tables)
        rels = sut.relations(tables)
        tracer = False
        if trace:
            from repro.obs.trace import Tracer

            tracer = Tracer(enabled=True)
        svc = sut.make_service(rels, config, tracer, spec["imputer"])
        warmed = sut.warm_up(tables, config, spec["imputer"])
        stream = querygen.QueryStream(tables, config["joins"], mix, seed)
        s0 = svc.summary()
        setup_s = time.perf_counter() - T_START
        log(f"set-up: {setup_s:.3f} s, {warmed} device programs warmed, "
            f"{compiles.n} compiles or cache loads")
        records, elapsed, prof, in_window = _window(svc, stream, seconds,
                                                    trace, compiles)
        s1 = svc.summary()
        device = _device()
        spans = ([(s.name, s.t0, s.t1, s.args) for s in svc.tracer.spans()]
                 if trace else [])
        svc.close()
        del svc, rels
        gc.collect()
    finally:
        compiles.close()
    done = [r for r in records if "error" not in r]
    lat = np.array([r["latency_s"] for r in done])
    log(f"window: {len(records)} queries in {elapsed:.3f} s, "
        f"{len(records) - len(done)} failed; latency samples: {len(lat)}; "
        f"{in_window} compiles or cache loads in the window")
    checks, compared = _checks(spec, records,
                               lambda rs: [r["answer"] for r in rs], log)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(records), "failed": len(records) - len(done),
              "metrics": {}, "device": device}
    if not trace:
        values = {
            "qps": len(done) / elapsed,
            "latency_p50_s": float(np.percentile(lat, 50)) if len(lat) else None,
            "latency_p90_s": float(np.percentile(lat, 90)) if len(lat) else None,
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    else:
        reduced = devtrace.reduce(devtrace.load_events(prof["dir"]))
        shutil.rmtree(prof["dir"], ignore_errors=True)
        ctx = {
            "queries": len(done),
            "spans": spans,
            "traced_spans": [s for s in spans
                             if prof["span0"] <= s[1] and s[2] <= prof["span1"]],
            "imputations": s1["imputations"] - s0["imputations"],
            "compiles": in_window,
            "trace": reduced,
            "device_kind": device["kind"],
            "log": log,
            **spec["imputer"].context(tables, config["imputer"]),
        }
        for m in spec["per_layer"]:
            value = reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        log(f"trace: {prof['traced_queries']} queries in "
            f"{reduced['window_s']:.3f} s traced, device busy "
            f"{reduced['busy_s']:.6f} s, programs {reduced['modules_s']}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']:.9g} (limit {c['limit']})")
    result["compared"] = compared
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    leaked = sorted(k for k in os.environ if k.startswith("QUIP_"))
    if leaked:
        print(f"bench: the service runs at its defaults; unset {leaked}",
              file=sys.stderr)
        return 2
    try:
        spec = load_spec(args.workload)
    except FileNotFoundError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sut.import_program(ROOT)
    try:
        check_device(spec["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
