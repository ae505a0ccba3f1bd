#!/usr/bin/env python3
"""Run QUIP's serving path once on one TPU chip and check its answers.

    python chip_smoke.py [--seed N]

One process drives ``QuipService.submit`` over a seeded wifi data set
(``wifi_dataset(n_users=4000, n_wifi=200_000, n_occ=100_000)``) and a
16-query ``serving_workload`` stream with repeats, imputing with the real
``KnnImputer``.  Phases:

* K — each device kernel the phases select, on inputs drawn from the data,
  against its numpy implementation: bloom probe, masked distance,
  neighbour mode/mean, segment reduce and the hash join (exact where the
  arithmetic is exact, else to a stated tolerance).
* A — the paper path: ``strategy="adaptive"``, ``use_vf=True``, every
  other option at its default.  Bloom probes and masked KNN run on the chip.
* B — ``strategy="eager"``, ``exec_impl="compiled"``,
  ``compile_after_hits=2``, ``join_impl="pallas"``,
  ``segment_impl="pallas"`` and Pallas neighbour aggregation, result cache
  off so repeats promote to compiled plans.  The stream must hold a
  GROUP BY so that the segment reduction runs.
* O — the oracle, in the same process: ``execute_offline`` with the numpy
  relational implementations gives every answer, and a serial
  ``execute_quip`` with the numpy implementations gives every executed
  query's imputation count.  The oracle's KNN uses the same distance
  kernel as the service: the expanded distance ``q² + r² − 2qr`` cancels
  in f32, so host and device rank near-tied neighbours differently, and
  phase K holds the kernel to the numpy distance instead.

Projections, COUNT, SUM (integer attributes) and MIN/MAX must equal the
oracle exactly, as must imputation counts; AVG is compared to a relative
tolerance of 1e-6 and its mismatches are printed.  Any failure raises.

Each phase prints the device kind, its compilations, each kernel's calls
(``<op>/<impl that ran>``), wall seconds and ``peak_bytes_in_use``.  The
last line is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}``.  Without a TPU, or without the repository's ``src/``
beside this file, it exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    n_users: int = 4000
    n_wifi: int = 200_000
    n_occ: int = 100_000
    n_queries: int = 16
    n_templates: int = 6
    knn_k: int = 5


#: its stream repeats a GROUP BY often enough for phase B to promote it to
#: a compiled plan, so the segment reduction runs
DEFAULT_SEED = 3

#: selective predicates (the paper's "low selectivity" set, §7.2): with
#: kind="random" the 3-table joins reach ~34M pairs at these sizes
WORKLOAD_KIND = "low"


def _import_repro():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke.py: no repro package under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


class _Compiles:
    """Counts backend compilations (persistent-cache hits included)."""

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


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in sorted(after.items())
            if v != before.get(k, 0)}


class _Phase:
    """Prints one phase's line: wall seconds, compilations, kernel calls
    and device peak memory."""

    def __init__(self, name: str, dev, compiles: _Compiles, log):
        from repro.kernels.ops import KERNEL_CALLS

        self.name, self.dev, self.compiles, self.log = name, dev, compiles, log
        self.calls0 = KERNEL_CALLS.snapshot()
        self.compiles0 = compiles.n
        self.t0 = time.perf_counter()
        self.calls: dict = {}

    def done(self, **extra) -> dict:
        from repro.kernels.ops import KERNEL_CALLS

        wall = time.perf_counter() - self.t0
        self.calls = _delta(KERNEL_CALLS.snapshot(), self.calls0)
        fields = {
            "device": self.dev.device_kind,
            "wall_s": round(wall, 3),
            "compiles": self.compiles.n - self.compiles0,
            "peak_bytes_in_use": _peak_bytes(self.dev),
            "kernel_calls": self.calls,
            **extra,
        }
        self.log(f"phase {self.name}: " + json.dumps(fields))
        return self.calls


def _expect_ran(calls: dict, op: str, impl: str, phase: str) -> None:
    from repro.kernels.ops import KERNEL_CALLS

    key = KERNEL_CALLS.key(op, impl)
    if calls.get(key, 0) <= 0:
        raise AssertionError(f"phase {phase}: {key} never ran ({calls})")


# --------------------------------------------------------------------------- #
# answer comparison
# --------------------------------------------------------------------------- #
def _canonical(rel):
    """(column names, row-sorted columns) with NULLs as a flag column."""
    import numpy as np

    names = rel.column_names()
    cols = []
    for n in names:
        null = rel.absent[n] | rel.missing[n]
        cols += [null, np.where(null, 0, rel.cols[n])]
    if not cols or rel.num_rows == 0:
        return names, cols
    order = np.lexsort(cols[::-1])
    return names, [c[order] for c in cols]


def _answer_class(query) -> str:
    agg = query.aggregate
    return "float" if agg is not None and agg.op == "avg" else "exact"


def _same_answer(got, want, cls: str) -> bool:
    """Exact equality of the row-sorted answers; for the float class the
    aggregate's value column (the last) is held to rtol 1e-6 instead."""
    import numpy as np

    g_names, g = _canonical(got)
    w_names, w = _canonical(want)
    if g_names != w_names or got.num_rows != want.num_rows:
        return False
    exact = len(g) - 1 if cls == "float" else len(g)
    return (all(np.array_equal(a, b) for a, b in zip(g[:exact], w[:exact]))
            and all(np.allclose(a, b, rtol=1e-6, atol=0.0)
                    for a, b in zip(g[exact:], w[exact:])))


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def _phase_kernels(tables, phase: _Phase) -> None:
    """Every device kernel against its numpy implementation, on data-drawn
    inputs at the smoke's sizes."""
    import numpy as np

    from repro.core.bloom import BloomFilter
    from repro.kernels import ops as kops

    rng = np.random.default_rng(7)
    users, wifi, occ = tables["users"], tables["wifi"], tables["occupancy"]
    seconds = {}

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        seconds[name] = round(time.perf_counter() - t0, 3)
        return out

    def bloom():
        bf = BloomFilter("users.mac_addr")
        bf.insert(users.values("users.mac_addr"))
        probe = wifi.values("wifi.mac_addr")
        if not np.array_equal(bf.might_contain(probe, impl="pallas"),
                              bf.might_contain(probe, impl="numpy")):
            raise AssertionError("bloom probe: device != numpy")

    def join():
        for build, probe in (
            (users.values("users.mac_addr"), wifi.values("wifi.mac_addr")),
            (occ.values("occupancy.lid")[:10_000],
             wifi.values("wifi.lid")[:20_000]),
        ):
            got = kops.hash_join_match(build, probe, impl="pallas")
            want = kops.hash_join_match(build, probe, impl="numpy")
            if not all(np.array_equal(a, b) for a, b in zip(got, want)):
                raise AssertionError("hash join: device pairs != numpy pairs")

    def distance():
        q = rng.normal(size=(1024, 4)).astype(np.float32)
        qm = (rng.random((1024, 4)) > 0.3).astype(np.float32)
        r = rng.normal(size=(8192, 4)).astype(np.float32)
        rm = (rng.random((8192, 4)) > 0.3).astype(np.float32)
        got = np.asarray(kops.masked_distance(q, qm, r, rm, impl="pallas"))
        want = kops.masked_distance(q, qm, r, rm, impl="numpy")
        finite = np.isfinite(want)
        err = float(np.max(np.abs(got[finite] - want[finite])))
        if not (np.array_equal(finite, np.isfinite(got))
                and np.allclose(got[finite], want[finite], rtol=1e-4,
                                atol=1e-3)):
            raise AssertionError(f"masked distance: device != numpy "
                                 f"(max abs err {err})")

    def neighbours():
        neigh = wifi.values("wifi.lid")[
            rng.integers(0, wifi.num_rows, (1024, 5))]
        for categorical in (True, False):
            got = kops.neighbor_aggregate(neigh, categorical=categorical,
                                          impl="pallas")
            want = kops.neighbor_aggregate(neigh, categorical=categorical,
                                           impl="numpy")
            ok = (np.array_equal(got, want) if categorical
                  else np.allclose(got, want, rtol=1e-6))
            if not ok:
                raise AssertionError(f"neighbour aggregation (categorical="
                                     f"{categorical}): device != numpy")

    def segments():
        seg = rng.integers(0, users.num_rows, wifi.num_rows)
        for op in ("count", "sum", "min", "max"):
            vals = None if op == "count" else wifi.values("wifi.duration")
            got = kops.segment_reduce(vals, seg, users.num_rows, op,
                                      impl="pallas")
            want = kops.segment_reduce(vals, seg, users.num_rows, op,
                                       impl="numpy")
            if not np.array_equal(got, want):
                raise AssertionError(f"segment {op}: device != numpy")

    for name, fn in (("bloom_probe", bloom), ("hash_join", join),
                     ("masked_distance", distance),
                     ("neighbor_aggregate", neighbours),
                     ("segment_reduce", segments)):
        timed(name, fn)
    calls = phase.done(check_s=seconds)
    for op, impl in (("bloom_probe", "pallas"),
                     ("hash_join", kops.resolve_join_impl("pallas")),
                     ("masked_distance", "pallas"),
                     ("neighbor_mode", "pallas"),
                     ("neighbor_mean", "pallas"),
                     ("segment_reduce", "pallas")):
        _expect_ran(calls, op, impl, phase.name)


def _serve(svc, stream):
    tickets = [svc.submit(q, tenant=t) for t, q in stream]
    svc.run_until_idle()
    results = [svc.result(tk) for tk in tickets]
    hits = {rec.ticket for rec in svc.serving.records if rec.result_cache_hit}
    return [(res, tk in hits) for tk, res in zip(tickets, results)]


def run_smoke(seed: int = DEFAULT_SEED, sizes: Sizes = Sizes(),
              log=print) -> dict:
    """All phases on the current JAX backend; raises on any failure and
    returns the device description for the result line."""
    _import_repro()
    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    compiles = _Compiles()
    try:
        return _run_phases(seed, sizes, log, cache_dir, compiles)
    finally:
        compiles.close()


def _run_phases(seed, sizes, log, cache_dir, compiles) -> dict:
    import jax
    import numpy as np

    from repro.core.executor import execute_offline, execute_quip
    from repro.data.queries import serving_workload
    from repro.data.synthetic import wifi_dataset
    from repro.imputers import ImputationEngine, KnnImputer
    from repro.kernels import ops as kops
    from repro.service import QuipService

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {json.dumps(device)}  compile cache: {cache_dir}")

    t0 = time.perf_counter()
    tables, _clean = wifi_dataset(np.random.default_rng(seed),
                                  n_users=sizes.n_users, n_wifi=sizes.n_wifi,
                                  n_occ=sizes.n_occ)
    stream = list(serving_workload(
        "wifi", tables, n_queries=sizes.n_queries,
        n_templates=sizes.n_templates, kind=WORKLOAD_KIND, seed=seed))
    grouped = [q for _t, q in stream
               if q.aggregate is not None and q.aggregate.group_by]
    log(f"data: users={sizes.n_users} wifi={sizes.n_wifi} "
        f"occupancy={sizes.n_occ} seed={seed}; stream: {len(stream)} "
        f"queries from {len({id(q) for _t, q in stream})} templates, "
        f"{len(grouped)} grouped; cut: kind={WORKLOAD_KIND} (selective "
        f"templates; kind=random draws ~34M-pair joins at full size); "
        f"set-up {time.perf_counter() - t0:.3f}s")
    if not grouped:
        raise AssertionError(
            f"seed {seed}: the stream holds no GROUP BY, so phase B would "
            f"not run the segment reduction; pick another seed")

    knn = lambda: KnnImputer(k=sizes.knn_k)  # noqa: E731

    _phase_kernels(tables, _Phase("K", dev, compiles, log))

    phase = _Phase("A", dev, compiles, log)
    svc_a = QuipService(tables, knn, strategy="adaptive", use_vf=True)
    served_a = _serve(svc_a, stream)
    s = svc_a.summary()
    calls = phase.done(queries=len(stream), imputations=s["imputations"],
                       result_cache_hits=s["result_cache_hits"],
                       plan_cache_hits=s["plan_cache_hits"])
    _expect_ran(calls, "bloom_probe", kops.resolve_bloom_impl(), "A")
    _expect_ran(calls, "masked_distance", kops.resolve_dist_impl(), "A")
    svc_a.close()

    phase = _Phase("B", dev, compiles, log)
    svc_b = QuipService(
        tables, lambda: KnnImputer(k=sizes.knn_k, agg_impl="pallas"),
        strategy="eager", use_vf=False, exec_impl="compiled",
        compile_after_hits=2, join_impl="pallas", segment_impl="pallas",
        result_cache_size=0)
    served_b = _serve(svc_b, stream)
    s = svc_b.summary()
    calls = phase.done(queries=len(stream), imputations=s["imputations"],
                       compiled_hits=s["compiled_hits"],
                       compile_fallbacks=s["compile_fallbacks"],
                       plan_cache_compiled=s["plan_cache_compiled"])
    if s["compiled_hits"] <= 0:
        raise AssertionError("phase B: no query ran a compiled plan")
    _expect_ran(calls, "masked_distance", kops.resolve_dist_impl(), "B")
    _expect_ran(calls, "neighbor_mode", kops.resolve_knn_impl("pallas"), "B")
    _expect_ran(calls, "hash_join", kops.resolve_join_impl("pallas"), "B")
    _expect_ran(calls, "segment_reduce", kops.resolve_segment_impl("pallas"),
                "B")
    svc_b.close()

    phase = _Phase("O", dev, compiles, log)
    offline_engine = ImputationEngine(
        {t: r.copy() for t, r in tables.items()},
        default=lambda: KnnImputer(k=sizes.knn_k, agg_impl="numpy"))
    offline, serial = {}, {}
    for _t, q in stream:
        if id(q) not in offline:
            offline[id(q)] = execute_offline(q, tables, offline_engine)

    def serial_imputations(q, strategy: str, use_vf: bool) -> int:
        key = (id(q), strategy)
        if key not in serial:
            engine = ImputationEngine(
                {t: r.copy() for t, r in tables.items()},
                default=lambda: KnnImputer(k=sizes.knn_k, agg_impl="numpy"))
            serial[key] = execute_quip(
                q, tables, engine, strategy=strategy, use_vf=use_vf,
                bloom_impl="numpy", join_impl="numpy",
                exec_impl="interp").counters.imputations
        return serial[key]

    mismatches = {"exact": 0, "float": 0, "imputations": 0}
    for name, served, strategy, use_vf in (
        ("A", served_a, "adaptive", True), ("B", served_b, "eager", False),
    ):
        for i, ((_t, q), (res, cache_hit)) in enumerate(zip(stream, served)):
            cls = _answer_class(q)
            if not _same_answer(res.relation, offline[id(q)].relation, cls):
                mismatches[cls] += 1
                log(f"  mismatch: phase {name} query {i} ({cls} class, "
                    f"{q.aggregate and q.aggregate.op or 'projection'})")
            if not cache_hit:
                want = serial_imputations(q, strategy, use_vf)
                if res.counters.imputations != want:
                    mismatches["imputations"] += 1
                    log(f"  mismatch: phase {name} query {i} imputed "
                        f"{res.counters.imputations}, serial oracle {want}")
    phase.done(answers_compared=2 * len(stream), mismatches=mismatches)
    if mismatches["exact"] or mismatches["imputations"]:
        raise AssertionError(f"answers differ from the oracle: {mismatches}")

    if dev.platform == "tpu":
        interpreted = [k for k in kops.KERNEL_CALLS.snapshot()
                       if k.endswith("-interpret")]
        if interpreted:
            raise AssertionError(f"interpret-mode kernels on TPU: "
                                 f"{interpreted}")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED,
                    help="seeds the data set and the query stream")
    args = ap.parse_args(argv)
    _import_repro()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    device = run_smoke(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
