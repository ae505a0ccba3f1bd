"""Observability layer: span tracing, metrics export, impute provenance.

Covers the four contracts of docs/observability.md:

* tracing changes **nothing** — answers and imputation totals bit-identical
  to untraced runs across strategy × policy × workers × exec_impl;
* span trees are **structurally deterministic** under the ``unit`` clock
  (CI asserts counts and nesting, never wall time);
* ``explain`` reports **reconcile exactly** with the recorded execution
  counters (per-operator computed totals sum to ``imputations``);
* the export formats are valid: Chrome trace-event JSON and Prometheus
  text exposition.

Plus the serving-telemetry satellites: ``ServingStats.tenant_summary``
edge cases and the ``QuipService.summary()`` schema pin.
"""

from __future__ import annotations

import json
from collections import Counter

import numpy as np
import pytest

from repro.core.env import env_int
from repro.core.stats import ExecutionCounters, QueryRecord, ServingStats
from repro.obs import (
    NULL_SPAN,
    NULL_TRACER,
    ProvenanceRecorder,
    Tracer,
    render_explain,
    resolve_explain,
    resolve_tracer,
)
from repro.service import QuipService
from repro.service.server import SUMMARY_KEYS, expected_summary_keys
from test_quip_correctness import GroundTruthImputer, _build_instance
from test_service import WORKLOAD, _instance, _query, _service

UNIT = dict(enabled=True, clock="unit")


def _traced_service(tables, truth, **kw):
    tracer = Tracer(**UNIT)
    svc = _service(tables, truth, tracer=tracer, explain=True, **kw)
    return svc, tracer


# --------------------------------------------------------------------------- #
# env_int (core/env.py)
# --------------------------------------------------------------------------- #
def test_env_int_parses_and_fails_loud(monkeypatch):
    monkeypatch.delenv("QUIP_TEST_INT", raising=False)
    assert env_int("QUIP_TEST_INT") is None
    assert env_int("QUIP_TEST_INT", 7) == 7
    monkeypatch.setenv("QUIP_TEST_INT", " 42 ")
    assert env_int("QUIP_TEST_INT") == 42
    monkeypatch.setenv("QUIP_TEST_INT", "")
    assert env_int("QUIP_TEST_INT", 9) == 9
    monkeypatch.setenv("QUIP_TEST_INT", "not-a-seed")
    with pytest.raises(ValueError):
        env_int("QUIP_TEST_INT")


# --------------------------------------------------------------------------- #
# tracer unit behavior
# --------------------------------------------------------------------------- #
def test_disabled_tracer_is_allocation_free():
    tr = Tracer(enabled=False)
    # the same shared singleton every call — the zero-allocation contract
    assert tr.span("x", foo=1) is NULL_SPAN
    assert tr.span("y") is NULL_SPAN
    assert NULL_TRACER.span("z") is NULL_SPAN
    assert tr.begin("q") is None
    tr.end(None)  # no-op, no raise
    tr.instant("evt")
    with tr.span("x") as sp:
        assert sp.set(a=1) is sp
        assert sp.add(b=2) is sp
    assert tr.spans() == []


def test_unit_clock_nesting_and_ticket_inheritance():
    tr = Tracer(**UNIT)
    with tr.span("outer", ticket=5):
        with tr.span("inner", bytes=1) as sp:
            sp.set(rows=3).add(bytes=4, calls=1).add(calls=1)
        tr.instant("evt")
    spans = tr.spans(ticket=5)
    by_name = {s.name: s for s in spans}
    assert set(by_name) == {"outer", "inner", "evt"}
    # nested spans inherit ticket + parent from the thread-local stack
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["evt"].parent_id == by_name["outer"].span_id
    assert all(s.ticket == 5 for s in spans)
    # ``add`` sums into an attribute, from 0 where it was unset
    assert by_name["inner"].args == {"rows": 3, "bytes": 5, "calls": 2}
    assert tr.span_tree(5) == [
        {"name": "outer", "children": [
            {"name": "inner", "children": []},
            {"name": "evt", "children": []},
        ]},
    ]
    # unit clock: bare monotone ticks, no wall time anywhere
    ticks = sorted(t for s in spans for t in (s.t0, s.t1))
    assert all(float(t).is_integer() for t in ticks)
    assert by_name["outer"].t0 < by_name["inner"].t0 < by_name["outer"].t1


def test_begin_end_cross_thread_span():
    tr = Tracer(**UNIT)
    sid = tr.begin("query", ticket=1, tenant=0)
    with tr.span("step", ticket=1, parent=sid):
        pass
    tr.end(sid, state="done")
    q = tr.spans(name="query")[0]
    assert q.parent_id is None and q.args == {"tenant": 0, "state": "done"}
    assert tr.spans(name="step")[0].parent_id == sid
    tr.end(sid)  # double-end is a no-op
    assert len(tr.spans(name="query")) == 1


def test_span_records_exception_and_propagates():
    tr = Tracer(**UNIT)
    with pytest.raises(KeyError):
        with tr.span("boom"):
            raise KeyError("x")
    assert tr.spans(name="boom")[0].args["error"] == "KeyError"


def test_chrome_trace_schema():
    tr = Tracer(**UNIT)
    sid = tr.begin("query", ticket=3)
    with tr.span("op:select", ticket=3, parent=sid, rows=8):
        tr.instant("admitted", cat="sched")
    tr.end(sid)
    doc = tr.chrome_trace()
    assert doc["metadata"]["clock"] == "unit"
    events = doc["traceEvents"]
    json.dumps(doc)  # must be JSON-serializable as-is
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"query", "op:select"}
    for e in complete:
        assert e["dur"] >= 0 and e["pid"] == 3 and e["tid"] >= 1
    (instant,) = [e for e in events if e["ph"] == "i"]
    assert instant["s"] == "t" and instant["pid"] == 3


def test_resolve_tracer_precedence(monkeypatch):
    monkeypatch.delenv("QUIP_TRACE", raising=False)
    monkeypatch.delenv("QUIP_TRACE_CLOCK", raising=False)
    assert resolve_tracer() is NULL_TRACER
    explicit = Tracer(**UNIT)
    assert resolve_tracer(explicit) is explicit  # passthrough, env ignored
    assert resolve_tracer(True).enabled
    assert resolve_tracer(False) is NULL_TRACER
    monkeypatch.setenv("QUIP_TRACE", "1")
    monkeypatch.setenv("QUIP_TRACE_CLOCK", "unit")
    tr = resolve_tracer()
    assert tr.enabled and tr.clock == "unit"
    monkeypatch.setenv("QUIP_TRACE_CLOCK", "sundial")
    with pytest.raises(ValueError):
        resolve_tracer()
    monkeypatch.setenv("QUIP_TRACE_CLOCK", "unit")
    monkeypatch.setenv("QUIP_TRACE", "maybe")
    with pytest.raises(ValueError):
        resolve_tracer()


def test_resolve_explain_precedence(monkeypatch):
    monkeypatch.delenv("QUIP_EXPLAIN", raising=False)
    assert resolve_explain() is False
    assert resolve_explain(True) is True
    monkeypatch.setenv("QUIP_EXPLAIN", "1")
    assert resolve_explain() is True
    assert resolve_explain(False) is False  # explicit beats env


# --------------------------------------------------------------------------- #
# tracing changes nothing: traced vs untraced equivalence
# --------------------------------------------------------------------------- #
# compact tier-1 matrix; the full sweep runs under --runslow below
_EQUIV_COMPACT = [
    ("lazy", "rr", 0, "interp"),
    ("adaptive", "wfq", 0, "interp"),
    ("eager", "deadline", 2, "interp"),
    ("eager", "rr", 0, "compiled"),
]
_EQUIV_FULL = [
    (strategy, policy, workers, impl)
    for strategy in ("eager", "lazy", "adaptive")
    for policy in ("rr", "wfq", "deadline")
    for workers in (0, 2)
    for impl in ("interp", "compiled")
    if not (impl == "compiled" and strategy != "eager")
]


def _run_matrix_case(strategy, policy, workers, exec_impl):
    tables, _clean, truth = _instance()
    kw = dict(strategy=strategy, scheduler_policy=policy, workers=workers,
              cost_model="unit", exec_impl=exec_impl)
    if exec_impl == "compiled":
        # compiled lowering requires the eager/no-VF/no-minmax regime
        kw.update(use_vf=False, minmax_opt=False, compile_after_hits=1)

    def _run(**obs_kw):
        svc = _service(tables, truth, **kw, **obs_kw)
        tenants = [i % 2 for i in range(len(WORKLOAD))]
        tickets = [svc.submit(q, tenant=t)
                   for q, t in zip(WORKLOAD, tenants)]
        svc.run_until_idle()
        answers = [Counter(svc.answers(t)) for t in tickets]
        total = svc.serving.total_counters()
        svc.close()
        return answers, total.imputations, svc.summary()["morsel_steps"]

    base = _run()
    traced = _run(tracer=Tracer(**UNIT), explain=True)
    assert traced == base, (
        f"tracing changed execution under {strategy}/{policy}/"
        f"workers={workers}/{exec_impl}"
    )


@pytest.mark.parametrize("strategy,policy,workers,exec_impl", _EQUIV_COMPACT)
@pytest.mark.timeout(60)
def test_traced_equals_untraced(strategy, policy, workers, exec_impl):
    """With tracing + explain on, answers, imputation totals and morsel
    steps are bit-identical to an untraced service."""
    _run_matrix_case(strategy, policy, workers, exec_impl)


@pytest.mark.slow
@pytest.mark.parametrize("strategy,policy,workers,exec_impl", _EQUIV_FULL)
@pytest.mark.timeout(120)
def test_traced_equals_untraced_full(strategy, policy, workers, exec_impl):
    _run_matrix_case(strategy, policy, workers, exec_impl)


# --------------------------------------------------------------------------- #
# span structure: determinism + expected shape
# --------------------------------------------------------------------------- #
def _traced_run(**kw):
    tables, _clean, truth = _instance()
    svc, tracer = _traced_service(tables, truth, cost_model="unit", **kw)
    tickets = [svc.submit(q) for q in WORKLOAD]
    return svc, tracer, tickets


def test_span_structure_deterministic():
    """Two identical serial runs under the unit clock produce identical
    span counts and identical nesting, per ticket."""
    runs = []
    for _ in range(2):
        svc, tracer, tickets = _traced_run()
        svc.run_until_idle()
        runs.append([
            (tracer.span_counts(t), tracer.span_tree(t)) for t in tickets
        ])
        svc.close()
    assert runs[0] == runs[1]


def test_span_tree_shape_matches_execution():
    """The span tree carries the documented chain: one query root per
    ticket, one morsel_step per scheduler-granted step, operator and
    kernel spans nested under the steps, scheduler instants throughout."""
    svc, tracer, tickets = _traced_run()
    svc.run_until_idle()
    for ticket in tickets:
        counts = tracer.span_counts(ticket)
        assert counts["query"] == 1
        record = next(r for r in svc.serving.records if r.ticket == ticket)
        assert counts["morsel_step"] == record.steps
        assert counts["sched_checkout"] == counts["sched_checkin"]
        assert counts["admitted"] == 1
        assert counts["op:select"] >= 1  # WORKLOAD always selects on R0.v
        assert counts["op:join_build"] >= 1
        # every span of the tree hangs under the single query root
        (root,) = tracer.span_tree(ticket)
        assert root["name"] == "query"
    # one trace export covers all tickets; per-ticket filtering partitions
    doc_all = tracer.chrome_trace()
    per = sum(
        sum(1 for e in tracer.chrome_trace(ticket=t)["traceEvents"]
            if e["ph"] != "M")
        for t in tickets
    )
    assert per == sum(1 for e in doc_all["traceEvents"] if e["ph"] != "M")
    svc.close()


def test_compiled_run_emits_compiled_spans():
    tables, _clean, truth = _instance()
    svc, tracer = _traced_service(
        tables, truth, strategy="eager", exec_impl="compiled",
        compile_after_hits=1, use_vf=False, minmax_opt=False,
        cost_model="unit",
    )
    hot = WORKLOAD[0]
    tickets = [svc.submit(hot) for _ in range(3)]
    svc.run_until_idle()
    assert svc.summary()["compiled_hits"] > 0
    compiled_tickets = [
        t for t in tickets if "compiled_exec" in tracer.span_counts(t)
    ]
    assert compiled_tickets, "no compiled execution was traced"
    counts = tracer.span_counts(compiled_tickets[-1])
    assert counts["morsel_step"] == 1  # one straight-line vectorized pass
    assert "kernel:multi_match" in counts
    svc.close()


# --------------------------------------------------------------------------- #
# explain: provenance reconciliation
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("strategy", ["eager", "lazy", "adaptive"])
def test_explain_reconciles_with_counters(strategy):
    """totals['imputed_cells'] equals the query's ExecutionCounters
    .imputations exactly, and the per-operator rollup sums to it."""
    tables, _clean, truth = _instance()
    svc, _tracer = _traced_service(tables, truth, strategy=strategy)
    tickets = [svc.submit(q) for q in WORKLOAD]
    svc.run_until_idle()
    for ticket in tickets:
        record = next(r for r in svc.serving.records if r.ticket == ticket)
        report = svc.explain(ticket)
        totals = report["totals"]
        assert totals["imputed_cells"] == record.counters.imputations
        assert sum(report["per_op_imputed"].values()) \
            == totals["imputed_cells"]
        assert sum(s["computed"] for s in report["sites"]) \
            == totals["imputed_cells"]
        for site in report["sites"]:
            # requested counts pre-dedup queued tids; computed + hits
            # covers the unique ones
            assert site["computed"] + site["cache_hits"] \
                <= site["requested"]
            assert site["computed"] + site["cache_hits"] > 0
        text = svc.explain_text(ticket)
        assert text.startswith(f"explain ticket={ticket}")
    svc.close()


def test_explain_decision_log_adaptive_costs():
    """Adaptive runs log every decision-function evaluation with the §9.2
    expected costs; eager/obligated verdicts carry reasons, not costs."""
    tables, _clean, truth = _instance()
    svc, _tracer = _traced_service(tables, truth, strategy="adaptive")
    ticket = svc.submit(_query(4))
    svc.run_until_idle()
    decisions = svc.explain(ticket)["decisions"]
    assert decisions, "adaptive run logged no decisions"
    reasons = {d["reason"] for d in decisions}
    assert reasons <= {"obligated", "cost:impute", "cost:delay"}
    for d in decisions:
        if d["reason"].startswith("cost:"):
            assert {"est_imp_impute", "est_imp_delay",
                    "est_qp_impute", "est_qp_delay"} <= set(d)
            expect = ((d["est_imp_impute"] - d["est_imp_delay"])
                      + (d["est_qp_impute"] - d["est_qp_delay"])) < 0.0
            assert d["impute"] == expect
        else:
            assert d["impute"] and "est_imp_impute" not in d
    assert "decision-function log" in svc.explain_text(ticket)
    svc.close()


def test_explain_result_cache_hit_and_errors():
    tables, _clean, truth = _instance()
    svc, _tracer = _traced_service(tables, truth, result_cache_size=8)
    q = _query(2)
    first = svc.submit(q)
    svc.run_until_idle()
    second = svc.submit(q)  # result-cache hit: born DONE
    assert svc.explain(second)["result_cache_hit"] is True
    assert "result-cache hit" in svc.explain_text(second)
    with pytest.raises(KeyError):
        svc.explain(10_000)
    svc.release(first)
    with pytest.raises(KeyError):  # reports die with release()
        svc.explain(first)
    svc.close()

    plain = _service(tables, truth)
    t = plain.submit(q)
    plain.run_until_idle()
    with pytest.raises(RuntimeError):
        plain.explain(t)
    plain.close()


def test_provenance_unattributed_fallback():
    prov = ProvenanceRecorder()
    prov.on_flush("R0", "R0.v", 4, 3, 1, 0, 0.25)
    with prov.at("select", 7):
        prov.on_flush("R0", "R0.v", 2, 2, 0, 0, 0.5)
    report = prov.report()
    assert report["totals"]["imputed_cells"] == 5
    assert report["per_op_imputed"] == {"select": 2, "unattributed": 3}
    assert "unattributed" in render_explain(report)


# --------------------------------------------------------------------------- #
# metrics: snapshot + Prometheus exposition
# --------------------------------------------------------------------------- #
def test_metrics_snapshot_tracks_serving_state():
    tables, _clean, truth = _instance()
    svc = _service(tables, truth, cost_model="unit")
    tickets = [svc.submit(q, tenant=i % 2)
               for i, q in enumerate(WORKLOAD)]
    svc.run_until_idle()
    snap = svc.metrics()
    summary = svc.summary()
    assert snap["quip_queries_total"]["value"] == len(WORKLOAD)
    assert snap["quip_morsel_steps_total"]["value"] \
        == summary["morsel_steps"]
    assert snap["quip_imputations_total"]["value"] == summary["imputations"]
    assert snap["quip_inflight"]["value"] == 0
    hist = snap["quip_query_latency_seconds"]
    assert hist["type"] == "histogram"
    assert hist["count"] == len(WORKLOAD)
    per_tenant = snap["quip_tenant_queries_total"]
    assert per_tenant["label"] == "tenant"
    assert sum(per_tenant["values"].values()) == len(WORKLOAD)
    json.dumps(snap)  # JSON-able end to end
    del tickets
    svc.close()


def _parse_prometheus(text):
    """Minimal exposition-format validator: returns {name: type}."""
    types = {}
    helped = set()
    for line in text.strip().splitlines():
        assert line, "blank line inside exposition"
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert name in helped, f"# TYPE before # HELP for {name}"
            assert kind in ("counter", "gauge", "histogram")
            types[name] = kind
        else:
            sample = line.split()[0].split("{")[0]
            base = sample
            for suffix in ("_bucket", "_sum", "_count"):
                if sample.endswith(suffix) \
                        and sample[: -len(suffix)] in types:
                    base = sample[: -len(suffix)]
            assert base in types, f"sample {sample} missing # TYPE"
            float(line.rsplit(" ", 1)[1].replace("+Inf", "inf"))
    return types


def test_metrics_prometheus_exposition():
    tables, _clean, truth = _instance()
    svc = _service(tables, truth, shared=True, cost_model="unit")
    for q in WORKLOAD:
        svc.submit(q)
    svc.run_until_idle()
    text = svc.metrics(fmt="prometheus")
    types = _parse_prometheus(text)
    assert types["quip_queries_total"] == "counter"
    assert types["quip_query_latency_seconds"] == "histogram"
    assert types["quip_store_filled_cells"] == "gauge"  # shared store on
    assert 'quip_query_latency_seconds_bucket{le="+Inf"}' in text
    with pytest.raises(ValueError):
        svc.metrics(fmt="xml")
    svc.close()


def test_metrics_names_unique_and_cheap_when_idle():
    tables, _clean, truth = _instance()
    svc = _service(tables, truth)
    names = svc._metrics.names()
    assert len(names) == len(set(names))
    assert all(n.startswith("quip_") for n in names)
    snap = svc.metrics()  # zero queries: everything renders at 0
    assert snap["quip_queries_total"]["value"] == 0
    assert snap["quip_query_latency_seconds"]["count"] == 0
    svc.close()


# --------------------------------------------------------------------------- #
# export_trace
# --------------------------------------------------------------------------- #
def test_export_trace_writes_loadable_json(tmp_path):
    tables, _clean, truth = _instance()
    svc, _tracer = _traced_service(tables, truth)
    ticket = svc.submit(_query(2))
    svc.run_until_idle()
    path = tmp_path / "trace.json"
    doc = svc.export_trace(str(path), ticket=ticket)
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(doc, default=str))
    assert on_disk["metadata"]["clock"] == "unit"
    assert any(e["name"] == "query" for e in on_disk["traceEvents"])
    svc.close()


# --------------------------------------------------------------------------- #
# satellite: ServingStats.tenant_summary edge cases
# --------------------------------------------------------------------------- #
def _record(ticket, tenant, *, failed=False, steps=3, cost=3.0,
            admit=0.0, finish=3.0, deadline_met=None, latency=0.01):
    return QueryRecord(
        ticket=ticket, tenant=tenant, strategy="lazy",
        queue_wait_s=0.0, latency_s=latency, plan_cache_hit=False,
        counters=ExecutionCounters(), failed=failed, steps=steps,
        sched_cost=cost, admit_clock=admit, finish_clock=finish,
        deadline_met=deadline_met,
    )


def test_tenant_summary_zero_finished_queries():
    stats = ServingStats()
    assert stats.tenant_summary() == {}
    assert stats.latency_quantile(0.95) == 0.0
    summary = stats.summary()
    assert summary["queries"] == 0 and summary["imputations"] == 0


def test_tenant_summary_all_failed_tenant():
    stats = ServingStats()
    for i in range(3):
        stats.record_query(_record(i, tenant=7, failed=True))
    out = stats.tenant_summary()[7]
    assert out["queries"] == 3 and out["failed"] == 3
    assert out["deadline_hit_rate"] is None  # no deadline class anywhere
    assert out["cost_share"] == 1.0  # sole tenant carries all charged cost


def test_tenant_summary_unadmitted_excluded_from_turnaround():
    """A cancelled-in-queue record (admit_clock None, steps 0) must not
    drag the turnaround quantile toward zero."""
    stats = ServingStats()
    stats.record_query(_record(1, tenant=0, admit=0.0, finish=10.0,
                               steps=10, cost=10.0))
    stats.record_query(_record(2, tenant=0, failed=True, steps=0,
                               cost=0.0, admit=None, finish=None))
    out = stats.tenant_summary()[0]
    assert out["queries"] == 2
    assert out["p95_turnaround_cost"] == 10.0  # only the admitted record
    assert _record(2, 0, admit=None, finish=None).turnaround_cost is None


def test_tenant_summary_mixed_deadline_classes():
    stats = ServingStats()
    stats.record_query(_record(1, tenant=0, deadline_met=True))
    stats.record_query(_record(2, tenant=0, deadline_met=False))
    stats.record_query(_record(3, tenant=0, deadline_met=None))  # no class
    stats.record_query(_record(4, tenant=1, deadline_met=None))
    out = stats.tenant_summary()
    # hit rate aggregates only records that carried a deadline class
    assert out[0]["deadline_hit_rate"] == pytest.approx(0.5)
    assert out[1]["deadline_hit_rate"] is None
    total = sum(out[t]["cost_share"] for t in out)
    assert total == pytest.approx(1.0)


# --------------------------------------------------------------------------- #
# satellite: summary() schema pin
# --------------------------------------------------------------------------- #
def test_summary_keys_documented_and_pinned():
    """Every key summary() can emit is documented in SUMMARY_KEYS, and the
    emitted key set matches expected_summary_keys() for each config."""
    assert all(isinstance(v, str) and v for v in SUMMARY_KEYS.values())
    tables, _clean, truth = _instance()
    configs = [
        (dict(), dict(result_cache=True, shared_store=False)),
        (dict(result_cache_size=0), dict(result_cache=False,
                                         shared_store=False)),
        (dict(shared=True), dict(result_cache=True, shared_store=True)),
        (dict(result_cache_size=0, shared=True),
         dict(result_cache=False, shared_store=True)),
    ]
    for svc_kw, expect_kw in configs:
        svc = _service(tables, truth, **svc_kw)
        svc.submit(_query(2))
        svc.run_until_idle()
        got = set(svc.summary())
        assert got == expected_summary_keys(**expect_kw), (
            f"summary schema drifted under {svc_kw}: "
            f"extra={got - expected_summary_keys(**expect_kw)} "
            f"missing={expected_summary_keys(**expect_kw) - got}"
        )
        svc.close()
    assert expected_summary_keys() < set(SUMMARY_KEYS) | set()
    assert expected_summary_keys(result_cache=False,
                                 shared_store=True) <= set(SUMMARY_KEYS)


# --------------------------------------------------------------------------- #
# tracing with worker pool: counts still reconcile (structure is
# thread-interleaved, so only aggregate invariants are asserted)
# --------------------------------------------------------------------------- #
@pytest.mark.timeout(60)
def test_traced_worker_pool_counts_reconcile():
    rng = np.random.default_rng(3)
    tables, _clean, truth = _build_instance(rng, 2, 48, 0.3, 5)
    svc, tracer = _traced_service(tables, truth, workers=2,
                                  cost_model="unit")
    tickets = [svc.submit(q) for q in WORKLOAD]
    svc.run_until_idle()
    for ticket in tickets:
        record = next(r for r in svc.serving.records if r.ticket == ticket)
        counts = tracer.span_counts(ticket)
        assert counts["query"] == 1
        assert counts["morsel_step"] == record.steps
        assert svc.explain(ticket)["totals"]["imputed_cells"] \
            == record.counters.imputations
    assert GroundTruthImputer is not None
    svc.close()


# --------------------------------------------------------------------------- #
# table snapshots, imputer fits, k-NN calls, finalize; the profiler mirror;
# compile spans
# --------------------------------------------------------------------------- #
#: each new span and the span it nests in
_NESTED_IN = {
    "session:snapshot": "session_setup",
    "impute:fit": "impute_flush",
    "knn:call": "impute_flush",
    "op:finalize": "morsel_step",
}


def _knn_service(tracer):
    from repro.imputers import KnnImputer

    tables, _clean, _truth = _instance()
    return QuipService(tables, lambda: KnnImputer(k=3), strategy="lazy",
                       max_inflight=1, morsel_rows=8, tracer=tracer)


def _knn_answers(tracer):
    svc = _knn_service(tracer)
    tickets = [svc.submit(q) for q in WORKLOAD]
    answers = [Counter(svc.answers(t)) for t in tickets]
    imputations = svc.serving.total_counters().imputations
    svc.close()
    return answers, imputations


def test_new_spans_nest_under_their_parents():
    tracer = Tracer(**UNIT)
    svc = _knn_service(tracer)
    tickets = [svc.submit(q) for q in WORKLOAD]
    svc.run_until_idle()
    spans = tracer.spans()
    name_of = {s.span_id: s.name for s in spans}
    for ticket in tickets:
        counts = tracer.span_counts(ticket)
        for child, parent in _NESTED_IN.items():
            assert counts.get(child, 0) >= 1, (ticket, child)
    for s in spans:
        if s.name in _NESTED_IN:
            assert name_of[s.parent_id] == _NESTED_IN[s.name], s.name
    # the probe side of a join runs under whatever pulls the join's stream
    probes = tracer.spans(name="op:join_probe")
    assert probes and all(name_of[s.parent_id] in ("morsel_step",
                                                   "op:join_build")
                          for s in probes)
    fits = tracer.spans(name="impute:fit")
    assert {s.args["fitted"] for s in fits} == {True, False}
    assert all(s.args["table"] and s.args["attr"] for s in fits)
    calls = tracer.spans(name="knn:call")
    for s in calls:
        a = s.args
        assert a["nq"] <= a["nq_padded"] and a["nq_padded"] % 128 == 0
        # on the CPU the distances run in XLA: q, qm at the padded rows,
        # and the reference rows r, rm once per (fit, attribute), on the
        # first call, which finds them not yet resident; all float32
        ref = 0 if a["ref_resident"] else a["nr"]
        assert a["h2d_bytes"] == 4 * a["d"] * 2 * (a["nq_padded"] + ref)
    # every model here imputes one attribute, so one upload per fit
    assert (sum(not s.args["ref_resident"] for s in calls)
            == sum(s.args["fitted"] for s in fits))
    for s in tracer.spans(name="session:snapshot"):
        assert s.args["tables"] == 2 and s.args["rows"] > 0
    for s in tracer.spans(name="op:finalize"):
        assert s.args["agg"] is False and s.args["rows"] >= 0
    svc.close()


def test_knn_traced_equals_untraced():
    assert _knn_answers(Tracer(**UNIT)) == _knn_answers(None)


@pytest.mark.parametrize("impl,sent", [
    # q and qm padded from 3 to 128 rows, r and rm at 10 rows: 4 features
    # of float32 each
    ("ref", 4 * 4 * (128 + 128 + 10 + 10)),
    # distances on the host: only the (128, 10) matrix goes to top-k
    ("numpy", 4 * 128 * 10),
])
def test_masked_knn_h2d_bytes_hand_counted(impl, sent):
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    q = rng.random((3, 4), dtype=np.float32)
    r = rng.random((10, 4), dtype=np.float32)
    tr = Tracer(**UNIT)
    with tr.span("knn:call") as sp:
        _d, nn = kops.masked_knn(q, np.ones_like(q), r, np.ones_like(r), 2,
                                 impl=impl, span=sp)
    assert nn.shape == (3, 2)
    assert tr.spans()[0].args == {"nq_padded": 128, "h2d_bytes": sent}


class _SpanRefusingTracer(Tracer):
    """A disabled tracer whose recording calls fail: a site that skips the
    ``if tracer.enabled`` guard builds its span, and the test sees it."""

    def span(self, *a, **kw):
        raise AssertionError(f"unguarded span {a}")


def test_disabled_tracer_records_no_knn_or_fit_spans():
    tracer = _SpanRefusingTracer(enabled=False)
    answers, imputations = _knn_answers(tracer)
    assert imputations > 0 and tracer.spans() == []
    assert (answers, imputations) == _knn_answers(None)


def _profiled_events(tmp_path, tracer):
    import jax

    from repro.obs.trace import PROFILER_PREFIX

    svc = _knn_service(tracer)
    svc.answers(svc.submit(WORKLOAD[0]))  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.answers(svc.submit(WORKLOAD[1]))
    finally:
        jax.profiler.stop_trace()
        svc.close()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return [(plane.name, line.name, ev.name, ev.start_ns,
             ev.start_ns + ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name.startswith(PROFILER_PREFIX)]


def test_profiler_trace_holds_the_program_spans(tmp_path):
    events = _profiled_events(tmp_path / "on", Tracer(enabled=True))
    names = Counter(e[2] for e in events)
    for name in ("session_setup", "impute_flush", "knn:call"):
        assert names["quip:" + name] >= 1, names
    assert all(not plane.startswith("/device:") or plane.startswith(
        "/device:CPU") for plane, *_ in events)
    flushes = [e for e in events if e[2] == "quip:impute_flush"]
    for plane, line, _n, s, t in (e for e in events
                                  if e[2] == "quip:knn:call"):
        assert any(f[0] == plane and f[1] == line and f[3] <= s and t <= f[4]
                   for f in flushes)
    assert _profiled_events(tmp_path / "off", Tracer(enabled=False)) == []


def test_compile_spans_nest_under_the_compiling_span():
    import jax
    from jax import monitoring

    tr = Tracer(enabled=True)
    with tr.span("outer", ticket=4):
        jax.jit(lambda x: x * 3 + 1)(np.arange(5.0))
        # a persistent-cache load reports its hit before the compile event
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.25,
            fun_name="loaded")
    (outer,) = tr.spans(name="outer")
    compiles = tr.spans(name="jax:compile")
    loaded = [s for s in compiles if s.args["fun_name"] == "loaded"]
    assert len(compiles) == 2 and len(loaded) == 1
    assert loaded[0].args == {"fun_name": "loaded", "secs": 0.25,
                              "cache_load": True}
    for s in compiles:
        assert s.parent_id == outer.span_id and s.ticket == 4
        assert s.t1 - s.t0 == pytest.approx(s.args["secs"])
    # the unit clock's structure does not depend on the process's caches
    unit = Tracer(**UNIT)
    with unit.span("outer"):
        jax.jit(lambda x: x * 5 - 2)(np.arange(5.0))
    assert unit.span_counts() == {"outer": 1}
