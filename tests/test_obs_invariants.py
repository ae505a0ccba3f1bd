"""Observability acceptance invariants: tracing changes nothing, and
costs nothing when off.

The skewed multi-tenant wifi stream is served twice over the same tables:
once plain (tracer off, the default) and once with the unit-clock tracer
and explain provenance on.  Every check is on deterministic counters or
on the shape of an export; wall clock is never compared.
"""

from __future__ import annotations

import json
from typing import Dict

import pytest

from repro.data.queries import serving_workload
from repro.data.synthetic import wifi_dataset
from repro.imputers.knn import KnnImputer
from repro.obs import NULL_SPAN, NULL_TRACER, Tracer
from repro.service import QuipService

# on-mode footprint gate: recorded spans per Python work unit (temp tuples
# + imputations + morsel steps, counters that repeat exactly run to run)
MAX_SPANS_PER_WORK_UNIT = 0.05


def _run_stream(stream, tables, *, tracer=None, explain=None) -> Dict:
    svc = QuipService(
        tables, lambda: KnnImputer(k=5, cost_per_value=2e-3),
        strategy="adaptive", morsel_rows=4096, shared_impute=False,
        max_inflight=4, cost_model="unit", tracer=tracer, explain=explain,
    )
    tickets = [svc.submit(q, tenant=tenant) for tenant, q in stream]
    svc.run_until_idle()
    total = svc.serving.total_counters()
    steps = svc.summary()["morsel_steps"]
    return {
        "svc": svc,
        "answers": [sorted(svc.answers(t)) for t in tickets],
        "imputations": total.imputations,
        "morsel_steps": steps,
        "work_units": total.temp_tuples + total.imputations + steps,
    }


@pytest.fixture(scope="module")
def obs(tmp_path_factory):
    tables, _ = wifi_dataset(n_users=150, n_wifi=2000, n_occ=1000)
    stream = list(serving_workload("wifi", tables, n_queries=20,
                                   n_templates=6, n_tenants=4, seed=5))
    plain = _run_stream(stream, tables)
    tracer = Tracer(enabled=True, clock="unit")
    traced = _run_stream(stream, tables, tracer=tracer, explain=True)
    svc = traced["svc"]
    trace_path = tmp_path_factory.mktemp("obs") / "trace.json"
    svc.export_trace(str(trace_path))
    out = {
        "plain": plain, "traced": traced, "queries": len(stream),
        "spans": len(tracer.spans()),
        "trace": json.loads(trace_path.read_text()),
        "prometheus": svc.metrics(fmt="prometheus"),
        "explains": [(r.ticket, svc.explain(r.ticket)["totals"],
                      r.counters.imputations)
                     for r in svc.serving.records],
    }
    yield out
    svc.close()
    plain["svc"].close()


def test_trace_document_schema(obs):
    doc = obs["trace"]
    assert set(doc) >= {"traceEvents", "metadata"}, sorted(doc)
    assert doc["metadata"]["clock"] == "unit"
    json.dumps(doc)  # must round-trip as-is
    assert doc["traceEvents"], "empty trace"


def test_trace_event_schema(obs):
    for ev in obs["trace"]["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M"), ev
        assert isinstance(ev["name"], str) and "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and "ts" in ev
        elif ev["ph"] == "i":
            assert ev["s"] == "t"


def test_prometheus_exposition_schema(obs):
    text = obs["prometheus"]
    types: Dict[str, str] = {}
    helped = set()
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        assert line, "blank line inside exposition"
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _h, _t, name, kind = line.split()
            assert name in helped, f"# TYPE before # HELP for {name}"
            assert kind in ("counter", "gauge", "histogram"), kind
            types[name] = kind
            continue
        name = line.split()[0].split("{")[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in types:
                base = name[: -len(suffix)]
        assert base in types, f"sample {name} has no # TYPE"
        float(line.rsplit(" ", 1)[1].replace("+Inf", "inf"))
    assert types, "no metric exported"
    assert any(k == "histogram" for k in types.values())


def test_disabled_tracer_records_nothing(obs):
    svc = obs["plain"]["svc"]
    assert svc.tracer is NULL_TRACER, "untraced service built a tracer"
    assert svc.tracer.span("probe") is NULL_SPAN
    assert svc.tracer.spans() == [], "disabled tracer recorded spans"
    assert not svc.explain_enabled


def test_traced_answers_equal_plain(obs):
    assert obs["traced"]["answers"] == obs["plain"]["answers"]


def test_traced_imputations_equal_plain(obs):
    assert obs["traced"]["imputations"] == obs["plain"]["imputations"]


def test_traced_morsel_steps_equal_plain(obs):
    assert obs["traced"]["morsel_steps"] == obs["plain"]["morsel_steps"]


def test_explains_reconcile_with_counters(obs):
    assert len(obs["explains"]) == obs["queries"], "an explain is missing"
    for ticket, totals, imputations in obs["explains"]:
        assert totals["imputed_cells"] == imputations, (ticket, totals)


def test_traced_run_records_spans(obs):
    assert obs["spans"] > 0


def test_span_overhead_within_gate(obs):
    ratio = obs["spans"] / max(obs["traced"]["work_units"], 1)
    assert ratio <= MAX_SPANS_PER_WORK_UNIT, (
        f"{ratio:.4f} spans per work unit exceeds {MAX_SPANS_PER_WORK_UNIT}")
