"""Compiled-plan acceptance invariants on a repeat-heavy serving stream.

A skewed 4-template wifi stream is served submit → drain by two services
that differ only in ``exec_impl`` (``interp`` vs ``compiled`` with
``compile_after_hits=K``), the result cache off so every repeat
re-executes.  Eager + ``use_vf=False`` + ``minmax_opt=False`` makes every
signature eligible for lowering, so no query may fall back.  The speed
proxy is Python work units, scheduler morsel steps plus impute-batch
flushes: the two counters that grow with the interpreter's
per-(morsel × operator) round trips and that a compiled session collapses.

A second run replays a ``mutating_workload`` against an epoch-versioned
registry with compilation on, each answer compared with a cold
interpreter service on the post-mutation tables.
"""

from __future__ import annotations

from typing import Dict

import pytest

from repro.data.queries import mutating_workload, serving_workload
from repro.data.synthetic import wifi_dataset
from repro.imputers.mean import MeanImputer
from repro.service import QuipService, TableRegistry
from repro.service.plan_cache import query_signature

MORSEL_ROWS = 8  # small on purpose: the interpreter pays per morsel
K = 2  # compile_after_hits

# eager + use_vf=False + minmax_opt=False: every signature in the stream
# is lowering-eligible, so compile_fallbacks must stay 0
_KNOBS = dict(strategy="eager", use_vf=False, minmax_opt=False,
              morsel_rows=MORSEL_ROWS, result_cache_size=0,
              shared_impute=False)


def _expected_compiled(stream) -> int:
    """Occurrence i (1-based) of a signature runs compiled iff its
    plan-cache hit count i−1 has reached K, so each signature with c
    occurrences contributes max(0, c − K)."""
    counts: Dict = {}
    for _tenant, q in stream:
        sig = query_signature(q)
        counts[sig] = counts.get(sig, 0) + 1
    return sum(max(0, c - K) for c in counts.values())


def _sequential(stream, tables, *, exec_impl: str) -> Dict:
    svc = QuipService(tables, MeanImputer, exec_impl=exec_impl,
                      compile_after_hits=K, **_KNOBS)
    answers = [sorted(svc.answers(svc.submit(q, tenant=tenant)), key=repr)
               for tenant, q in stream]
    return {"answers": answers, **svc.summary()}


def _mutation_replay(tables) -> Dict:
    """A long-lived compiling service against a cold interpreter service
    per query: compiled artifacts must die with their table's epoch."""
    registry = TableRegistry({t: r.copy() for t, r in tables.items()})
    svc = QuipService(registry, MeanImputer, exec_impl="compiled",
                      compile_after_hits=1, **_KNOBS)
    events = list(mutating_workload("wifi", tables, n_queries=12,
                                    mutate_every=3, n_templates=4, seed=9))
    mismatches = 0
    for event in events:
        if event[0] == "mutate":
            event[1].apply(registry)
            continue
        _kind, tenant, q = event
        got = sorted(svc.answers(svc.submit(q, tenant=tenant)), key=repr)
        cold = QuipService({t: registry[t].copy() for t in registry},
                           MeanImputer, exec_impl="interp", **_KNOBS)
        want = sorted(cold.answers(cold.submit(q)), key=repr)
        mismatches += int(got != want)
    return {"mismatches": mismatches, **svc.summary()}


@pytest.fixture(scope="module")
def compiled():
    tables, _ = wifi_dataset(n_users=150, n_wifi=2000, n_occ=1000)
    # repeat-heavy: few templates, strong skew → hot signatures cross K fast
    stream = list(serving_workload("wifi", tables, n_queries=24,
                                   n_templates=4, n_tenants=4, skew=1.4,
                                   seed=5))
    return {
        "interp": _sequential(stream, tables, exec_impl="interp"),
        "compiled": _sequential(stream, tables, exec_impl="compiled"),
        "expected_hits": _expected_compiled(stream),
        "replay": _mutation_replay(tables),
    }


def _work_units(run: Dict) -> int:
    return run["morsel_steps"] + run["impute_batches"]


def test_compiled_answers_match_interpreter(compiled):
    assert compiled["compiled"]["answers"] == compiled["interp"]["answers"]


def test_compiled_imputations_equal_interpreter(compiled):
    assert (compiled["compiled"]["imputations"]
            == compiled["interp"]["imputations"])


def test_compiled_hits_equal_prediction(compiled):
    assert compiled["compiled"]["compiled_hits"] == compiled["expected_hits"]


def test_hot_signatures_get_compiled(compiled):
    assert compiled["compiled"]["compiled_hits"] > 0


def test_eligible_signatures_never_fall_back(compiled):
    assert compiled["compiled"]["compile_fallbacks"] == 0


def test_compiled_cuts_work_units_2x(compiled):
    ratio = (_work_units(compiled["interp"])
             / max(_work_units(compiled["compiled"]), 1))
    assert ratio >= 2.0, f"work-unit drop only {ratio:.2f}x"


def test_no_stale_compiled_answer_across_mutations(compiled):
    assert compiled["replay"]["mismatches"] == 0


def test_mutations_invalidate_compiled_plans(compiled):
    assert compiled["replay"]["invalidation_events"] > 0
