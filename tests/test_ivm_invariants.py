"""IVM acceptance invariants: delta-driven result-cache maintenance.

A mutation-heavy repeat workload built so that patching is possible: the
mutated table ``R0`` has no missing cells, and every missing value lives
on its join partner ``R1``, which is never mutated.  A cached answer then
depends on ``R0`` only through stored values, so the imputation-interaction
fallback cannot fire and count/sum/avg aggregates and select/project
answers can be patched in place.

One event stream (skewed repeats over six templates, with an update,
delete or insert commit on ``R0`` every fourth query) is replayed against
a service that evicts on mutation (``ivm=False``) and one that patches
(``ivm=True``); the patching service's every answer is also compared with
a cold execution over the post-mutation tables.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
import pytest

from repro.core.executor import execute_quip
from repro.core.plan import Aggregate, Query
from repro.core.predicates import JoinPredicate, SelectionPredicate
from repro.core.relation import MaskedRelation
from repro.core.schema import ColumnSpec, Schema
from repro.imputers.base import ImputationService
from repro.imputers.mean import MeanImputer
from repro.service import QuipService, TableRegistry

STRATEGY = "adaptive"
MORSEL_ROWS = 4096
KEY_CARD = 8
VAL_CARD = 32
ROWS = 1500


def _instance(rows: int, missing_rate: float, seed: int
              ) -> Dict[str, MaskedRelation]:
    """R0 ⋈ R1 on ``k``; missing cells only on ``R1.v`` (never mutated)."""
    rng = np.random.default_rng(seed)
    tables: Dict[str, MaskedRelation] = {}
    for name in ("R0", "R1"):
        schema = Schema(name, [ColumnSpec(f"{name}.k", "int"),
                               ColumnSpec(f"{name}.v", "int")])
        cols = {
            f"{name}.k": rng.integers(0, KEY_CARD, size=rows,
                                      dtype=np.int64),
            f"{name}.v": rng.integers(0, VAL_CARD, size=rows,
                                      dtype=np.int64),
        }
        missing = None
        if name == "R1":
            missing = {f"{name}.v": rng.random(rows) < missing_rate}
        tables[name] = MaskedRelation.from_columns(
            schema, cols, missing=missing, base_table=name
        )
    return tables


def _templates() -> List[Query]:
    join = (JoinPredicate("R0.k", "R1.k"),)
    return [
        # single-table select/project on the mutated side (tuple patches)
        Query(("R0",), (SelectionPredicate("R0.v", "<=", 12),), (),
              ("R0.v",)),
        Query(("R0",), (SelectionPredicate("R0.v", ">", 20),), (),
              ("R0.k", "R0.v")),
        # join aggregates over the imputed side (agg-sidecar patches)
        Query(("R0", "R1"), (SelectionPredicate("R0.v", "<=", 16),), join,
              (), aggregate=Aggregate("count", None)),
        Query(("R0", "R1"), (), join, (),
              aggregate=Aggregate("sum", "R1.v")),
        Query(("R0", "R1"), (SelectionPredicate("R0.v", ">", 8),), join,
              (), aggregate=Aggregate("avg", "R1.v", group_by="R1.k")),
        Query(("R0", "R1"), (), join, (),
              aggregate=Aggregate("count", "R1.v", group_by="R0.k")),
    ]


def _events(n_queries: int, mutate_every: int, rows: int, seed: int
            ) -> List[Tuple]:
    """Skewed repeats over the templates, and a commit on R0 every
    ``mutate_every`` queries (mostly updates, some deletes and inserts)."""
    rng = np.random.default_rng(seed)
    templates = _templates()
    weights = np.array([2.0 ** -i for i in range(len(templates))])
    weights /= weights.sum()
    out: List[Tuple] = []
    n_rows = rows  # track R0's row count without a registry
    for i in range(n_queries):
        out.append(("query", templates[int(rng.choice(len(templates),
                                                      p=weights))]))
        if (i + 1) % mutate_every:
            continue
        r = rng.random()
        if r < 0.6:
            k = int(rng.integers(2, 6))
            ids = rng.choice(n_rows, size=k, replace=False).astype(np.int64)
            vals = rng.integers(0, VAL_CARD, size=k).astype(np.int64)
            out.append(("mutate", "update", ids, {"R0.v": vals}))
        elif r < 0.8:
            k = int(rng.integers(1, 4))
            ids = rng.choice(n_rows, size=k, replace=False).astype(np.int64)
            out.append(("mutate", "delete", ids, None))
            n_rows -= k
        else:
            k = int(rng.integers(1, 4))
            values = {
                "R0.k": rng.integers(0, KEY_CARD, size=k, dtype=np.int64),
                "R0.v": rng.integers(0, VAL_CARD, size=k, dtype=np.int64),
            }
            out.append(("mutate", "insert", None, values))
            n_rows += k
    return out


def _cold_answers(query: Query, registry: TableRegistry) -> List[tuple]:
    tables = {t: registry[t].copy() for t in query.tables}
    engine = ImputationService(tables, default=MeanImputer)
    return sorted(execute_quip(query, tables, engine, strategy=STRATEGY,
                               morsel_rows=MORSEL_ROWS).answer_tuples())


def _serve(events: List[Tuple], tables: Dict[str, MaskedRelation], *,
           ivm: bool, check_cold: bool) -> Dict:
    registry = TableRegistry({t: r.copy() for t, r in tables.items()})
    svc = QuipService(registry, MeanImputer, strategy=STRATEGY,
                      morsel_rows=MORSEL_ROWS, result_cache_size=128,
                      ivm=ivm)
    answers: List[List[tuple]] = []
    stale = 0
    for event in events:
        if event[0] == "mutate":
            _kind, op, ids, payload = event
            if op == "update":
                registry.update_rows("R0", ids, payload)
            elif op == "delete":
                registry.delete_rows("R0", ids)
            else:
                registry.insert_rows("R0", payload)
            continue
        _kind, query = event
        got = sorted(svc.answers(svc.submit(query)))
        answers.append(got)
        if check_cold:
            stale += int(got != _cold_answers(query, registry))
    out = {"answers": answers, "stale_answers": stale, **svc.summary()}
    if ivm:
        out["fallback_reasons"] = dict(svc._ivm.fallback_reasons)
    return out


@pytest.fixture(scope="module")
def ivm():
    tables = _instance(ROWS, missing_rate=0.25, seed=14)
    events = _events(60, mutate_every=4, rows=ROWS, seed=14)
    return {
        "off": _serve(events, tables, ivm=False, check_cold=False),
        "on": _serve(events, tables, ivm=True, check_cold=True),
    }


def test_ivm_patches_results(ivm):
    assert ivm["on"]["results_patched"] > 0, ivm["on"]["fallback_reasons"]


def test_patched_answers_match_cold_replay(ivm):
    assert ivm["on"]["stale_answers"] == 0


def test_patched_answers_match_evicting_service(ivm):
    assert ivm["on"]["answers"] == ivm["off"]["answers"]


def test_patching_gains_result_cache_hits(ivm):
    assert (ivm["on"]["queries_result_cache_hit"]
            > ivm["off"]["queries_result_cache_hit"])


def test_evicting_service_patches_nothing(ivm):
    assert ivm["off"]["results_patched"] == 0
    assert ivm["off"]["ivm_fallbacks"] == 0
