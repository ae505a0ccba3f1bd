"""Shared benchmark harness: run a workload under the paper's strategies and
collect (#imputations, runtime, temp tuples) — the quantities of every table
and figure in §7."""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.executor import execute_offline, execute_quip, make_plan
from repro.core.plan import Query
from repro.core.relation import MaskedRelation
from repro.imputers import (
    ImputationEngine,
    KnnImputer,
    LocaterImputer,
    MeanImputer,
    XgbHistImputer,
)

__all__ = ["IMPUTER_FACTORIES", "run_workload", "StrategyResult"]

# Simulated per-value / training costs follow the paper's Fig. 2 profile:
# KNN: expensive inference; XGBoost: training dominates; LOCATER: expensive
# per value; Mean: free.
IMPUTER_FACTORIES: Dict[str, Callable[[], object]] = {
    "mean": lambda: MeanImputer(),
    "knn": lambda: KnnImputer(k=5, cost_per_value=2e-3),
    "xgboost": lambda: XgbHistImputer(rounds=16, train_cost=1.0,
                                      cost_per_value=2e-5),
    "locater": lambda: LocaterImputer(cost_per_value=4e-3),
}


@dataclasses.dataclass
class StrategyResult:
    strategy: str
    imputations: int
    impute_batches: int  # imputer invocations (batched-service flush batches)
    wall_seconds: float
    temp_tuples: int
    filtered_by_bloom: int
    trigger_joins: int
    answers: List[tuple]


def _engine(tables, imputer: str) -> ImputationEngine:
    return ImputationEngine(
        {t: r.copy() for t, r in tables.items()},
        default=IMPUTER_FACTORIES[imputer],
    )


def run_workload(
    tables: Dict[str, MaskedRelation],
    queries: List[Query],
    imputer: str,
    strategies=("offline", "eager", "lazy", "adaptive"),
    planner: str = "imputedb",
    morsel_rows: int = 4096,
    minmax_opt: bool = True,
) -> Dict[str, StrategyResult]:
    out: Dict[str, StrategyResult] = {}
    for strat in strategies:
        imps = batches = wall = temps = bloom = trig = 0
        answers: List[tuple] = []
        for q in queries:
            eng = _engine(tables, imputer)
            if strat == "offline":
                res = execute_offline(q, tables, eng)
            else:
                res = execute_quip(
                    q, tables, eng, strategy=strat, planner=planner,
                    morsel_rows=morsel_rows, minmax_opt=minmax_opt,
                )
            imps += res.counters.imputations
            batches += res.counters.impute_batches
            wall += res.counters.wall_seconds
            temps += res.counters.temp_tuples
            bloom += res.counters.filtered_by_bloom
            trig += res.counters.trigger_joins
            answers.extend(res.answer_tuples())
        out[strat] = StrategyResult(
            strat, imps, batches, wall, temps, bloom, trig, answers
        )
    return out
