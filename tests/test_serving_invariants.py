"""Serving acceptance invariants on the skewed multi-tenant wifi stream.

Four workloads, each run once per module by a fixture and checked by one
test per invariant:

* **sharing** — a 20-query overlapping stream served one query at a time
  from cold engines (``serial``), by ``QuipService`` with per-query
  imputation isolation, and by ``QuipService`` with one ``ImputeStore``
  across queries (``shared_impute``);
* **result cache** — a repeat-heavy stream served submit → drain with the
  result cache off and on, plus a ``mutating_workload`` replay against an
  epoch-versioned ``TableRegistry`` where every answer is compared with a
  cold service built on the post-mutation tables;
* **QoS** — a two-tenant stream where tenant 0 floods and tenant 1 is the
  victim, served under ``rr``, ``rr`` plus an admission quota, ``wfq`` and
  ``deadline``.  The scheduler's ``unit`` cost model makes every fairness
  figure step accounting, not wall clock;
* **worker pool** — the stream served by 1, 2 and 4 worker threads, and at
  4 workers under every policy with and without the shared store.  The
  k-NN imputer sleeps 40 ms per call, a model of native inference that
  releases the GIL, so throughput can scale across threads.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np
import pytest

from repro.core.executor import execute_quip
from repro.data.queries import mutating_workload, serving_workload
from repro.data.synthetic import wifi_dataset
from repro.imputers.base import ImputationService, Imputer
from repro.imputers.knn import KnnImputer
from repro.service import QuipService, TableRegistry


STRATEGY = "adaptive"
MORSEL_ROWS = 4096


def _knn() -> Imputer:
    return KnnImputer(k=5, cost_per_value=2e-3)


def _serial(stream, tables, factory, strategy, morsel_rows) -> Dict:
    """Cold-engine replay, one query at a time: the oracle for every
    served configuration."""
    answers = []
    imputations = batches = 0
    for _tenant, q in stream:
        eng = ImputationService(
            {t: tables[t].copy() for t in q.tables}, default=factory,
        )
        res = execute_quip(q, tables, eng, strategy=strategy,
                           morsel_rows=morsel_rows)
        answers.append(sorted(res.answer_tuples()))
        imputations += res.counters.imputations
        batches += res.counters.impute_batches
    return {"answers": answers, "imputations": imputations,
            "impute_batches": batches}


# --------------------------------------------------------------------------- #
# sharing: plan cache and shared impute store
# --------------------------------------------------------------------------- #


def _served(stream, tables, *, shared: bool) -> Dict:
    svc = QuipService(
        tables, _knn, strategy=STRATEGY, morsel_rows=MORSEL_ROWS,
        shared_impute=shared, max_inflight=4,
    )
    tickets = [svc.submit(q, tenant=tenant) for tenant, q in stream]
    svc.run_until_idle()
    return {"answers": [sorted(svc.answers(t)) for t in tickets],
            **svc.summary()}


@pytest.fixture(scope="module")
def sharing():
    tables, _ = wifi_dataset(n_users=150, n_wifi=2000, n_occ=1000)
    stream = list(serving_workload("wifi", tables, n_queries=20,
                                   n_templates=6, n_tenants=4, seed=5))
    return {
        "serial": _serial(stream, tables, _knn, STRATEGY, MORSEL_ROWS),
        "service": _served(stream, tables, shared=False),
        "shared": _served(stream, tables, shared=True),
    }


def test_service_answers_match_serial(sharing):
    assert sharing["service"]["answers"] == sharing["serial"]["answers"]


def test_shared_store_answers_match_serial(sharing):
    assert sharing["shared"]["answers"] == sharing["serial"]["answers"]


def test_shared_store_saves_imputer_invocations(sharing):
    assert (sharing["shared"]["impute_batches"]
            < sharing["serial"]["impute_batches"])


def test_skewed_stream_hits_plan_cache(sharing):
    assert sharing["shared"]["plan_cache_hits"] > 0


# --------------------------------------------------------------------------- #
# result cache and mutation invalidation
# --------------------------------------------------------------------------- #
def _sequential(stream, tables, *, result_cache_size: int) -> Dict:
    """Submit → drain each query in turn, so a repeat can hit the result
    cache at submit time."""
    svc = QuipService(
        tables, _knn, strategy=STRATEGY, morsel_rows=MORSEL_ROWS,
        result_cache_size=result_cache_size,
    )
    answers = [sorted(svc.answers(svc.submit(q, tenant=tenant)))
               for tenant, q in stream]
    return {"answers": answers, **svc.summary()}


def _mutation_replay(tables) -> Dict:
    """A long-lived service with the result cache and the shared store on,
    against a cold service per query on the post-mutation tables."""
    registry = TableRegistry({t: r.copy() for t, r in tables.items()})
    svc = QuipService(
        registry, _knn, strategy=STRATEGY, morsel_rows=MORSEL_ROWS,
        shared_impute=True,
    )
    events = list(mutating_workload("wifi", tables, n_queries=12,
                                    mutate_every=3, n_templates=4, seed=9))
    mismatches = 0
    for event in events:
        if event[0] == "mutate":
            event[1].apply(registry)
            continue
        _kind, tenant, q = event
        got = sorted(svc.answers(svc.submit(q, tenant=tenant)))
        cold = QuipService(
            {t: registry[t].copy() for t in registry}, _knn,
            strategy=STRATEGY, morsel_rows=MORSEL_ROWS,
            result_cache_size=0,
        )
        mismatches += int(got != sorted(cold.answers(cold.submit(q))))
    return {"mismatches": mismatches, **svc.summary()}


@pytest.fixture(scope="module")
def result_cache():
    tables, _ = wifi_dataset(n_users=150, n_wifi=2000, n_occ=1000)
    # repeat-heavy: few templates, strong skew → many repeated signatures
    stream = list(serving_workload("wifi", tables, n_queries=24,
                                   n_templates=4, n_tenants=4, skew=1.4,
                                   seed=5))
    return {
        "off": _sequential(stream, tables, result_cache_size=0),
        "on": _sequential(stream, tables, result_cache_size=128),
        "replay": _mutation_replay(tables),
    }


def test_repeats_hit_result_cache(result_cache):
    assert result_cache["on"]["result_cache_hits"] > 0


def test_cached_answers_match_uncached(result_cache):
    assert result_cache["on"]["answers"] == result_cache["off"]["answers"]


def test_cached_repeats_skip_imputation(result_cache):
    assert (result_cache["on"]["imputations"]
            < result_cache["off"]["imputations"])


def test_no_stale_answer_across_mutations(result_cache):
    assert result_cache["replay"]["mismatches"] == 0


def test_mutations_invalidate_caches(result_cache):
    assert result_cache["replay"]["invalidation_events"] > 0


# --------------------------------------------------------------------------- #
# QoS: per-tenant scheduling under an aggressor
# --------------------------------------------------------------------------- #
QOS_MORSEL_ROWS = 16  # small morsels: many scheduler steps per query
AGGRESSOR, VICTIM = 0, 1  # zipf rank 1 floods; rank 2 is the victim
QOS_MODES = ("rr", "rr_quota", "wfq", "deadline")


def _run_policy(stream, tables, mode: str, victim_deadline: float) -> Dict:
    svc = QuipService(
        tables, _knn, strategy=STRATEGY, morsel_rows=QOS_MORSEL_ROWS,
        shared_impute=False, max_inflight=6,
        result_cache_size=0,  # every repeat re-executes: pure scheduling
        scheduler_policy="rr" if mode == "rr_quota" else mode,
        cost_model="unit",  # deterministic step accounting, no wall clock
        tenant_deadlines={VICTIM: victim_deadline},
        tenant_quotas={AGGRESSOR: 2} if mode == "rr_quota" else None,
    )
    tickets = [svc.submit(q, tenant=tenant) for tenant, q in stream]
    svc.run_until_idle()
    ts = svc.tenant_summary()
    victim_recs = [r for r in svc.serving.records if r.tenant == VICTIM]
    # residency share: of the scheduler steps that elapsed while a victim
    # query was in the system (admission → completion), how many did that
    # query get?  1/2 is the two-tenant fair share; round-robin under an
    # aggressor flood of k sessions degrades it toward 1/(k+1)
    victim_share = sum(
        r.steps / r.turnaround_cost for r in victim_recs
    ) / len(victim_recs)
    return {
        "answers": [sorted(svc.answers(t)) for t in tickets],
        "victim_queries": len(victim_recs),
        "victim_steps": ts[VICTIM]["steps"],
        "victim_share": victim_share,
        "victim_p95_turnaround": ts[VICTIM]["p95_turnaround_cost"],
        "victim_deadline_hit_rate": ts[VICTIM]["deadline_hit_rate"],
    }


@pytest.fixture(scope="module")
def qos():
    tables, _ = wifi_dataset(n_users=120, n_wifi=1500, n_occ=800)
    stream = list(serving_workload("wifi", tables, n_queries=30,
                                   n_templates=6, n_tenants=2, seed=5,
                                   tenant_skew=1.8))
    serial = _serial(stream, tables, _knn, STRATEGY, QOS_MORSEL_ROWS)
    # the victim's own per-query step count under round-robin sizes its
    # deadline: generous for its own work, tight behind the aggressor
    probe = _run_policy(stream, tables, "rr", victim_deadline=float("inf"))
    victim_deadline = 1.5 * probe["victim_steps"] / max(
        probe["victim_queries"], 1)
    runs = {mode: _run_policy(stream, tables, mode, victim_deadline)
            for mode in QOS_MODES}
    return {"serial": serial["answers"], **runs}


@pytest.mark.parametrize("mode", QOS_MODES)
def test_policy_answers_match_serial(qos, mode):
    assert qos[mode]["answers"] == qos["serial"]


def test_wfq_raises_victim_share_over_rr(qos):
    assert qos["wfq"]["victim_share"] > qos["rr"]["victim_share"]


def test_wfq_cuts_victim_p95_turnaround(qos):
    assert (qos["wfq"]["victim_p95_turnaround"]
            < qos["rr"]["victim_p95_turnaround"])


def test_deadline_policy_hits_victim_deadlines(qos):
    assert (qos["deadline"]["victim_deadline_hit_rate"]
            >= qos["rr"]["victim_deadline_hit_rate"])


# --------------------------------------------------------------------------- #
# worker pool: answers under real concurrency, throughput scaling
# --------------------------------------------------------------------------- #
POOL_STRATEGY = "lazy"
POOL_MORSEL_ROWS = 1024
SLEEP_S = 0.040  # per impute_attr call: the part that releases the GIL
POOL_TIMEOUT_S = 180


class _InferenceLatencyImputer(Imputer):
    """k-NN with a fixed sleep per call, a model of a model server or
    native inference call that releases the GIL while it runs."""

    def __init__(self):
        self._inner = _knn()
        self.blocking = self._inner.blocking
        self.cost_per_value = self._inner.cost_per_value
        self.train_cost = self._inner.train_cost

    def fit(self, table) -> None:
        self._inner.fit(table)

    def impute_attr(self, table, attr: str, tids: np.ndarray) -> np.ndarray:
        time.sleep(SLEEP_S)
        return self._inner.impute_attr(table, attr, tids)


def _pooled(stream, tables, workers: int, policy: str = "rr",
            shared: bool = False) -> Dict:
    # result cache off: repeated templates must re-execute, or the pool
    # has nothing to parallelise and qps measures cache lookups
    svc = QuipService(
        tables, _InferenceLatencyImputer, strategy=POOL_STRATEGY,
        morsel_rows=POOL_MORSEL_ROWS, shared_impute=shared, max_inflight=8,
        result_cache_size=0, scheduler_policy=policy, workers=workers,
    )
    t0 = time.perf_counter()
    tickets = [svc.submit(q, tenant=tenant) for tenant, q in stream]
    svc.run_until_idle()
    wall = time.perf_counter() - t0
    answers = [sorted(svc.answers(t)) for t in tickets]
    failed = svc.summary()["failed"]
    svc.close()
    return {"answers": answers, "failed": failed,
            "qps": len(stream) / wall}


@pytest.fixture(scope="module")
def pool():
    tables, _ = wifi_dataset(n_users=100, n_wifi=1200, n_occ=600)
    stream = list(serving_workload("wifi", tables, n_queries=16,
                                   n_templates=6, n_tenants=4, seed=5))
    serial = _serial(stream, tables, _InferenceLatencyImputer,
                     POOL_STRATEGY, POOL_MORSEL_ROWS)
    # throughput scaling: isolation + rr, so the only cross-thread
    # serialisation is the scheduler checkout, not the shared store
    runs: Dict[str, Dict] = {
        f"pool{w}_rr": _pooled(stream, tables, w) for w in (1, 2, 4)
    }
    # answer matrix at 4 workers: every policy × sharing mode
    for policy in ("rr", "wfq", "deadline"):
        for shared in (False, True):
            if policy == "rr" and not shared:
                continue  # already run in the scaling sweep
            mode = f"pool4_{policy}" + ("_shared" if shared else "")
            runs[mode] = _pooled(stream, tables, 4, policy, shared)
    return {"serial": serial["answers"], "runs": runs}


@pytest.mark.timeout(POOL_TIMEOUT_S)
def test_pool_fails_no_query(pool):
    failed = {m: r["failed"] for m, r in pool["runs"].items() if r["failed"]}
    assert not failed, failed


@pytest.mark.timeout(POOL_TIMEOUT_S)
def test_pool_answers_match_serial(pool):
    diverged: List[str] = [m for m, r in pool["runs"].items()
                           if r["answers"] != pool["serial"]]
    assert not diverged, diverged


@pytest.mark.timeout(POOL_TIMEOUT_S)
def test_pool_qps_scales_2x_from_1_to_4_workers(pool):
    qps1 = pool["runs"]["pool1_rr"]["qps"]
    qps4 = pool["runs"]["pool4_rr"]["qps"]
    assert qps4 >= 2.0 * qps1, f"qps1={qps1} qps4={qps4}"
