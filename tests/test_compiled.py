"""Compiled tensor plans (docs/compiled.md): lowering, fallback, segment
kernels, plan-cache hotness/artifacts, and the serving-layer promotion path.

The correctness contract under test everywhere: the compiled whole-relation
program and the morsel interpreter return **bit-identical answers and
imputation counts** — compilation is an optimization, never a semantics
change.  The complementary strategy-matrix test lives in
``test_strategy_equivalence.py::test_compiled_exec_matches_interp``.
"""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from repro.core.compiled import (
    CompileFallback,
    CompiledPlan,
    compile_plan,
    resolve_exec_impl,
)
from repro.core.env import env_choice
from repro.core.executor import execute_quip, make_plan
from repro.core.plan import Aggregate, Query
from repro.core.predicates import JoinPredicate, SelectionPredicate
from repro.core.triggers import resolve_join_impl
from repro.imputers.base import ImputationEngine
from repro.kernels import ops as kops
from repro.service.plan_cache import PlanCache
from repro.service.registry import TableRegistry
from repro.service.server import QuipService
from test_quip_correctness import GroundTruthImputer, _build_instance


# --------------------------------------------------------------------- #
# instance helpers
# --------------------------------------------------------------------- #
def _instance(seed: int = 7, rows: int = 24):
    rng = np.random.default_rng(seed)
    tables, clean, truth = _build_instance(rng, 2, rows, 0.3, 5)
    return tables, clean, truth


def _query(agg=None):
    return Query(
        tables=("R0", "R1"),
        selections=(SelectionPredicate("R0.v", "<=", 3),),
        joins=(JoinPredicate("R0.k1", "R1.k1"),),
        projection=() if agg is not None else ("R0.v", "R1.v"),
        aggregate=agg,
    )


def _engine(tables, truth):
    return ImputationEngine(
        {t: tables[t].copy() for t in tables},
        default=lambda: GroundTruthImputer(truth),
    )


# --------------------------------------------------------------------- #
# env_choice (satellite: shared env-var parsing)
# --------------------------------------------------------------------- #
def test_env_choice_parses_and_defaults(monkeypatch):
    monkeypatch.delenv("QUIP_TEST_CHOICE", raising=False)
    assert env_choice("QUIP_TEST_CHOICE", ("a", "b"), "a") == "a"
    monkeypatch.setenv("QUIP_TEST_CHOICE", "")
    assert env_choice("QUIP_TEST_CHOICE", ("a", "b"), "a") == "a"
    monkeypatch.setenv("QUIP_TEST_CHOICE", "  B ")
    assert env_choice("QUIP_TEST_CHOICE", ("a", "b"), "a") == "b"


def test_env_choice_garbage_raises(monkeypatch):
    monkeypatch.setenv("QUIP_TEST_CHOICE", "banana")
    with pytest.raises(ValueError, match="QUIP_TEST_CHOICE"):
        env_choice("QUIP_TEST_CHOICE", ("a", "b"), "a")


@pytest.mark.parametrize(
    "var,resolver",
    [
        ("QUIP_EXEC_IMPL", resolve_exec_impl),
        ("QUIP_JOIN_IMPL", resolve_join_impl),
        ("QUIP_KNN_IMPL", kops.resolve_knn_impl),
        ("QUIP_SEGMENT_IMPL", kops.resolve_segment_impl),
    ],
)
def test_impl_env_garbage_raises(var, resolver, monkeypatch):
    monkeypatch.setenv(var, "warp-drive")
    with pytest.raises(ValueError, match=var):
        resolver()


def test_resolve_exec_impl_explicit(monkeypatch):
    monkeypatch.setenv("QUIP_EXEC_IMPL", "compiled")
    assert resolve_exec_impl("interp") == "interp"  # explicit beats env
    assert resolve_exec_impl() == "compiled"
    with pytest.raises(ValueError, match="unknown exec impl"):
        resolve_exec_impl("jit")


# --------------------------------------------------------------------- #
# segment reductions (kernels/segment_ops.py + kernels/ops.py)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("impl", ["numpy", "ref", "pallas"])
@pytest.mark.parametrize("op", ["count", "sum", "min", "max"])
def test_segment_reduce_impls_agree(impl, op):
    rng = np.random.default_rng(3)
    seg = rng.integers(0, 9, size=300).astype(np.int64)
    seg[seg == 7] = 8  # leave segment 7 empty
    vals = rng.integers(-50, 50, size=300).astype(np.int64)
    got = kops.segment_reduce(vals, seg, 10, op, impl=impl)
    ref = kops.segment_reduce(vals, seg, 10, op, impl="numpy")
    np.testing.assert_array_equal(got, ref)


def test_segment_reduce_numpy_bit_identical_to_groupwise_float():
    """The serving-default numpy impl must reproduce the interpreter's
    per-group ``group.sum()`` at full float64 bit precision (stable argsort
    → contiguous slice → pairwise sum)."""
    rng = np.random.default_rng(5)
    seg = rng.integers(0, 6, size=500).astype(np.int64)
    vals = rng.normal(size=500)
    got = kops.segment_reduce(vals, seg, 6, "sum", impl="numpy")
    oracle = np.array([vals[seg == s].sum() for s in range(6)])
    assert got.tolist() == oracle.tolist()  # exact equality, not allclose


def test_segment_reduce_negative_ids_dropped():
    seg = np.array([0, -1, 1, -1, 0], dtype=np.int64)
    vals = np.array([1, 100, 2, 100, 3], dtype=np.int64)
    for impl in ("numpy", "ref", "pallas"):
        assert kops.segment_reduce(vals, seg, 2, "sum", impl=impl).tolist() \
            == [4, 2]
        assert kops.segment_reduce(vals, seg, 2, "count", impl=impl).tolist() \
            == [2, 1]


# --------------------------------------------------------------------- #
# compile_plan: eligibility + aggregate lowering
# --------------------------------------------------------------------- #
def test_compile_fallback_reasons():
    tables, _clean, truth = _instance()
    q = _query()
    plan = make_plan(q, tables)
    with pytest.raises(CompileFallback, match="defer"):
        compile_plan(q, plan, tables, "lazy", use_vf=False, minmax_opt=False)
    with pytest.raises(CompileFallback, match="VF"):
        compile_plan(q, plan, tables, "eager", use_vf=True, minmax_opt=False)
    qm = _query(Aggregate("max", "R1.v"))
    pm = make_plan(qm, tables)
    with pytest.raises(CompileFallback, match="MIN/MAX"):
        compile_plan(qm, pm, tables, "eager", use_vf=False, minmax_opt=True)
    # the imputedb alias forces eager + use_vf=False itself → compiles
    cp = compile_plan(q, plan, tables, "imputedb")
    assert isinstance(cp, CompiledPlan)


@pytest.mark.parametrize("group_by", [None, "R1.v"])
@pytest.mark.parametrize("op", ["count", "sum", "avg", "min", "max"])
def test_compiled_aggregates_match_interp(op, group_by):
    tables, _clean, truth = _instance(seed=11)
    q = _query(Aggregate(op, "R0.v", group_by=group_by))
    kwargs = dict(strategy="eager", morsel_rows=7, use_vf=False,
                  minmax_opt=False)
    base = execute_quip(q, tables, _engine(tables, truth), **kwargs)
    comp = execute_quip(q, tables, _engine(tables, truth),
                        exec_impl="compiled", **kwargs)
    assert comp.counters.exec_impl == "compiled"
    assert comp.counters.compiled_hits == 1
    assert Counter(comp.answer_tuples()) == Counter(base.answer_tuples())
    assert comp.counters.imputations == base.counters.imputations


@pytest.mark.parametrize("segment_impl", ["numpy", "ref", "pallas"])
def test_compiled_grouped_agg_segment_impls(segment_impl, monkeypatch):
    """QUIP_SEGMENT_IMPL routes the grouped reduction through the numpy /
    jax.ops / Pallas segment kernels; integer aggregates stay identical."""
    monkeypatch.setenv("QUIP_SEGMENT_IMPL", segment_impl)
    tables, _clean, truth = _instance(seed=13)
    q = _query(Aggregate("sum", "R0.v", group_by="R1.v"))
    kwargs = dict(strategy="eager", morsel_rows=7, use_vf=False,
                  minmax_opt=False)
    base = execute_quip(q, tables, _engine(tables, truth), **kwargs)
    comp = execute_quip(q, tables, _engine(tables, truth),
                        exec_impl="compiled", **kwargs)
    assert Counter(comp.answer_tuples()) == Counter(base.answer_tuples())


# --------------------------------------------------------------------- #
# PlanCache: per-signature hits, eviction, artifacts (satellite 2)
# --------------------------------------------------------------------- #
def test_plan_cache_hit_counts_and_eviction_at_capacity_one():
    tables, _clean, _truth = _instance()
    cache = PlanCache(capacity=1)
    q1, q2 = _query(), _query(Aggregate("count", None))

    cache.get(q1, tables)  # miss → planned + interned
    assert cache.hit_count(q1) == 0
    cache.get(q1, tables)  # hit
    cache.get(q1, tables)  # hit
    assert cache.hit_count(q1) == 2

    cache.get(q2, tables)  # miss at capacity 1 → evicts q1's entry
    assert cache.stats()["evictions"] == 1
    assert cache.hit_count(q1) == 0  # hotness died with the entry
    _plan, hit = cache.get(q1, tables)  # re-planned from scratch
    assert not hit

    summary = cache.summary()
    assert summary["size"] == 1
    assert summary["compiled"] == 0
    assert sum(summary["signature_hits"].values()) == 0


def test_plan_cache_artifact_epoch_gate():
    tables, _clean, _truth = _instance()
    cache = PlanCache(capacity=4)
    q = _query()
    plan, _hit = cache.get(q, tables)
    artifact = compile_plan(q, plan, tables, "eager", use_vf=False,
                            minmax_opt=False)

    cache.store_compiled(q, "eager", (0, 0), artifact)
    assert cache.compiled_artifact(q, "eager", (0, 0)) is artifact
    assert cache.compiled_count() == 1
    # stale epochs: never served, and dropped on sight
    assert cache.compiled_artifact(q, "eager", (1, 0)) is None
    assert cache.compiled_count() == 0
    # cached fallbacks are artifacts too, but not "compiled" in telemetry
    cache.store_compiled(q, "lazy", (0, 0), CompileFallback("nope"))
    assert cache.compiled_count() == 0
    assert isinstance(cache.compiled_artifact(q, "lazy", (0, 0)),
                      CompileFallback)
    # table mutation hook drops the whole entry, artifacts included
    cache.store_compiled(q, "eager", (0, 0), artifact)
    assert cache.invalidate_table("R0") == 1
    assert cache.compiled_count() == 0
    assert cache.hit_count(q) == 0


# --------------------------------------------------------------------- #
# QuipService: promotion on the Kth hit + epoch invalidation
# --------------------------------------------------------------------- #
def _service(tables, truth, **kw):
    registry = TableRegistry({t: r.copy() for t, r in tables.items()})
    service = QuipService(
        registry,
        imputer_factory=lambda: GroundTruthImputer(truth),
        strategy="eager",
        use_vf=False,
        minmax_opt=False,
        morsel_rows=7,
        result_cache_size=0,
        shared_impute=False,
        **kw,
    )
    return registry, service


def _canon(answers):
    return Counter(tuple(repr(v) for v in t) for t in answers)


def test_service_promotes_on_kth_hit_and_invalidates_on_mutation():
    tables, _clean, truth = _instance()
    q = _query()
    reg_c, svc_c = _service(tables, truth, exec_impl="compiled",
                            compile_after_hits=2)
    reg_i, svc_i = _service(tables, truth)

    def run(svc):
        return _canon(svc.answers(svc.submit(q)))

    for _ in range(4):
        assert run(svc_c) == run(svc_i)
    impls = [r.counters.exec_impl for r in svc_c.serving.records]
    # submissions 1–2 are hits 0 and 1 (< K=2); 3–4 run compiled
    assert impls == ["interp", "interp", "compiled", "compiled"]
    summary = svc_c.summary()
    assert summary["compiled_hits"] == 2
    assert summary["compile_fallbacks"] == 0
    assert summary["plan_cache_compiled"] == 1
    assert summary["exec_impl"] == "compiled"

    # mutation bumps the epoch: the artifact (and plan) die with the entry
    rows = np.array([0, 1])
    vals = {"R0.v": np.array([2, 3], dtype=np.int64)}
    reg_c.update_rows("R0", rows, vals)
    reg_i.update_rows("R0", rows, vals)
    assert svc_c.plan_cache.compiled_count() == 0
    for _ in range(4):
        assert run(svc_c) == run(svc_i)  # zero stale answers
    impls = [r.counters.exec_impl for r in svc_c.serving.records[4:]]
    assert impls == ["interp", "interp", "compiled", "compiled"]


def test_service_segment_impl_reaches_compiled_plans():
    """QuipService(segment_impl=) picks the grouped reduction of the plans
    it compiles, and answers stay those of the interpreter."""
    tables, _clean, truth = _instance()
    q = _query(Aggregate("count", "R0.v", group_by="R1.v"))
    _reg, svc_c = _service(tables, truth, exec_impl="compiled",
                           compile_after_hits=1, segment_impl="ref")
    _reg, svc_i = _service(tables, truth)
    before = kops.KERNEL_CALLS.snapshot().get("segment_reduce/ref", 0)
    for _ in range(2):
        assert (_canon(svc_c.answers(svc_c.submit(q)))
                == _canon(svc_i.answers(svc_i.submit(q))))
    after = kops.KERNEL_CALLS.snapshot().get("segment_reduce/ref", 0)
    assert svc_c.summary()["compiled_hits"] == 1
    assert after > before


def test_service_caches_fallback_for_ineligible_strategy():
    tables, _clean, truth = _instance()
    q = _query()
    _reg, svc = _service(tables, truth, exec_impl="compiled",
                         compile_after_hits=1)
    for _ in range(3):
        svc.answers(svc.submit(q, strategy="lazy"))
    summary = svc.summary()
    # hits 1 and 2 consult the (cached) fallback — lowering ran only once
    assert summary["compile_fallbacks"] == 2
    assert summary["compiled_hits"] == 0
    assert svc.plan_cache.compiled_count() == 0
    impls = [r.counters.exec_impl for r in svc.serving.records]
    assert impls == ["interp"] * 3


def test_service_rejects_bad_compile_knobs():
    tables, _clean, truth = _instance()
    with pytest.raises(ValueError, match="compile_after_hits"):
        _service(tables, truth, compile_after_hits=0)
    with pytest.raises(ValueError, match="unknown exec impl"):
        _service(tables, truth, exec_impl="jit")
