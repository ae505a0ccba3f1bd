"""KnnImputer keeps each attribute's reference rows on the device.

The first ``impute_attr`` of an attribute after ``fit`` selects the rows
that observe it and places them on the device; every later batch sends
only its query rows.  These tests pin that residency changes nothing the
imputer returns (bit-identical to the per-batch host-array path), never
outlives a refit, is counted once in ``knn:call``'s ``h2d_bytes``, and
compiles nothing beyond the programs a numpy warm-up compiled.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.relation import MaskedRelation
from repro.core.schema import ColumnSpec, Schema
from repro.imputers.base import ImputeStore
from repro.imputers.knn import KnnImputer
from repro.kernels import ops as kops
from repro.obs import Tracer
from repro.service.registry import TableRegistry

UNIT = dict(enabled=True, clock="unit")
BATCH = 128


def _table(n: int, seed: int, missing: float = 0.4) -> MaskedRelation:
    """``T`` with float ``a``, dictionary-coded int ``c`` and two more
    features; ``a`` and ``c`` miss ``missing`` of their cells."""
    rng = np.random.default_rng(seed)
    schema = Schema("T", [ColumnSpec("T.a", "float"), ColumnSpec("T.b", "float"),
                          ColumnSpec("T.c", "int"), ColumnSpec("T.e", "int")])
    cols = {"T.a": rng.normal(size=n), "T.b": rng.normal(size=n),
            "T.c": rng.integers(0, 5, n), "T.e": rng.integers(0, 40, n)}
    miss = {"T.a": rng.random(n) < missing, "T.c": rng.random(n) < missing,
            "T.b": rng.random(n) < 0.1}
    return MaskedRelation.from_columns(schema, cols, miss)


def _host_path(model: KnnImputer, table: MaskedRelation, attr: str,
               tids: np.ndarray) -> np.ndarray:
    """The oracle: reference rows selected and handed to ``masked_knn`` as
    host arrays in every batch."""
    ai = model._cols.index(attr)
    ref_rows = model._mask[:, ai] > 0
    r, rm = model._feat[ref_rows], model._mask[ref_rows]
    tgt = table.values(attr)[ref_rows.nonzero()[0]]
    keep = np.ones(model._feat.shape[1], dtype=bool)
    keep[ai] = False
    is_int = not np.issubdtype(table.cols[attr].dtype, np.floating)
    out = np.zeros(len(tids), dtype=np.float64)
    for lo in range(0, len(tids), model.batch):
        idx = tids[lo:lo + model.batch]
        q, qm = model._feat[idx][:, keep], model._mask[idx][:, keep]
        _d, nn = kops.masked_knn(q, qm, r[:, keep], rm[:, keep],
                                 k=min(model.k, r.shape[0]), impl=model.impl)
        out[lo:lo + len(idx)] = kops.neighbor_aggregate(
            tgt[nn], categorical=is_int, impl=model.agg_impl)
    return out


def _missing_tids(table: MaskedRelation, attr: str) -> np.ndarray:
    return table.missing[attr].nonzero()[0].astype(np.int64)


# --------------------------------------------------------------------------- #
# equivalence with the host-array path
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("impl", ["ref", "pallas", "numpy"])
def test_resident_reference_is_bit_identical(impl):
    # 480 rows, ~200 observe each attribute: the distances are (128, ~200),
    # the scale of the kernel tests' (130, 200, 96) case
    table = _table(480, seed=3, missing=0.58)
    model = KnnImputer(k=3, impl=impl, batch=BATCH)
    model.fit(table)
    for attr in ("T.a", "T.a", "T.c"):  # reuse, then another attribute
        tids = _missing_tids(table, attr)
        assert len(tids) > 2 * BATCH
        got = model.impute_attr(table, attr, tids)
        assert np.array_equal(got, _host_path(model, table, attr, tids))
    on_device = impl != "numpy"
    assert set(model._refs) == {"T.a", "T.c"}
    assert all(isinstance(x, jax.Array) is on_device
               for ref in model._refs.values() for x in ref[:2])


# --------------------------------------------------------------------------- #
# staleness: a refit after a table mutation drops the kept rows
# --------------------------------------------------------------------------- #
def test_refit_after_mutation_drops_resident_rows():
    registry = TableRegistry({"T": _table(300, seed=5)})
    store = ImputeStore(registry)
    model = KnnImputer(k=3, batch=BATCH)
    per_attr = {"T.a": model}

    def impute():
        with store.flush_lock("T", "T.a"):
            m, _wall = store.model_for("T", "T.a", KnnImputer, per_attr)
            assert m is model
            return m.impute_attr(registry["T"], "T.a",
                                 _missing_tids(registry["T"], "T.a"))

    before = impute()
    observed = (~registry["T"].missing["T.a"]).nonzero()[0]
    registry.update_rows("T", observed,
                         {"T.a": registry["T"].values("T.a")[observed] + 100.0})
    store.invalidate("T")
    after = impute()
    fresh = KnnImputer(k=3, batch=BATCH)
    fresh.fit(registry["T"])
    expect = fresh.impute_attr(registry["T"], "T.a",
                               _missing_tids(registry["T"], "T.a"))
    assert np.array_equal(after, expect)
    assert not np.allclose(after, before)


# --------------------------------------------------------------------------- #
# accounting: the upload is counted once per (fit, attribute)
# --------------------------------------------------------------------------- #
def test_reference_upload_counted_once_per_fit_and_attribute():
    table = _table(400, seed=7, missing=0.7)
    model = KnnImputer(k=3, batch=BATCH)
    tracer = Tracer(**UNIT)
    model.tracer = tracer
    for _fit in range(2):
        model.fit(table)
        for attr in ("T.a", "T.a", "T.c"):
            model.impute_attr(table, attr, _missing_tids(table, attr))
    spans = tracer.spans(name="knn:call")
    assert len(spans) > 2 * 3  # more than one batch per call
    first = [s for s in spans if not s.args["ref_resident"]]
    # one per (fit, attribute), in order
    assert [s.args["attr"] for s in first] == ["T.a", "T.c"] * 2
    for s in spans:
        a = s.args
        query = 4 * a["d"] * 2 * a["nq_padded"]
        ref = 0 if a["ref_resident"] else 4 * a["d"] * 2 * a["nr"]
        assert a["h2d_bytes"] == query + ref


# --------------------------------------------------------------------------- #
# compiles: the resident path reuses the programs a numpy warm-up built
# --------------------------------------------------------------------------- #
class _Compiles:
    def __init__(self):
        self.n = 0

    def __call__(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def test_resident_path_compiles_nothing_after_numpy_warm_up():
    # a reference row count no other test uses, so the warm-up compiles
    table = _table(251, seed=11, missing=0.2)
    attr = "T.a"
    model = KnnImputer(k=3, batch=BATCH)
    model.fit(table)
    nr = int((~table.missing[attr]).sum())
    d = len(table.column_names()) - 1
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        # the benchmark's warm-up: zeros at each (reference, query) shape
        r = np.zeros((nr, d), np.float32)
        q = np.zeros((BATCH, d), np.float32)
        kops.masked_knn(q, q, r, r, min(model.k, nr))
        warmed = compiles.n
        for _ in range(2):
            model.impute_attr(table, attr, _missing_tids(table, attr))
        in_window = compiles.n - warmed
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert warmed >= 1
    assert in_window == 0
