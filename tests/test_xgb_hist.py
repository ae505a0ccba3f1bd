"""The XGBoost-hist device programs (``kernels/gbdt_hist.py``) and imputer
(``imputers/xgb_hist.py``) against a plain float64 numpy XGBoost-hist: the
benchmark's reference (``bench/xgb_reference.py``), at a tiny size on
the CPU."""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.relation import MaskedRelation
from repro.core.schema import ColumnSpec, Schema
from repro.imputers.base import ImputationService
from repro.imputers.xgb_hist import XgbHistImputer, warm_up
from repro.kernels import gbdt_hist as kg
from repro.obs import Tracer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
ROUNDS = 12
PARAMS = dict(rounds=ROUNDS, depth=6, eta=0.3, lam=1.0, gamma=0.0,
              min_child_weight=1.0)


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (``bench/xgb_reference.py``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import xgb_reference

    return xgb_reference


@pytest.fixture(scope="module")
def kind(ref):
    """The benchmark's imputer kind, loaded from its file."""
    spec = importlib.util.spec_from_file_location(
        "xgb_hist_kind", os.path.join(BENCH, "imputers", "xgb_hist.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tables(n: int, seed: int) -> dict:
    """One table ``T`` in the benchmark's dict form: ``T.id`` and five
    correlated float attributes rounded to 0.1, each missing a fifth of
    its cells."""
    rng = np.random.default_rng(seed)
    latent = rng.normal(0, 1, n)
    cols = {"T.id": np.arange(n, dtype=np.int64)}
    missing = {"T.id": np.zeros(n, bool)}
    columns = [("T.id", "int")]
    for a in "abcde":
        name = f"T.{a}"
        m = rng.random(n) < 0.2
        cols[name] = np.where(m, 0.0, np.round(
            rng.normal(50, 10, n) + 12 * latent, 1))
        missing[name] = m
        columns.append((name, "float"))
    return {"T": {"columns": columns, "cols": cols, "missing": missing}}


def _relation(tables: dict) -> MaskedRelation:
    tab = tables["T"]
    schema = Schema("T", [ColumnSpec(c, k) for c, k in tab["columns"]])
    return MaskedRelation.from_columns(
        schema, {c: tab["cols"][c].copy() for c, _k in tab["columns"]},
        missing={c: m.copy() for c, m in tab["missing"].items()})


def _fit_inputs(ref, tables: dict, attr: str, bucket=None, params=PARAMS):
    """The training rows' bins and ``g``, as the imputer hands them to
    ``boost``, and the reference's fit of the same rows."""
    tab = tables["T"]
    train = np.nonzero(~tab["missing"][attr])[0]
    names = [c for c, _k in tab["columns"]
             if c != attr and c.split(".")[-1] != "id"]
    bins = np.zeros((len(train), len(names)), np.int64)
    for j, c in enumerate(names):
        x, obs = tab["cols"][c], ~tab["missing"][c]
        bins[:, j] = ref.bins_of(x[train], obs[train],
                                 ref.cuts(x[train][obs[train]]))
    y = tab["cols"][attr][train]
    fit = ref._Fit(bins, y, params, False)
    n = bucket or kg.train_bucket(len(y))
    b = np.full((n, len(names)), kg.MISSING, np.int16)
    b[:len(y)] = bins
    g = fit.g0
    hi = np.zeros(n, np.float32)
    hi[:len(y)] = g
    lo = np.zeros(n, np.float32)
    lo[:len(y)] = g - hi[:len(y)]
    h = np.zeros(n, np.float32)
    h[:len(y)] = 1
    return (b, hi, lo, h, np.float32(kg.digit_unit(g)),
            np.int32(kg.filled_chunks(len(y)))), fit, len(y)


def _boost(args, **over):
    return [np.asarray(a) for a in kg.boost(*args, **dict(PARAMS, **over))]


def test_histogram_sums_are_exact_to_float32_rounding():
    rng = np.random.default_rng(0)
    n, nodes = kg.CHUNK, 4
    g = rng.normal(0, 20, n) * rng.choice([1e-3, 1.0, 30.0], n)
    q = kg.digit_unit(g)
    hi = g.astype(np.float32)
    lo = (g - hi).astype(np.float32)
    digits = np.asarray(kg._digits(hi, lo, np.float32(1 / q)))
    assert np.abs(digits.astype(np.float64)).max() <= 128
    node = rng.integers(0, nodes, n).astype(np.int32)
    slot = rng.integers(0, kg.SLOTS, n).astype(np.int32)
    w = np.concatenate([digits, np.ones((n, 1), digits.dtype)], axis=1)
    sums = np.asarray(kg._node_sums(w, node, nodes, 1,
                                    kg._onehot(slot[:, None])))
    G = np.asarray(kg._value(np.moveaxis(sums[:, :kg.LIMBS], 1, -1),
                             np.float32(q)))
    exact = np.zeros((nodes, kg.SLOTS))
    np.add.at(exact, (node, slot), g)
    count = np.zeros((nodes, kg.SLOTS))
    np.add.at(count, (node, slot), 1)
    assert np.array_equal(sums[:, kg.LIMBS], count)
    # one float32 rounding of a sum off by at most q a row
    tol = np.spacing(np.abs(exact).astype(np.float32)) + count * q
    assert (np.abs(G - exact) <= tol).all()


def test_onehot_marks_each_rows_bin_of_each_feature():
    rng = np.random.default_rng(10)
    bins = rng.integers(0, kg.SLOTS, (300, 3)).astype(np.int32)
    bins[rng.random(bins.shape) < 0.2] = kg.MISSING
    got = np.asarray(kg._onehot(bins))
    assert got.dtype == np.int8 and got.shape == (300, 3 * kg.SLOTS)
    want = np.zeros((300, 3, kg.SLOTS), np.int8)
    np.put_along_axis(want, bins[:, :, None], 1, axis=2)
    assert np.array_equal(got, want.reshape(300, -1))


def _node_sums_per_chunk(w, node, nodes, chunks, bins=None):
    """``kg._node_sums`` from the bins themselves: each chunk's one-hot
    compared and laid out inside the chunk loop, at every level."""
    k = w.shape[1]
    cols = 1 if bins is None else bins.shape[1] * kg.SLOTS
    ids = jnp.arange(nodes, dtype=node.dtype)

    def add(c, acc):
        lo = c * kg.CHUNK
        nd = jax.lax.dynamic_slice_in_dim(node, lo, kg.CHUNK)
        wc = jax.lax.dynamic_slice_in_dim(w, lo, kg.CHUNK)
        lhs = ((nd[:, None] == ids).astype(jnp.bfloat16)[:, :, None]
               * wc[:, None, :]).reshape(kg.CHUNK, nodes * k)
        if bins is None:
            rhs = jnp.ones((kg.CHUNK, 1), jnp.bfloat16)
        else:
            bc = jax.lax.dynamic_slice_in_dim(bins, lo, kg.CHUNK)
            rhs = (bc[:, :, None] == jnp.arange(kg.SLOTS, dtype=bc.dtype)
                   ).astype(jnp.bfloat16).reshape(kg.CHUNK, cols)
        return acc + jax.lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    out = jax.lax.fori_loop(0, chunks, add,
                            jnp.zeros((nodes * k, cols), jnp.float32))
    return out.reshape(nodes, k, cols)


@pytest.mark.parametrize("rows, bucket", [(900, None), (6000, 1 << 15)])
def test_boost_matches_the_per_chunk_histograms(ref, monkeypatch, rows,
                                                bucket):
    """The resident one-hot gives the trees and leaves, bit for bit, of
    histograms whose one-hot is built anew at every chunk: at two buckets,
    with fewer chunks filled than the bucket has and features missing
    cells."""
    args, _fit, n = _fit_inputs(ref, _tables(rows, 11), "T.a", bucket=bucket)
    assert 0 < int(args[5]) < args[0].shape[0] // kg.CHUNK
    assert (args[0][:n] == kg.MISSING).any(axis=0).all()
    got = _boost(args)
    monkeypatch.setattr(kg, "_onehot", lambda bins: bins)
    monkeypatch.setattr(kg, "_node_sums", _node_sums_per_chunk)
    want = jax.jit(lambda *a: kg.boost.__wrapped__(*a, **PARAMS))(*args)
    for x, y in zip(got, want):
        assert np.array_equal(x, np.asarray(y))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_split_choices_match_the_reference_outside_its_band(ref, seed):
    """Every node has the reference's rule up to the reference's first
    tie (a candidate in the band that parts the training rows otherwise):
    among candidates that part them alike, both take the first."""
    tables = _tables(900, seed)
    args, fit, n = _fit_inputs(ref, tables, "T.a")
    feat, thr, dleft, leaves = _boost(args)
    traj = ref._Trajectory(ROUNDS, 6)
    fit.run(traj)
    last = min([b[0] for b in traj.branches], default=ROUNDS)
    compared = 0
    for t in range(last):
        for hp in range(63):
            assert feat[t, hp] == traj.feat[t, hp], (t, hp)
            if traj.feat[t, hp] >= 0:
                assert thr[t, hp] == traj.thr[t, hp], (t, hp)
                assert dleft[t, hp] == traj.dleft[t, hp], (t, hp)
                compared += 1
    assert compared >= 40


def test_missing_rows_learn_their_default_direction(ref):
    rng = np.random.default_rng(4)
    n = 600
    x = np.round(rng.uniform(0, 10, n), 1)
    miss_x = rng.random(n) < 0.3
    # rows missing x behave like large x: they should go right with them
    y = np.where(miss_x, 20.0, x) + rng.normal(0, 0.1, n)
    tables = {"T": {
        "columns": [("T.id", "int"), ("T.x", "float"), ("T.y", "float")],
        "cols": {"T.id": np.arange(n), "T.x": np.where(miss_x, 0.0, x),
                 "T.y": np.round(y, 2)},
        "missing": {"T.id": np.zeros(n, bool), "T.x": miss_x,
                    "T.y": np.zeros(n, bool)}}}
    args, fit, _n = _fit_inputs(ref, tables, "T.y",
                                params=dict(PARAMS, rounds=1))
    feat, thr, dleft, _leaves = _boost(args, rounds=1)
    traj = ref._Trajectory(1, 6)
    fit.run(traj)
    # the root parts the missing rows off to the right, alone
    assert feat[0, 0] == 0 and not dleft[0, 0]
    assert (feat[0, 0], thr[0, 0], dleft[0, 0]) == (
        traj.feat[0, 0], traj.thr[0, 0], traj.dleft[0, 0])
    # rows missing x that behave like small x join them on the left
    tables["T"]["cols"]["T.y"] = np.round(np.where(miss_x, -1.0, x), 2)
    args, _fit, _n = _fit_inputs(ref, tables, "T.y")
    feat, thr, dleft, _leaves = _boost(args, rounds=1)
    assert feat[0, 0] == 0 and dleft[0, 0]


def test_min_child_weight_and_gamma_are_honoured(ref):
    args, _fit, n = _fit_inputs(ref, _tables(700, 5), "T.b")
    leaves = _boost(args, min_child_weight=25.0)[3][:, :n]
    for per_round in leaves:
        counts = np.bincount(per_round.astype(np.int64), minlength=64)
        assert counts[counts > 0].min() >= 25
    feat, _thr, _dleft, leaves = _boost(args, gamma=1e12)
    assert (feat == -1).all() and (leaves[:, :n] == 0).all()


def test_padded_bucket_rows_change_no_tree(ref):
    tables = _tables(800, 6)
    args, _fit, n = _fit_inputs(ref, tables, "T.c")
    padded, _fit, _n = _fit_inputs(ref, tables, "T.c",
                                   bucket=kg.train_bucket(n) + 1024)
    a, b = _boost(args), _boost(padded)
    for x, y in zip(a[:3], b[:3]):
        assert np.array_equal(x, y)
    assert np.array_equal(a[3][:, :n], b[3][:, :n])


def test_predictions_match_the_reference(kind):
    tables = _tables(900, 7)
    rel = _relation(tables)
    imputation = kind.Imputation(tables, {
        "rounds": ROUNDS, "max_depth": 6, "max_bin": 256, "eta": 0.3,
        "lambda": 1.0, "gamma": 0.0, "min_child_weight": 1.0})
    for attr in ("T.a", "T.d"):
        model = XgbHistImputer(rounds=ROUNDS)
        model.fit(rel)
        model.fit_attr(rel, attr)
        tids = np.nonzero(tables["T"]["missing"][attr])[0]
        got = model.impute_attr(rel, attr, tids)
        col = imputation.column(attr)
        sure = ~col.amb[tids]
        assert sure.sum() > len(tids) // 2
        assert np.array_equal(got[sure], col.val[tids][sure])
        assert col.admits(tids, got).all()


def test_fit_and_predict_spans_nest_under_the_imputation_spans():
    rel = _relation(_tables(500, 8))
    tracer = Tracer(enabled=True, clock="unit")
    svc = ImputationService({"T": rel}, lambda: XgbHistImputer(rounds=4),
                            tracer=tracer)
    tids = np.nonzero(rel.missing["T.e"])[0]
    svc.impute("T", "T.e", tids)
    spans = tracer.spans()
    name_of = {s.span_id: s.name for s in spans}
    parent = {"gbdt:fit": "impute:fit", "gbdt:bin": "gbdt:fit",
              "gbdt:boost": "gbdt:fit", "gbdt:predict": "impute_flush"}
    for child, up in parent.items():
        got = [s for s in spans if s.name == child]
        assert len(got) == 1 and name_of[got[0].parent_id] == up, child
    fit = next(s for s in spans if s.name == "gbdt:fit")
    rows = int((~rel.missing["T.e"]).sum())
    bucket = kg.train_bucket(rows)
    assert fit.args == {"table": "T", "attr": "T.e", "rows": rows,
                        "bucket": bucket, "rounds": 4,
                        "h2d_bytes": bucket * (4 * 2 + 3 * 4)}
    assert next(s for s in spans if s.name == "gbdt:boost").args == {
        "bucket": bucket, "onehot_bytes": bucket * 4 * kg.SLOTS}
    assert next(s for s in spans if s.name == "gbdt:predict").args == {
        "rows": len(tids)}


def test_warm_up_compiles_every_program_a_fit_calls():
    rel = _relation(_tables(600, 9))
    attr = "T.b"
    rows = int((~rel.missing[attr]).sum())
    warm_up([(rows, 4)], {"rounds": 3})
    compiles = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        model = XgbHistImputer(rounds=3)
        model.fit(rel)
        model.fit_attr(rel, attr)
        model.impute_attr(rel, attr, np.nonzero(rel.missing[attr])[0])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
