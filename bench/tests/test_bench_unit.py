"""The benchmark's arithmetic and plumbing on hand-made inputs (CPU)."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import devtrace  # noqa: E402
import intervals  # noqa: E402
import querygen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402
from reference import Reference  # noqa: E402

KNN = run.imputer("knn")


def _knn_ref(tables: dict, k: int) -> Reference:
    """The SPJA reference over the k-NN kind's reference imputation."""
    return Reference(tables, KNN.reference(tables, {"k": k}))


# --------------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------------- #
def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


TPU, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    _ev(HOST, "python", devtrace.WINDOW, 0, 1000),
    _ev(HOST, "python", "harness:run 2t-count", 0, 500),
    _ev(HOST, "python", "harness:submit", 500, 50),
    _ev(HOST, "python", "harness:run 3t-proj", 550, 450),
    _ev(TPU, "XLA Ops", "fusion.1", 100, 200),
    _ev(TPU, "XLA Ops", "fusion.2", 200, 200),
    _ev(TPU, "XLA Ops", "sort.3", 600, 100),
    _ev(TPU, "XLA Ops", "late", 900, 300),  # runs past the window's end
    _ev(TPU, "XLA Modules", "jit_masked_distance_pallas(12)", 100, 300),
    _ev(TPU, "XLA Modules", "jit__top_k_jit(3)", 600, 100),
]


def test_trace_reduction_busy_idle_programs_and_gaps():
    r = devtrace.reduce(EVENTS)
    assert r["window_s"] == pytest.approx(1000e-9)
    # union of [100,400], [600,700], [900,1000]: 500 ns
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["devices"] == 1
    assert r["modules_s"] == pytest.approx(
        {"jit_masked_distance_pallas": 300e-9, "jit__top_k_jit": 100e-9})
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 200e-9, "fusion.2": 200e-9, "sort.3": 100e-9,
         "late": 100e-9})
    # gaps [0,100], [400,600], [700,900]; the middle one's midpoint (500)
    # lies in two marks and goes to the innermost, the submit
    assert r["idle_gaps"] == [["harness:submit", pytest.approx(200e-9)],
                              ["harness:run 3t-proj", pytest.approx(200e-9)],
                              ["harness:run 2t-count", pytest.approx(100e-9)]]
    idle = run.reader("device_idle").read({"trace": r})
    assert idle == pytest.approx(50.0)


def test_trace_without_a_window_span_is_refused():
    with pytest.raises(ValueError):
        devtrace.reduce(EVENTS[1:])


def test_interval_arithmetic():
    assert intervals.covered([(0, 2), (1, 3), (5, 6)]) == 4
    assert intervals.covered_minus([(0, 10)], [(2, 3), (2.5, 4), (9, 12)]) == 7
    assert intervals.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


# --------------------------------------------------------------------------- #
# work and roofline
# --------------------------------------------------------------------------- #
def test_knn_work_hand_computed():
    # 4 queries x 10 reference rows x 3 features: 3 flops each = 360;
    # bytes: values and masks of 14 rows x 3 features in f32 (336) plus
    # 4 x 2 neighbours at 8 bytes (64)
    assert work.knn_work(4, 10, 3, 2) == (360, 400)
    peak = work.peaks("TPU v5 lite")
    secs, bound = work.roofline_s(360, 400, peak)
    assert bound == "memory" and secs == pytest.approx(400 / 819e9)
    secs, bound = work.roofline_s(10 ** 12, 1, peak)
    assert bound == "compute" and secs == pytest.approx(10 ** 12 / 197e12)


def test_knn_roofline_reader_hand_computed():
    ctx = {
        "trace": {"modules_s": {"jit_masked_distance_pallas": 0.75e-6,
                                "jit__top_k_jit": 0.25e-6,
                                "jit_bloom_probe_pallas": 5.0}},
        "traced_spans": [
            ("impute_flush", 0.0, 1.0, {"attr": "t.a", "computed": 4}),
            ("impute_flush", 1.0, 2.0, {"attr": "t.a"}),  # all cache hits
            ("op:select", 0.0, 1.0, {}),
        ],
        "knn_shapes": {"t.a": (10, 3)},
        "k": 2,
        "device_kind": "TPU v5 lite",
        "log": lambda s: None,
    }
    share = run.reader("knn_roofline").read(ctx)
    assert share == pytest.approx(100 * (400 / 819e9) / 1e-6)
    ctx["trace"] = {"modules_s": {}}
    assert run.reader("knn_roofline").read(ctx) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


# --------------------------------------------------------------------------- #
# span readers
# --------------------------------------------------------------------------- #
def test_span_readers_per_query():
    spans = [
        ("session_setup", 0.0, 0.002, {}),
        ("op:select", 0.002, 0.010, {}),
        ("kernel:multi_match", 0.004, 0.006, {}),  # nested: counted once
        ("impute_flush", 0.007, 0.009, {"attr": "t.a", "computed": 3}),
        ("session_setup", 0.010, 0.011, {}),
        ("op:rho", 0.011, 0.015, {}),
    ]
    ctx = {"spans": spans, "queries": 2, "imputations": 9, "compiles": 0}
    assert run.reader("session_setup_ms").read(ctx) == pytest.approx(1.5)
    # ops cover 8 + 4 ms, less the 2 ms flush inside the select
    assert run.reader("relational_ms").read(ctx) == pytest.approx(5.0)
    assert run.reader("impute_ms").read(ctx) == pytest.approx(1.0)
    assert run.reader("imputations_per_query").read(ctx) == 4.5
    assert run.reader("compiles_in_window").read(ctx) == 0
    assert run.reader("impute_ms").read(dict(ctx, spans=[])) is None


# --------------------------------------------------------------------------- #
# answers, reference and traffic
# --------------------------------------------------------------------------- #
def _ans(cols, values, null=None):
    values = [np.asarray(v) for v in values]
    null = null or [np.zeros(len(v), dtype=bool) for v in values]
    return {"columns": cols, "values": values, "null": null}


def _table(cols: dict, missing: dict, kinds: dict) -> dict:
    return {"columns": [(c, kinds.get(c, "int")) for c in cols],
            "cols": {c: np.asarray(v) for c, v in cols.items()},
            "missing": {c: np.asarray(missing.get(c, [False] * len(v)))
                        for c, v in cols.items()}}


def _join_ref():
    a = _table({"a.k": [1, 2, 3], "a.v": [5, 6, 7]}, {}, {})
    b = _table({"b.k": [1, 1, 3, 4], "b.w": [1, 2, 3, 4]}, {}, {})
    return _knn_ref({"a": a, "b": b}, k=1)


JOIN_Q = {"tables": ["a", "b"], "joins": [["a.k", "b.k"]],
          "selections": [["b.w", ">=", 2]], "projection": ["a.v", "b.w"],
          "aggregate": None}


def _rows(vals, tids):
    """A projection answer over a.v, b.w with its row ids."""
    vals = np.asarray(vals).reshape(-1, 2)
    tids = np.asarray(tids).reshape(-1, 2)
    return {"columns": ["a.v", "b.w"], "values": [vals[:, 0], vals[:, 1]],
            "null": [np.zeros(len(vals), dtype=bool)] * 2,
            "tids": {"a": tids[:, 0], "b": tids[:, 1]}}


def test_projection_items_off_row_by_row():
    ref = _join_ref()
    exp = ref.expect(JOIN_Q)
    # (a0, b1) -> (5, 2) and (a2, b2) -> (7, 3)
    assert compare.items_off(ref.answer(JOIN_Q), exp) == (2, 0)
    assert compare.items_off(_rows([5, 2, 7, 3], [0, 1, 2, 2]), exp) == (2, 0)
    # a sure row lacking, a row no answer has, a row twice, a wrong value
    assert compare.items_off(_rows([5, 2], [0, 1]), exp) == (2, 1)
    assert compare.items_off(_rows([5, 2, 7, 3, 5, 1], [0, 1, 2, 2, 0, 0]),
                             exp) == (3, 1)
    assert compare.items_off(_rows([5, 2, 7, 3, 7, 3], [0, 1, 2, 2, 2, 2]),
                             exp) == (3, 1)
    assert compare.items_off(_rows([5, 2, 7, 4], [0, 1, 2, 2]), exp) == (2, 1)
    # no row ids, or other columns: every item is off
    bare = dict(_rows([5, 2, 7, 3], [0, 1, 2, 2]), tids=None)
    assert compare.items_off(bare, exp) == (2, 2)
    other = dict(_rows([5, 2, 7, 3], [0, 1, 2, 2]), columns=["a.v", "b.k"])
    assert compare.items_off(other, exp) == (2, 2)


def _tie_ref():
    """Row 1 misses t.y; with k=1 rows 0 and 2 are exactly as near."""
    t = _table({"t.x": [0, 1, 2, 3], "t.y": [10, 0, 30, 40]},
               {"t.y": [False, True, False, False]}, {})
    return _knn_ref({"t": t}, k=1)


def test_reference_admits_either_side_of_an_exact_tie():
    ref = _tie_ref()
    col = ref.column("t.y")
    # float32 rounding picks one neighbour; either is admissible
    assert col.val[1] in (10, 30)
    assert col.amb.tolist() == [False, True, False, False]
    assert col.sets[1] == {10, 30}
    assert col.admits(np.array([1, 1, 1, 2]),
                      np.array([10, 30, 20, 10])).tolist() == [
                          True, True, False, False]
    q = {"tables": ["t"], "joins": [], "selections": [],
         "projection": ["t.y"], "aggregate": None}
    exp = ref.expect(q)
    got = ref.answer(q)
    for v in (10, 30):
        got["values"] = [np.array([10, v, 30, 40])]
        assert compare.items_off(got, exp) == (4, 0)
    got["values"] = [np.array([10, 20, 30, 40])]
    assert compare.items_off(got, exp) == (4, 1)


def test_band_from_the_float32_bound_of_a_column_sum():
    # 7 sqrt(n) + 6 roundoffs of the distance, 8 of the magnitude
    assert KNN.rel_units(4) == 20.0
    assert KNN.rel_units(50_000) == pytest.approx(1571.25, abs=0.01)
    got = KNN.band(np.array([1.0, 0.0]), np.array([0.0, 2.0]), 4)
    assert got.tolist() == [20 * KNN.EPS32, 16 * KNN.EPS32]


def test_open_cell_is_bounded_by_the_attribute_range():
    # every reference row is as near as any other: more candidates lie in
    # the band than are fetched, so row 0 may take any of them
    n = KNN.EXTRA + 4
    ys = [0] + list(range(10, 10 + n - 1))
    t = _table({"t.x": [0] * n, "t.y": ys},
               {"t.y": [True] + [False] * (n - 1)}, {})
    col = _knn_ref({"t": t}, k=1).column("t.y")
    assert col.open.tolist() == [True] + [False] * (n - 1)
    assert (col.lo[0], col.hi[0]) == (10.0, 10.0 + n - 2)
    assert col.admits(np.array([0, 0, 0]),
                      np.array([10, 10 + n - 2, 10 + n - 1])).tolist() == [
                          True, True, False]
    # a sum over the open cell is bounded, not any value
    ref = _knn_ref({"t": t}, k=1)
    exp = ref.expect({"tables": ["t"], "joins": [], "selections": [],
                      "projection": [], "aggregate": ["sum", "t.y", None]})
    rest = sum(ys[1:])
    assert exp["groups"].bounds()[2:] == (rest + 10.0, rest + 10.0 + n - 2)
    # a float mean of k = 2: both places free
    f = _table({"f.x": [0.0] * n, "f.y": [0.0] + [float(y) for y in ys[1:]]},
               {"f.y": [True] + [False] * (n - 1)},
               {"f.x": "float", "f.y": "float"})
    fcol = _knn_ref({"f": f}, k=2).column("f.y")
    assert fcol.open[0] and (fcol.lo[0], fcol.hi[0]) == (10.0, 10.0 + n - 2)


def _agg(columns, values, null=None):
    values = [np.asarray(v) for v in values]
    null = null or [np.zeros(len(v), dtype=bool) for v in values]
    return {"columns": columns, "values": values, "null": null}


def test_aggregate_items_off_against_bounds():
    ref = _tie_ref()
    grouped = {"tables": ["t"], "joins": [], "selections": [],
               "projection": [], "aggregate": ["sum", "t.x", "t.y"]}
    exp = ref.expect(grouped)
    cols = ["t.y", "sum(t.x)"]
    # row 1 (x = 1) may sit in group 10 or in group 30
    assert compare.items_off(ref.answer(grouped), exp) == (3, 0)
    assert compare.items_off(_agg(cols, [[10, 30, 40], [0, 3, 3]]), exp) == (
        3, 0)
    assert compare.items_off(_agg(cols, [[10, 30, 40], [5, 2, 3]]), exp) == (
        3, 1)
    # group 40 surely exists; group 20 cannot
    assert compare.items_off(_agg(cols, [[10, 30], [1, 2]]), exp) == (3, 1)
    assert compare.items_off(_agg(cols, [[10, 20, 30, 40], [1, 0, 2, 3]]),
                             exp) == (4, 1)
    counted = {"tables": ["t"], "joins": [],
               "selections": [["t.y", ">=", 20]], "projection": [],
               "aggregate": ["count", "t.x", None]}
    exp = ref.expect(counted)
    for n, off in ((2, 0), (3, 0), (4, 1)):
        assert compare.items_off(_agg(["count(t.x)"], [[n]]), exp) == (1, off)
    empty = dict(counted, selections=[["t.y", ">=", 99]],
                 aggregate=["avg", "t.x", None])
    null = _agg(["avg(t.x)"], [[0.0]], [np.ones(1, dtype=bool)])
    assert compare.items_off(null, ref.expect(empty)) == (1, 0)
    assert compare.items_off(_agg(["avg(t.x)"], [[1.0]]),
                             ref.expect(empty)) == (1, 1)


def test_group_bounds_hand_computed():
    lo = np.array([1.0, 2.0, -5.0])
    hi = np.array([1.0, 3.0, -4.0])
    sure = np.array([True, True, False])

    def bounds(op):
        return reference.Groups(op, lo, hi, sure).bounds()

    assert bounds("count") == (2, 1, 2.0, 3.0)
    # the optional row adds its negative part to the least sum only
    assert bounds("sum") == (2, 1, -2.0, 4.0)
    assert bounds("min") == (2, 1, -5.0, 1.0)
    assert bounds("max") == (2, 1, 2.0, 3.0)
    # sums in [-2, 4] over counts in [2, 3]
    assert bounds("avg") == (2, 1, -1.0, 2.0)
    none = reference.Groups("avg", lo[:0], hi[:0], sure[:0]).bounds()
    assert none == (0, 0, None, None)


def test_reference_knn_hand_computed():
    # t.x is one feature; row 2 misses t.y.  With k=2 its neighbours are
    # rows 1 and 3 (both at distance 1, row 0 at 2 and row 4 at 3): the
    # mode of {20, 30} ties and goes to the smaller value.
    t = _table({"t.x": [0, 1, 2, 3, 5], "t.y": [10, 20, 0, 30, 30]},
               {"t.y": [False, False, True, False, False]}, {})
    ref = _knn_ref({"t": t}, k=2)
    assert ref.column("t.y").val.tolist() == [10, 20, 20, 30, 30]
    assert not ref.column("t.y").amb.any()
    # k=3: row 0 joins; mode of {20, 30, 10} ties three ways -> 10
    assert _knn_ref({"t": t}, k=3).column("t.y").val[2] == 10
    # a float attribute takes the mean of its neighbours
    f = _table({"f.x": [0, 1, 2, 3], "f.y": [1.0, 2.0, 0.0, 4.0]},
               {"f.y": [False, False, True, False]}, {"f.y": "float"})
    assert _knn_ref({"f": f}, k=2).column("f.y").val[2] == pytest.approx(
        3.0)


def test_reference_evaluates_spja():
    a = _table({"a.k": [1, 2, 3], "a.v": [5, 6, 7]}, {}, {})
    b = _table({"b.k": [1, 1, 3, 4], "b.w": [1, 2, 3, 4]}, {}, {})
    ref = _knn_ref({"a": a, "b": b}, k=1)
    base = {"tables": ["a", "b"], "joins": [["a.k", "b.k"]],
            "selections": [["b.w", ">=", 2]], "projection": ["a.v", "b.w"],
            "aggregate": None}
    got = ref.answer(base)
    assert sorted(zip(*[v.tolist() for v in got["values"]])) == [(5, 2),
                                                                 (7, 3)]
    grouped = dict(base, aggregate=["sum", "b.w", "a.v"], projection=[])
    got = ref.answer(grouped)
    assert got["columns"] == ["a.v", "sum(b.w)"]
    assert [v.tolist() for v in got["values"]] == [[5, 7], [2, 3]]
    empty = dict(base, selections=[["b.w", ">=", 9]],
                 aggregate=["avg", "b.w", None], projection=[])
    assert ref.answer(empty)["null"][0].tolist() == [True]


def test_query_stream_shapes_fixed_constants_seeded():
    spec = run.load_spec("wifi_uci.adhoc_selective")
    p = dict(spec["config"]["params"], n_users=100, n_wifi=2000, n_occ=300)
    tables = spec["generator"].make(np.random.default_rng(5), p)
    joins, mix = spec["config"]["joins"], spec["traffic"]

    def draw(seed, n=40):
        s = querygen.QueryStream(tables, joins, mix, seed)
        return [next(s) for _ in range(n)]

    a, b, c = draw(1), draw(1), draw(2)
    assert a == b

    def shape(q):
        return (q["tables"], [s[:2] for s in q["selections"]],
                q["projection"], q["aggregate"])

    assert [shape(q) for q in a] == [shape(q) for q in c]
    assert a != c  # the in-sets differ
    for q in a:
        assert 2 <= len(q["tables"]) <= 3
        assert len(q["joins"]) == len(q["tables"]) - 1


# --------------------------------------------------------------------------- #
# files found by name
# --------------------------------------------------------------------------- #
def test_cell_traffic_config_and_metric_found_from_files_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    mix = dict(json.loads((tmp_path / "bench" / "traffic" /
                           "adhoc_loose.json").read_text()),
               name="adhoc_mid", selectivity=0.5)
    (tmp_path / "bench" / "traffic" / "adhoc_mid.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "cells" / "cdc_nhanes.adhoc_mid.json").write_text(
        (tmp_path / "bench" / "cells" /
         "cdc_nhanes.adhoc_loose.json").read_text())
    (tmp_path / "bench" / "metrics" / "queries_read.py").write_text(
        "def read(ctx):\n    return ctx['queries']\n")
    bench["workloads"].append({"name": "cdc_nhanes.adhoc_mid",
                               "config": "cdc_nhanes", "traffic": "adhoc_mid",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queries_read", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "service", "moves": "qps",
                               "workloads": ["cdc_nhanes.adhoc_mid"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = run.load_spec("cdc_nhanes.adhoc_mid", root=str(tmp_path))
    assert spec["traffic"]["selectivity"] == 0.5
    assert spec["config"]["generator"] == "cdc"
    assert spec["generator"].make.__module__ == "datagen_cdc"
    assert [m["name"] for m in spec["per_layer"]] == ["queries_read"]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "qps", "latency_p50_s", "latency_p90_s", "setup_s"}
    assert run.reader("queries_read", root=str(tmp_path)).read(
        {"queries": 7}) == 7
    old = run.load_spec("wifi_uci.adhoc_selective", root=str(tmp_path))
    assert "queries_read" not in [m["name"] for m in old["per_layer"]]


# --------------------------------------------------------------------------- #
# no chip, no result
# --------------------------------------------------------------------------- #
def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "wifi_uci.adhoc_selective", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cpu_only_host_exits_nonzero_with_no_result():
    out = _bench_cmd(ROOT)
    assert out.returncode == run.NO_CHIP, out.stderr[-2000:]
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_bare_benchmark_directory_exits_nonzero_with_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
