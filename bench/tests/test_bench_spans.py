"""The readers of the program's table-snapshot, imputer-fit, k-NN-call,
finalize and compile spans, on hand-made spans and in a traced tiny run
(CPU)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import run  # noqa: E402

run.sut.import_program(run.ROOT)

NEW = ("snapshot_ms", "impute_fit_ms", "knn_call_ms", "knn_h2d_mb",
       "unattributed_ms", "cache_loads_in_window")

#: two queries; times in seconds on the tracer's clock
SPANS = [
    ("jax:compile", 0.0, 0.5, {"cache_load": False}),  # set-up
    ("query", 1.0, 1.100, {}),
    ("session_setup", 1.001, 1.011, {}),
    ("session:snapshot", 1.002, 1.006, {"tables": 2, "rows": 10}),
    ("morsel_step", 1.011, 1.095, {}),
    ("op:select", 1.012, 1.050, {}),
    ("impute_flush", 1.020, 1.045, {"computed": 5}),
    ("impute:fit", 1.020, 1.030, {"fitted": True}),
    ("knn:call", 1.031, 1.041, {"h2d_bytes": 3_000_000}),
    ("jax:compile", 1.035, 1.040, {"cache_load": True}),
    ("op:join_probe", 1.052, 1.058, {"rows": 8}),
    ("op:finalize", 1.060, 1.070, {"rows": 4, "agg": True}),
    ("query", 2.0, 2.050, {}),
    ("session_setup", 2.001, 2.005, {}),
    ("session:snapshot", 2.002, 2.004, {"tables": 2, "rows": 10}),
    ("impute_flush", 2.010, 2.030, {"computed": 2}),
    ("impute:fit", 2.010, 2.012, {"fitted": False}),  # a lookup: left out
    ("knn:call", 2.015, 2.020, {"h2d_bytes": 1_000_000}),
    ("compiled_exec", 2.025, 2.040, {}),  # overlaps the flush
    ("jax:compile", 3.0, 3.1, {"cache_load": True}),  # after the window
]


def _read(name, spans, queries=2):
    return run.reader(name).read({"spans": spans, "queries": queries})


def test_span_readers_hand_computed():
    assert _read("snapshot_ms", SPANS) == pytest.approx((4 + 2) / 2)
    assert _read("impute_fit_ms", SPANS) == pytest.approx(10 / 2)
    assert _read("knn_call_ms", SPANS) == pytest.approx((10 + 5) / 2)
    assert _read("knn_h2d_mb", SPANS) == pytest.approx(4.0 / 2)
    assert _read("cache_loads_in_window", SPANS) == 1


def test_unattributed_ms_on_overlapping_spans():
    # query 1: 100 ms less session_setup [1, 11], op:select [12, 50] (the
    # flush inside it counted once), op:join_probe [52, 58], op:finalize
    # [60, 70]: 100 - 64 = 36; query 2: 50 ms less session_setup [1, 5] and
    # flush and compiled_exec overlapping over [10, 40]: 50 - 34 = 16
    assert _read("unattributed_ms", SPANS) == pytest.approx((36 + 16) / 2)


def test_readers_find_nothing_without_their_spans():
    # a program without these spans (as before they existed)
    old = [s for s in SPANS if s[0] in ("query", "session_setup", "op:select",
                                        "impute_flush", "compiled_exec")]
    for name in NEW:
        if name != "unattributed_ms":
            assert _read(name, old) is None, name
    assert _read("unattributed_ms", old) is not None
    assert _read("impute_fit_ms", [s for s in SPANS
                                   if s[3].get("fitted") is not True]) == 0
    for name in NEW:
        if name != "cache_loads_in_window":  # a count, not per query
            assert _read(name, SPANS, queries=0) is None, name


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_program_spans_in_the_profile_leave_the_reduction_as_it_was():
    tpu, host = "/device:TPU:0", "/host:CPU"
    events = [
        _ev(host, "python", devtrace.WINDOW, 0, 1000),
        _ev(host, "python", "harness:run 3t-proj", 0, 1000),
        _ev(tpu, "XLA Ops", "fusion.1", 100, 200),
        _ev(tpu, "XLA Modules", "jit_masked_distance_pallas(12)", 100, 200),
    ]
    spans = [
        _ev(host, "python", "quip:impute_flush", 50, 500),
        _ev(host, "python", "quip:knn:call", 90, 300),
        _ev(host, "python", "quip:session:snapshot", 700, 100),
    ]
    assert devtrace.reduce(events + spans) == devtrace.reduce(events)


@pytest.fixture
def tiny_cdc(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    spec = run.load_spec("cdc_nhanes.adhoc_loose")
    spec["config"]["params"].update(
        {"n_demo": 3_000, "n_labs": 2_850, "n_exams": 2_850})
    return spec


def test_traced_run_reads_the_new_metrics(tiny_cdc):
    import jax

    jax.clear_caches()  # set-up compiles, as in a fresh process
    res = run.run_cell(tiny_cdc, 21, 1.5, True, log=lambda _s: None)
    assert res["correct"]
    got = {k: m["value"] for k, m in res["metrics"].items()}
    assert set(NEW) <= set(got), got
    assert got["impute_fit_ms"] + got["knn_call_ms"] <= got["impute_ms"]
    assert got["snapshot_ms"] <= got["session_setup_ms"]
    assert got["knn_h2d_mb"] > 0 and got["unattributed_ms"] >= 0
    assert got["cache_loads_in_window"] == 0
