"""A run of each cell at a tiny size on the CPU, the harness's look for a
chip skipped: the program's answers against the plain reference, the
control, and planted faults in the timed path, each of which must come out
as not correct.

The limits are the cells' own: the comparison is exact (every answer item
admissible, ``compare.items_off``), so a sound run reads 0 at any size.
At the tiny size the control's answers are off on some seeds and not on
others (WiFi: 3 of 24 seeds); the seeds below are ones where they are
(CPU, PR 12).
"""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import calibrate  # noqa: E402
import run  # noqa: E402

run.sut.import_program(run.ROOT)

#: params at the test size, a seed for the program, and a seed on which
#: the control's answers leave what the reference admits
TINY = {
    "wifi_uci.adhoc_selective": (
        {"n_users": 200, "n_wifi": 10_000, "n_occ": 1_000}, 2, 17),
    "cdc_nhanes.adhoc_loose": (
        {"n_demo": 5_000, "n_labs": 4_750, "n_exams": 4_750}, 21, 21),
}


def _log(_s):
    pass


@pytest.fixture
def tiny(monkeypatch):
    # the persistent compile cache is the run's, not the test process's
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)

    def make(workload):
        spec = run.load_spec(workload)
        spec["config"]["params"].update(TINY[workload][0])
        return spec

    return make


def _values(checks):
    return {k: c["value"] for k, c in checks.items()}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_program_matches_reference(tiny, workload):
    res = run.run_cell(tiny(workload), TINY[workload][1], 1.5, False,
                       log=_log)
    assert res["correct"], _values(res["checks"])
    assert _values(res["checks"]) == {"failed_queries": 0,
                                      "answer_items_off": 0}
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "latency_p50_s", "latency_p90_s",
                                   "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["compared"]["queries"] == res["attempted"]


def test_traced_run_reads_the_per_layer_metrics(tiny):
    workload = "wifi_uci.adhoc_selective"
    res = run.run_cell(tiny(workload), TINY[workload][1], 1.5, True,
                       log=_log)
    assert res["correct"], _values(res["checks"])
    # no device plane on the CPU: the device metrics find nothing to read
    assert set(res["metrics"]) == {"session_setup_ms", "relational_ms",
                                   "impute_ms", "imputations_per_query",
                                   "compiles_in_window"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["imputations_per_query"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_is_not_correct(tiny, workload):
    checks, _ = calibrate.control_checks(tiny(workload), TINY[workload][2],
                                         12, _log)
    assert any(c["value"] > c["limit"] for c in checks.values()), \
        _values(checks)


def _half_batch(monkeypatch):
    """The imputer computes the first half of each batch and fills the rest
    with the mean of what it computed."""
    from repro.imputers.knn import KnnImputer

    orig = KnnImputer.impute_attr

    def half(self, table, attr, tids):
        h = (len(tids) + 1) // 2
        done = orig(self, table, attr, tids[:h])
        return np.concatenate([done, np.full(len(tids) - h, done.mean())])

    monkeypatch.setattr(KnnImputer, "impute_attr", half)


def _answer_altered(monkeypatch):
    """Every aggregate answer is off by one where the executor makes it."""
    import repro.core.executor as ex

    orig = ex._aggregate

    def altered(rel, agg):
        out = orig(rel, agg)
        name = out.column_names()[-1]
        out.cols[name] = out.cols[name] + 1
        return out

    monkeypatch.setattr(ex, "_aggregate", altered)


@pytest.mark.parametrize("fault", [_half_batch, _answer_altered],
                         ids=["half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", sorted(TINY))
def test_planted_fault_is_not_correct(tiny, monkeypatch, workload, fault):
    spec = tiny(workload)
    fault(monkeypatch)
    res = run.run_cell(spec, TINY[workload][1], 1.5, False, log=_log)
    assert not res["correct"], _values(res["checks"])
