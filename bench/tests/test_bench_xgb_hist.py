"""The XGBoost-hist kind (``imputers/xgb_hist.py``) through the harness at
a tiny size on the CPU: the program's answers against the plain float64
reference, and the bfloat16 control and two planted faults in the
program, each of which must come out as not correct.  Twenty rounds
instead of the configuration's 100 keep the reference quick here."""

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

WORKLOAD = "cdc_nhanes_xgb.adhoc_loose"
TINY = {"n_demo": 3_000, "n_labs": 2_850, "n_exams": 2_850}
ROUNDS = 20
SEED = 3000001707


def _log(_s):
    pass


@pytest.fixture
def spec(monkeypatch):
    # the persistent compile cache is the run's, not the test process's
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    s = run.load_spec(WORKLOAD)
    s["config"]["params"].update(TINY)
    s["config"]["imputer"]["rounds"] = ROUNDS
    return s


def _values(checks):
    return {k: c["value"] for k, c in checks.items()}


def test_kind_is_served_and_matches_the_reference(spec):
    assert spec["imputer"].__name__ == "imputer_xgb_hist"
    res = run.run_cell(spec, SEED, 1.5, False, log=_log)
    assert res["correct"], _values(res["checks"])
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["compared"]["imputed_cells"] > 0
    assert res["compared"]["items"] > 0


def test_control_is_not_correct(spec):
    checks, compared = calibrate.control_checks(spec, SEED, 4, _log)
    assert compared["items"] > 0
    assert any(c["value"] > c["limit"] for c in checks.values()), \
        _values(checks)


def _missing_left(monkeypatch):
    """Every missing feature goes left, whatever direction its node
    learned."""
    from repro.kernels import gbdt_hist as kg

    orig = kg.predict
    monkeypatch.setattr(kg, "predict", lambda feat, thr, dleft, bins: orig(
        feat, thr, np.ones_like(dleft), bins))


def _last_round_dropped(monkeypatch):
    """The model keeps every round's trees but the last one's weights."""
    from repro.imputers.xgb_hist import XgbHistImputer

    orig = XgbHistImputer._refit

    def dropped(self, leaves, g):
        out = orig(self, leaves, g)
        out[-1] = 0.0
        return out

    monkeypatch.setattr(XgbHistImputer, "_refit", dropped)


@pytest.mark.parametrize("fault", [_missing_left, _last_round_dropped],
                         ids=["missing_left", "last_round_dropped"])
def test_planted_fault_is_not_correct(spec, monkeypatch, fault):
    fault(monkeypatch)
    res = run.run_cell(spec, SEED, 1.5, False, log=_log)
    assert not res["correct"], _values(res["checks"])
