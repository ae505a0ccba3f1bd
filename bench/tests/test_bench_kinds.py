"""Imputer kinds found by a configuration's ``imputer.kind``: an unknown
kind is refused before any set-up, and a second kind (``kind_mean.py``, the
program's ``MeanImputer``), dropped into a temporary tree, is served, warmed
and checked through the same harness (CPU, tiny size)."""

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

import calibrate  # noqa: E402
import run  # noqa: E402

run.sut.import_program(run.ROOT)

WORKLOAD = "cdc_mean.adhoc_loose"
TINY = {"n_demo": 3_000, "n_labs": 2_850, "n_exams": 2_850}
SEED = 3000000701


def _tree(tmp_path, imputer: dict) -> str:
    """A checkout's benchmark with one more configuration, ``cdc_mean``:
    ``cdc_nhanes``' tables and traffic under the imputer ``imputer``."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    config = json.loads(
        (tmp_path / "bench" / "configs" / "cdc_nhanes.json").read_text())
    config.update(name="cdc_mean", imputer=imputer)
    (tmp_path / "bench" / "configs" / "cdc_mean.json").write_text(
        json.dumps(config))
    shutil.copy(tmp_path / "bench" / "cells" / "cdc_nhanes.adhoc_loose.json",
                tmp_path / "bench" / "cells" / f"{WORKLOAD}.json")
    bench["configs"].append(dict(bench["configs"][1], name="cdc_mean",
                                 file="bench/configs/cdc_mean.json"))
    bench["workloads"].append({"name": WORKLOAD, "config": "cdc_mean",
                               "traffic": "adhoc_loose", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_unknown_kind_fails_before_any_set_up(tmp_path):
    root = _tree(tmp_path, {"kind": "gbdt", "rounds": 100})
    marker = tmp_path / "generated"
    (tmp_path / "bench" / "datagen" / "cdc.py").write_text(
        f"def make(rng, p):\n    open({str(marker)!r}, 'w').close()\n")
    with pytest.raises(FileNotFoundError, match="bench/imputers/gbdt.py"):
        run.load_spec(WORKLOAD, root=root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
    assert "add bench/imputers/gbdt.py" in out.stderr
    assert not marker.exists()


@pytest.fixture
def mean_spec(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    root = _tree(tmp_path, {"kind": "mean", "bins": 64})
    shutil.copy(os.path.join(BENCH, "tests", "kind_mean.py"),
                tmp_path / "bench" / "imputers" / "mean.py")
    spec = run.load_spec(WORKLOAD, root=root)
    spec["config"]["params"].update(TINY)
    return spec


def test_second_kind_is_served_and_checked(mean_spec):
    assert mean_spec["imputer"].__name__ == "imputer_mean"
    res = run.run_cell(mean_spec, SEED, 1.5, False, log=lambda _s: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["compared"]["imputed_cells"] > 0
    # a float fill is known to its summation's rounding
    assert res["compared"]["ambiguous_cells"] == res["compared"][
        "imputed_cells"]


def test_second_kind_with_one_value_off_is_not_correct(mean_spec,
                                                       monkeypatch):
    from repro.imputers.mean import MeanImputer

    orig = MeanImputer.impute_attr

    def one_off(self, table, attr, tids):
        out = orig(self, table, attr, tids)
        out[:1] += 1.0
        return out

    monkeypatch.setattr(MeanImputer, "impute_attr", one_off)
    res = run.run_cell(mean_spec, SEED, 1.5, False, log=lambda _s: None)
    assert not res["correct"], res["checks"]


def test_kind_without_a_control_exits(mean_spec, monkeypatch, capsys):
    monkeypatch.setattr(run, "load_spec", lambda _w: mean_spec)
    monkeypatch.setattr(run, "check_device", lambda _chips: None)
    with pytest.raises(SystemExit,
                       match="imputer kind 'mean' has no control"):
        calibrate.main(["--workload", WORKLOAD, "--seeds", str(SEED),
                        "--control", "1", "--queries", "3"])
    assert capsys.readouterr().out == ""


def test_knn_context_is_the_roofline_readers(mean_spec):
    tables = mean_spec["generator"].make(np.random.default_rng(SEED),
                                         mean_spec["config"]["params"])
    knn = run.imputer("knn")
    ctx = knn.context(tables, {"k": 5, "batch": 1024})
    assert ctx["k"] == 5
    assert ctx["knn_shapes"]["labs.albumin"] == (
        int((~tables["labs"]["missing"]["labs.albumin"]).sum()), 9)
    assert "demo.id" not in ctx["knn_shapes"]
    assert mean_spec["imputer"].context(tables, {"bins": 64}) == {}
