"""The XGBoost-hist imputer kind: the program's ``XgbHistImputer``, its
warm-up, its per-layer context, and the reference imputation with its
control.

The reference is ``xgb_reference.py``: XGBoost-hist written out in float64
numpy, the semantics the configuration states (its docstring sets them out,
with the band in which float32 cannot rank two split candidates, and how
the ties it leaves are followed).  Every attribute with a missing cell is
fitted at once, on ``PROCESSES`` worker processes.  A cell whose followed
trajectories agree is *determined* and compared to float64 rounding; one
where they differ is *ambiguous* and admits their interval (an integer
cell: every observed value between the snapped ends); past
``xgb_reference.MAX_BRANCHES`` ties an attribute's cells are *open*.  The
control is the same reference with every histogram sum rounded to
bfloat16.

The program is imported only inside ``factory`` and ``warm_up``, which
``sut.py`` calls; the reference reads only the generated tables.
"""

from __future__ import annotations

import functools
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from reference import Column, table_of
from xgb_reference import MAX_BIN, bins_of, cuts, fit_attribute

__all__ = ["factory", "shapes", "warm_up", "context", "reference"]

#: worker processes of the reference, at most the host's cores
PROCESSES = min(8, os.cpu_count() or 1)


# --------------------------------------------------------------------------- #
# the program's imputer
# --------------------------------------------------------------------------- #
def _program_args(params: dict) -> dict:
    """``XgbHistImputer``'s arguments from the configuration's entry,
    which may state only what the program implements: squared error and
    256 bins."""
    if params.get("objective", "reg:squarederror") != "reg:squarederror":
        raise ValueError(f"objective {params['objective']!r}: the kind "
                         f"implements reg:squarederror")
    if params.get("max_bin", MAX_BIN) != MAX_BIN:
        raise ValueError(f"max_bin {params['max_bin']}: the kind implements "
                         f"{MAX_BIN}")
    return {"rounds": params["rounds"], "max_depth": params["max_depth"],
            "eta": params["eta"], "lam": params["lambda"],
            "gamma": params["gamma"],
            "min_child_weight": params["min_child_weight"]}


def factory(params: dict):
    """The ``XgbHistImputer`` factory ``QuipService`` takes, from the
    configuration's ``imputer`` entry."""
    from repro.imputers import XgbHistImputer

    return functools.partial(XgbHistImputer, **_program_args(params))


def _features(tab: dict, attr: str) -> list:
    return [c for c, _k in tab["columns"]
            if c != attr and c.split(".")[-1] != "id"]


def shapes(tables: dict) -> dict:
    """``attr -> (training rows, features)`` for every attribute with a
    missing cell: the sizes of its fit."""
    out = {}
    for tab in tables.values():
        for c, m in tab["missing"].items():
            if m.any():
                out[c] = (int((~m).sum()), len(_features(tab, c)))
    return out


def warm_up(tables: dict, params: dict) -> int:
    """One boosting program per (training-row bucket, features) the
    attributes with a missing cell need, one prediction program per query
    bucket (``repro.imputers.xgb_hist.warm_up``).  Returns how many."""
    from repro.imputers.xgb_hist import warm_up as program_warm_up

    return program_warm_up(shapes(tables).values(), _program_args(params))


def context(tables: dict, params: dict) -> dict:
    """What the ``gbdt_*`` readers need: each table's feature count and the
    trees' settings."""
    return {"gbdt_features": {t: len(tab["columns"]) - 2
                              for t, tab in tables.items()},
            "gbdt": {"rounds": params["rounds"],
                     "depth": params["max_depth"],
                     "max_bin": params["max_bin"]}}


def reference(tables: dict, params: dict, control: bool = False):
    """The reference imputation, or with ``control`` the control: every
    histogram sum rounded to bfloat16, one precision step below the
    configuration's float32."""
    return Imputation(tables, params, control)


def _inputs(tab: dict, attr: str):
    """The training rows' bins and targets and the missing rows' bins."""
    miss = tab["missing"][attr]
    train, query = np.nonzero(~miss)[0], np.nonzero(miss)[0]
    names = _features(tab, attr)
    btr = np.zeros((len(train), len(names)), np.int64)
    bq = np.zeros((len(query), len(names)), np.int64)
    for j, c in enumerate(names):
        x = tab["cols"][c].astype(np.float64)
        obs = ~tab["missing"][c]
        cut = cuts(x[train][obs[train]])
        btr[:, j] = bins_of(x[train], obs[train], cut)
        bq[:, j] = bins_of(x[query], obs[query], cut)
    return btr, tab["cols"][attr][train].astype(np.float64), bq


class Imputation:
    """Completed columns: every attribute with a missing cell is fitted at
    once (``PROCESSES`` workers, started afresh: the caller may hold the
    chip); ``ambiguous`` counts the columns asked for."""

    def __init__(self, tables: dict, params: dict, control: bool = False):
        self.tables = tables
        a = _program_args(params)
        p = {"rounds": a["rounds"], "depth": a["max_depth"], "eta": a["eta"],
             "lam": a["lam"], "gamma": a["gamma"],
             "min_child_weight": a["min_child_weight"]}
        self.full: dict = {}  # attr -> Column, the columns handed out
        attrs = list(shapes(tables))
        with ProcessPoolExecutor(
                PROCESSES,
                mp_context=multiprocessing.get_context("spawn")) as pool:
            jobs = {}
            for attr in attrs:
                btr, y, bq = _inputs(tables[table_of(attr)], attr)
                jobs[attr] = (y, pool.submit(fit_attribute, btr, y, bq, p,
                                             control))
            self._done = {attr: self._column(attr, y, *job.result())
                          for attr, (y, job) in jobs.items()}

    def _column(self, attr: str, y, val, lo, hi, opened: bool) -> Column:
        tab = self.tables[table_of(attr)]
        col = tab["cols"][attr]
        query = np.nonzero(tab["missing"][attr])[0]
        out = col.copy()
        if opened:
            lo[:], hi[:] = y.min(), y.max()
        if np.issubdtype(col.dtype, np.floating):
            out[query] = val
            c = Column(out)
            amb = lo < hi
            c.lo[query[amb]], c.hi[query[amb]] = lo[amb], hi[amb]
        else:
            vocab = np.unique(y)
            out[query] = _snap(val, vocab)
            c = Column(out)
            slo, shi = _snap(lo, vocab), _snap(hi, vocab)
            amb = slo < shi
            for i in np.nonzero(amb)[0]:
                c.sets[int(query[i])] = (None if opened else frozenset(
                    vocab[(vocab >= slo[i]) & (vocab <= shi[i])].tolist()))
            c.lo[query[amb]], c.hi[query[amb]] = slo[amb], shi[amb]
        c.amb[query] = amb
        c.open[query] = amb & opened
        return c

    def column(self, attr: str) -> Column:
        got = self.full.get(attr)
        if got is None:
            got = self.full[attr] = self._done.get(attr) or Column(
                self.tables[table_of(attr)]["cols"][attr])
        return got

    def ambiguous(self) -> tuple:
        """``(ambiguous cells, open cells, imputed cells)`` over the
        columns filled."""
        n_amb = sum(int(c.amb.sum()) for c in self.full.values())
        n_open = sum(int(c.open.sum()) for c in self.full.values())
        n_imp = sum(int(self.tables[table_of(a)]["missing"][a].sum())
                    for a in self.full)
        return n_amb, n_open, n_imp


def _snap(pred: np.ndarray, vocab: np.ndarray) -> np.ndarray:
    """Each value's nearest value of ``vocab`` (sorted), ties up."""
    hi = np.clip(np.searchsorted(vocab, pred), 0, len(vocab) - 1)
    lo = np.clip(hi - 1, 0, len(vocab) - 1)
    return np.where(np.abs(vocab[lo] - pred) < np.abs(vocab[hi] - pred),
                    vocab[lo], vocab[hi])
