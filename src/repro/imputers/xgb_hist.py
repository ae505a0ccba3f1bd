"""XGBoost-hist imputation (blocking): 100 boosted trees of depth 6 per
(table, attribute), trained on the device when a query first needs the
attribute (Chen and Guestrin, KDD 2016; ``tree_method=hist``).

Per (table, attribute) fit (:meth:`XgbHistImputer.fit_attr`):

* training rows are the rows that observe the attribute, its target;
  features are the table's other columns but ``id``, their missing cells
  left missing (the default directions route them);
* each feature's cut points come from its observed values on the training
  rows (:func:`cuts`); a value's bin is the number of cuts at or below it,
  a missing cell takes the missing slot (``kernels.gbdt_hist.MISSING``);
* the base score is the mean of the target, and one device program
  (``kernels.gbdt_hist.boost``) grows every tree at the training rows'
  bucket (``train_bucket``, at most ``MAX_TRAIN_ROWS``): splits from
  histogram sums exact to float32 rounding, each row's leaf per round back
  to the host;
* the leaf weights are refit in float64 on the host from those leaves:
  ``g`` starts at base less target, and per round each leaf takes
  ``-eta * G / (H + lambda)`` over its rows and every row's ``g`` moves by it.

Prediction (:meth:`XgbHistImputer.impute_attr`) bins the requested rows
with the same cuts, routes them through every tree on the device
(``kernels.gbdt_hist.predict``, batches of ``PREDICT_BATCH`` padded to
``predict_bucket``) and sums base plus the leaf weights in float64, tree by
tree.  Integer-typed columns snap to the nearest observed value.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from repro.core.relation import MaskedRelation
from repro.imputers.base import Imputer
from repro.kernels import gbdt_hist as kg
from repro.obs.trace import NULL_SPAN

__all__ = ["XgbHistImputer", "cuts", "warm_up"]


def cuts(values: np.ndarray) -> np.ndarray:
    """A feature's cut points from its observed values on the training
    rows: every distinct value but the least where there are at most
    ``MAX_BIN`` of them, else the distinct values among the sorted values
    at positions ``j * m // MAX_BIN``, ``j = 1 .. MAX_BIN - 1`` (``m``
    values).  At most ``MAX_BIN - 1`` cuts, so at most ``MAX_BIN`` bins."""
    s = np.sort(np.asarray(values, dtype=np.float64))
    u = np.unique(s)
    if len(u) <= kg.MAX_BIN:
        return u[1:]
    return np.unique(s[np.arange(1, kg.MAX_BIN) * len(s) // kg.MAX_BIN])


def _bins(x: np.ndarray, observed: np.ndarray, cut: np.ndarray) -> np.ndarray:
    return np.where(observed, np.searchsorted(cut, x, side="right"),
                    kg.MISSING).astype(np.int16)


def _snap(pred: np.ndarray, vocab: np.ndarray) -> np.ndarray:
    """Each prediction's nearest value of ``vocab`` (sorted), ties up."""
    if not len(vocab):
        return pred
    hi = np.clip(np.searchsorted(vocab, pred), 0, len(vocab) - 1)
    lo = np.clip(hi - 1, 0, len(vocab) - 1)
    return np.where(np.abs(vocab[lo] - pred) < np.abs(vocab[hi] - pred),
                    vocab[lo], vocab[hi])


class _Model:
    __slots__ = ("features", "cuts", "base", "trees", "weights", "vocab")


class XgbHistImputer(Imputer):
    blocking = True

    def __init__(self, rounds: int = 100, max_depth: int = 6,
                 eta: float = 0.3, lam: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, cost_per_value: float = 0.0,
                 train_cost: float = 0.0):
        self.params = dict(rounds=int(rounds), depth=int(max_depth),
                           eta=float(eta), lam=float(lam),
                           gamma=float(gamma),
                           min_child_weight=float(min_child_weight))
        self.cost_per_value = cost_per_value
        self.train_cost = train_cost
        # per attribute, its model as of the last fit; callers hold the
        # store's (table, attr) flush lock around ``fit_attr`` and
        # ``impute_attr``
        self._models: Dict[str, _Model] = {}  # guarded-by: flush_lock

    def fit(self, table: MaskedRelation) -> None:  # requires: flush_lock
        self._models = {}

    def fit_attr(self, table: MaskedRelation, attr: str
                 ) -> None:  # requires: flush_lock
        p = self.params
        rows = np.nonzero(table.is_present(attr))[0]
        if len(rows) > kg.MAX_TRAIN_ROWS:
            raise ValueError(f"{attr}: {len(rows)} training rows, more than "
                             f"a boosting program takes "
                             f"({kg.MAX_TRAIN_ROWS})")
        bucket = kg.train_bucket(len(rows))
        tr = self.tracer
        with (tr.span("gbdt:fit", cat="impute", table=table.schema.name,
                      attr=attr,
                      rows=len(rows), bucket=bucket)
              if tr.enabled else NULL_SPAN) as sp:
            m = _Model()
            m.features = [c for c in table.column_names()
                          if c != attr and c.split(".")[-1] != "id"]
            with (tr.span("gbdt:bin", cat="impute") if tr.enabled
                  else NULL_SPAN):
                m.cuts, bins = [], np.full((bucket, len(m.features)),
                                           kg.MISSING, np.int16)
                for j, c in enumerate(m.features):
                    obs = table.is_present(c)[rows]
                    x = table.values(c)[rows].astype(np.float64)
                    m.cuts.append(cuts(x[obs]))
                    bins[:len(rows), j] = _bins(x, obs, m.cuts[-1])
            y = table.values(attr)[rows].astype(np.float64)
            m.base = float(y.mean()) if len(y) else 0.0
            g = m.base - y
            g_hi = np.zeros(bucket, np.float32)
            g_hi[:len(rows)] = g
            g_lo = np.zeros(bucket, np.float32)
            g_lo[:len(rows)] = g - g_hi[:len(rows)]
            h = np.zeros(bucket, np.float32)
            h[:len(rows)] = 1.0
            q = np.float32(kg.digit_unit(g))
            n_chunks = np.int32(kg.filled_chunks(len(rows)))
            sp.add(rounds=p["rounds"], h2d_bytes=bins.nbytes + g_hi.nbytes
                   + g_lo.nbytes + h.nbytes)
            with (tr.span("gbdt:boost", cat="kernel", bucket=bucket,
                          onehot_bytes=bins.size * kg.SLOTS)
                  if tr.enabled else NULL_SPAN):
                feat, thr, dleft, leaves = (np.asarray(a) for a in kg.boost(
                    bins, g_hi, g_lo, h, q, n_chunks, **p))
            m.trees = (feat, thr, dleft)
            m.weights = self._refit(leaves[:, :len(rows)], g)
            m.vocab = (None if np.issubdtype(table.cols[attr].dtype,
                                             np.floating)
                       else np.unique(table.values(attr)[rows]))
        self._models[attr] = m

    def _refit(self, leaves: np.ndarray, g: np.ndarray) -> np.ndarray:
        """``(rounds, leaves)`` float64 leaf weights from each training
        row's leaf per round; ``g`` the base score less the target."""
        p = self.params
        n_leaf = 1 << p["depth"]
        out = np.zeros((len(leaves), n_leaf))
        for t, leaf in enumerate(leaves.astype(np.int64)):
            G = np.bincount(leaf, weights=g, minlength=n_leaf)
            H = np.bincount(leaf, minlength=n_leaf)
            out[t] = np.where(H > 0, -p["eta"] * G / (H + p["lam"]), 0.0)
            g = g + out[t][leaf]
        return out

    def impute_attr(self, table: MaskedRelation, attr: str, tids: np.ndarray
                    ) -> np.ndarray:  # requires: flush_lock
        tids = np.asarray(tids, dtype=np.int64)
        if attr not in self._models:  # a model shared by several attributes
            self.fit_attr(table, attr)
        m = self._models[attr]
        tr = self.tracer
        out = np.full(len(tids), m.base)
        with (tr.span("gbdt:predict", cat="kernel", rows=len(tids))
              if tr.enabled else NULL_SPAN):
            for lo in range(0, len(tids), kg.PREDICT_BATCH):
                idx = tids[lo:lo + kg.PREDICT_BATCH]
                bins = np.full((kg.predict_bucket(len(idx)),
                                len(m.features)), kg.MISSING, np.int16)
                for j, c in enumerate(m.features):
                    bins[:len(idx), j] = _bins(
                        table.values(c)[idx].astype(np.float64),
                        table.is_present(c)[idx], m.cuts[j])
                leaves = np.asarray(kg.predict(*m.trees, bins))
                leaves = leaves[:, :len(idx)].astype(np.int64)
                acc = out[lo:lo + len(idx)]
                for t in range(len(leaves)):
                    acc += m.weights[t][leaves[t]]
        return out if m.vocab is None else _snap(out, m.vocab)


def warm_up(shapes: Iterable, params: dict) -> int:
    """Run each device program the imputer can call at each of its shapes
    once: ``shapes`` holds ``(training rows, features)`` pairs; one
    ``boost`` per (training bucket, features) and one ``predict`` per
    (query bucket up to ``PREDICT_BATCH``, features).  ``params`` as
    ``XgbHistImputer`` takes them.  Returns how many."""
    p = XgbHistImputer(**params).params
    shapes = list(shapes)
    nodes = (1 << p["depth"]) - 1
    n = 0
    done = set()
    for rows, nf in shapes:
        key = (kg.train_bucket(rows), nf)
        if key in done:
            continue
        done.add(key)
        zeros = np.zeros(key[0], np.float32)
        kg.boost(np.zeros(key, np.int16), zeros, zeros, zeros,
                 np.float32(1.0), np.int32(1), **p)
        n += 1
    trees = (np.zeros((p["rounds"], nodes), np.int32),
             np.zeros((p["rounds"], nodes), np.int32),
             np.zeros((p["rounds"], nodes), bool))
    for nf in sorted({nf for _rows, nf in shapes}):
        m = 128
        while m <= kg.PREDICT_BATCH:
            np.asarray(kg.predict(*trees, np.zeros((m, nf), np.int16)))
            n += 1
            m *= 2
    return n
