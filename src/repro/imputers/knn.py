"""Masked-KNN imputation (blocking; sklearn.impute.KNNImputer semantics).

The reference matrix is the whole table (standardized numeric view, missing
cells masked).  Inference computes partial L2 distances over co-observed
dimensions — the imputation hot spot the paper measures (Fig. 2: KNN
inference dominates query time) — via ``kernels.ops.masked_knn``: unset,
the masked distance is the Mosaic-compiled Pallas kernel on TPU and the jnp
``ref`` path on the CPU (``QUIP_DIST_IMPL``), and top-k runs on the same
device.  Neighbour aggregation (mean / categorical mode) is the vectorized
``kernels.ops.neighbor_aggregate`` op, dispatched with ``QUIP_KNN_IMPL``
(numpy by default | ref | pallas).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.relation import MaskedRelation
from repro.imputers.base import Imputer
from repro.kernels import ops as kops
from repro.obs.trace import NULL_SPAN

__all__ = ["KnnImputer"]


class KnnImputer(Imputer):
    blocking = True

    def __init__(self, k: int = 5, cost_per_value: float = 0.0,
                 train_cost: float = 0.0, impl: Optional[str] = None,
                 agg_impl: Optional[str] = None, batch: int = 1024):
        self.k = k
        self.cost_per_value = cost_per_value
        self.train_cost = train_cost
        self.impl = impl  # masked-distance dispatch (None: backend default)
        self.agg_impl = agg_impl  # neighbour aggregation (None: QUIP_KNN_IMPL)
        self.batch = batch
        self._feat = None  # (n, d) float32, 0-filled
        self._mask = None  # (n, d) float32 observed mask
        self._mean = None
        self._std = None
        self._cols = None

    def fit(self, table: MaskedRelation) -> None:
        cols = table.column_names()
        n = table.num_rows
        feat = np.zeros((n, len(cols)), dtype=np.float32)
        mask = np.zeros((n, len(cols)), dtype=np.float32)
        for i, c in enumerate(cols):
            present = table.is_present(c)
            v = table.values(c).astype(np.float32)
            feat[:, i] = np.where(present, v, 0.0)
            mask[:, i] = present.astype(np.float32)
        denom = np.maximum(mask.sum(axis=0), 1.0)
        mean = (feat * mask).sum(axis=0) / denom
        var = ((feat - mean) ** 2 * mask).sum(axis=0) / denom
        std = np.sqrt(np.maximum(var, 1e-6))
        self._feat = ((feat - mean) / std) * mask
        self._mask = mask
        self._mean, self._std = mean, std
        self._cols = cols

    def impute_attr(self, table: MaskedRelation, attr: str, tids: np.ndarray
                    ) -> np.ndarray:
        ai = self._cols.index(attr)
        ref_rows = self._mask[:, ai] > 0  # neighbours must observe attr
        r, rm = self._feat[ref_rows], self._mask[ref_rows]
        tgt = table.values(attr)[ref_rows.nonzero()[0]]  # aligned targets
        # exclude attr itself from the distance features
        keep = np.ones(self._feat.shape[1], dtype=bool)
        keep[ai] = False
        out = np.zeros(len(tids), dtype=np.float64)
        is_int = not np.issubdtype(table.cols[attr].dtype, np.floating)
        tr = self.tracer
        for lo in range(0, len(tids), self.batch):
            idx = tids[lo : lo + self.batch]
            q, qm = self._feat[idx][:, keep], self._mask[idx][:, keep]
            with (tr.span("knn:call", cat="kernel", attr=attr, nq=len(idx),
                          nr=r.shape[0], d=int(keep.sum()))
                  if tr.enabled else NULL_SPAN) as sp:
                _d, nn = kops.masked_knn(
                    q, qm, r[:, keep], rm[:, keep],
                    k=min(self.k, r.shape[0]), impl=self.impl, span=sp,
                )
            neigh = tgt[nn]  # (b, k) raw target values
            # vectorized neighbour aggregation: bincount-argmax mode for
            # dictionary-coded categoricals, mean for floats (no per-row
            # Python loop — this is the Fig. 2 inference hot spot)
            out[lo : lo + len(idx)] = kops.neighbor_aggregate(
                neigh, categorical=is_int, impl=self.agg_impl
            )
        return out
