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

Each attribute's reference rows are selected once per fit, on its first
``impute_attr``, and kept on the device: every query batch after that sends
only its own rows.  ``fit`` drops them, so a refit never reads stale rows.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
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
        # per attribute, its reference (r, rm, tgt) as of the last fit;
        # callers hold the store's (table, attr) flush lock around
        # ``fit`` and ``impute_attr``
        self._refs: Dict[str, Tuple] = {}  # guarded-by: flush_lock

    def fit(self, table: MaskedRelation) -> None:  # requires: flush_lock
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
        self._refs = {}

    def _reference(self, table: MaskedRelation, attr: str, keep: np.ndarray,
                   span) -> Tuple:  # requires: flush_lock
        """``attr``'s reference ``(r, rm, tgt)``: the rows that observe
        ``attr``, their ``keep`` features and masks, and their values of
        ``attr``.  Made once per fit and kept; ``r`` and ``rm`` go to the
        device (uncommitted, so the programs compiled for host arrays of
        the same shape serve them) unless the distances run on the host,
        and ``span`` counts the upload."""
        ref = self._refs.get(attr)
        if ref is not None:
            return ref
        rows = self._mask[:, self._cols.index(attr)].nonzero()[0]
        r, rm = self._feat[rows][:, keep], self._mask[rows][:, keep]
        if kops.resolve_dist_impl(self.impl) != "numpy":
            span.add(h2d_bytes=r.nbytes + rm.nbytes)
            r, rm = jax.device_put(r), jax.device_put(rm)
        ref = self._refs[attr] = (r, rm, table.values(attr)[rows])
        return ref

    def impute_attr(self, table: MaskedRelation, attr: str, tids: np.ndarray
                    ) -> np.ndarray:  # requires: flush_lock
        # exclude attr itself from the distance features
        keep = np.ones(self._feat.shape[1], dtype=bool)
        keep[self._cols.index(attr)] = False
        out = np.zeros(len(tids), dtype=np.float64)
        is_int = not np.issubdtype(table.cols[attr].dtype, np.floating)
        tr = self.tracer
        for lo in range(0, len(tids), self.batch):
            idx = tids[lo : lo + self.batch]
            q, qm = self._feat[idx][:, keep], self._mask[idx][:, keep]
            with (tr.span("knn:call", cat="kernel", attr=attr, nq=len(idx),
                          d=int(keep.sum()),
                          ref_resident=attr in self._refs)
                  if tr.enabled else NULL_SPAN) as sp:
                r, rm, tgt = self._reference(table, attr, keep, sp)
                sp.set(nr=r.shape[0])
                _d, nn = kops.masked_knn(
                    q, qm, r, rm,
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
