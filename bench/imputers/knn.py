"""The k-NN imputer kind: the program's ``KnnImputer``, its warm-up, its
per-layer context, and the reference imputation with its control.

The reference imputes every missing cell of an attribute with the semantics
the configuration states (sklearn ``KNNImputer``-style masked Euclidean
distance):

* features are every column of the table as float32 values, z-scored
  over their present cells, missing cells masked out; the imputed
  attribute is left out;
* reference rows are the rows that observe the attribute;
* ``dist = (d / n_co) * sum over co-observed features of (q - r)**2``, with
  ``d`` the feature count and ``n_co`` the co-observed count (no overlap:
  +inf);
* the ``k`` nearest (ties to the lower row), then the mode of their values
  for an integer attribute (ties to the smaller value) or their mean for a
  float one.

Distances are direct differences in float32 on the default device, a
straightforward form with no cancellation; the control computes them
instead in the expanded form ``q^2 + r^2 - 2qr`` with every product in
three bfloat16 passes (``precision="high"``), and must come out as not
correct.

**Admissible values.**  Float32 distances cannot rank two neighbours whose
distances differ by less than their rounding, and integer-coded data has
exact ties that any rounding breaks one way or the other.  So each imputed
cell also gets the set of values it may take: the reference's ``k`` nearest
are fetched with ``EXTRA`` more, and a candidate whose distance lies within
the float32 bound of the ``k``-th's (``band``) may take or leave a place.
A cell with no such candidate beyond the ``k``-th is *determined*: it has
one admissible value, the reference's.  Otherwise it is *ambiguous*: an
integer cell may take the mode of any admissible choice of neighbours (a
set), a float cell any mean between the least and the largest choice (an
interval).  Where the last fetched candidate still lies in the band, the
cell is *open*: the free places may hold any value in the attribute's
range.  The band is the configuration's float32 precision, not a
tolerance on answers: a neighbour outside it, or any determined cell, has
to be exactly the reference's.

The program is imported only inside ``factory`` and ``warm_up``, which
``sut.py`` calls; the reference reads only the generated tables.
"""

from __future__ import annotations

import functools
import itertools
import math

import numpy as np

import jax
import jax.numpy as jnp

from reference import Column, table_of

__all__ = ["factory", "knn_shapes", "warm_up", "context", "reference"]


# --------------------------------------------------------------------------- #
# the program's imputer
# --------------------------------------------------------------------------- #
def factory(params: dict):
    """The ``KnnImputer`` factory ``QuipService`` takes, from the
    configuration's ``imputer`` entry."""
    from repro.imputers import KnnImputer

    return functools.partial(KnnImputer, k=params["k"], batch=params["batch"],
                             cost_per_value=params["cost_per_value"])


def knn_shapes(tables: dict) -> dict:
    """``attr -> (reference rows, features)`` for every attribute with a
    missing cell: the sizes of the imputer's device programs."""
    out = {}
    for tab in tables.values():
        d = len(tab["columns"]) - 1
        for c, m in tab["missing"].items():
            if m.any():
                out[c] = (int((~m).sum()), d)
    return out


def warm_up(tables: dict, params: dict) -> int:
    """One masked-distance and one top-k program per (attribute with a
    missing cell, query bucket): the imputer pads query batches of at most
    ``batch`` rows to powers of two from 128, and reference rows are not
    padded.  Returns how many."""
    from repro.kernels import ops as kops

    n = 0
    for nr, d in knn_shapes(tables).values():
        r = np.zeros((nr, d), np.float32)
        nq = 128
        while nq <= params["batch"]:
            q = np.zeros((nq, d), np.float32)
            kops.masked_knn(q, q, r, r, min(params["k"], nr))
            n += 2
            nq *= 2
    return n


def context(tables: dict, params: dict) -> dict:
    """What ``metrics/knn_roofline.py`` reads: each attribute's program
    sizes and ``k``."""
    return {"knn_shapes": knn_shapes(tables), "k": params["k"]}


# --------------------------------------------------------------------------- #
# the reference imputation
# --------------------------------------------------------------------------- #
#: query rows per device block, and the multiple reference rows pad to
QUERY_BLOCK = 512
REF_PAD = 4096
#: candidates fetched beyond the k nearest, to find the ties at the k-th
EXTRA = 8
#: neighbour choices enumerated for an ambiguous integer cell; beyond it
#: every value of a candidate is admissible
MAX_CHOICES = 512
#: float32 unit roundoff
EPS32 = 2.0 ** -24
#: roundoffs of the expanded form's magnitude ``sum of co-observed
#: q^2 + r^2`` (scaled like the distance) that a distance may be off by:
#: per-element rounding of the z-scores and of the distance's own sums
ABS_UNITS = 8.0
#: the probabilistic bound's confidence: a sum of n float32 terms is off
#: by more than ``LAMBDA * sqrt(n)`` roundoffs of the sum of their
#: magnitudes with probability under ``2 n exp(-LAMBDA**2 / 2)`` (Higham and
#: Mary, "A new approach to probabilistic rounding error analysis", SIAM J.
#: Sci. Comput. 41(5), 2019: the bound on inner products), under 1e-5 for
#: n = 200,000
LAMBDA = 7.0


def rel_units(n: int) -> float:
    """Roundoffs of a distance that a column's float32 standard deviation
    over ``n`` rows may move it by.

    The variance is a float32 sum of ``n`` squares, each rounded twice
    (subtract, square): off by ``LAMBDA * sqrt(n) + 3`` roundoffs, one
    more for the division.  The standard deviation is off by half that
    plus one for the square root, and a feature's share of a distance
    scales by the inverse square of it: twice that, ``LAMBDA * sqrt(n) +
    6`` roundoffs of the distance.  The column's mean cancels in every
    difference of two z-scores."""
    return LAMBDA * math.sqrt(n) + 6.0



def band(dist: np.ndarray, mag: np.ndarray, n: int) -> np.ndarray:
    """The float32 rounding bound of each distance over a table of ``n``
    rows: ``ABS_UNITS`` unit roundoffs of ``mag`` plus ``rel_units(n)``
    of the distance."""
    with np.errstate(invalid="ignore"):
        return EPS32 * (ABS_UNITS * mag + rel_units(n) * np.abs(dist))


def _hi(x):
    """``x`` rounded to bfloat16, kept in float32 (``reduce_precision`` is
    never folded away, as a round trip through ``astype`` can be)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dot3(a, b):
    """``a @ b.T`` in three bfloat16 passes (hi*hi + hi*lo + lo*hi), each
    product exact in float32: the ``high`` precision."""
    ah, bh = _hi(a), _hi(b)
    al, bl = _hi(a - ah), _hi(b - bh)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return mm(ah, bh.T) + mm(ah, bl.T) + mm(al, bh.T)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _nearest(q, qm, r, rm, *, k: int, precision: str):
    """The ``k`` nearest reference rows per query row: indices, distances
    and each distance's magnitude ``(d / n_co) * sum (q^2 + r^2)`` over
    the co-observed features."""
    d = q.shape[1]
    if precision == "high":
        qv, rv = q * qm, r * rm
        sq = _dot3(qv * qv, rm) + _dot3(qm, rv * rv) - 2.0 * _dot3(qv, rv)
        sq = jnp.maximum(sq, 0.0)
    else:
        sq = jnp.zeros((q.shape[0], r.shape[0]), jnp.float32)
        for j in range(d):
            diff = q[:, j:j + 1] - r[None, :, j]
            sq = sq + (qm[:, j:j + 1] * rm[None, :, j]) * diff * diff
    nco = jnp.zeros_like(sq)
    for j in range(d):
        nco = nco + qm[:, j:j + 1] * rm[None, :, j]
    scale = d / jnp.maximum(nco, 1.0)
    dist = jnp.where(nco > 0, sq * scale, jnp.inf)
    neg, idx = jax.lax.top_k(-dist, k)
    both = qm[:, None, :] * rm[idx]
    mag = jnp.sum(both * (q[:, None, :] ** 2 + r[idx] ** 2), axis=-1)
    return idx, -neg, mag * jnp.take_along_axis(scale, idx, axis=1)


def _mode(neigh: np.ndarray) -> np.ndarray:
    """Row-wise mode, ties to the smallest value."""
    s = np.sort(neigh, axis=1)
    best = s[:, 0].copy()
    best_n = np.zeros(len(s), dtype=np.int64)
    run = np.ones(len(s), dtype=np.int64)
    for j in range(1, s.shape[1] + 1):
        if j < s.shape[1]:
            same = s[:, j] == s[:, j - 1]
        else:
            same = np.zeros(len(s), dtype=bool)
        end = ~same
        better = end & (run > best_n)
        best = np.where(better, s[:, j - 1], best)
        best_n = np.where(better, run, best_n)
        run = np.where(same, run + 1, 1)
    return best


def reference(tables: dict, params: dict, control: bool = False):
    """The reference imputation, or with ``control`` the control: distances
    in the expanded form at ``high`` precision (three bfloat16 passes), one
    step below the configuration's float32 at ``highest``."""
    return Imputation(tables, params["k"],
                      precision="high" if control else "highest")


class Imputation:
    """Completed columns, filled on demand per (table, attribute)."""

    def __init__(self, tables: dict, k: int, precision: str = "highest"):
        self.tables = tables
        self.k = int(k)
        self.precision = precision
        self._z: dict = {}  # table -> (z-scores, masks), float32
        self.full: dict = {}  # attr -> Column

    def _features(self, t: str):
        got = self._z.get(t)
        if got is None:
            tab = self.tables[t]
            names = [c for c, _k in tab["columns"]]
            # the features are the float32 values of the cells
            x = np.stack([tab["cols"][c].astype(np.float32).astype(np.float64)
                          for c in names], 1)
            m = ~np.stack([tab["missing"][c] for c in names], 1)
            x = np.where(m, x, 0.0)
            n = np.maximum(m.sum(0), 1)
            mean = x.sum(0) / n
            std = np.sqrt(np.maximum(((x - mean) ** 2 * m).sum(0) / n, 1e-6))
            z = np.where(m, (x - mean) / std, 0.0)
            got = self._z[t] = (z.astype(np.float32), m.astype(np.float32))
        return got

    def _impute(self, attr: str) -> Column:
        t = table_of(attr)
        tab = self.tables[t]
        col = tab["cols"][attr]
        miss = tab["missing"][attr]
        if not miss.any():
            return Column(col)
        names = [c for c, _k in tab["columns"]]
        z, m = self._features(t)
        keep = np.array([c != attr for c in names])
        refs = ~miss
        r, rm = z[refs][:, keep], m[refs][:, keep]
        target = col[refs]
        nr = len(r)
        pad = -nr % REF_PAD
        r = np.concatenate([r, np.zeros((pad, r.shape[1]), np.float32)])
        rm = np.concatenate([rm, np.zeros((pad, r.shape[1]), np.float32)])
        rows = np.nonzero(miss)[0]
        k = min(self.k, nr)
        fetch = min(k + EXTRA, nr)
        r_dev, rm_dev = jnp.asarray(r), jnp.asarray(rm)
        idx, dist, mag = [], [], []
        for lo in range(0, len(rows), QUERY_BLOCK):
            ix = rows[lo:lo + QUERY_BLOCK]
            q = np.zeros((QUERY_BLOCK, r.shape[1]), np.float32)
            qm = np.zeros_like(q)
            q[:len(ix)], qm[:len(ix)] = z[ix][:, keep], m[ix][:, keep]
            got = _nearest(jnp.asarray(q), jnp.asarray(qm), r_dev, rm_dev,
                           k=fetch, precision=self.precision)
            for acc, a in zip((idx, dist, mag), got):
                acc.append(np.asarray(a)[:len(ix)])
        # padded reference rows observe nothing (+inf) and sit after every
        # real row, so with k <= nr the lower-index tie rule never picks one
        idx, dist = np.concatenate(idx), np.concatenate(dist)
        mag = np.concatenate(mag)
        neigh = target[idx[:, :k]]
        is_float = np.issubdtype(col.dtype, np.floating)
        vals = (neigh.astype(np.float64).mean(axis=1) if is_float
                else _mode(neigh))
        out = col.copy()
        out[rows] = vals
        amb, open_, lo_, hi_, sets = self._admissible(
            target, idx, dist, band(dist, mag, len(col)), k, is_float)
        c = Column(out)
        c.amb[rows] = amb
        c.open[rows] = open_
        c.lo[rows[amb]], c.hi[rows[amb]] = lo_, hi_
        c.sets = {int(rows[i]): s for i, s in zip(np.nonzero(amb)[0], sets)}
        return c

    @staticmethod
    def _admissible(target, idx, dist, bnd, k: int, is_float: bool):
        """Which query rows are ambiguous and which of them open, and the
        ambiguous rows' ``lo``, ``hi`` and (integer) admissible sets, from
        the fetched candidates.  An open row may take any reference row in
        the places its band leaves free: any value in the attribute's
        range there."""
        tmin, tmax = float(target.min()), float(target.max())
        kth, bk = dist[:, k - 1:k], bnd[:, k - 1:k]
        with np.errstate(invalid="ignore"):
            near = (np.abs(dist - kth) <= bnd + bk) | (
                np.isinf(dist) & np.isinf(kth))
        chosen = near[:, k:].any(axis=1)
        # the last fetched candidate in the band: more may lie beyond it
        open_ = near[:, -1] & chosen if idx.shape[1] > k else chosen
        amb = chosen
        if is_float:
            # a mean is summed in its neighbours' order: where two of the k
            # nearest are as near as rounding, it is known to rounding
            with np.errstate(invalid="ignore"):
                swap = (np.abs(np.diff(dist[:, :k], axis=1))
                        <= bnd[:, :k - 1] + bnd[:, 1:k]).any(axis=1)
            amb = chosen | swap
        lo, hi, sets = [], [], []
        for i in np.nonzero(amb)[0]:
            sure = [j for j in range(k) if not near[i, j]]
            maybe = [j for j in range(idx.shape[1]) if near[i, j]]
            vs = target[idx[i, sure]]
            vb = np.sort(target[idx[i, maybe]])
            m = k - len(sure)
            if open_[i] and is_float:
                lo.append((vs.sum() + m * tmin) / k)
                hi.append((vs.sum() + m * tmax) / k)
                sets.append(None)
            elif open_[i]:
                lo.append(tmin)
                hi.append(tmax)
                sets.append(None)
            elif not chosen[i]:  # the same neighbours in another order
                v = target[idx[i, :k]].astype(np.float64)
                r = 4 * np.finfo(np.float64).eps * np.abs(v).sum()
                lo.append(v.mean() - r)
                hi.append(v.mean() + r)
                sets.append(None)
            elif is_float:
                lo.append((vs.sum() + vb[:m].sum()) / k)
                hi.append((vs.sum() + vb[-m:].sum()) / k)
                sets.append(None)
            else:
                if math.comb(len(vb), m) <= MAX_CHOICES:
                    pick = np.array(list(itertools.combinations(vb, m)))
                    full = np.concatenate(
                        [np.broadcast_to(vs, (len(pick), len(vs))), pick], 1)
                    s = frozenset(_mode(full).tolist())
                else:
                    s = frozenset(np.concatenate([vs, vb]).tolist())
                lo.append(min(s))
                hi.append(max(s))
                sets.append(s)
        return (amb, open_ & amb, np.array(lo, np.float64),
                np.array(hi, np.float64), sets)

    def column(self, attr: str) -> Column:
        got = self.full.get(attr)
        if got is None:
            got = self.full[attr] = self._impute(attr)
        return got

    def ambiguous(self) -> tuple:
        """``(ambiguous cells, open cells, imputed cells)`` over the
        columns filled."""
        n_amb = sum(int(c.amb.sum()) for c in self.full.values())
        n_open = sum(int(c.open.sum()) for c in self.full.values())
        n_imp = sum(int(self.tables[table_of(a)]["missing"][a].sum())
                    for a in self.full)
        return n_amb, n_open, n_imp
