"""A second imputer kind, kept as a test fixture and not under
``bench/imputers/``: the program's ``MeanImputer`` (ImputeDB's histogram
statistics) and a plain reference of the same semantics.  The tests copy it
into a temporary tree as ``bench/imputers/mean.py``; no cell runs it.

Semantics: a float attribute's missing cells take the mean of its present
values' histogram (``bins`` equal-width bins from the least to the largest
present value, the top bin closed, each bin weighed at its centre); an
integer attribute's take the mode of its present values, ties to the
smaller.  A float fill is a float64 sum of ``bins`` terms over the count,
which the program may add in another order, so it is known to that
rounding alone: each imputed float cell is ambiguous in an interval of
``bins + 2`` roundoffs of the sum of the terms' magnitudes (the sum's
rounding, the products' and the division's), over the count.  An integer
fill is exact.
"""

from __future__ import annotations

import functools

import numpy as np

from reference import Column, table_of


def factory(params: dict):
    from repro.imputers import MeanImputer

    return functools.partial(MeanImputer, bins=params["bins"])


def warm_up(tables: dict, params: dict) -> int:
    """The imputer runs on the host: no device program."""
    return 0


def context(tables: dict, params: dict) -> dict:
    return {}


def reference(tables: dict, params: dict, control: bool = False):
    if control:
        raise NotImplementedError("a histogram mean states no precision")
    return Imputation(tables, params["bins"])


def _fill(present: np.ndarray, bins: int) -> tuple:
    """The fill value and the rounding it is known to."""
    if not np.issubdtype(present.dtype, np.floating):
        values, counts = np.unique(present, return_counts=True)
        return float(values[counts == counts.max()].min()), 0.0
    lo, hi = float(present.min()), float(present.max())
    if lo == hi:
        lo, hi = lo - 0.5, hi + 0.5
    edges = np.linspace(lo, hi, bins + 1)
    which = np.minimum(np.searchsorted(edges, present, side="right") - 1,
                       bins - 1)
    counts = np.bincount(which, minlength=bins)
    centres = (edges[:-1] + edges[1:]) / 2
    terms = [int(c) * float(m) for c, m in zip(counts, centres)]
    n = len(present)
    return (sum(terms) / n,
            (bins + 2) * np.finfo(np.float64).eps * sum(map(abs, terms)) / n)


class Imputation:
    def __init__(self, tables: dict, bins: int):
        self.tables = tables
        self.bins = int(bins)
        self.full: dict = {}  # attr -> Column

    def column(self, attr: str) -> Column:
        got = self.full.get(attr)
        if got is None:
            tab = self.tables[table_of(attr)]
            col, miss = tab["cols"][attr], tab["missing"][attr]
            out = col.copy()
            fill, r = _fill(col[~miss], self.bins) if miss.any() else (0, 0)
            out[miss] = fill
            got = self.full[attr] = Column(out)
            if r:
                got.amb[miss] = True
                got.lo[miss], got.hi[miss] = fill - r, fill + r
        return got

    def ambiguous(self) -> tuple:
        n_amb = sum(int(c.amb.sum()) for c in self.full.values())
        n_imp = sum(int(self.tables[table_of(a)]["missing"][a].sum())
                    for a in self.full)
        return n_amb, 0, n_imp
