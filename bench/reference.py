"""The plain reference: what every answer must be.

It imputes every missing cell of the attributes a query names, the paper's
offline approach, then evaluates the query over the completed tables with
numpy.  The imputation is the configuration's imputer kind's
(``imputers/<kind>.py``, ``reference``): an object whose ``column(attr)``
gives a completed ``Column`` and whose ``ambiguous()`` counts the cells it
could not decide.

**Admissible values.**  A ``Column`` holds the reference's value of every
row and, where the precision the configuration states cannot decide a cell,
what else it may hold: an *ambiguous* integer cell a set of values, a float
cell an interval; an *open* cell any value in the attribute's range.  The
kind writes beside its band why a cell is ambiguous.  ``expect`` evaluates a
query over these sets: the rows that must be in the answer, the rows that
may be, and bounds on every aggregate.  A determined cell has to be exactly
the reference's.

Nothing here imports the program: the reference reads only the generated
tables (``datagen/``) and the plain query dicts (``querygen.py``).
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["Reference", "Column", "Groups", "key_code", "table_of"]


def table_of(attr: str) -> str:
    return attr.split(".", 1)[0]


class Column:
    """A completed column: the reference's value of every row, and for the
    ambiguous ones what else they may hold.

    ``amb`` marks the ambiguous rows; ``lo``/``hi`` bound their values
    (float64, the value itself elsewhere); ``sets[row]`` is an integer
    cell's admissible values, or ``None`` for any value."""

    def __init__(self, val: np.ndarray):
        self.val = val
        self.is_int = not np.issubdtype(val.dtype, np.floating)
        self.amb = np.zeros(len(val), dtype=bool)
        #: ambiguous rows with more candidates in the band than were
        #: fetched: bounded only by the attribute's range
        self.open = np.zeros(len(val), dtype=bool)
        self.lo = val.astype(np.float64)
        self.hi = val.astype(np.float64)
        self.sets: dict = {}

    def admits(self, rows: np.ndarray, got: np.ndarray) -> np.ndarray:
        """Whether ``got[i]`` is an admissible value of row ``rows[i]``."""
        got = np.asarray(got)
        want = self.val[rows]
        if self.is_int:
            ok = got == want
        else:
            ok = np.abs(got - want) <= 1e-9 * np.maximum(1.0, np.abs(want))
        for i in np.nonzero(self.amb[rows] & ~ok)[0]:
            ok[i] = self.admits_one(int(rows[i]), float(got[i]))
        return ok

    def admits_one(self, row: int, value: float) -> bool:
        if self.is_int:
            s = self.sets.get(row)
            if s is None:
                return self.lo[row] <= value <= self.hi[row]
            return value in s
        tol = 1e-9 * max(1.0, abs(self.lo[row]), abs(self.hi[row]))
        return self.lo[row] - tol <= value <= self.hi[row] + tol


class Reference:
    """SPJA evaluation over the tables completed by ``imputation`` (the
    kind's reference: ``column(attr) -> Column``)."""

    def __init__(self, tables: dict, imputation):
        self.tables = tables
        self.imputation = imputation

    def column(self, attr: str) -> Column:
        return self.imputation.column(attr)

    # -- evaluation ------------------------------------------------------#
    def _selected(self, q: dict, t: str):
        """Rows of ``t`` that pass its selections: ``(reference's, sure,
        possible)`` masks."""
        n = len(self.tables[t]["cols"][self.tables[t]["columns"][0][0]])
        ref, sure, poss = (np.ones(n, dtype=bool) for _ in range(3))
        for attr, op, val in q["selections"]:
            if table_of(attr) != t:
                continue
            c = self.column(attr)
            hit = _compare(c.val, op, val)
            ref &= hit
            s, p = hit.copy(), hit.copy()
            for row in np.nonzero(c.amb)[0]:
                s[row], p[row] = _admits_cmp(c, int(row), op, val)
            sure &= s
            poss &= p
        return ref, sure, poss

    def _chain(self, q: dict, keep: dict, exploded: bool) -> dict:
        """The joined rows: ``table -> row ids``, one entry per output row.
        ``exploded``: an ambiguous key matches every admissible value (rows
        that may be in the answer), else the reference's."""
        order = q["tables"]
        cur = {order[0]: np.nonzero(keep[order[0]])[0]}
        for left, right in q["joins"]:
            if table_of(left) not in cur:
                left, right = right, left
            lt, rt = table_of(left), table_of(right)
            if rt in cur:
                raise NotImplementedError("cyclic joins")
            lcol, rcol = self.column(left), self.column(right)
            rrows = np.nonzero(keep[rt])[0]
            if exploded:
                lpos, lkeys = _explode(lcol, cur[lt], rcol.val[rrows])
                rpos, rkeys = _explode(rcol, rrows, lcol.val[cur[lt]])
            else:
                lpos, lkeys = np.arange(len(cur[lt])), lcol.val[cur[lt]]
                rpos, rkeys = np.arange(len(rrows)), rcol.val[rrows]
            probe, build = _equi_join(lkeys, rkeys)
            cur = {t: ix[lpos[probe]] for t, ix in cur.items()}
            cur[rt] = rrows[rpos[build]]
            if exploded:  # two admissible keys can match the same rows
                seen = [t for t in order if t in cur]
                _, first = np.unique(_codes(cur, self.tables, seen),
                                     return_index=True)
                cur = {t: ix[np.sort(first)] for t, ix in cur.items()}
        return cur

    def answer(self, q: dict) -> dict:
        """The query's answer from the reference's own values:
        ``{"columns", "values", "null"}``, and for a projection ``tids``
        (``table -> row ids``)."""
        keep = {t: self._selected(q, t)[0] for t in q["tables"]}
        cur = self._chain(q, keep, exploded=False)

        def col(attr):
            return self.column(attr).val[cur[table_of(attr)]]

        n = len(next(iter(cur.values())))
        if q["aggregate"] is None:
            vals = [col(a) for a in q["projection"]]
            return {"columns": list(q["projection"]), "values": vals,
                    "null": [np.zeros(n, dtype=bool) for _ in vals],
                    "tids": cur}
        op, attr, gb = q["aggregate"]
        name = f"{op}({attr})"
        v = col(attr)
        if gb is None:
            if op == "count":
                return {"columns": [name], "values": [np.array([n])],
                        "null": [np.zeros(1, dtype=bool)]}
            if n == 0:
                return {"columns": [name], "values": [np.array([0])],
                        "null": [np.ones(1, dtype=bool)]}
            return {"columns": [name], "values": [np.array([_reduce(op, v)])],
                    "null": [np.zeros(1, dtype=bool)]}
        keys = col(gb)
        order = np.argsort(keys, kind="stable")
        keys, v = keys[order], v[order]
        uniq, start = np.unique(keys, return_index=True)
        bounds = list(start) + [len(keys)]
        out = np.array([_reduce(op, v[bounds[i]:bounds[i + 1]])
                        for i in range(len(uniq))])
        return {"columns": [gb, name], "values": [uniq, out],
                "null": [np.zeros(len(uniq), dtype=bool)] * 2}

    def expect(self, q: dict) -> dict:
        """What an answer may be, for ``compare.items_off``.

        A projection: ``rows`` (``"sure"``: row-id codes every admissible
        answer has, ``"possible"``: codes any may have), the row-id
        ``radix`` and the projected ``Column``s.  An aggregate: a
        ``Groups`` that bounds each group's value."""
        sel = {t: self._selected(q, t) for t in q["tables"]}
        ref = self._chain(q, {t: s[0] for t, s in sel.items()}, False)
        poss = self._chain(q, {t: s[2] for t, s in sel.items()}, True)
        # a row of the reference's answer is sure when every table's row
        # surely passes and no join key it matched on is ambiguous
        certain = np.ones(len(next(iter(ref.values()))), dtype=bool)
        for t, ix in ref.items():
            certain &= sel[t][1][ix]
        for pair in q["joins"]:
            for a in pair:
                certain &= ~self.column(a).amb[ref[table_of(a)]]
        order = q["tables"]
        sure = _codes({t: ix[certain] for t, ix in ref.items()}, self.tables,
                      order)
        pcodes = _codes(poss, self.tables, order)
        if q["aggregate"] is None:
            return {"aggregate": False, "columns": list(q["projection"]),
                    "tables": list(order), "sure": np.sort(sure),
                    "possible": np.sort(pcodes),
                    "radix": _radix(self.tables, order),
                    "cols": [self.column(a) for a in q["projection"]]}
        op, attr, gb = q["aggregate"]
        is_sure = np.isin(pcodes, sure)
        vcol = self.column(attr)
        vrows = poss[table_of(attr)]
        groups = Groups(op, vcol.lo[vrows], vcol.hi[vrows], is_sure,
                        None if gb is None else self.column(gb),
                        None if gb is None else poss[table_of(gb)])
        name = f"{op}({attr})"
        return {"aggregate": True, "op": op,
                "columns": [name] if gb is None else [gb, name],
                "groups": groups}


def key_code(keys) -> np.ndarray:
    """Group keys as int64 codes: integers as they are, floats by their
    float64 bits (a key known only to rounding is an ambiguous one)."""
    keys = np.asarray(keys)
    if np.issubdtype(keys.dtype, np.floating):
        return (keys.astype(np.float64) + 0.0).view(np.int64)
    return keys.astype(np.int64)


class Groups:
    """Bounds on a (grouped) aggregate over rows that surely or possibly
    pass, each with an interval value and, when grouped, a key that is
    fixed or (ambiguous) admissible from a set or an interval.

    Rows with a fixed key are summed per key once; a row with an ambiguous
    key may belong to every group its key admits."""

    #: per-group statistics: (name, reduction, neutral, rows, of)
    _STATS = (("ns", np.add, 0.0, "s", "one"), ("no", np.add, 0.0, "o", "one"),
              ("sum_lo_s", np.add, 0.0, "s", "lo"),
              ("sum_hi_s", np.add, 0.0, "s", "hi"),
              ("sum_lo_o", np.add, 0.0, "o", "lo0"),
              ("sum_hi_o", np.add, 0.0, "o", "hi0"),
              ("min_lo_s", np.minimum, np.inf, "s", "lo"),
              ("min_hi_s", np.minimum, np.inf, "s", "hi"),
              ("min_lo_o", np.minimum, np.inf, "o", "lo"),
              ("max_lo_s", np.maximum, -np.inf, "s", "lo"),
              ("max_hi_s", np.maximum, -np.inf, "s", "hi"),
              ("max_hi_o", np.maximum, -np.inf, "o", "hi"))

    def __init__(self, op, lo, hi, sure, kcol=None, krows=None):
        self.op = op
        self.grouped = kcol is not None
        kamb = (kcol.amb[krows] if self.grouped
                else np.zeros(len(lo), dtype=bool))
        codes = (key_code(kcol.val[krows]) if self.grouped
                 else np.zeros(len(lo), dtype=np.int64))
        fixed = ~kamb
        self.keys, self.stats = self._reduce(codes[fixed], lo[fixed],
                                             hi[fixed], sure[fixed])
        self.index = {int(c): i for i, c in enumerate(self.keys)}
        #: codes of the keys that some surely passing row surely holds
        self.sure_keys = self.keys[self.stats["ns"] > 0]
        # rows whose key is ambiguous: possibly in each group they admit
        if self.grouped:
            self.kcol = kcol
            self.arows = krows[kamb]
            self.alo, self.ahi = lo[kamb], hi[kamb]

    @classmethod
    def _reduce(cls, codes, lo, hi, sure):
        order = np.argsort(codes, kind="stable")
        codes, lo, hi, sure = codes[order], lo[order], hi[order], sure[order]
        keys, start = np.unique(codes, return_index=True)
        src = {"one": np.ones(len(lo)), "lo": lo, "hi": hi,
               "lo0": np.minimum(lo, 0.0), "hi0": np.maximum(hi, 0.0)}
        stats = {}
        for name, ufunc, neutral, rows, of in cls._STATS:
            take = sure if rows == "s" else ~sure
            v = np.where(take, src[of], neutral)
            stats[name] = (ufunc.reduceat(v, start) if len(v)
                           else np.zeros(0))
        return keys, stats

    def bounds(self, key=None):
        """``(rows surely in, rows possibly in, least, largest)`` of the
        group's value (``None`` bounds for an empty group); ``key`` is the
        answer's key value (``None`` ungrouped)."""
        code = 0 if key is None else int(key_code([key])[0])
        i = self.index.get(code)
        st = {name: (self.stats[name][i] if i is not None else neutral)
              for name, _u, neutral, _r, _o in self._STATS}
        if self.grouped and len(self.arows):
            if self.kcol.is_int:
                hit = np.array([self.kcol.admits_one(int(r), float(key))
                                for r in self.arows], dtype=bool)
            else:
                klo, khi = self.kcol.lo[self.arows], self.kcol.hi[self.arows]
                tol = 1e-9 * np.maximum(1.0, np.maximum(np.abs(klo),
                                                        np.abs(khi)))
                hit = (klo - tol <= key) & (key <= khi + tol)
            alo, ahi = self.alo[hit], self.ahi[hit]
            st["no"] += len(alo)
            st["sum_lo_o"] += np.minimum(alo, 0.0).sum()
            st["sum_hi_o"] += np.maximum(ahi, 0.0).sum()
            st["min_lo_o"] = min(st["min_lo_o"], alo.min(initial=np.inf))
            st["max_hi_o"] = max(st["max_hi_o"], ahi.max(initial=-np.inf))
        ns, no = int(st["ns"]), int(st["no"])
        op = self.op
        if op == "count":
            return ns, no, float(ns), float(ns + no)
        if ns + no == 0:
            return ns, no, None, None
        if op == "sum":
            return (ns, no, st["sum_lo_s"] + st["sum_lo_o"],
                    st["sum_hi_s"] + st["sum_hi_o"])
        if op == "min":
            return (ns, no, min(st["min_lo_s"], st["min_lo_o"]),
                    st["min_hi_s"])
        if op == "max":
            return (ns, no, st["max_lo_s"],
                    max(st["max_hi_s"], st["max_hi_o"]))
        # avg: the sum's bounds over the count's, at the corners
        s_lo = st["sum_lo_s"] + st["sum_lo_o"]
        s_hi = st["sum_hi_s"] + st["sum_hi_o"]
        c_lo, c_hi = max(ns, 1), ns + no
        corners = [s_lo / c_lo, s_lo / c_hi, s_hi / c_lo, s_hi / c_hi]
        return ns, no, min(corners), max(corners)


def _admits_cmp(c: Column, row: int, op: str, val):
    """``(surely, possibly)`` passes ``op val`` for an ambiguous cell."""
    if c.is_int and c.sets.get(row) is not None:
        hits = [bool(_compare(np.array([v]), op, val)[0])
                for v in c.sets[row]]
        return all(hits), any(hits)
    lo, hi = c.lo[row], c.hi[row]
    if op in (">=", ">"):
        return bool(_compare(lo, op, val)), bool(_compare(hi, op, val))
    if op in ("<=", "<"):
        return bool(_compare(hi, op, val)), bool(_compare(lo, op, val))
    if op == "in":  # an interval or any value against a set
        vs = np.asarray(sorted(val), dtype=np.float64)
        inside = vs[(vs >= lo) & (vs <= hi)]
        return bool(lo == hi and len(inside)), bool(len(inside))
    if op == "==":
        return bool(lo == hi == val), bool(lo <= val <= hi)
    raise NotImplementedError(f"selection {op!r} on an ambiguous cell")


def _explode(c: Column, rows: np.ndarray, other: np.ndarray):
    """``(position, key)`` pairs of the join keys of ``rows``: one per
    admissible value (an open cell: every key of the ``other`` side)."""
    pos = [np.arange(len(rows))]
    keys = [c.val[rows]]
    for p in np.nonzero(c.amb[rows])[0]:
        row = int(rows[p])
        if not c.is_int:
            raise NotImplementedError("ambiguous float join key")
        s = c.sets.get(row)
        extra = np.unique(other) if s is None else np.array(sorted(s))
        extra = extra[extra != c.val[row]].astype(c.val.dtype)
        pos.append(np.full(len(extra), p))
        keys.append(extra)
    return np.concatenate(pos), np.concatenate(keys)


def _equi_join(lkeys: np.ndarray, rkeys: np.ndarray):
    """``(probe, build)`` index pairs with ``lkeys[probe] == rkeys[build]``,
    probe-major, build rows in their order."""
    order = np.argsort(rkeys, kind="stable")
    sk = rkeys[order]
    lo = np.searchsorted(sk, lkeys, "left")
    hi = np.searchsorted(sk, lkeys, "right")
    cnt = hi - lo
    probe = np.repeat(np.arange(len(lkeys)), cnt)
    offs = np.arange(cnt.sum()) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    return probe, order[np.repeat(lo, cnt) + offs]


def _radix(tables: dict, order: list) -> list:
    sizes = [len(tables[t]["cols"][tables[t]["columns"][0][0]])
             for t in order]
    if math.prod(sizes) >= 2 ** 62:
        raise OverflowError("row-id tuples do not fit one int64")
    return sizes


def _codes(cur: dict, tables: dict, order: list) -> np.ndarray:
    """One int64 per joined row: its row ids in mixed radix."""
    code = np.zeros(len(cur[order[0]]), dtype=np.int64)
    for t, size in zip(order, _radix(tables, order)):
        code = code * size + cur[t]
    return code


def _compare(v, op: str, val):
    if op == "in":
        return np.isin(v, np.asarray(sorted(val)))
    return {"==": np.equal, "!=": np.not_equal, "<": np.less,
            "<=": np.less_equal, ">": np.greater,
            ">=": np.greater_equal}[op](v, val)


def _reduce(op: str, v: np.ndarray):
    if op == "count":
        return len(v)
    if op == "sum":
        return v.sum()
    if op == "avg":
        return v.mean()
    return v.max() if op == "max" else v.min()
