"""How an answer departs from what the reference admits: the count of
answer items (projected rows, aggregate groups) that no admissible
completion of the tables would give.  0 for a sound answer.

* A projection is matched row by row through its row ids (``tids``, the
  base row of each table that made the output row): a row every
  admissible answer has and the answer lacks, a row no admissible answer
  has, a row given twice, and a row whose projected values are not
  admissible each count one.
* An aggregate is matched group by group: a group that surely exists and
  is missing, a group that cannot exist, and a value outside the group's
  bounds (``reference.Groups``) each count one.  Float sums and means may
  differ from the bounds by their rounding alone (relative 1e-9).
"""

from __future__ import annotations

import numpy as np

from reference import key_code, table_of

__all__ = ["items_off", "canonical"]

#: float rounding allowed on a sum or mean, relative to its size
REL_TOL = 1e-9


def canonical(answer: dict) -> dict:
    """Answer with columns, values and NULL flags as numpy arrays."""
    out = {"columns": list(answer["columns"]),
           "values": [np.asarray(v) for v in answer["values"]],
           "null": [np.asarray(n, dtype=bool) for n in answer["null"]]}
    if answer.get("tids") is not None:
        out["tids"] = {t: np.asarray(v) for t, v in answer["tids"].items()}
    return out


def _within(v: float, lo: float, hi: float) -> bool:
    tol = REL_TOL * max(1.0, abs(lo), abs(hi)) if np.isfinite(
        lo) and np.isfinite(hi) else 0.0
    return lo - tol <= v <= hi + tol


def _rows_off(got: dict, exp: dict):
    tids = got.get("tids") or {}
    n = len(got["values"][0]) if got["values"] else 0
    if any(t not in tids or len(tids[t]) != n for t in exp["tables"]):
        return max(n, 1), max(n, 1)
    code = np.zeros(n, dtype=np.int64)
    for t, size in zip(exp["tables"], exp["radix"]):
        code = code * size + tids[t].astype(np.int64)
    uniq = np.unique(code)
    twice = n - len(uniq)
    extra = ~np.isin(code, exp["possible"])
    lacking = len(exp["sure"]) - int(np.isin(exp["sure"], uniq).sum())
    bad = np.zeros(n, dtype=bool)
    for name, col, v, null in zip(exp["columns"], exp["cols"],
                                  got["values"], got["null"]):
        bad |= null | ~col.admits(tids[table_of(name)], v)
    off = twice + int(extra.sum()) + lacking + int((bad & ~extra).sum())
    return n + lacking, off


def _groups_off(got: dict, exp: dict):
    g = exp["groups"]
    vals, nulls = got["values"][-1], got["null"][-1]
    if not g.grouped:
        ns, _no, lo, hi = g.bounds()
        if len(vals) != 1:
            return 1, 1
        v, null = float(vals[0]), bool(nulls[0])
        if lo is None:  # no row can pass: NULL (a count reads 0)
            ok = (v == 0 and not null) if exp["op"] == "count" else null
        else:
            ok = (null and ns == 0) or (not null and _within(v, lo, hi))
        return 1, int(not ok)
    keys, knull = got["values"][0], got["null"][0]
    off = 0
    for key, kn, v, null in zip(keys.tolist(), knull.tolist(),
                                vals.tolist(), nulls.tolist()):
        if kn:
            off += 1
            continue
        ns, no, lo, hi = g.bounds(key)
        if ns + no == 0 or null or not _within(float(v), lo, hi):
            off += 1
    lacking = int((~np.isin(g.sure_keys, key_code(keys))).sum())
    return len(keys) + lacking, off + lacking


def items_off(got: dict, exp: dict) -> tuple:
    """``(items, items off)`` of an answer against ``Reference.expect``;
    a different column list puts every item off."""
    got = canonical(got)
    if got["columns"] != exp["columns"]:
        n = max(len(got["values"][0]) if got["values"] else 0, 1)
        return n, n
    if exp["aggregate"]:
        return _groups_off(got, exp)
    return _rows_off(got, exp)
