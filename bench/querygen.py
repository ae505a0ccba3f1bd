"""The one query generator: reads a traffic mix (``traffic/<name>.json``) and
a configuration's join graph, and yields ad-hoc SPJA queries lazily.

The draw is the QUIP paper's section 7.2 template, as
``repro.data.queries.workload`` makes it: a chain of 2 to ``len(joins) + 1``
tables; on each table a selection with probability ``p_selection`` on a
random non-id attribute, at the mix's selectivity (an ``in`` set over a
categorical attribute, else ``>=`` at the matching quantile); then with
probability ``p_aggregate`` one aggregate (a random op over a random
attribute, grouped with probability ``p_group_by``), else a projection of
one attribute per table.

Two random streams keep the work the same across seeds: the *shapes*
(tables, attributes, operators) come from the mix's ``shape_seed``, so every
run sends the same sequence of query shapes; the *constants* (which rooms an
``in`` set names) and the data come from the run's seed.

A query is a plain dict::

    {"tables": [...], "joins": [[left, right], ...],
     "selections": [[attr, op, value], ...], "projection": [...],
     "aggregate": None | [op, attr, group_by or None]}
"""

from __future__ import annotations

import numpy as np

__all__ = ["QueryStream"]


class QueryStream:
    """Endless iterator of queries over ``tables`` (the dataset dicts)."""

    def __init__(self, tables: dict, joins: list, mix: dict, seed: int):
        self.tables = tables
        self.joins = [list(j) for j in joins]
        self.mix = mix
        self.shapes = np.random.default_rng(mix["shape_seed"])
        self.consts = np.random.default_rng(seed)
        self._values: dict = {}  # attr -> (sorted present values, uniques)

    def __iter__(self):
        return self

    def _attrs(self, table: str) -> list:
        return [c for c, _k in self.tables[table]["columns"]
                if not c.endswith(".id")]

    def _pick(self, items: list):
        return items[int(self.shapes.integers(0, len(items)))]

    def _sorted(self, attr: str):
        got = self._values.get(attr)
        if got is None:
            t = self.tables[attr.split(".")[0]]
            vals = np.sort(t["cols"][attr][~t["missing"][attr]])
            got = self._values[attr] = (vals, np.unique(vals))
        return got

    def _selection(self, attr: str) -> list:
        vals, uniq = self._sorted(attr)
        sel = self.mix["selectivity"]
        if len(vals) == 0:
            return [attr, ">=", 0]
        if (len(uniq) <= self.mix["categorical_max_distinct"]
                and not np.issubdtype(vals.dtype, np.floating)):
            k = min(max(1, int(round(sel * len(uniq)))), len(uniq))
            pick = self.consts.choice(uniq, size=k, replace=False)
            return [attr, "in", sorted(int(v) for v in pick)]
        v = vals[int((1.0 - sel) * (len(vals) - 1))]
        return [attr, ">=", float(v) if np.issubdtype(vals.dtype, np.floating)
                else int(v)]

    def __next__(self) -> dict:
        mix = self.mix
        n_tables = int(self.shapes.integers(2, len(self.joins) + 2))
        joins = self.joins[:n_tables - 1]
        tabs: list = []
        for j in joins:
            for a in j:
                t = a.split(".")[0]
                if t not in tabs:
                    tabs.append(t)
        sels = [self._selection(self._pick(self._attrs(t))) for t in tabs
                if self.shapes.random() < mix["p_selection"]]
        agg, proj = None, []
        if self.shapes.random() < mix["p_aggregate"]:
            attr = self._pick(self._attrs(self._pick(tabs)))
            op = self._pick(mix["aggregate_ops"])
            gb = None
            if self.shapes.random() < mix["p_group_by"]:
                gb = self._pick(self._attrs(self._pick(tabs)))
            agg = [op, attr, gb]
        else:
            proj = [self._pick(self._attrs(t)) for t in tabs]
        return {"tables": tabs, "joins": joins, "selections": sels,
                "projection": proj, "aggregate": agg}
