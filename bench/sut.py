"""The system under test as the benchmark drives it: QUIP's serving path,
``repro.service.QuipService``, over ``MaskedRelation`` tables built from the
generated data.  The only module of the benchmark that imports the program,
with the imputer kinds (``imputers/<kind>.py``), which import it only inside
the ``factory`` and ``warm_up`` that this module calls; from it the
benchmark takes the service, its spans and counters, and the kernel entry
points it warms.
"""

from __future__ import annotations

import os
import sys

import numpy as np

__all__ = ["import_program", "relations", "to_query", "make_service",
           "warm_up", "answer_of"]


def import_program(root: str) -> None:
    """Put ``<root>/src`` first on the import path, or exit."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def relations(tables: dict) -> dict:
    """The generated tables as the program's ``MaskedRelation``s (copies:
    the program never shares an array with the reference)."""
    from repro.core.relation import MaskedRelation
    from repro.core.schema import ColumnSpec, Schema

    out = {}
    for t, tab in tables.items():
        schema = Schema(t, [ColumnSpec(c, k) for c, k in tab["columns"]])
        out[t] = MaskedRelation.from_columns(
            schema, {c: tab["cols"][c].copy() for c, _k in tab["columns"]},
            missing={c: m.copy() for c, m in tab["missing"].items()},
            base_table=t)
    return out


def to_query(q: dict):
    from repro.core.plan import Aggregate, Query
    from repro.core.predicates import JoinPredicate, SelectionPredicate

    sels = tuple(SelectionPredicate(a, op, frozenset(v) if op == "in" else v)
                 for a, op, v in q["selections"])
    agg = None
    if q["aggregate"] is not None:
        op, attr, gb = q["aggregate"]
        agg = Aggregate(op, attr, group_by=gb)
    return Query(tables=tuple(q["tables"]), selections=sels,
                 joins=tuple(JoinPredicate(l, r) for l, r in q["joins"]),
                 projection=tuple(q["projection"]), aggregate=agg)


def make_service(rels: dict, config: dict, tracer, kind):
    """``QuipService`` with the configuration's settings and the imputer of
    its kind (``imputers/<kind>.py``, ``factory``).  ``tracer`` is a program
    ``Tracer`` or False."""
    from repro.service import QuipService

    return QuipService(rels, kind.factory(config["imputer"]), tracer=tracer,
                       **config["service"])


def warm_up(tables: dict, config: dict, kind) -> int:
    """Run every device program the window can call once, at each of its
    shapes, so that nothing compiles in the window.  Returns how many.

    The imputer's programs are its kind's (``warm_up``).  Bloom probe: one
    program per probe bucket, powers of two from 512 up to the largest
    table's row count (a probe checks rows of one join side)."""
    from repro.core.bloom import BloomFilter

    n = kind.warm_up(tables, config["imputer"])
    bf = BloomFilter("warm")
    most = max(len(m) for tab in tables.values()
               for m in tab["missing"].values())
    rows = 512
    while rows < 2 * most:
        bf.might_contain(np.arange(rows, dtype=np.int64))
        n += 1
        rows *= 2
    return n


def answer_of(result, projection: bool) -> dict:
    """An ``ExecutionResult``'s answer as plain arrays; a projection also
    carries each output row's base row ids (``tids``)."""
    rel = result.relation
    names = rel.column_names()
    out = {"columns": names,
           "values": [np.asarray(rel.cols[c]) for c in names],
           "null": [np.asarray(rel.absent[c] | rel.missing[c])
                    for c in names]}
    if projection:
        out["tids"] = {t: np.asarray(v) for t, v in rel.tids.items()}
    return out
