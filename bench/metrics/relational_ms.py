"""Executor and operators: milliseconds per completed query covered by the
operator spans (``op:select``, ``op:join_build``, ``kernel:multi_match``,
``op:rho``), less the ``impute_flush`` spans nested in them."""

from intervals import covered_minus

OPS = ("op:select", "op:join_build", "kernel:multi_match", "op:rho")


def read(ctx):
    ops = [(t0, t1) for name, t0, t1, _a in ctx["spans"] if name in OPS]
    if not ops or not ctx["queries"]:
        return None
    flush = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "impute_flush"]
    return 1e3 * covered_minus(ops, flush) / ctx["queries"]
