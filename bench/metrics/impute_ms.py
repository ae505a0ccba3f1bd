"""Imputation layer: milliseconds of ``impute_flush`` spans per completed
query."""

from intervals import covered


def read(ctx):
    flush = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "impute_flush"]
    if not flush or not ctx["queries"]:
        return None
    return 1e3 * covered(flush) / ctx["queries"]
