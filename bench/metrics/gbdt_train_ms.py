"""Imputation layer: milliseconds per completed query of the ``gbdt:fit``
spans, each (table, attribute) model's training inside ``impute:fit``:
binning, the boosting program and the leaf weights' refit."""

from intervals import covered


def read(ctx):
    spans = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "gbdt:fit"]
    if not spans or not ctx["queries"]:
        return None
    return 1e3 * covered(spans) / ctx["queries"]
