"""Imputation layer: milliseconds per completed query of the ``impute:fit``
spans that trained a model (``fitted``); a span that found its model fitted
costs a lookup and is left out."""

from intervals import covered


def read(ctx):
    spans = [(t0, t1, a) for name, t0, t1, a in ctx["spans"]
             if name == "impute:fit"]
    if not spans or not ctx["queries"]:
        return None
    fits = [(t0, t1) for t0, t1, a in spans if a.get("fitted")]
    return 1e3 * covered(fits) / ctx["queries"]
