"""Kernels: milliseconds per completed query of the ``gbdt:predict``
spans: binning the requested rows, the routing programs and the float64
sums of the leaf weights, from host rows to host values."""

from intervals import covered


def read(ctx):
    spans = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "gbdt:predict"]
    if not spans or not ctx["queries"]:
        return None
    return 1e3 * covered(spans) / ctx["queries"]
