"""Kernels: milliseconds of ``knn:call`` spans per completed query: each
k-NN batch's padding, transfer, distance and top-k programs and the copy
back, from host arrays to host arrays."""

from intervals import covered


def read(ctx):
    spans = [(t0, t1) for name, t0, t1, _a in ctx["spans"]
             if name == "knn:call"]
    if not spans or not ctx["queries"]:
        return None
    return 1e3 * covered(spans) / ctx["queries"]
