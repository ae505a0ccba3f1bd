"""Kernels: megabytes (1e6 bytes) that the k-NN batches move from host
arrays to the device, per completed query: the sum of the ``knn:call``
spans' ``h2d_bytes``."""


def read(ctx):
    sent = [a["h2d_bytes"] for name, _t0, _t1, a in ctx["spans"]
            if name == "knn:call" and "h2d_bytes" in a]
    if not sent or not ctx["queries"]:
        return None
    return sum(sent) / 1e6 / ctx["queries"]
