"""Operations and bytes of the work the benchmark counts, and the chip's
peaks (``peaks.json``, keyed by JAX's ``device_kind``)."""

from __future__ import annotations

import json
import os

__all__ = ["knn_work", "peaks", "roofline_s"]

_HERE = os.path.dirname(os.path.abspath(__file__))


def knn_work(nq: int, nr: int, d: int, k: int):
    """``(flops, bytes)`` of masked k-NN for ``nq`` query rows against ``nr``
    reference rows over ``d`` features: the least an exact implementation
    must do, whatever pads or fuses it.

    flops: a difference, a product and a sum per (query, reference,
    feature).  bytes: each input once, values and masks in float32, and the
    ``k`` neighbours out (int32 index and float32 distance)."""
    flops = 3 * nq * nr * d
    nbytes = 4 * 2 * d * (nq + nr) + 8 * nq * k
    return flops, nbytes


def peaks(kind: str) -> dict:
    """The peak row for a ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def roofline_s(flops: float, nbytes: float, peak: dict):
    """``(least seconds, bound)``: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s, and which of the two it was."""
    compute = flops / peak["flops_per_s"]
    memory = nbytes / peak["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
