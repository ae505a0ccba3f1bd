"""Operations and bytes of the XGBoost-hist device programs' work
(``imputers/xgb_hist.py``, ``metrics/gbdt_train_roofline.py``): the least
an exact implementation must do, whatever formulation implements it, so
that a later change of formulation is judged on the same yardstick."""

from __future__ import annotations

__all__ = ["SPLIT_FLOPS", "train_work", "predict_work"]

#: flops per (node, feature, bin) of split finding: two prefix sums, the
#: missing slot added for the left direction (two), and per direction the
#: right side (two subtractions), the score (two products, a subtraction,
#: a square, three products and a division) and a comparison
SPLIT_FLOPS = 2 + 2 + 2 * (2 + 8 + 1)


def train_work(rows: int, features: int, rounds: int, depth: int,
               max_bin: int = 256):
    """``(flops, bytes)`` of one fit over ``rows`` training rows.  Per round
    and level: every row's bin index (one byte a feature) and ``g`` (four
    bytes) read once, and ``g`` and ``h`` added into its (node, feature)
    slot (two adds a feature); the histogram, ``(G, H)`` in float32 per
    (node, feature, bin and the missing slot), written once; split finding,
    ``SPLIT_FLOPS`` per (node, feature, bin).  Per round the leaves' ``G``
    and ``H`` (two adds a row) and the update of ``g`` (one add a row,
    ``g`` read and written)."""
    flops = nbytes = 0
    for d in range(depth):
        nodes = 1 << d
        flops += (2 * rows * features
                  + SPLIT_FLOPS * nodes * features * max_bin)
        nbytes += rows * (features + 4) + 8 * nodes * features * (max_bin + 1)
    flops += 3 * rows
    nbytes += 8 * rows
    return rounds * flops, rounds * nbytes


def predict_work(rows: int, features: int, rounds: int, depth: int):
    """``(flops, bytes)`` of routing ``rows`` rows through every tree: one
    comparison per (row, tree, level); each row's bins read once (one byte
    a feature), its leaf per tree written (one byte), each tree's inner
    nodes read once (feature, threshold and direction: nine bytes)."""
    return (rounds * rows * depth,
            rows * features + rounds * rows
            + 9 * rounds * ((1 << depth) - 1))
