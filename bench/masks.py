"""Missing-cell masks of an exact size."""

from __future__ import annotations

import numpy as np

__all__ = ["exact_mask"]


def exact_mask(rng: np.random.Generator, n: int, rate: float) -> np.ndarray:
    """``round(rate * n)`` cells of ``n``, drawn without replacement: every
    seed misses as many cells, so the imputer's shapes never change."""
    m = np.zeros(n, dtype=bool)
    m[rng.choice(n, size=int(round(rate * n)), replace=False)] = True
    return m
