"""Interval arithmetic shared by the trace reduction and the span readers."""

from __future__ import annotations

__all__ = ["merge", "covered", "covered_minus", "gaps"]


def merge(intervals) -> list:
    """Sorted, non-overlapping ``[start, end]`` pairs covering the input."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals) -> float:
    """Length of the union."""
    return sum(e - s for s, e in merge(intervals))


def covered_minus(keep, drop) -> float:
    """Length of the union of ``keep`` outside the union of ``drop``."""
    keep, drop = merge(keep), merge(drop)
    total, j = 0.0, 0
    for s, e in keep:
        total += e - s
        while j < len(drop) and drop[j][1] <= s:
            j += 1
        i = j
        while i < len(drop) and drop[i][0] < e:
            total -= min(e, drop[i][1]) - max(s, drop[i][0])
            i += 1
    return total


def gaps(intervals, start, end) -> list:
    """``(gap start, gap end)`` pairs of ``[start, end]`` left uncovered."""
    out, cur = [], start
    for s, e in merge(intervals):
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return [(s, e) for s, e in out if e > s]
