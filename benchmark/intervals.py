"""Interval algebra on lists of ``[start, end]`` for the readers that
attribute time by OVERLAP: how much of one set of intervals lies under
(or outside) another.  Every function takes and returns what
``trace_reduce.union`` returns: merged, sorted, non-overlapping."""

from __future__ import annotations

from benchmark.trace_reduce import clip, union


def named(events, names, lo: float = float("-inf"),
          hi: float = float("inf")) -> list:
    """The union of the ``(name, start, end)`` events called one of
    ``names``, clipped to ``[lo, hi]``."""
    names = set(names)
    return union(clip([[s, e] for name, s, e in events if name in names],
                      lo, hi))


def intersect(a: list, b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """What of ``a`` no interval of ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append([lo, b[k][0]])
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append([lo, hi])
    return out


def measure(a: list) -> float:
    return sum(e - s for s, e in a)
