"""The bytes each roofline divides by, computed from shapes and counts.

Each function gives the least traffic the algorithm needs, whatever
implements it: a kernel that moves more than this shows a lower share of
its roofline, never one above 100%.
"""
from __future__ import annotations

import math

WORD_ROWS = 32      # corpus rows per packed pass-bitmap word


def filter_eval_bytes(n: int, fields: int, lanes: int) -> int:
    """One predicate sweep of a batch: the (n, fields) int32 metadata read
    once, and the (lanes, ceil(n/32)) uint32 pass bitmap written. The
    per-query clause tables (a few hundred bytes a query) are left out."""
    return 4 * n * fields + 4 * lanes * math.ceil(n / WORD_ROWS)


def walk_bytes(hops: int, mean_degree: float, d: int) -> float:
    """Rows the walk must read: each hop of each lane gathers its node's
    neighbours, each a d-wide float32 row and its int32 id."""
    return hops * mean_degree * (4 * d + 4)
