"""The sparse-rows step: table[ids] − target[ids], dedup, count-sketch Adam
on the unique rows, the table update.

Needed bytes a step (4-byte cells and ids): the ids; each unique row of
the table and of the target read once and of the table written once; each
unique row's ``depth`` buckets of the m and the v sketch read and written
once.  Needed operations: the loss over every id's row, and per unique
element the two estimators and the Adam arithmetic."""
from __future__ import annotations

from typing import Dict, List


def flops_per_unique_element(depth: int) -> int:
    # m: depth sign products, the median, Δ and its depth adds; v: the min,
    # Δ and its depth adds; the direction: two bias corrections, sqrt,
    # divide, clamp, lr scale
    return 4 * depth + 12


def cost(steps_work: List[Dict[str, int]], *, dim: int, depth: int) -> dict:
    """Mean per step over the steps ``steps_work`` describes."""
    n = max(len(steps_work), 1)
    ids = sum(w["ids"] for w in steps_work) / n
    uniq = sum(w["unique"] for w in steps_work) / n
    row = dim * 4
    nbytes = ids * 4 + uniq * row * 3 + 2 * depth * uniq * row * 2
    flops = ids * dim * 3 + uniq * dim * flops_per_unique_element(depth)
    return {"flops": flops, "bytes": nbytes}
