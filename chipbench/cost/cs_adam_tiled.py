"""``cs_adam_tiled``: count-sketch Adam over a step's unique rows.

Needed bytes a step: each unique gradient row read and its update row
written; each unique row's ``depth`` buckets of the m and the v sketch
read and written once (the kernel's aligned 8-row groups move 8 times
that, which is not counted: it is how the kernel reaches a row, not what
the step needs)."""
from __future__ import annotations

from typing import Dict, List


def cost(steps_work: List[Dict[str, int]], *, dim: int, depth: int) -> dict:
    n = max(len(steps_work), 1)
    uniq = sum(w["unique"] for w in steps_work) / n
    row = dim * 4
    return {"flops": uniq * dim * (4 * depth + 12),
            "bytes": uniq * row * 2 + 2 * depth * uniq * row * 2}
