"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its own limit (``cells/<cell>.json``):

* ``loss``: the worst of the first three steps' |loss − loss_ref| / |loss_ref|;
* ``grad``: the worst leaf of the first gradient as the optimizer got it,
  worked out from the optimizer state after one step:
  |‖g‖ − ‖g_ref‖| / max(‖g_ref‖, median leaf's ‖g_ref‖), once from the
  first moment (g) and once from the second (g², its own median);
* ``change``: the worst leaf of the parameters' change after three steps,
  the same gap of norms — leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (nought to rounding, such as a
  key bias under softmax: Adam moves them by round-off alone).
"""
from __future__ import annotations

import math
from typing import Dict, List

SMALL_GRAD = 1e-3


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                keep=None) -> float:
    med = _median(list(ref.values()))
    worst = 0.0
    for path, r in ref.items():
        if keep is not None and path not in keep:
            continue
        p = prog.get(path, float("nan"))
        gap = abs(p - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            return float("inf")
        worst = max(worst, gap)
    return worst


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """{loss, grad, change} gaps of the program's readings ``prog`` against
    the reference's ``ref`` (each: ``loss`` list, ``grad`` {moment: {leaf:
    norm}} and ``change`` {leaf: norm}; ``ref`` also ``leaf_grad``, each parameter
    leaf's reference gradient norm, for the rule on near-nought leaves)."""
    loss = 0.0
    for p, r in zip(prog["loss"], ref["loss"]):
        g = abs(p - r) / max(abs(r), 1e-30)
        loss = max(loss, g if math.isfinite(g) else float("inf"))
    leaf = ref["leaf_grad"]          # parameter path -> ‖g_ref‖
    med = _median(list(leaf.values()))
    change_keep = {k for k, v in leaf.items() if v >= SMALL_GRAD * med}
    return {"loss": loss,
            "grad": max(_worst_leaf(prog["grad"].get(k, {}), r)
                        for k, r in ref["grad"].items()),
            "change": _worst_leaf(prog["change"], ref["change"],
                                  keep=change_keep)}


def judge(g: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every gap is finite and within its limit."""
    return all(math.isfinite(g[k]) and g[k] <= limits[k] for k in limits)
