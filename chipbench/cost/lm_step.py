"""The language-model step: model FLOPs, and the held experts' grouped
matmuls.

Model FLOPs a step (what the forward and backward passes need, 2 a
multiply-add forward and 4 backward; recomputation is not counted):
6 × the matmul parameters each token passes through × tokens — the
latent attention's projections and every dense, router and shared-expert
matrix, the output head — plus 6 × the expert matrices × the (token,
slot) pairs the held experts computed (``routed_here``), plus causal
attention, 3 × 2·b·h·s(s+1)/2·(q/k head width + v head width) a layer.
The embedding is a gather and counts none."""
from __future__ import annotations

from typing import Dict, List


def _mean(xs) -> float:
    xs = list(xs)
    return sum(xs) / max(len(xs), 1)


def token_params(a: dict) -> int:
    """Matmul parameters every token passes through (``sizes`` of
    ``reference/lm_train.py``)."""
    d, h, r = a["d"], a["h"], a["r"]
    attn = d * h * (a["dn"] + a["dr"]) + d * (r + a["dr"]) \
        + r * h * (a["dn"] + a["dv"]) + h * a["dv"] * d
    dense = 3 * d * a["ff_dense"]
    moe = d * a["E"] + 3 * d * a["shared"]
    layers = a["n_dense"] + a["n_moe"]
    return attn * layers + dense * a["n_dense"] + moe * a["n_moe"] \
        + a["V"] * d


def attention_flops(a: dict, b: int, s: int) -> float:
    layers = a["n_dense"] + a["n_moe"]
    width = a["dn"] + a["dr"] + a["dv"]
    return 3 * 2 * b * a["h"] * s * (s + 1) / 2 * width * layers


def experts_cost(a: dict, routed: List[float]) -> Dict[str, float]:
    """The held experts' grouped matmuls a step, forward and backward,
    over the mean ``routed_here`` pairs: FLOPs, and the bytes they need —
    each held expert's three bf16 matrices read forward and twice
    backward (input gradient, weight gradient) and its f32 weight
    gradient written; per pair, its bf16 input row, the gate and up
    outputs, their product and the output row forward, and as many
    backward."""
    d, f = a["d"], a["ff"]
    pairs = _mean(routed)
    flops = 6.0 * pairs * 3 * d * f
    weights = a["n_moe"] * a["held"] * 3 * d * f
    per_pair = 2 * (d + 3 * f + d)
    nbytes = weights * (3 * 2 + 4) + 2 * pairs * per_pair
    return {"flops": flops, "bytes": nbytes}


def cost(steps_work: List[Dict[str, int]], a: dict, routed: List[float],
         *, batch: int, seq: int) -> dict:
    """Mean per step over the steps ``steps_work`` describes, each a
    ``batch`` × ``seq`` token array, ``routed`` their ``routed_here``."""
    tokens = _mean(w["ids"] for w in steps_work)
    flops = 6.0 * token_params(a) * tokens \
        + attention_flops(a, batch, seq) + experts_cost(a, routed)["flops"]
    return {"flops": flops, "tokens": tokens}
