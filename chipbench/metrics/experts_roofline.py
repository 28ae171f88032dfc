"""Roofline share of the held experts' grouped matmuls: the least time
the FLOPs and bytes of the (token, slot) pairs routed here need
(``cost/lm_step.py``, from ``routed_here``) at the chip's peaks —
FLOPs bound it — over the exclusive device time of the ops whose
innermost scope is ``obs.experts``, in %.  Work on the dispatch
buffer's padding, or recomputation, shows as a low share."""
from chipbench import spans

SCOPE = "obs.experts"


def read(ctx):
    s = spans.of(ctx)
    c = ctx.cost.get("kernels", {}).get("experts")
    if s is None or not s.scopes or SCOPE not in s.scopes.values() \
            or c is None or ctx.steps <= 0:
        return None
    ns = s.scope_ns(SCOPE)
    if ns <= 0:
        return None
    least = max(c["flops"] / ctx.peaks["bf16_flops_per_s"],
                c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / (ns / 1e9)
