"""The whole step's share of the chips' bf16 peak: the model FLOPs a step
needs (``cost/lm_step.py``: the matmuls every token passes through, the
held experts' over the pairs routed here, causal attention; forward and
backward, no recomputation) over the measured time per step in the
traced window, in %."""


def read(ctx):
    t = ctx.trace
    step = ctx.cost.get("step")
    if step is None or ctx.steps <= 0 or t.window_s <= 0:
        return None
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * step["flops"] / peak / (t.window_s / ctx.steps)
