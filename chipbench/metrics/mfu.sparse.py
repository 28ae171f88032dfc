"""The whole sparse step's share of the chips' peak: the least time the
step's needed bytes and operations (``cost/sparse_step.py``) take at the
peaks of the chips the cell uses — bytes bound it — over the measured time
per step in the traced window, in %."""


def read(ctx):
    t = ctx.trace
    step = ctx.cost.get("step")
    if step is None or ctx.steps <= 0 or t.window_s <= 0:
        return None
    chips = ctx.cell.chips
    least = max(step["flops"] / (chips * ctx.peaks["bf16_flops_per_s"]),
                step["bytes"] / (chips * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / (t.window_s / ctx.steps)
