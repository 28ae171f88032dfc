"""Roofline share of the ``cs_adam_tiled`` kernel: the least time its
needed bytes and operations (``cost/cs_adam_tiled.py``) take at the
chip's peaks — bytes bound it — over its device time, in %."""

KERNEL = "cs_adam_tiled"


def read(ctx):
    ns = ctx.trace.kernel_ns(KERNEL)
    c = ctx.cost.get("kernels", {}).get(KERNEL)
    if ns <= 0 or c is None or ctx.steps <= 0:
        return None
    least = max(c["flops"] / ctx.peaks["bf16_flops_per_s"],
                c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.steps / (ns / 1e9)
