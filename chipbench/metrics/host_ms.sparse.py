"""The host's own work per traced step, in ms: the program's ``train.*``
spans (``Trainer.fit``) other than ``train.wait``, summed over the traced
window and divided by its steps (``spans.py``)."""
from chipbench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.program or ctx.steps <= 0:
        return None
    return s.host_ns() / 1e6 / ctx.steps
