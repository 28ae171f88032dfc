"""The whole optimizer update's exclusive device time per step, in ms:
the ops whose innermost scope is ``obs.dedup``, ``obs.kernel`` or
``obs.apply`` (the embedding's sparse-rows step, the output head's
count-sketch Adam and every dense leaf's Adam), each op's time less
that of the ops nested in it (``spans.py``)."""
from chipbench import spans

SCOPES = ("obs.dedup", "obs.kernel", "obs.apply")


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.scopes or ctx.steps <= 0:
        return None
    present = set(s.scopes.values())
    if not any(sc in present for sc in SCOPES):
        return None
    return sum(s.scope_ns(sc) for sc in SCOPES) / 1e6 / ctx.steps
