"""Exclusive device time per step of the ops whose innermost scope is
``obs.attn``, in ms: each op's time less that of the ops nested in it,
its scope read from the traced module's metadata (``spans.py``)."""
from chipbench import spans

SCOPE = "obs.attn"


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.scopes or SCOPE not in s.scopes.values() \
            or ctx.steps <= 0:
        return None
    return s.scope_ns(SCOPE) / 1e6 / ctx.steps
