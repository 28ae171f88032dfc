"""How far the attention kernel engages: the exclusive device time of the
splash attention kernel's op events (its forward, ``dq`` and ``dkv``
calls, whose names start with ``splash_mha_``) among the ops whose
innermost scope is ``obs.attn``, over the exclusive device time of all
of those ops, in %.  Reads 0 where ``obs.attn`` ran but no kernel op
did (the attention core as an XLA scan)."""
from chipbench import spans

SCOPE = "obs.attn"
KERNEL = "splash_mha_"


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.scopes or SCOPE not in s.scopes.values() \
            or ctx.steps <= 0:
        return None
    total = kernel = 0.0
    for ops in s.reduced.devices.values():
        for o, ns in spans.exclusive_ns(ops):
            if s.scopes.get(o.name, spans.UNSCOPED) != SCOPE:
                continue
            total += ns
            if o.name.startswith(KERNEL):
                kernel += ns
    if total <= 0:
        return None
    return 100.0 * kernel / total
