"""How many ``cs_adam_tiled`` calls ran per step and device: the kernel's
op events in the traced window (ops whose name starts with the kernel's,
as ``kernel_ms.cs_adam_tiled`` selects them), over devices and steps.  A
batch longer than one call's address budget runs as a loop of calls, and
each call is its own op event."""

KERNEL = "cs_adam_tiled"


def read(ctx):
    devices = ctx.trace.devices
    n = sum(1 for ops in devices.values() for o in ops
            if o.name.startswith(KERNEL))
    if n == 0 or ctx.steps <= 0:
        return None
    return n / len(devices) / ctx.steps
