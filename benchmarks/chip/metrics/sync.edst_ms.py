"""sync.edst_ms: device time per step of the operations traced under the
EDST sync's ``edst/`` named scopes, on the chip where it is longest,
in ms.  Nothing to read where no operation carries the scope."""

SCOPE = "edst/"


def in_sync(op):
    return SCOPE in op.op_name


def read(ctx):
    rec = ctx.trace
    times = [rec.busy_ns(d, in_sync) for d in rec.devices]
    if not rec.steps or not max(times, default=0):
        return None
    return max(times) / rec.steps / 1e6
