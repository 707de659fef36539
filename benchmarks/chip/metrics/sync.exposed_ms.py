"""sync.exposed_ms: the part of the EDST sync's device time per step in
which no operation outside the ``edst/`` scopes runs on that chip, on
the chip where the sync is longest, in ms."""

SCOPE = "edst/"


def in_sync(op):
    return SCOPE in op.op_name


def read(ctx):
    rec = ctx.trace
    if not rec.steps:
        return None
    times = [rec.busy_ns(d, in_sync) for d in rec.devices]
    if not max(times, default=0):
        return None
    dev = rec.devices[times.index(max(times))]
    return rec.exposed_ns(dev, in_sync) / rec.steps / 1e6
