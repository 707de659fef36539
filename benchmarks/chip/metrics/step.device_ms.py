"""step.device_ms: busy time per step of the busiest chip, in ms."""


def read(ctx):
    rec = ctx.trace
    if not rec.steps:
        return None
    return rec.busy_ns(rec.busiest()) / rec.steps / 1e6
