"""device.idle_pct: share of the traced window in which no operation
runs on the busiest chip, in %."""


def read(ctx):
    rec = ctx.trace
    if not rec.window_ns:
        return None
    return 100.0 * (1.0 - rec.busy_ns(rec.busiest()) / rec.window_ns)
