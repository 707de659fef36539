"""host.input_ms: mean host time per step to take the next batch from
the pool and put it on the device (the ``bench/input`` span), in ms."""


def read(ctx):
    spans = ctx.trace.host_spans.get("bench/input")
    if spans is None or not len(spans):
        return None
    lo, hi = ctx.trace.window
    inside = spans[(spans[:, 0] >= lo) & (spans[:, 1] <= hi)]
    if not len(inside):
        return None
    return float((inside[:, 1] - inside[:, 0]).mean()) / 1e6
