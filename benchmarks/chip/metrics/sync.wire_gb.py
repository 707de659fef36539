"""sync.wire_gb: bytes the EDST sync program ships a step on its busiest
schedule, from the program's own ``edst_wire_bytes`` gauge
(``repro.telemetry.metrics``, set when the step is traced) for the
cell's engine, in GB (1e9 bytes).  Nothing to read where the program
set no such gauge."""


def read(ctx):
    from repro.telemetry import metrics
    gauge = metrics.REGISTRY.get("edst_wire_bytes")
    if gauge is None:
        return None
    value = gauge.value(engine=ctx.cell.traffic["engine"])
    return None if value is None else value / 1e9
