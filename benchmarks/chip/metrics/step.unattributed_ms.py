"""step.unattributed_ms: device time per step of the ops that no phase
scope claims (``scopes.py``), on the busiest chip, in ms: a scope lost
on the way to the compiled program shows here.  Nothing to read where
no op carries a ``step/`` scope (a program without them)."""
import scopes


def read(ctx):
    rec = ctx.trace
    if not rec.steps or not any("step/" in op.op_name for op in rec.ops):
        return None
    ns = scopes.phase_ns(rec, rec.busiest(), "unattributed")
    return (ns or 0) / rec.steps / 1e6
