"""step.sync_ms: device time per step of the gradient exchange, the ops
under the ``step/sync`` scope or an EDST wave scope (``edst/``): the
ravel, the waves, the division and the unravel, on the busiest chip, in
ms."""
import scopes


def read(ctx):
    return scopes.read_phase_ms(ctx, "sync")
