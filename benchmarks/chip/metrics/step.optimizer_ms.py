"""step.optimizer_ms: device time per step of gradient clipping and the
AdamW update, the ops under the ``step/optimizer`` scope, on the busiest
chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_phase_ms(ctx, "optimizer")
