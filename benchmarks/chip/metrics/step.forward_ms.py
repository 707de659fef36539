"""step.forward_ms: device time per step of the forward pass, the ops
under the ``step/model`` scope or a ``model/`` sublayer and not in the
backward pass (``scopes.py``), on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_phase_ms(ctx, "forward")
