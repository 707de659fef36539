"""step.backward_ms: device time per step of the backward pass, the ops
under ``transpose(jvp(step/model))`` and those it recomputes
(``rematted_computation``; ``scopes.py``), on the busiest chip, in ms."""
import scopes


def read(ctx):
    return scopes.read_phase_ms(ctx, "backward")
