"""step.remat_ms: the part of ``step.backward_ms`` that recomputes the
forward pass (ops under ``rematted_computation``), on the busiest chip,
in ms."""
import scopes


def in_remat(op):
    return scopes.phase(op.op_name) == "backward" and \
        scopes.is_remat(op.op_name)


def read(ctx):
    return scopes.read_ms(ctx, in_remat)
