"""kernel.tree_combine_ms: device time per step of the tree-combine
kernel's calls (``kernels/tree_combine.py`` says which ops they are), on
the chip where it is longest, in ms.  Nothing to read where the kernel
did not run, or where the calls found write less than the gradient's
all-reduce has to combine."""

KERNEL = "tree_combine"


def read(ctx):
    found = ctx.cell.kernels[KERNEL].slowest(ctx)
    if found is None:
        return None
    return found[1] / ctx.trace.steps / 1e6
