"""kernel.tree_combine_gb: bytes that the tree-combine kernel's calls
read and write per step, by the operand and result shapes in the trace
(``kernels/tree_combine.py``), on the chip where the kernel's time is
longest, in GB (1e9 bytes).  Read beside ``kernel.tree_combine_ms``.
Nothing to read where that metric has nothing."""

KERNEL = "tree_combine"


def read(ctx):
    kernel = ctx.cell.kernels[KERNEL]
    found = kernel.slowest(ctx)
    if found is None:
        return None
    return ctx.trace.per_step(found[0], kernel.is_call,
                              kernel.call_bytes) / 1e9
