"""Device time of the MoE grouped-matmul kernels per step, averaged over
the cell's chips: the ops whose name starts with a ``moe_gmm`` or
``moe_tgmm`` kernel name (the ``name=`` of their ``pallas_call``s, which
XLA gives the kernel's instruction).  None where the program has no such
kernel."""

KERNELS = ("moe_gmm", "moe_tgmm")


def is_moe_gmm(op) -> bool:
    return op.name.lstrip("%").startswith(KERNELS)


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    v = sum(red.op_seconds(d, is_moe_gmm) for d in red.ops) / len(red.ops)
    return 1e3 * v / steps if steps and v > 0 else None
