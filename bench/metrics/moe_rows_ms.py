"""Device time of the MoE row kernels per step, averaged over the cell's
chips: the ops whose name starts with ``moe_rows`` (the ``name=`` of the
``pallas_call``s that pack, gather and sum the routed rows, which XLA
gives the kernel's instruction), comm_pack kernels left out.  None where
the program has no such kernel."""

from bench import trace

PREFIX = "moe_rows"


def is_moe_rows(op) -> bool:
    return op.name.lstrip("%").startswith(PREFIX) and not trace.is_comm_pack(op)


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    v = sum(red.op_seconds(d, is_moe_rows) for d in red.ops) / len(red.ops)
    return 1e3 * v / steps if steps and v > 0 else None
