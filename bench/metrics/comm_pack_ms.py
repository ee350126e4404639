"""Device time of the comm_pack pack and unpack kernels per step,
averaged over the cell's chips."""

from bench import trace


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    v = sum(red.op_seconds(d, trace.is_comm_pack) for d in red.ops) / len(red.ops)
    return 1e3 * v / steps if steps and v > 0 else None
