"""Device time of the flash attention kernels per step, averaged over the
cell's chips: the ops whose name starts with ``flash_`` (the ``name=`` of
the forward and backward ``pallas_call``s, which XLA gives the kernel's
instruction), comm_pack kernels left out.  None where the program runs no
flash kernel."""

from bench import trace

PREFIX = "flash_"


def is_flash(op) -> bool:
    return op.name.lstrip("%").startswith(PREFIX) and not trace.is_comm_pack(op)


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    v = sum(red.op_seconds(d, is_flash) for d in red.ops) / len(red.ops)
    return 1e3 * v / steps if steps and v > 0 else None
