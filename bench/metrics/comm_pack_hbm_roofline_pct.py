"""Share of the HBM roofline that the comm_pack kernels reach: the least
bytes the wire format forces per step (read each gradient, write it to
the wire in the wire dtype, read the reduced wire, write the gradient
back; from the weights' shapes and the wire dtype alone) over the
chip's HBM bandwidth, divided by the kernels' device time per step."""

from bench import counts, trace
from bench.peaks import peak_for


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    secs = sum(red.op_seconds(d, trace.is_comm_pack) for d in red.ops) / len(red.ops)
    if not steps or secs <= 0:
        return None
    nbytes = counts.comm_pack_bytes(ctx["family"], ctx["config"], ctx["wire_bytes"])
    return 100.0 * nbytes / peak_for(ctx["kind"])["hbm_bytes_per_s"] / (secs / steps)
