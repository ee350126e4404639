"""Device busy time per step outside the gradient all-reduces and the
comm_pack kernels, averaged over the cell's chips."""

from bench import trace


def read(ctx):
    red, steps = ctx["trace"], ctx["steps"]
    if not steps:
        return None
    other = lambda o: not (trace.is_allreduce(o) or trace.is_comm_pack(o))  # noqa: E731
    secs = [trace.covered(trace.union((max(o.start, red.t0), min(o.end, red.t1))
                                      for o in red.ops[d] if other(o)), red.t0, red.t1) * 1e-9
            for d in red.ops]
    v = 1e3 * sum(secs) / len(secs) / steps
    return v if v > 0 else None
