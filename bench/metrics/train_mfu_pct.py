"""Model FLOP utilization of the traced window: the operations the
forward and backward passes require per token (6 x the weights a token
multiplies through, plus the attention or recurrence; recomputation
under remat not counted) x the tokens of the traced steps over the
trace's window, over the cell's chips x the chip's peak bf16 FLOP/s."""

from bench import counts
from bench.peaks import peak_for


def read(ctx):
    window = ctx["trace"].window_s
    if window <= 0 or not ctx["tokens"]:
        return None
    flops = counts.model_flops_per_token(ctx["family"], ctx["config"], ctx["seq"])
    peak = peak_for(ctx["kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops * ctx["tokens"] / window / peak
