"""The ``moe_rows_ms`` reader on synthetic device ops: it sums the MoE row
kernels (``moe_rows_*``) per step and per chip, leaves out the grouped
matmuls and the comm_pack kernels, and reads nothing where there is no
row kernel (a program without them, such as a dense model's)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

read = harness.load_reader(ROOT, "moe_rows_ms")

ROW = 'moe_rows_gather.3 = bf16[131072,2048]{1,0} custom-call(s32[1]{0} %a, s32[131072]{0} %b), custom_call_target="tpu_custom_call"'
SUM = 'moe_rows_sum.4 = bf16[16384,2048]{1,0} custom-call(s32[128]{0} %c), custom_call_target="tpu_custom_call"'
PACK = 'moe_rows_pack.5 = u32[131072,1,1024]{2,1,0} custom-call(s32[1]{0} %n), custom_call_target="tpu_custom_call"'
GMM = 'moe_gmm.6 = bf16[131072,768]{1,0} custom-call(bf16[131072,2048]{1,0} %x), custom_call_target="tpu_custom_call"'
TGMM = 'moe_tgmm.7 = bf16[16,2048,768]{2,1,0} custom-call(bf16[131072,2048]{1,0} %x), custom_call_target="tpu_custom_call"'
WIRE = 'moe_rows_wire.8 = f32[4096]{0} custom-call(bf16[4096]{0} %g), custom_call_target="tpu_custom_call"'
FUSION = "fusion.9 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %y)"


def ctx(ops_by_dev, steps=2):
    ops = {d: [trace.Op(s * 1e6, e * 1e6, n) for n, s, e in v] for d, v in ops_by_dev.items()}
    return {"trace": trace.Reduced(ops=ops, host=[], t0=0.0, t1=1e9), "steps": steps}


def test_counts_the_row_kernels_per_step_and_chip():
    one = [(ROW, 0, 3), (GMM, 3, 10), (SUM, 10, 12), (PACK, 12, 13), (TGMM, 13, 20),
           (FUSION, 20, 25), (WIRE, 25, 40)]
    other = [(ROW, 0, 5), (SUM, 5, 6), (PACK, 6, 8)]
    # ms: chip 0 has 3 + 2 + 1, chip 1 has 5 + 1 + 2, over 2 steps
    assert read(ctx({0: one, 1: other})) == pytest.approx((6 + 8) / 2 / 2)


def test_grouped_matmuls_and_comm_pack_are_not_counted():
    assert trace.is_comm_pack(trace.Op(0, 1, WIRE))
    assert read(ctx({0: [(GMM, 0, 5), (TGMM, 5, 9), (WIRE, 9, 12), (FUSION, 12, 20)]})) is None


def test_nothing_to_read_without_row_kernels():
    assert read(ctx({0: []})) is None
    assert read(ctx({0: [(ROW, 0, 3)]}, steps=0)) is None
