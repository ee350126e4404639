"""The ``flash_attention_ms`` reader on synthetic device ops: it sums the
flash attention kernels (``flash_*``: the forward and the backward) per
step and per chip, leaves out the comm_pack kernels and every other op,
and reads nothing where no flash kernel ran (a program on the jnp
attention path)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

read = harness.load_reader(ROOT, "flash_attention_ms")

FWD = 'flash_fwd.110 = (bf16[64,8192,128]{2,1,0}, f32[64,8192,128]{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q, bf16[8,8192,128]{2,1,0} %k), custom_call_target="tpu_custom_call"'
BWD = 'flash_bwd.45 = (bf16[64,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q, bf16[8,128,8192]{2,1,0} %kt), custom_call_target="tpu_custom_call"'
DKV = 'flash_dkv.46 = (bf16[8,8192,128]{2,1,0}, bf16[8,8192,128]{2,1,0}) custom-call(bf16[64,8192,128]{2,1,0} %q), custom_call_target="tpu_custom_call"'
GMM = 'moe_gmm.6 = bf16[131072,768]{1,0} custom-call(bf16[131072,2048]{1,0} %x), custom_call_target="tpu_custom_call"'
PACK = 'comm_pack_pack.25 = f32[342908928]{0} custom-call(f32[1]{0} %s, bf16[3072]{0} %g), custom_call_target="tpu_custom_call"'
WIRE = 'flash_wire.8 = f32[4096]{0} custom-call(bf16[4096]{0} %g), custom_call_target="tpu_custom_call"'
FUSION = "fusion.9 = bf16[8192,2048]{1,0} fusion(bf16[8192,2048]{1,0} %y)"


def ctx(ops_by_dev, steps=2):
    ops = {d: [trace.Op(s * 1e6, e * 1e6, n) for n, s, e in v] for d, v in ops_by_dev.items()}
    return {"trace": trace.Reduced(ops=ops, host=[], t0=0.0, t1=1e9), "steps": steps}


def test_counts_the_flash_kernels_per_step_and_chip():
    one = [(FWD, 0, 3), (GMM, 3, 10), (BWD, 10, 17), (FUSION, 17, 25), (PACK, 25, 40),
           (FWD, 40, 43)]
    other = [(FWD, 0, 4), (BWD, 4, 12)]
    # ms: chip 0 has 3 + 7 + 3, chip 1 has 4 + 8, over 2 steps
    assert read(ctx({0: one, 1: other})) == pytest.approx((13 + 12) / 2 / 2)


def test_the_parents_two_backward_kernels_count_too():
    """Every kernel named ``flash_*`` counts, so a program whose backward
    is split in two reads on the same scale."""
    assert read(ctx({0: [(FWD, 0, 2), (DKV, 2, 7)]}, steps=1)) == pytest.approx(7)


def test_comm_pack_and_other_ops_are_not_counted():
    assert trace.is_comm_pack(trace.Op(0, 1, WIRE))
    assert not trace.is_comm_pack(trace.Op(0, 1, BWD))
    assert read(ctx({0: [(GMM, 0, 5), (PACK, 5, 9), (WIRE, 9, 12), (FUSION, 12, 20)]})) is None


def test_nothing_to_read_without_flash_kernels():
    assert read(ctx({0: []})) is None
    assert read(ctx({0: [(BWD, 0, 3)]}, steps=0)) is None
