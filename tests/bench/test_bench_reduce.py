"""The benchmark's yardstick: trace reduction, operation and byte counts,
and the peak table.

The trace checks read each profile recorded on the chip under
``data/`` (``<name>.xplane.pb``, with ``<name>.json`` saying what it is
and what the reduction read from it when it was committed), and compare
the reduction with a brute-force recount of the same events.
"""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts, trace  # noqa: E402
from bench.peaks import PEAKS, peak_for  # noqa: E402
from bench.refs import starcoder2  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_flops_per_token_match_hand_counts():
    # starcoder2-3b, L layers: q 3072x3072, k and v 3072x256, o 3072x3072,
    # MLP 2 x 3072x12288 per layer; the head (tied to the embedding) 3072x49152.
    cfg = config("starcoder2-3b")
    L = cfg["num_hidden_layers"]
    layer = 3072 * 3072 * 2 + 3072 * 256 * 2 + 2 * 3072 * 12288
    dense = L * layer + 3072 * 49152
    attn = 3 * L * 2 * 2 * 24 * 128 * 4096 / 2  # QK and PV over the mean causal context
    assert counts.model_flops_per_token(starcoder2, cfg, 4096) == 6 * dense + attn
    assert counts.model_flops_per_token(starcoder2, {**cfg, "num_hidden_layers": 18}, 4096) \
        == 12_626_952_192


def test_comm_pack_bytes_match_hand_counts():
    # per weight: read and write the gradient in its dtype, write and read the f32 wire
    bf16, f32 = 2 * 2 + 2 * 4, 2 * 4 + 2 * 4
    cfg = config("starcoder2-3b")
    layer = 3072 * 3072 * 2 + 3072 * 256 * 2 + 2 * 3072 * 12288 + 4 * 3072
    tied = (cfg["num_hidden_layers"] * layer + 2 * 3072) * bf16 + 49152 * 3072 * f32
    assert counts.comm_pack_bytes(starcoder2, cfg, 4) == tied
    assert counts.comm_pack_bytes(starcoder2, {**cfg, "num_hidden_layers": 18}, 4) \
        == 23_142_703_104
    # an untied head is one more bf16 weight matrix on the wire
    untied = {**cfg, "tie_word_embeddings": False}
    assert counts.comm_pack_bytes(starcoder2, untied, 4) == tied + 3072 * 49152 * bf16


def test_peak_table_refuses_an_unknown_chip():
    assert peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peak table entry"):
        peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peak_for("cpu")
    assert all("source" in v for v in PEAKS.values())


def test_interval_arithmetic():
    ops = {0: [trace.Op(0, 10, "fusion.1"), trace.Op(5, 20, "all-reduce.2"),
               trace.Op(30, 40, "all-reduce.3"), trace.Op(35, 36, "fusion.4")]}
    red = trace.Reduced(ops=ops, host=[(0, 50, "bench.dispatch")], t0=0, t1=50)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.op_seconds(0, trace.is_allreduce) == pytest.approx(25e-9)
    # 10..20 and 30..35, 36..40 run alone
    assert red.exposed_seconds(0, trace.is_allreduce) == pytest.approx(19e-9)
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.dispatch", pytest.approx(10e-9)]


@pytest.fixture(scope="module", params=sorted(p.stem for p in DATA.glob("*.json")))
def recorded(request):
    expected = json.loads((DATA / f"{request.param}.json").read_text())
    red = trace.reduce_file(str(DATA / f"{request.param}.xplane.pb"), expected["devices"])
    return red, expected


def _brute_busy(ops, t0, t1, step=1000.0):
    """Busy time by sampling the window every ``step`` ns."""
    n, t = 0, t0
    while t < t1:
        n += any(o.start <= t < o.end for o in ops)
        t += step
    return n * step * 1e-9


def _brute_exposed(ops, t0, t1, step=1000.0):
    """All-reduce time with nothing else running, by the same sampling."""
    n, t = 0, t0
    while t < t1:
        on = [o for o in ops if o.start <= t < o.end]
        n += any(map(trace.is_allreduce, on)) and all(map(trace.is_allreduce, on))
        t += step
    return n * step * 1e-9


def test_recorded_trace_reduces_to_its_recorded_numbers(recorded):
    red, want = recorded
    assert sorted(red.ops) == want["devices"]
    assert all(red.ops[d] for d in red.ops)
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.busy_s == pytest.approx(want["busy_s"])
    for d in red.ops:
        assert _brute_busy(red.ops[d], red.t0, red.t1) == pytest.approx(
            trace.covered(red.busy(d), red.t0, red.t1) * 1e-9, rel=0.02)
    ar = [red.op_seconds(d, trace.is_allreduce) for d in red.ops]
    ex = [red.exposed_seconds(d, trace.is_allreduce) for d in red.ops]
    kern = [red.op_seconds(d, trace.is_comm_pack) for d in red.ops]
    assert ar == pytest.approx(want["allreduce_s"])
    assert ex == pytest.approx(want["exposed_s"])
    assert kern == pytest.approx(want["comm_pack_s"])
    assert all(0 <= e <= a for e, a in zip(ex, ar))
    for d, e in zip(red.ops, ex):
        assert _brute_exposed(red.ops[d], red.t0, red.t1) == pytest.approx(e, rel=0.05, abs=2e-6)
    assert all(k > 0 for k in kern)
    assert 0 < red.busy_s < red.window_s
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
