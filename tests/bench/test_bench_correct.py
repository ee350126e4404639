"""``correct`` at a size a test run can hold: a sound run passes, and
the control and every fault a training cell can have fail.

The harness runs end to end on the CPU with its look for a chip
skipped, on a tiny StarCoder2-shaped configuration, under the limits
that the committed cells hold the chip runs to.  The faults are planted in
the program underneath the timed path:

* a step that returns its state unchanged (the optimizer update skipped);
* half of the batch left out, the mean taken over the rest (the loss
  over the first half of the sequence);
* the exchange between chips left out (the all-reduce an identity), on
  four virtual CPU devices in a child process.

A training cell produces no tokens or answers, so that fault has no
place here.  The control is the reference one precision lower (float8
for the bfloat16 configurations) put in the program's place.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import correct, harness  # noqa: E402

TINY = dict(num_hidden_layers=2, hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, vocab_size=512, embed_multiplier=11.3125)
SEED = 2**31 + 1234
#: At these widths a gradient is large next to the weights: a smaller
#: step than the cells' keeps three steps of float32 and bfloat16 on one
#: path, as the cells' own step does at their widths.
TINY_LR = 0.01


def limits(cell: str) -> dict:
    return json.loads((ROOT / "bench" / "cells" / f"{cell}.json").read_text())["limits"]


def tiny_root(tmp: pathlib.Path, chips: int, cell_limits: dict) -> pathlib.Path:
    """A checkout with one tiny cell ``tiny.cell`` of ``starcoder2-3b``."""
    shutil.copytree(ROOT / "bench", tmp / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/starcoder2-3b.json").read_text())
    cfg.update(TINY, name="tiny")
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic/train.seq4096.json").read_text())
    traffic["seq"] = 128
    (tmp / "bench/traffic/tiny.json").write_text(json.dumps(traffic))
    spec = json.loads((ROOT / "bench/cells/sc2-3b.train.dp1.json").read_text())
    spec.update(limits=cell_limits, lr=TINY_LR)
    (tmp / "bench/cells/tiny.cell.json").write_text(json.dumps(spec))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"] = [{"name": "tiny", "source": cfg["source"], "file": "bench/configs/tiny.json",
                     "reduced": [], "why": "test"}]
    m["workloads"] = [{"name": "tiny.cell", "config": "tiny", "traffic": "tiny",
                       "chips": chips, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp


def run(root: pathlib.Path) -> dict:
    import jax

    cell = harness.load_cell("tiny.cell", root)
    return harness.run_cell(cell, SEED, 0.2, False, jax.devices(), time.perf_counter())


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")


def test_sound_run_is_correct(tmp_path):
    out = run(tiny_root(tmp_path, 1, limits("sc2-3b.train.dp1")))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    import repro.optim.optimizers as opt

    monkeypatch.setattr(opt, "sgd_update", lambda g, s, p, lr, momentum=0.0: (p, s))
    out = run(tiny_root(tmp_path, 1, limits("sc2-3b.train.dp1")))
    assert not out["correct"]
    assert dict((k, v) for k, v, _ in out["checks"])["update_gap"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    import repro.models.transformer as tr

    full = tr._ce_from_hidden

    def half(cfg, head, x, targets, *a, **kw):
        S = targets.shape[1] // 2
        return full(cfg, head, x[:, :S], targets[:, :S], *a, **kw)

    monkeypatch.setattr(tr, "_ce_from_hidden", half)
    out = run(tiny_root(tmp_path, 1, limits("sc2-3b.train.dp1")))
    assert not out["correct"], out["checks"]


_DP4 = """
import json, pathlib, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
import repro.launch.compile_cache as cc
cc.enable_compile_cache = lambda: ""
if {broken!r}:
    import repro.core.sync as sync
    sync.issue = lambda kind, v, axes: v
from bench import harness
cell = harness.load_cell("tiny.cell", pathlib.Path({tiny!r}))
out = harness.run_cell(cell, {seed}, 0.2, False, jax.devices(), time.perf_counter())
print(json.dumps(out))
"""


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "exchange_left_out"])
def test_exchange_between_chips_left_out_is_not_correct(tmp_path, broken):
    tiny = tiny_root(tmp_path, 4, {**limits("sc2-3b.train.dp1"), "replica_mismatch": 0})
    env = {"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = _DP4.format(root=str(ROOT), src=str(ROOT / "src"), tiny=str(tiny), seed=SEED,
                       broken=broken)
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["checks"]
    if broken:
        assert dict((k, v) for k, v, _ in out["checks"])["replica_mismatch"] > 0


def test_control_one_precision_lower_is_not_correct(tmp_path):
    import jax

    lim = limits("sc2-3b.train.dp1")
    c = harness.load_cell("tiny.cell", tiny_root(tmp_path, 1, lim))
    b = harness.build(c, jax.devices())
    ref = harness.reference_readings(c, SEED, b)
    ctl = harness.reference_readings(c, SEED, b, mode="control")
    ok, rows = correct.judge(correct.numbers(ctl, ref), lim)
    assert not ok, rows


def test_numbers_read_each_layer_and_join_stacked_leaves():
    w = "['stages']['attn_0']['attn']['wq']"
    ref = {"grad": {"['head']": 4.0, f"{w}[0]": 3.0, f"{w}[1]": 4.0, "['b']": 1.0},
           "update": {"['head']": 4.0, f"{w}[0]": 3.0, f"{w}[1]": 4.0, "['b']": 1.0},
           "losses": [2.0, 1.0, 0.5]}
    # layer 1 of wq off by a quarter; its stacked norm (5) by a sixth
    prog = {"grad": {**ref["grad"], f"{w}[1]": 5.0}, "update": {**ref["update"], f"{w}[1]": 5.0},
            "losses": [2.0, 1.0, 0.75]}
    assert correct.stacked(ref["update"]) == {"['head']": 4.0, w: 5.0, "['b']": 1.0}
    got = correct.numbers(prog, ref)
    assert got["grad_gap"] == pytest.approx(0.25)
    assert got["grad_median_gap"] == 0.0
    assert got["update_gap"] == pytest.approx((34 ** 0.5 - 5) / 5)
    assert got["loss_gap"] == pytest.approx(0.25)
    assert correct.numbers({**prog, "losses": [float("nan")] * 3}, ref)["loss_gap"] == float("inf")
