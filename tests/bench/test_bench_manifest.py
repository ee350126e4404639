"""The benchmark's manifest (``BENCHMARK.json``) and the files it names.

Every cell names a configuration, traffic mix and cell file that exist;
every metric names cells and an end-to-end metric those cells report;
names and units keep to the allowed characters; a configuration, cell,
traffic mix and metric reader added as new files (and manifest entries)
are found by name; and ``bench/run.py`` refuses a machine without a TPU.
"""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)


def test_cells_and_configs_name_each_other():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs, w
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "cells" / f"{w['name']}.json").is_file()
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == set(configs)
    for c in configs.values():
        f = ROOT / c["file"]
        assert f.is_file() and f.parts[len(ROOT.parts)] == "bench"
        cfg = json.loads(f.read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert (ROOT / "bench" / "refs" / f"{cfg['reference']}.py").is_file()
        for k in c["reduced"]:
            assert not (k.endswith("_dim") or k.endswith("_rank") or k.endswith("_size")), k
            assert k in cfg.get("published", {}), k


def test_metrics_name_cells_and_a_reported_end_to_end_metric():
    layers = {}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in E2E
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS, (m["name"], cell)
            # an end-to-end metric without ``workloads`` is reported by every cell
            assert cell in E2E[m["moves"]].get("workloads", CELLS)
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in CELLS:
        assert any(cell in m.get("workloads", CELLS) for m in MANIFEST["per_layer"])


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]] + list(CELLS) \
        + [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]] \
        + [w["traffic"] for w in MANIFEST["workloads"]] \
        + [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in MANIFEST[kind]]
        assert len(got) == len(set(got)), kind


def test_added_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, cell and metric reader are new
    files plus manifest entries; no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/starcoder2-3b.json").read_text())
    cfg.update(name="starcoder2-3b-short", num_hidden_layers=2)
    (tmp_path / "bench/configs/starcoder2-3b-short.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic/train.seq4096.json").read_text())
    traffic["seq"] = 1024
    (tmp_path / "bench/traffic/train.seq1024.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/cells/new.cell.json").write_text(
        (ROOT / "bench/cells/sc2-3b.train.dp1.json").read_text())
    (tmp_path / "bench/metrics/new_metric_ms.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    m["configs"].append({"name": "starcoder2-3b-short", "source": cfg["source"],
                         "file": "bench/configs/starcoder2-3b-short.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({"name": "new.cell", "config": "starcoder2-3b-short",
                           "traffic": "train.seq1024", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "new_metric_ms", "unit": "ms", "better": "lower",
                           "source": "device_trace", "layer": "kernel",
                           "moves": "train_tokens_per_s", "workloads": ["new.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    cell = harness.load_cell("new.cell", tmp_path)
    assert cell.config["num_hidden_layers"] == 2 and cell.traffic["seq"] == 1024
    assert "new_metric_ms" in [x["name"] for x in cell.metrics]
    assert harness.load_reader(tmp_path, "new_metric_ms")({}) == 42.0
    old = harness.load_cell("sc2-3b.train.dp1", tmp_path)
    assert "new_metric_ms" not in [x["name"] for x in old.metrics]
    assert all(p.read_bytes() == b for p, b in before.items())


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2-3b.train.dp1", "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_run_fails_without_the_program(tmp_path):
    """A checkout that holds only the benchmark's files gives no result."""
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_limits_cover_the_compared_numbers(cell):
    spec = json.loads((ROOT / "bench" / "cells" / f"{cell}.json").read_text())
    want = {"loss_gap", "grad_gap", "grad_median_gap", "update_gap"} | (
        {"replica_mismatch"} if CELLS[cell]["chips"] > 1 else set())
    assert want <= set(spec["limits"])
    assert spec["limits"].get("replica_mismatch", 0) == 0
    assert spec["lr"] > 0 and "--policy" in spec["launcher"]
