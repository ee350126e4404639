"""chip_smoke.py and the launcher pieces it drives, at reduced size on CPU.

The script itself must refuse to run without a TPU; its phases are
exercised here through the same functions it calls (``train_phase``,
``serve_phase``), on the reduced tinyllama config — one CPU device for
the one-chip phases, four virtual CPU devices for ``--four-chips``.
Also covered: the compile-cache helper, the serve launcher's refusal of
a TP mesh wider than the visible devices, and the hardware preset
lookup by device kind.
"""

import importlib.util
import json
import subprocess
import sys
import textwrap
import types

import pytest

from _env import REPO_ROOT, SUBPROC_ENV

from repro.core.cost_model import TPU_V5E, hardware_for
from repro.launch import compile_cache

REDUCED = ["--arch", "tinyllama-1.1b", "--reduced"]
CANDIDATES = (("adamw", 2, 32),)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_cpu():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_smoke_phases_one_device(tmp_path, monkeypatch):
    # the cache helper then sets nothing in this test process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "SERVE_PROMPT", 16)
    monkeypatch.setattr(smoke, "SERVE_TOKENS", 4)
    rep = smoke.train_phase(REDUCED, CANDIDATES, 3, 0, tmp_path)
    assert rep["config"]["batch"] * rep["config"]["seq"] == 64
    assert len(rep["losses_ours"]) == len(rep["losses_other"]) == 3
    assert rep["loss_rel_gap"] <= smoke.LOSS_RTOL
    assert set(rep["config"]["compiles"]) == {"ours", "other"}
    serve = smoke.serve_phase(REDUCED, 0)
    assert serve["tokens"] == smoke.SERVE_REQUESTS * 4


def test_compare_losses_rejects_drift_and_nan():
    smoke = _load_smoke()
    assert smoke.compare_losses([2.0, 1.0], [2.0, 1.0], 2) == 0.0
    with pytest.raises(AssertionError, match="differ"):
        smoke.compare_losses([2.0, 1.1], [2.0, 1.0], 2)
    with pytest.raises(AssertionError, match="non-finite"):
        smoke.compare_losses([2.0, float("nan")], [2.0, 1.0], 2)
    with pytest.raises(AssertionError, match="expected 3"):
        smoke.compare_losses([2.0, 1.0], [2.0, 1.0], 3)


FOUR_DEVICE_SCRIPT = textwrap.dedent("""
    import importlib.util, json, pathlib, sys
    spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.SERVE_PROMPT, smoke.SERVE_TOKENS = 16, 4
    model = ["--arch", "tinyllama-1.1b", "--reduced"]
    rep = smoke.train_phase(model, (("adamw", 1, 32),), 2, 0, pathlib.Path(sys.argv[1]),
                            four_chips=True)
    srv = smoke.serve_phase(model, 0, four_chips=True)
    print(json.dumps({"train": rep, "serve": srv}))
""")


def test_smoke_four_device_phases(tmp_path):
    """The ``--four-chips`` flow on four virtual CPU devices: DP=4
    mg_wfbp/dag against wfbp/post, replicas checked, TP=4 decode equal to
    unsharded."""
    env = dict(SUBPROC_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["train"]["config"]["batch"] == 4
    assert rep["train"]["replica_devices"] == 4
    assert rep["train"]["replica_leaves"] > 0
    assert rep["serve"]["tp_tokens_identical"]


def test_compile_cache_honours_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    cache = tmp_path / "cache"
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((64, 64))).compile()
    """)
    env = dict(SUBPROC_ENV, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         timeout=300, env=env, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    was_tb = jax.config.jax_traceback_in_locations_limit
    was_key = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        # the innermost frame alone, with the name scopes still in op_name
        assert jax.config.jax_traceback_in_locations_limit == 1
        assert jax.config.jax_include_full_tracebacks_in_locations is True
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_traceback_in_locations_limit", was_tb)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", was_key)
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_serve_refuses_tp_wider_than_devices():
    env = dict(SUBPROC_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *REDUCED, "--sharded",
         "--virtual-tp", "4", "--requests", "1", "--tokens", "2"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO_ROOT,
    )
    assert out.returncode != 0
    assert "needs 4 devices" in out.stderr


def test_hardware_preset_by_device_kind():
    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert hardware_for(dev("tpu", "TPU v5 lite")) is TPU_V5E
    assert hardware_for(dev("cpu", "cpu")) is TPU_V5E  # the CPU rehearsal
    with pytest.raises(ValueError, match="TPU v4"):
        hardware_for(dev("tpu", "TPU v4"))
