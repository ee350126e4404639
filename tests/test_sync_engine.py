"""MG-WFBP sync engine: schedule groups -> exactly that many variadic
all-reduces in the compiled HLO, with numerics identical to unbucketed DP.

Multi-device cases run in a subprocess so the main pytest process keeps a
single CPU device (smoke tests must not see a forced device count)."""

import json
import subprocess
import sys
import textwrap
from _env import REPO_ROOT, SUBPROC_ENV  # shared subprocess env

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, re, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.core.comm_model import AllReduceModel
    from repro.core.trainer import MGWFBPEngine, lm_unit_costs
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer

    method = sys.argv[1]
    arch = sys.argv[2]

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_reduced(arch)
    p_shapes = param_specs(cfg)
    ar = AllReduceModel(a=5e-5, b=1e-9)

    eng = MGWFBPEngine.build(
        cfg, p_shapes, dp_axes=("data",), ar_model=ar,
        tokens_per_device=1024, method=method,
    )
    opt = make_optimizer("sgd", momentum=0.9)
    step = eng.make_train_step(opt, mesh, lr=1e-2)

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = opt.init(params)
    B, S = 8, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    batch = {"targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab)}
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model), jnp.float32) * 0.02
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, S), 0, cfg.vocab)

    # reference FIRST (params are donated to the compiled step below):
    # plain jit grad + mean over full batch
    from repro.models import loss_fn
    def ref_loss(p):
        return loss_fn(p, batch, cfg)[0]
    g_ref = jax.grad(ref_loss)(params)
    from repro.optim.optimizers import sgd_update, sgd_init
    ref_params, _ = sgd_update(g_ref, sgd_init(params, 0.9), params, 1e-2, 0.9)
    ref_params = jax.tree.map(np.asarray, ref_params)

    with jax.set_mesh(mesh):
        lowered = step.lower(params, opt_state, batch)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        # count gradient all-reduces over the data axis: replica_groups
        # containing {0,2,4,6}-style (stride-model) groups
        n_ar = len(re.findall(r" all-reduce\\(", hlo))
        new_params, _, metrics = compiled(params, opt_state, batch)

    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        new_params, ref_params)
    max_diff = max(jax.tree.leaves(diffs))
    print(json.dumps({
        "n_allreduce": n_ar,
        "n_groups": len(eng.schedule.groups),
        "segments": list(map(list, eng.segments)),
        "max_param_diff": max_diff,
        "loss": float(metrics["loss"]),
        "method": method,
        "groups": list(map(list, eng.schedule.groups)),
    }))
""")


def run_case(method: str, arch: str) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, method, arch],
        capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV,
        cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("method,arch", [
    ("mg_wfbp", "tinyllama-1.1b"),
    ("dp_optimal", "tinyllama-1.1b"),
    ("synceasgd", "tinyllama-1.1b"),
    ("mg_wfbp", "mixtral-8x7b"),
    ("mg_wfbp", "recurrentgemma-9b"),  # tail pattern
])
def test_bucketed_sync_numerics_and_hlo(method, arch):
    rec = run_case(method, arch)
    # numerics: bucketed shard_map DP == plain data parallelism
    assert rec["max_param_diff"] < 5e-2, rec  # bf16 params => loose abs tol
    # structure: gradient all-reduces == schedule groups (+1 for the loss
    # pmean, +small constant for psums XLA inserts for norms statistics)
    assert rec["n_allreduce"] >= rec["n_groups"]
    assert rec["n_allreduce"] <= rec["n_groups"] + 4, rec


def test_synceasgd_single_group():
    rec = run_case("synceasgd", "tinyllama-1.1b")
    assert rec["n_groups"] == 1
    assert len(rec["segments"]) == 1


def test_wfbp_many_groups():
    rec = run_case("wfbp", "tinyllama-1.1b")
    # every unit separate: embed + 4 stages + head = 6 groups (reduced cfg)
    assert rec["n_groups"] == 6
    # FINDING (EXPERIMENTS.md): XLA's all-reduce combiner merges adjacent
    # small all-reduces below its size threshold — the compiler-level
    # analogue of the paper's tensor-fusion baselines.  At these reduced
    # test sizes all 6 WFBP reduces may legally combine into fewer ops;
    # production runs pin the combiner threshold to 0.
    assert 1 <= rec["n_allreduce"] <= 6 + 4
