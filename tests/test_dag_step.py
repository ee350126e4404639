"""DAG issue order: the in-backward step must be a pure reordering.

One subprocess (8 virtual CPU devices) compiles the same engine twice —
``issue="post"`` and ``issue="dag"`` — on identical params/batch and
checks that

  * the dag HLO still carries one gradient all-reduce per schedule group
    (plus the loss pmean and whatever small psums XLA adds);
  * losses and updated parameters are bit-identical between the two
    issue orders: moving the collectives inside backward must not change
    a single ulp of the math.
"""

import json
import subprocess
import sys
import textwrap

from _env import REPO_ROOT, SUBPROC_ENV

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, re
    import jax, jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.configs import get_reduced
    from repro.core.comm_model import AllReduceModel
    from repro.core.sync import SyncConfig
    from repro.core.trainer import MGWFBPEngine
    from repro.launch.specs import param_specs
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer

    mesh = make_mesh((8,), ("data",))
    cfg = get_reduced("tinyllama-1.1b")
    eng = MGWFBPEngine.build(
        cfg, param_specs(cfg), dp_axes=("data",),
        ar_model=AllReduceModel(a=5e-5, b=1e-9),
        tokens_per_device=1024, method="wfbp",
        sync_config=SyncConfig(),
    )
    opt = make_optimizer("sgd", momentum=0.9)

    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 8, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    batch = {"targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab)}
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model), jnp.float32) * 0.02
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, S), 0, cfg.vocab)

    out = {"n_groups": len(eng.schedule.groups)}
    results = {}
    for issue in ("post", "dag"):
        step = eng.make_train_step(opt, mesh, lr=1e-2, issue=issue)
        # the step donates params/opt_state: hand it fresh copies
        p0 = jax.tree.map(jnp.array, params)
        with jax.set_mesh(mesh):
            lowered = step.lower(p0, opt.init(p0), batch)
            compiled = lowered.compile()
            out[f"n_allreduce_{issue}"] = len(
                re.findall(r" all-reduce\\(", compiled.as_text()))
            new_params, _, metrics = compiled(p0, opt.init(p0), batch)
        results[issue] = jax.tree.map(np.asarray, new_params)
        out[f"loss_{issue}"] = float(metrics["loss"])

    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(a, b)), results["post"], results["dag"])
    out["params_bit_identical"] = all(jax.tree.leaves(same))
    print(json.dumps(out))
""")


def test_dag_issue_order_structure_and_numerics():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=SUBPROC_ENV, cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_groups"] == 6  # wfbp on reduced tinyllama: one per unit
    for issue in ("post", "dag"):
        # one gradient all-reduce per group + loss pmean (+ small slack
        # for statistics psums): the train step keeps XLA's all-reduce
        # combiner from merging the groups
        assert rec["n_groups"] <= rec[f"n_allreduce_{issue}"] <= rec["n_groups"] + 4, rec
    # the dag reordering must not change the math at all
    assert rec["loss_post"] == rec["loss_dag"], rec
    assert rec["params_bit_identical"], rec
