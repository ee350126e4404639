"""Fault-tolerance + elasticity demo: train, inject a failure, restore
from the atomic checkpoint, and — the elastic part — recompute the
MG-WFBP schedule for a different cluster size.  The checkpoint layout is
schedule-agnostic, so the same weights resume under a different bucket
structure (paper Algorithm 1 reruns with the new N's α–β model).

Phase 3 is the serving mirror: snapshot a mid-generation ServingEngine,
"kill" it, restore into a fresh engine, and verify the resumed run emits
exactly the tokens the uninterrupted run would have.

    PYTHONPATH=src python examples/elastic_restart.py
"""

import dataclasses
import shutil
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import tpu_psum_model
from repro.core.trainer import MGWFBPEngine
from repro.data import DataConfig, make_stream
from repro.checkpoint import latest_step, load_plan, restore
from repro.launch.mesh import make_mesh
from repro.launch.specs import param_specs
from repro.models.transformer import init_params
from repro.optim import make_optimizer
from repro.runtime import RunState, StragglerMonitor, resilient_loop
from repro.serving import (
    Request,
    ServingEngine,
    restore_latest_snapshot,
    save_snapshot,
)

CKPT = "/tmp/repro_elastic_ckpt"
SERVE_SNAP = "/tmp/repro_elastic_serve_snap"


def make_engine(cfg, shapes, n_virtual: int):
    """Schedule as it would be on an n_virtual-chip DP group."""
    return MGWFBPEngine.build(
        cfg, shapes, dp_axes=("data",),
        ar_model=tpu_psum_model({"data": n_virtual}),
        tokens_per_device=2048 // max(jax.device_count(), 1),
        method="mg_wfbp",
    )


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = dataclasses.replace(get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32)
    shapes = param_specs(cfg)
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    opt = make_optimizer("adamw")
    data = make_stream(DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8))

    # phase 1: "16-chip" schedule
    eng16 = make_engine(cfg, shapes, 16)
    print("schedule @ N=16:", eng16.schedule.describe())
    step16 = eng16.make_train_step(opt, mesh, lr=1e-3)

    def init_state():
        params = init_params(jax.random.PRNGKey(0), cfg)
        return RunState(step=0, params=params, opt_state=opt.init(params))

    crashes = {25}

    def fault(step):
        if step in crashes:
            crashes.discard(step)
            raise RuntimeError(f"injected node failure at step {step}")

    def do_step(state, step):
        batch = jax.tree.map(jnp.asarray, data.batch_at(step))
        with jax.set_mesh(mesh):
            p, o, m = step16(state.params, state.opt_state, batch)
        return RunState(step=state.step, params=p, opt_state=o, restarts=state.restarts)

    mon = StragglerMonitor(factor=3.0, patience=3)
    state = resilient_loop(
        num_steps=40, init_state=init_state, train_step=do_step,
        checkpoint_dir=CKPT, checkpoint_every=10,
        fault_injector=fault, straggler=mon,
        # plan-aware checkpointing: every checkpoint carries the active plan
        plan_provider=lambda: eng16.plan,
    )
    print(f"phase 1 done: step={state.step} restarts={state.restarts} "
          f"(failure at 25 -> restored from step 20)")

    # Same-N restart: the plan rides beside the weights — reload it instead
    # of recomputing Algorithm 1, and resume under the *exact* schedule the
    # run crashed with.
    ck = latest_step(CKPT)
    stored = load_plan(CKPT, ck)
    assert stored == eng16.plan
    eng_resumed = MGWFBPEngine.build(cfg, None, dp_axes=("data",), plan=stored)
    assert eng_resumed.schedule.groups == eng16.schedule.groups
    print(f"plan restored from checkpoint step {ck}: {stored.describe()}")

    # phase 2: the cluster grew to "64 chips" — elastic restart: same
    # checkpoint (weights are schedule-agnostic), but the stored plan's
    # α–β model is the old N's, so the same policy re-plans at the new N
    eng64 = make_engine(cfg, shapes, 64)
    print("schedule @ N=64:", eng64.schedule.describe())
    assert eng64.schedule.groups != eng16.schedule.groups or True  # may differ
    fresh = init_state()
    tree, _ = restore(CKPT, ck, {"params": fresh.params, "opt_state": fresh.opt_state})
    step64 = eng64.make_train_step(opt, mesh, lr=1e-3)
    params, opt_state = tree["params"], tree["opt_state"]
    with jax.set_mesh(mesh):
        for s in range(ck, ck + 5):
            batch = jax.tree.map(jnp.asarray, data.batch_at(s))
            params, opt_state, m = step64(params, opt_state, batch)
    print(f"phase 2: resumed step {ck} under the N=64 schedule, "
          f"5 more steps OK (loss {float(m['loss']):.3f})")

    # phase 3: serve-side elastic restart — snapshot mid-generation, kill
    # the engine, restore into a fresh one, and the resumed decode emits
    # token-for-token what the uninterrupted run would have
    shutil.rmtree(SERVE_SNAP, ignore_errors=True)
    serve_params = init_params(jax.random.PRNGKey(0), cfg)

    def make_serve_engine():
        return ServingEngine(cfg, serve_params, slots=2, max_seq=64)

    def submit_all(eng):
        rng = np.random.default_rng(0)
        for rid in range(3):
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, size=8, dtype=np.int32),
                max_new_tokens=12,
            ))

    ref = make_serve_engine()
    submit_all(ref)
    expected = {r.rid: r.generated for r in ref.run_to_completion()}

    eng = make_serve_engine()
    submit_all(eng)
    for _ in range(5):
        eng.step()
    save_snapshot(eng, SERVE_SNAP, 5)
    del eng  # the "kill": the mid-generation engine is gone

    fresh = make_serve_engine()
    step, _ = restore_latest_snapshot(fresh, SERVE_SNAP)
    while fresh.active or fresh.waiting:
        fresh.step()
    resumed = {r.rid: r.generated for r in fresh.completed}
    assert resumed == expected, "restored decode diverged from baseline"
    print(f"phase 3: serve snapshot at step {step} restored into a fresh "
          f"engine; all {len(resumed)} requests token-identical to the "
          f"uninterrupted run")


if __name__ == "__main__":
    main()
