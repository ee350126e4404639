"""End-to-end training driver: a small LM trained with the full MG-WFBP
stack — schedule computation, bucket-segmented scan, packed-arena
gradient sync inside shard_map, synthetic data pipeline, async atomic
checkpointing, and fault-tolerant restart.

    PYTHONPATH=src python examples/train_lm.py --steps 200            # ~25M params
    PYTHONPATH=src python examples/train_lm.py --steps 300 --full     # ~110M params
    PYTHONPATH=src python examples/train_lm.py --steps 40 --tiny      # smoke

The loss must fall well below the unigram entropy of the synthetic
mixture — the stream embeds a repeated motif (data/pipeline.py) so a
working model reaches ~half the initial loss within a few hundred steps.
"""

import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core import tpu_psum_model
from repro.core.trainer import MGWFBPEngine
from repro.data import DataConfig, make_stream
from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.launch.mesh import make_mesh
from repro.launch.specs import param_specs
from repro.models.common import Attention
from repro.models.transformer import init_params
from repro.optim import make_optimizer


def build_cfg(size: str):
    cfg = get_reduced("tinyllama-1.1b")
    if size == "tiny":
        return dataclasses.replace(cfg, param_dtype=jnp.float32)
    if size == "full":  # ~110M params
        return dataclasses.replace(
            cfg,
            name="tinyllama-110m",
            n_layers=8,
            d_model=768,
            d_ff=2048,
            vocab=8192,
            attention=Attention(n_heads=12, n_kv_heads=4, head_dim=64),
            param_dtype=jnp.float32,
            q_chunk=64,
        )
    return dataclasses.replace(  # default ~25M
        cfg,
        name="tinyllama-25m",
        n_layers=6,
        d_model=384,
        d_ff=1024,
        vocab=4096,
        attention=Attention(n_heads=6, n_kv_heads=2, head_dim=64),
        param_dtype=jnp.float32,
        q_chunk=64,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--method", default="mg_wfbp",
                    choices=["mg_wfbp", "dp_optimal", "wfbp", "synceasgd", "fixed"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()

    cfg = build_cfg("tiny" if args.tiny else "full" if args.full else "mid")
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev, 1), ("data", "model"))

    shapes = param_specs(cfg)
    eng = MGWFBPEngine.build(
        cfg, shapes,
        dp_axes=("data",),
        ar_model=tpu_psum_model({"data": max(n_dev, 2)}),
        tokens_per_device=args.batch * args.seq // n_dev,
        method=args.method,
    )
    print(f"schedule: {eng.schedule.describe()}")
    print(f"scan segments: {eng.segments}")

    opt = make_optimizer("adamw", weight_decay=0.01)
    step_fn = eng.make_train_step(opt, mesh, lr=args.lr)

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  params: {n_params / 1e6:.1f}M")
    opt_state = opt.init(params)

    start = 0
    ck = latest_step(args.ckpt_dir)
    if ck is not None:
        tree, extra = restore(args.ckpt_dir, ck, {"params": params, "opt_state": opt_state})
        params, opt_state = tree["params"], tree["opt_state"]
        start = ck
        print(f"resumed from checkpoint step {ck}")

    data = make_stream(DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))
    ckpt = AsyncCheckpointer(args.ckpt_dir)

    t0 = time.time()
    first_loss = None
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                if first_loss is None:
                    first_loss = loss
                dt = time.time() - t0
                print(f"step {step:4d}  loss {loss:.4f}  ({dt:.1f}s)")
            if (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt_state": opt_state})
    ckpt.wait()
    final = float(metrics["loss"])
    print(f"\nloss: {first_loss:.4f} -> {final:.4f} "
          f"({'OK: learned' if final < 0.7 * first_loss else 'WARNING: check'})")


if __name__ == "__main__":
    main()
