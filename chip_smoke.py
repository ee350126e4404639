#!/usr/bin/env python3
"""Smoke run of the training and decode paths on TPU chips.

    python chip_smoke.py                # one chip: train + serve
    python chip_smoke.py --four-chips   # four chips: DP=4 train, TP=4 decode

Everything runs in this one process, through the launchers' own entry
points (``repro.launch.train.main`` / ``repro.launch.serve.main``), on
tinyllama-1.1b at its published widths: 22 layers, d_model 2048, d_ff
5632, 32 heads with 4 KV heads, vocab 32000, bf16 weights drawn from
``--seed``.

One chip:

* training under the system's own configuration (``--policy mg_wfbp
  --issue-order dag``) for ``STEPS`` steps on the seeded synthetic
  stream, then the same steps under ``--issue-order post``.  The
  optimizer and tokens per step are the first of ``TRAIN_CANDIDATES``
  whose compiled steps (both configurations) leave ``HEADROOM_BYTES`` of
  the device's memory free.  The two loss sequences differ only in issue
  order and must agree within ``LOSS_RTOL``; the step must contain the
  Pallas pack/unpack kernels of the arena wire (``tpu_custom_call``).
  Both runs pack and unpack through the same kernels, so the comparison
  checks the issue order only, not the wire's numerics: those are held
  on the chip by each benchmark cell's ``correct`` against its plain
  reference, and by ``tests/test_arena.py`` (the kernels in interpret
  mode against ``comm_pack.ref``);
* serving: ``SERVE_REQUESTS`` requests of ``SERVE_PROMPT`` prompt tokens
  and ``SERVE_TOKENS`` new tokens on ``SERVE_SLOTS`` slots, unsharded;
  every request must complete with its full token count.

Four chips (``--four-chips``, and nothing else):

* DP=4 training under ``mg_wfbp``/``dag`` against ``wfbp``/``post``:
  losses agree within ``LOSS_RTOL``, the final weights are bit-identical
  on all four devices (each holding its own replica), and every device's
  peak memory carries a full replica;
* ``--sharded`` TP=4 decode against unsharded decode: identical tokens.

The script refuses to run anywhere but on a TPU.  Progress lines go to
stdout and a JSON report to ``--out``; the last line of stdout is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = ["--arch", "tinyllama-1.1b"]

#: (optimizer, sequences per device, sequence length), in order of
#: preference: the launcher's default optimizer at a full 2048-token
#: sequence first, then plain SGD (no optimizer state) at shrinking batch.
#: Compiled for a described v5e (15.75 GiB of HBM), adamw at 1 x 2048 does
#: not fit under the post order (its f32 moments and gradients alone take
#: most of it), and SGD at 4 x 2048 peaks at 6.58 GB (post) and 7.90 GB
#: (dag).
TRAIN_CANDIDATES = (("adamw", 1, 2048), ("sgd", 4, 2048), ("sgd", 2, 2048),
                    ("sgd", 1, 2048))
HEADROOM_BYTES = 1 << 30
STEPS = 5
#: The compared runs do the same math on bf16 weights with f32 gradients
#: on the wire; only the program structure (fusion, and so the rounding
#: order of bf16 intermediates) differs.  That moves a mean loss over
#: thousands of tokens by far less than one bf16 ulp (2**-8 relative) in
#: a few steps; a wrong wire moves it by more.
LOSS_RTOL = 2.0 ** -8
SERVE_SLOTS, SERVE_REQUESTS, SERVE_PROMPT, SERVE_TOKENS = 4, 4, 128, 32

GiB = 2.0 ** 30


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _compile_step(argv: list[str]):
    """Compile the train step ``argv`` would run, without running it:
    the same setup and engine as ``train.main``, lowered on shapes.
    Returns ``(compiled, seconds)``, or ``(None, seconds)`` when the
    compiler finds the step does not fit the device's memory."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import train
    from repro.launch.specs import param_specs

    args = train.parse_args(argv)
    ts = train.setup(args)
    step = ts.train_step(ts.engine())
    rep = NamedSharding(ts.mesh, P())

    def on_mesh(tree, sharding):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
        )

    params = on_mesh(param_specs(ts.cfg), rep)
    opt_state = on_mesh(jax.eval_shape(ts.opt.init, params), rep)
    # the launcher feeds host batches, uncommitted: so are these
    rows = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    batch = {"tokens": rows, "targets": rows}
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(ts.mesh):  # as train.main runs it
            compiled = step.lower(params, opt_state, batch).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        log(f"does not fit: {str(e).splitlines()[0]}")
        compiled = None
    return compiled, time.perf_counter() - t0


def choose_train_config(configs: dict[str, list[str]], candidates, n_dev: int,
                        limit_bytes: int | None) -> dict:
    """The first candidate whose compiled step fits in ``limit_bytes``
    minus ``HEADROOM_BYTES`` under every one of ``configs`` (name ->
    launcher flags).  With no limit (a backend that reports none) the
    first candidate is taken."""
    for opt, rows, seq in candidates:
        cand = {"optimizer": opt, "batch": rows * n_dev, "seq": seq, "compiles": {}}
        fits = True
        for name, flags in configs.items():
            argv = flags + ["--optimizer", opt, "--batch", str(rows * n_dev),
                                   "--seq", str(seq)]
            compiled, secs = _compile_step(argv)
            if compiled is None:
                fits = False
                break
            peak = int(compiled.memory_analysis().peak_memory_in_bytes)
            cand["compiles"][name] = {
                "compile_s": secs, "peak_bytes": peak,
                "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            }
            log(f"compiled {name} step ({opt}, {rows * n_dev}x{seq} tokens) in "
                f"{secs:.1f}s: compiler peak {peak / GiB:.2f} GiB"
                + (f" of {limit_bytes / GiB:.2f} GiB" if limit_bytes else ""))
            if limit_bytes is not None and peak + HEADROOM_BYTES > limit_bytes:
                fits = False
                break
        if fits:
            return cand
    raise RuntimeError("no training candidate leaves "
                       f"{HEADROOM_BYTES / GiB:.0f} GiB of device memory free")


def run_training(flags: list[str], cand: dict, steps: int, seed: int, out: pathlib.Path,
                 tag: str):
    """One ``train.main`` run; returns its ``TrainResult``."""
    from repro.launch import train

    argv = flags + [
        "--optimizer", cand["optimizer"], "--batch", str(cand["batch"]),
        "--seq", str(cand["seq"]), "--steps", str(steps), "--seed", str(seed),
        "--max-restarts", "0", "--replan-every", "0",
        "--ckpt-dir", str(out / f"ckpt_{tag}"),
    ]
    log(f"train.main {' '.join(argv)}")
    t0 = time.perf_counter()
    res = train.main(argv)
    log(f"{tag}: {len(res.losses)} steps in {time.perf_counter() - t0:.1f}s "
        f"(compile included), losses {res.losses}")
    return res


def compare_losses(a: list[float], b: list[float], steps: int) -> float:
    """Max relative loss gap; raises unless both runs are finite, full
    length, and within ``LOSS_RTOL``."""
    if len(a) != steps or len(b) != steps:
        raise AssertionError(f"expected {steps} losses, got {len(a)} and {len(b)}")
    if not all(math.isfinite(x) for x in a + b):
        raise AssertionError(f"non-finite loss: {a} / {b}")
    gap = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    if gap > LOSS_RTOL:
        raise AssertionError(f"losses differ by {gap:.3e} > {LOSS_RTOL:.3e}: {a} vs {b}")
    return gap


def train_phase(model: list[str], candidates, steps: int, seed: int,
                out: pathlib.Path, *, four_chips: bool = False) -> dict:
    """Train under the system's configuration and its comparison; on one
    chip the comparison is post-backward issue, on four the WFBP policy
    with post-backward issue.  On one chip both sides run the same arena
    wire, so their losses agree whatever the wire does to the gradients:
    the comparison checks the issue order, not the wire."""
    import jax

    ours = ["--policy", "mg_wfbp", "--issue-order", "dag"]
    other = ["--policy", "wfbp" if four_chips else "mg_wfbp", "--issue-order", "post"]
    n_dev = jax.device_count()
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    cand = choose_train_config({"ours": model + ours, "other": model + other},
                               candidates, n_dev, limit)
    log(f"chosen: {cand['optimizer']}, {cand['batch']}x{cand['seq']} = "
        f"{cand['batch'] * cand['seq']} tokens per step over {n_dev} device(s)")
    res = run_training(model + ours, cand, steps, seed, out, "ours")
    ours_losses = res.losses
    report = {"config": cand, "losses_ours": ours_losses}
    replica_bytes = sum(x.nbytes for x in jax.tree.leaves(res.final.params))
    if four_chips:
        report.update(check_replicas(res.final.params))
    del res
    other_res = run_training(model + other, cand, steps, seed, out, "other")
    report["losses_other"] = other_res.losses
    del other_res
    report["loss_rel_gap"] = compare_losses(ours_losses, report["losses_other"], steps)
    report["loss_rtol"] = LOSS_RTOL
    log(f"losses agree: max relative gap {report['loss_rel_gap']:.3e} <= {LOSS_RTOL:.3e}")
    peaks = report["peak_bytes_in_use"] = peak_bytes_in_use()
    if four_chips and any(p is not None and p < replica_bytes for p in peaks):
        raise AssertionError(f"a device peaked below one replica ({replica_bytes} B): {peaks}")
    return report


def check_replicas(params) -> dict:
    """Every leaf has one full copy per device, all bit-identical."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    n_leaves = 0
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        if sorted(s.device.id for s in shards) != sorted(d.id for d in devices):
            raise AssertionError(f"leaf {leaf.shape} is not on every device: "
                                 f"{[s.device for s in shards]}")
        first = shards[0]
        for s in shards:
            if s.data.shape != leaf.shape:
                raise AssertionError(f"shard {s.data.shape} of {leaf.shape} is not a replica")
            if s is not first and not bool(
                jnp.array_equal(jax.device_put(s.data, first.device), first.data)
            ):
                raise AssertionError(f"replica on {s.device} differs from {first.device}")
        n_leaves += 1
    log(f"params: {n_leaves} leaves, one bit-identical replica on each of "
        f"{len(devices)} devices")
    return {"replica_leaves": n_leaves, "replica_devices": len(devices)}


def peak_bytes_in_use() -> list[int | None]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    log("peak_bytes_in_use per device: "
        + ", ".join("n/a" if p is None else f"{p / GiB:.2f} GiB" for p in peaks))
    return peaks


def serve_run(model: list[str], seed: int, extra: list[str]) -> tuple[dict, float]:
    """One ``serve.main`` run: tokens by request id, and its wall time."""
    from repro.launch import serve

    argv = model + [
        "--slots", str(SERVE_SLOTS), "--requests", str(SERVE_REQUESTS),
        "--prompt-len", str(SERVE_PROMPT), "--tokens", str(SERVE_TOKENS),
        "--seed", str(seed),
    ] + extra
    log(f"serve.main {' '.join(argv)}")
    t0 = time.perf_counter()
    done = serve.main(argv)
    wall = time.perf_counter() - t0
    tokens = {r.rid: [int(t) for t in r.generated] for r in done}
    short = {rid: len(t) for rid, t in tokens.items() if len(t) != SERVE_TOKENS}
    if len(tokens) != SERVE_REQUESTS or short:
        raise AssertionError(f"{len(tokens)}/{SERVE_REQUESTS} requests completed; "
                             f"short ones: {short}")
    n = sum(len(t) for t in tokens.values())
    log(f"served {len(tokens)} requests, {n} tokens, {wall:.1f}s wall (compile included)")
    return tokens, wall


def serve_phase(model: list[str], seed: int, *, four_chips: bool = False) -> dict:
    """Unsharded decode; on four chips also TP=4 decode, token-identical."""
    tokens, wall = serve_run(model, seed, [])
    report = {"tokens": sum(map(len, tokens.values())), "wall_s": wall}
    if four_chips:
        tp, tp_wall = serve_run(model, seed, ["--sharded", "--virtual-tp", "4"])
        if tp != tokens:
            raise AssertionError("TP=4 decode tokens differ from unsharded decode")
        log("TP=4 decode tokens identical to unsharded decode")
        report.update(tp_tokens_identical=True, tp_wall_s=tp_wall)
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (DP=4 train, TP=4 decode)")
    ap.add_argument("--seed", type=int, default=0, help="seed of weights, data and prompts")
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for the JSON report and checkpoints")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH[1])
    att = cfg.attention
    log(f"model: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff {cfg.d_ff}, "
        f"{att.n_heads} heads (kv {att.n_kv_heads}, head_dim {att.head_dim}), "
        f"vocab {cfg.vocab}, params {jax.numpy.dtype(cfg.param_dtype).name}")
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    report = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    report["train"] = train_phase(ARCH, TRAIN_CANDIDATES, STEPS, args.seed, out,
                                  four_chips=args.four_chips)
    if not report["train"]["config"]["compiles"]["ours"]["tpu_custom_call"]:
        raise AssertionError("the arena train step holds no Pallas kernel (tpu_custom_call)")
    report["serve"] = serve_phase(ARCH, args.seed, four_chips=args.four_chips)
    (out / ("report_4chips.json" if args.four_chips else "report.json")).write_text(
        json.dumps(report, indent=1))
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
