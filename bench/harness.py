"""One run of one training cell: set-up, measured window, optional
device trace, and the comparison with the plain reference.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the cell's configuration file (``configs[].file``), which names the
  program's architecture (``arch``) and the family module of its plain
  reference (``reference``, a module of ``bench/refs``);
* its traffic mix, ``bench/traffic/<traffic>.json``;
* its own file, ``bench/cells/<cell>.json``: the launcher flags of the
  system under test, the learning rate, and the limits of ``correct``;
* each per-layer metric's reader, ``bench/metrics/<metric>.py``.

The step is built through the program's normal path
(``repro.launch.train.setup`` -> ``TrainSetup.engine`` ->
``TrainSetup.train_step``), lowered and compiled ahead of time, and
called as that executable.  Re-planning, comm refits, autotuning and
checkpoints stay off: they would recompile or stall inside the window.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from collections import deque

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Steps that set-up drives and the reference repeats.
CHECK_STEPS = 3
#: Steps in flight before the host waits: an input pipeline runs ahead
#: of the device by about this much.
IN_FLIGHT = 2
#: Traced steady steps of a ``--trace 1`` run.
TRACE_STEPS = 4


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict
    metrics: list[dict]
    root: pathlib.Path

    @property
    def family(self):
        return importlib.import_module(f"bench.refs.{self.config['reference']}")

    @property
    def rows(self) -> int:
        return int(self.traffic["rows_per_chip"]) * self.chips


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in manifest["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}") from None
    c = next(c for c in manifest["configs"] if c["name"] == w["config"])
    metrics = [m for m in manifest["per_layer"] if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / c["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        spec=json.loads((root / "bench" / "cells" / f"{name}.json").read_text()),
        metrics=metrics, root=root,
    )


def load_reader(root: pathlib.Path, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def wire_itemsize(launcher: list[str]) -> int:
    """Bytes per element on the gradient wire under the launcher flags."""
    if "--comm-dtype" in launcher and launcher[launcher.index("--comm-dtype") + 1] == "bf16":
        return 2
    return 4


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, also past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _check_layout(params, specs) -> None:
    import jax

    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), specs)
    if got != want:
        raise ValueError(f"the benchmark's weights do not match the program's layout:\n"
                         f"{got}\n!=\n{want}")


def replica_mismatch(params) -> int:
    """Leaves whose copies on the devices are not bit-identical (compared
    as raw bits, so that equal NaNs count as equal)."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))

    bad = 0
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        first = bits(shards[0].data)
        for s in shards[1:]:
            other = bits(jax.device_put(s.data, shards[0].device))
            if other.shape != first.shape or not bool(jnp.array_equal(other, first)):
                bad += 1
                break
    return bad


@dataclasses.dataclass
class Built:
    """A cell's compiled train step and what it runs on."""

    mesh: object
    by_row: object  # sharding of a batch: rows split over the chips
    init: object  # jitted: seed key -> the benchmark's weights, replicated
    opt_init: object
    compiled: object
    memory: object  # the compiled step's memory analysis
    groups: int
    compile_s: float


def build(cell: Cell, devices) -> Built:
    """Compile ``cell``'s train step through the program's normal path."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.specs import param_specs

    enable_compile_cache()
    cfg, fam, chips = cell.config, cell.family, cell.chips
    seq, rows = int(cell.traffic["seq"]), cell.rows
    argv = ["--arch", cfg["arch"], "--batch", str(rows), "--seq", str(seq),
            "--lr", str(cell.spec["lr"]), "--replan-every", "0", *cell.spec["launcher"]]
    ts = train.setup(train.parse_args(argv))
    mesh = Mesh(np.array(devices[:chips]).reshape(chips, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ts = dataclasses.replace(ts, cfg=fam.program_config(cfg, ts.cfg), mesh=mesh)
    eng = ts.engine()
    step = ts.train_step(eng)
    rep, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("data", None))
    init = jax.jit(functools.partial(fam.init, cfg), out_shardings=rep)
    specs = jax.eval_shape(init, seed_key(0))
    _check_layout(specs, param_specs(ts.cfg))
    opt_init = jax.jit(ts.opt.init, out_shardings=rep)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), t)
    rows_spec = jax.ShapeDtypeStruct((rows, seq), np.int32, sharding=by_row)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = step.lower(on(specs), on(jax.eval_shape(opt_init, specs)),
                              {"tokens": rows_spec, "targets": rows_spec}).compile()
    b = Built(mesh=mesh, by_row=by_row, init=init, opt_init=opt_init, compiled=compiled,
              memory=compiled.memory_analysis(), groups=len(eng.plan.schedule.groups),
              compile_s=time.perf_counter() - t0)
    _log(f"{cell.name}: {b.groups} groups, compile {b.compile_s:.1f}s, compiled peak "
         f"{b.memory.peak_memory_in_bytes / 2**30:.2f} GiB")
    return b


def first_steps(b: Built, cell: Cell, seed: int):
    """Draw the weights from ``seed`` and drive the compiled step through
    its first ``CHECK_STEPS`` steps, with the window's own call and feed.
    Returns ``(readings, params, opt_state, feed)``: the program's losses,
    first-step changes over the learning rate and change over the
    steps, the state to go on from, and the batch feed (step -> batch)."""
    import jax

    from bench import reference
    from bench.traffic.zipf import ZipfBatches

    lr = float(cell.spec["lr"])
    gen = ZipfBatches(cell.traffic, cell.config["vocab_size"], cell.rows, seed)

    def feed(i):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return jax.device_put(gen.batch_at(i), b.by_row)

    key = seed_key(seed)
    params = b.init(key)
    opt_state = b.opt_init(params)
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        params, opt_state, m = b.compiled(params, opt_state, feed(i))
        prog["losses"].append(float(m["loss"]))
        if i == 0:
            first = reference.change_norms(params, b.init(key))
            prog["grad"] = {k: v / lr for k, v in first.items()}
    prog["update"] = reference.change_norms(params, b.init(key))
    return prog, params, opt_state, feed


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float) -> dict:
    """One run; returns the result line's object."""
    import jax

    from bench import correct

    cfg, fam, chips = cell.config, cell.family, cell.chips
    seq, rows = int(cell.traffic["seq"]), cell.rows
    b = build(cell, devices)
    compiled, mem = b.compiled, b.memory
    prog, params, opt_state, feed = first_steps(b, cell, seed)
    setup_s = time.perf_counter() - t_start

    def window(first: int, n_steps: int | None, secs: float):
        nonlocal params, opt_state
        pending = deque()
        n, start = 0, time.perf_counter()
        while True:
            b = feed(first + n)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt_state, m = compiled(params, opt_state, b)
            pending.append(m["loss"])
            n += 1
            if len(pending) > IN_FLIGHT:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    pending.popleft().block_until_ready()
            if (n >= n_steps) if n_steps else (time.perf_counter() - start >= secs):
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((params, opt_state, list(pending)))
        return n, time.perf_counter() - start

    tokens_per_step = rows * seq
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": chips}
    out = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}, "device": device}
    if trace:
        from bench import trace as tr

        window(CHECK_STEPS, IN_FLIGHT + 1, 0.0)  # steady: the pipeline is full
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tdir)
            n, elapsed = window(CHECK_STEPS + IN_FLIGHT + 1, TRACE_STEPS, 0.0)
            jax.profiler.stop_trace()
            red = tr.reduce_dir(tdir, [d.id for d in devices[:chips]])
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        out["attempted"] = n
        ctx = {"trace": red, "steps": n, "window_s": elapsed, "tokens": n * tokens_per_step,
               "chips": chips, "kind": devices[0].device_kind, "config": cfg, "seq": seq,
               "family": fam, "wire_bytes": wire_itemsize(cell.spec["launcher"])}
        for m in cell.metrics:
            v = load_reader(cell.root, m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = red.breakdown()
    else:
        n, elapsed = window(CHECK_STEPS, None, seconds)
        out["attempted"] = n
        out["metrics"] = {
            "train_tokens_per_s": {"value": n * tokens_per_step / elapsed, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    _log(f"{cell.name}: {n} steps in {elapsed:.3f}s, set-up {setup_s:.1f}s")

    if chips > 1:
        prog["replica_mismatch"] = replica_mismatch(params)
    stats = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[:chips]]
    device["memory_peak_bytes"] = int(max([mem.peak_memory_in_bytes, *stats]))
    del params, opt_state, compiled, b.compiled
    gc.collect()

    ref = reference_readings(cell, seed, b)
    ok, rows_ = correct.judge(correct.numbers(prog, ref), cell.spec["limits"])
    out["correct"] = ok
    out["checks"] = rows_
    for name, v, lim in rows_:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    return out


def reference_readings(cell: Cell, seed: int, b: Built, mode: str = "reference") -> dict:
    """The reference's readings: its losses, first-step changes over the
    learning rate, and change over ``CHECK_STEPS`` steps.  ``mode`` plants a
    fault or the control in the reference: ``control`` (one precision
    lower), ``half_batch`` (the loss over half of the batch) or
    ``local_grad`` (device 0's rows alone: the exchange left out)."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from bench.traffic.zipf import ZipfBatches

    lr = float(cell.spec["lr"])
    ref = reference.Reference(cell.family, cell.config, lowp=(mode == "control"))
    gen = ZipfBatches(cell.traffic, cell.config["vocab_size"], cell.rows, seed)
    key = seed_key(seed)
    out = {"losses": []}
    params = b.init(key)
    for i in range(CHECK_STEPS):
        batch = gen.batch_at(i)
        w = jnp.ones(batch["targets"].shape, jnp.float32)
        if mode == "half_batch":
            R, S = w.shape
            w = w.at[R // 2:].set(0.0) if R > 1 else w.at[:, S // 2:].set(0.0)
        if mode == "local_grad":
            w = w.at[gen.rows // cell.chips:].set(0.0)
        params, loss = ref.step(params, jax.device_put({**batch, "weights": w}, b.by_row), lr)
        out["losses"].append(loss)
        if i == 0:
            first = reference.change_norms(params, b.init(key))
            out["grad"] = {k: v / lr for k, v in first.items()}
    out["update"] = reference.change_norms(params, b.init(key))
    return out


def main(argv: list[str] | None = None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    from bench.peaks import PEAKS

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or dev.device_kind not in PEAKS:
        print(f"bench: needs a TPU in the peak table, JAX found {dev.platform} "
              f"{dev.device_kind!r}", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, t_start)
    checks = out.pop("checks")
    out["checks"] = checks  # last key of the line
    print(json.dumps(out))
    return 0
