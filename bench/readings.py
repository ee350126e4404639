"""The readings that a cell's ``correct`` limits are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,...,12 \
        --fault-seeds 1,2,3 [--out <file.jsonl>]

One process, one compile: for every seed, the program's first steps
against the reference (the sound runs, which set each number's lower
reading); for every fault seed, the control (the reference one
precision lower, in the program's place) and the faults a training cell
can have, planted in the reference: half of the batch left out and, on
several chips, the exchange between chips left out (device 0's rows
alone).  A state left unchanged reads 1 on ``update_gap`` by
construction and needs no run.  Each reading is one JSON line with the
compared numbers and the raw readings they come from.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import correct, harness  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    b = harness.build(cell, devices)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    refs = {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        prog, params, opt_state, _ = harness.first_steps(b, cell, seed)
        if cell.chips > 1:
            prog["replica_mismatch"] = harness.replica_mismatch(params)
        del params, opt_state
        t1 = time.perf_counter()
        refs[seed] = ref = harness.reference_readings(cell, seed, b)
        t2 = time.perf_counter()
        emit({"cell": cell.name, "kind": "program", "seed": seed, "lr": cell.spec["lr"],
              "numbers": correct.numbers(prog, ref), "got": prog, "reference": ref,
              "program_s": t1 - t0, "reference_s": t2 - t1})
    modes = ["control", "half_batch"] + (["local_grad"] if cell.chips > 1 else [])
    for seed in [int(s) for s in args.fault_seeds.split(",") if s]:
        ref = refs.get(seed) or harness.reference_readings(cell, seed, b)
        for mode in modes:
            t0 = time.perf_counter()
            got = harness.reference_readings(cell, seed, b, mode=mode)
            emit({"cell": cell.name, "kind": mode, "seed": seed, "lr": cell.spec["lr"],
                  "numbers": correct.numbers(got, ref), "got": got, "reference": ref,
                  "seconds": time.perf_counter() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
