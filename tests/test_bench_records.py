"""Committed BENCH record hygiene.

Every ``benchmarks/results/BENCH_*.json`` must round-trip byte-identically
through the writer's serialization (``json.dumps(..., indent=1,
sort_keys=True)`` + trailing newline) — so re-running a suite that
produces the same numbers yields a zero diff, and nobody hand-edits a
record into a shape the writer would immediately rewrite.

``BENCH_overlap.json`` additionally carries the tentpole claim and is
pinned structurally: the dag issue order starts its group all-reduces
inside backward, the post order fewer of them, each order compiles one
all-reduce per group, and the two are bit-identical in loss.
"""

import json
import pathlib

import pytest

RESULTS = pathlib.Path(__file__).parent.parent / "benchmarks" / "results"
BENCH_FILES = sorted(RESULTS.glob("BENCH_*.json"))


def test_some_records_committed():
    assert len(BENCH_FILES) >= 9, BENCH_FILES


@pytest.mark.parametrize("path", BENCH_FILES, ids=lambda p: p.stem)
def test_roundtrips_byte_identically(path):
    raw = path.read_text()
    rec = json.loads(raw)
    # suites publish either one record dict or a list of row dicts
    assert isinstance(rec, (dict, list)) and rec, path
    assert raw == json.dumps(rec, indent=1, sort_keys=True) + "\n", (
        f"{path.name} is not in the writer's canonical serialization; "
        f"regenerate it through benchmarks.run.write_bench"
    )


def test_overlap_record_claims():
    rec = json.loads((RESULTS / "BENCH_overlap.json").read_text())
    for key in ("arch", "policy", "fuse", "n_groups", "n_devices",
                "group_wire_bytes", "post", "dag", "loss_bit_identical"):
        assert key in rec, key
    assert rec["loss_bit_identical"] is True
    assert len(rec["group_wire_bytes"]) == rec["n_groups"]
    for issue in ("post", "dag"):
        side = rec[issue]
        assert side["n_comm_spans"] == rec["n_groups"] * rec["n_devices"]
        assert side["total_comm_us"] > 0
        assert rec["n_groups"] <= side["allreduce_ops"] <= rec["n_groups"] + 4
    # the tentpole: dag hides wire inside backward, post less of it (not
    # none: XLA:CPU starts the head's group once its gradient is ready)
    assert rec["dag"]["overlap_fraction"] > 0
    assert rec["dag"]["n_overlapped_starts"] > 0
    assert rec["post"]["n_overlapped_starts"] < rec["dag"]["n_overlapped_starts"]
