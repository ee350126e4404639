"""Golden-trace regression suite for the span/overlap pipeline.

``tests/data/overlap_trace.json`` is a committed Chrome trace recorded by
a real ``--dryrun 2 --issue-order dag`` launcher run (tinyllama-1.1b
reduced, wfbp policy, fuse=arena, 8 virtual devices).  The suite pins:

  * span parsing (dict / JSON string / path / gzip round-trips);
  * ``wfbp_group{gi}_l{lo}_{hi}`` attribution: group indices, layer
    ranges, per-device counts;
  * per-group wire bytes in the trace == ``sync.group_wire_bytes`` of
    the same (arch, policy, fuse) rebuilt from the planning stack — the
    trace's payload accounting must stay tied to the arena layout;
  * the overlap-report arithmetic, to the float (the fixture is static,
    so the report is a pure function with golden outputs).

The device-trace path that now produces such spans
(``profiler.scope_spans``) is pinned by ``tests/test_trace_scopes.py``.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib

import pytest

import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core import stacked_lm_layout
from repro.core.comm_model import AllReduceModel
from repro.core.profiler import (
    GROUP_SPAN_RE,
    overlap_report,
    parse_trace_spans,
)
from repro.core.sync import SyncConfig, make_gradient_sync
from repro.launch.specs import param_specs
from repro.planning import build_schedule

FIXTURE = pathlib.Path(__file__).parent / "data" / "overlap_trace.json"

# The run that recorded the fixture: 6 wfbp groups on 8 data shards.
N_DEVICES = 8
N_GROUPS = 6
GROUP_BYTES = [131584, 738304, 738304, 738304, 738304, 131072]


@pytest.fixture(scope="module")
def spans():
    return parse_trace_spans(FIXTURE)


class TestParsing:
    def test_all_input_forms_agree(self, spans, tmp_path):
        raw = FIXTURE.read_text()
        assert parse_trace_spans(json.loads(raw)) == spans  # dict
        assert parse_trace_spans(raw) == spans  # JSON string
        gz = tmp_path / "trace.json.gz"
        gz.write_bytes(gzip.compress(raw.encode()))
        assert parse_trace_spans(gz) == spans  # gzip path

    def test_span_population(self, spans):
        comm = [s for s in spans if GROUP_SPAN_RE.match(s.name)]
        bwd = [s for s in spans if s.name.startswith("bwd_")]
        assert len(spans) == 96
        assert len(comm) == N_DEVICES * N_GROUPS == 48
        assert len(bwd) == 48
        assert {s.device for s in spans} == set(range(N_DEVICES))
        assert all(s.dur_us > 0 for s in spans)

    def test_group_attribution(self, spans):
        """wfbp groups issue in backward order: group 0 is layers (6,6),
        group 5 is layers (1,1) — every device agrees."""
        for s in spans:
            m = GROUP_SPAN_RE.match(s.name)
            if not m:
                continue
            gi, lo, hi = int(m.group(1)), int(m.group(2)), int(m.group(3))
            assert (lo, hi) == (N_GROUPS - gi, N_GROUPS - gi), s.name
            assert int(s.args["bytes"]) == GROUP_BYTES[gi], s.name


class TestWireBytes:
    def test_trace_bytes_match_arena_layout(self, spans):
        """The bytes each span carries must equal the group's arena wire
        bytes rebuilt from the same (arch, policy, fuse) planning path."""
        cfg = dataclasses.replace(
            get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32
        )
        shapes = param_specs(cfg)
        layout = stacked_lm_layout(shapes, cfg.n_stages)
        costs = layout.layer_costs(8 * 64 // 8, None)
        sched = build_schedule("wfbp", costs, AllReduceModel(a=5e-5, b=1e-9))
        sync = make_gradient_sync(
            layout, sched, ("data",), SyncConfig(fuse="arena")
        )
        assert list(sync.group_wire_bytes) == GROUP_BYTES
        for s in spans:
            m = GROUP_SPAN_RE.match(s.name)
            if m:
                assert int(s.args["bytes"]) == sync.group_wire_bytes[int(m.group(1))]


class TestOverlapReport:
    def test_golden_numbers(self, spans):
        rep = overlap_report(spans)
        assert rep["n_devices"] == N_DEVICES
        assert rep["n_comm_spans"] == 48
        assert rep["n_bwd_spans"] == 48
        assert rep["n_overlapped_starts"] == 40
        assert rep["total_comm_us"] == pytest.approx(42091.329, abs=1e-6)
        assert rep["windowed_comm_us"] == pytest.approx(18515.952991, abs=1e-5)
        assert rep["overlap_fraction"] == pytest.approx(0.4398994622, abs=1e-9)
        # serial CPU backend: comm executes in the gaps between backward
        # segments, so strict concurrency is honestly zero
        assert rep["hidden_comm_us"] == 0.0
        assert rep["hidden_fraction"] == 0.0

    def test_group_rows(self, spans):
        rep = overlap_report(spans)
        # one steady-state step x 6 groups on the first device (the
        # dryrun drops the warm-up/compile step's spans)
        assert len(rep["groups"]) == 6
        assert [g["group"] for g in rep["groups"]] == sorted(
            g["group"] for g in rep["groups"]
        )
        for g in rep["groups"]:
            assert g["layers"] == [N_GROUPS - g["group"]] * 2
            assert g["bytes"] == GROUP_BYTES[g["group"]]
            # trace durations are rounded to 3 decimals; allow that slack
            assert g["window_us"] <= g["dur_us"] + 1e-3
        # at least one non-final group demonstrably starts inside backward
        assert any(
            g["starts_before_bwd_end"] for g in rep["groups"] if g["group"] < N_GROUPS - 1
        )

    def test_empty_trace_reports_zeros(self):
        rep = overlap_report([])
        assert rep["n_comm_spans"] == 0
        assert rep["overlap_fraction"] == 0.0
        assert rep["groups"] == []
