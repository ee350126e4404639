"""The train step's name scopes, read back from a ``jax.profiler`` trace.

A tiny ``starcoder2-3b --reduced`` step under both issue orders, built
through the launcher's own path and traced on the CPU:

  * every ``fwd_*``, ``bwd_*``, ``optimizer``, ``attention`` and
    ``wfbp_group*`` scope reaches the compiled module's ``op_name``
    metadata, under the compile-cache helper's settings too;
  * the remat recompute sits under its segment's ``bwd_seg{j}``;
  * ``profiler.spans_from_ops`` joins traced ops to those scopes (a
    chip-form module with an unnamed fusion, an asynchronous copy and an
    asynchronous all-reduce), a group's span being its all-reduce;
  * ``profiler.layer_split`` puts each traced op down to its layer (wire,
    remat, backward, forward, optimizer, unscoped; attention and the MoE
    layer across them), by hand and on a traced step, the layers adding
    up to the busy time;
  * an MoE step (reduced SDAR, reduced mixtral) names the MoE layer's
    parts and counts the rows routed to its held experts;
  * ``launch/train.py --dryrun`` on four virtual devices prints the
    overlap report of the trace it writes under ``--trace-out``: the DAG
    step's group all-reduces start inside backward, more of them than
    the post order's, and the layer split of that trace.
"""

from __future__ import annotations

import glob
import json
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from _env import REPO_ROOT, SUBPROC_ENV

from repro import scopes
from repro.core import profiler
from repro.launch import train
from repro.models.transformer import init_params

ARGV = ["--arch", "starcoder2-3b", "--reduced", "--batch", "2", "--seq", "64",
        "--policy", "wfbp", "--fuse", "arena", "--optimizer", "sgd", "--replan-every", "0"]


def build(issue: str):
    """(setup, engine, compiled step, step inputs) of one issue order."""
    ts = train.setup(train.parse_args(ARGV + ["--issue-order", issue]))
    eng = ts.engine()
    params = init_params(jax.random.PRNGKey(0), ts.cfg)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (2, 64), 0, ts.cfg.vocab),
             "targets": jax.random.randint(key, (2, 64), 0, ts.cfg.vocab)}
    with jax.set_mesh(ts.mesh):
        compiled = ts.train_step(eng).lower(params, ts.opt.init(params), batch).compile()
    return ts, eng, compiled, (params, batch)


@pytest.fixture(scope="module")
def dag():
    return build("dag")


@pytest.fixture(scope="module")
def post():
    return build("post")


def op_names(compiled) -> set[str]:
    """The step's scope paths (a reduction's own body carries a partial
    path, without the ``jit(...)`` root: left out)."""
    return {n for n in profiler.hlo_op_names(compiled.as_text()).values()
            if n.startswith("jit(")}


def is_attention(component: str) -> bool:
    return re.fullmatch(r"(\w*\()*attention\)*", component) is not None


def components(names) -> set[str]:
    return {c for n in names for c in n.split("/")}


def test_dag_step_carries_every_scope(dag):
    ts, eng, compiled, _ = dag
    parts = components(op_names(compiled))
    n_seg = len(eng.segments)
    assert n_seg > 1
    want = {scopes.FWD_EMBED, scopes.FWD_HEAD, scopes.BWD_HEAD, scopes.BWD_EMBED,
            scopes.OPTIMIZER}
    want |= {scopes.fwd_seg(j) for j in range(n_seg)}
    want |= {scopes.bwd_seg(j) for j in range(n_seg)}
    want |= {f"wfbp_group{gi}_l{lo}_{hi}" for gi, (lo, hi) in enumerate(eng.sync.group_spans)}
    assert want <= parts, want - parts
    # attention inside the forward segments and their backward
    att = [n for n in op_names(compiled) if any(is_attention(c) for c in n.split("/"))]
    for j in range(n_seg):
        assert any(f"/{scopes.fwd_seg(j)}/" in n for n in att)
        assert any(f"/{scopes.bwd_seg(j)}/" in n for n in att)
    assert not ts.cfg.tail_pattern  # starcoder2 has no tail: no fwd_tail / bwd_tail
    # the pullbacks' ops are transposed forward ops under their bwd_* scope
    assert any(re.search(r"/bwd_seg0/transpose\(", n) for n in op_names(compiled))


def test_remat_recompute_sits_under_its_backward_segment(dag):
    names = op_names(dag[2])
    remat = [n for n in names if "rematted_computation" in n.split("/")]
    assert remat
    assert all(any(c.startswith(profiler.BWD_SPAN_PREFIX) for c in n.split("/")) for n in remat)
    assert any("attention" in n.split("/") for n in remat)


def test_post_step_carries_forward_optimizer_and_attention(post):
    parts = components(op_names(post[2]))
    assert {"jvp(fwd_model)", "transpose(jvp(fwd_model))", scopes.OPTIMIZER} <= parts
    assert any(is_attention(p) for p in parts)
    assert not any(p.startswith(profiler.BWD_SPAN_PREFIX) for p in parts)


def test_scopes_survive_the_compile_cache_settings(monkeypatch):
    """The helper keeps one frame of traceback in locations: op_name keeps
    the scope path (with tracebacks off, XLA would drop it)."""
    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was_dir = jax.config.jax_compilation_cache_dir
    was_tb = jax.config.jax_traceback_in_locations_limit
    was_key = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        compile_cache.enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", was_dir)  # keep this compile out

        def f(x):
            with jax.named_scope(scopes.OPTIMIZER):
                return jnp.tanh(x) * 2

        names = profiler.hlo_op_names(jax.jit(f).lower(jnp.ones(8)).compile().as_text())
        assert any(scopes.OPTIMIZER in n.split("/") for n in names.values()), names
    finally:
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update("jax_traceback_in_locations_limit", was_tb)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", was_key)


def test_span_scope_rules():
    assert profiler.span_scope("jit(body)/shard_map/bwd_seg2/transpose(jvp(fwd_seg2))/dot") \
        == "bwd_seg2"
    assert profiler.span_scope("jit(body)/shard_map/wfbp_group3_l4_5/psum", allreduce=True) \
        == "wfbp_group3_l4_5"
    # a group's pack and unpack are not its time on the wire
    assert profiler.span_scope("jit(body)/shard_map/wfbp_group3_l4_5/comm_pack_pack") is None
    assert profiler.span_scope("jit(body)/shard_map/psum", allreduce=True) is None  # the loss
    assert profiler.span_scope("jit(body)/shard_map/transpose(jvp(fwd_model))/mul") == "bwd_model"
    assert profiler.span_scope("jit(body)/shard_map/fwd_seg0/jvp(attention)/exp") is None
    assert profiler.span_scope("jit(body)/shard_map/optimizer/sub") is None
    assert profiler.span_scope("") is None


#: A compiled module in the chip's form: a fusion whose line has no
#: metadata (its scope sits in the computation it calls), an asynchronous
#: copy of a scoped value, and an asynchronous all-reduce between a group's
#: pack and unpack.
HLO = """\
HloModule jit_body, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(body)/shard_map/bwd_seg1/transpose(jvp(fwd_seg1))/mul"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.7)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %pack.1 = f32[8]{0} custom-call(%copy-done.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/wfbp_group0_l1_2/comm_pack_pack"}
  %all-reduce-start.4 = f32[8]{0} all-reduce-start(%pack.1), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(body)/shard_map/wfbp_group0_l1_2/psum"}
  %all-reduce-done.4 = f32[8]{0} all-reduce-done(%all-reduce-start.4), metadata={op_name="jit(body)/shard_map/wfbp_group0_l1_2/psum"}
  ROOT %unpack.1 = f32[8]{0} custom-call(%all-reduce-done.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/wfbp_group0_l1_2/comm_pack_unpack"}
}
"""


def test_hlo_op_names_resolves_unnamed_fusions_and_copies():
    names = profiler.hlo_op_names(HLO)
    bwd = "jit(body)/shard_map/bwd_seg1/transpose(jvp(fwd_seg1))/mul"
    assert names["fusion.7"] == bwd  # from the computation it calls
    assert names["copy-start.2"] == names["copy-done.2"] == bwd  # from the value it moves
    assert profiler.hlo_allreduces(HLO) == {"all-reduce-start.4", "all-reduce-done.4"}


def test_spans_from_ops_time_a_group_by_its_allreduce():
    # (device, run, start_ns, end_ns, instruction) as the trace gives them
    ops = []
    for dev in (0, 1):
        ops += [(dev, 0, 0, 1000, "fusion.7"), (dev, 0, 1000, 1500, "copy-start.2"),
                (dev, 0, 1500, 2000, "copy-done.2"), (dev, 0, 2000, 3000, "pack.1"),
                (dev, 0, 3000, 3100, "all-reduce-start.4"),
                (dev, 0, 5000, 6000 + dev, "all-reduce-done.4"), (dev, 0, 6000, 7000, "unpack.1")]
    spans = profiler.spans_from_ops(ops, HLO, group_bytes=(32,))
    by = {(s.name, s.device): s for s in spans}
    assert set(by) == {(n, d) for n in ("bwd_seg1", "wfbp_group0_l1_2") for d in (0, 1)}
    assert (by["bwd_seg1", 0].start_us, by["bwd_seg1", 0].end_us) == (0.0, 2.0)
    for dev in (0, 1):  # the wire alone: not the pack before it nor the unpack after
        g = by["wfbp_group0_l1_2", dev]
        assert (g.start_us, g.dur_us) == (3.0, 3.0 + dev / 1e3)
        assert g.args == {"step": 0, "bytes": 32}



#: A module with one op per layer: nested ``transpose(jvp(...))`` and
#: ``rematted_computation`` paths, XLA's ``.remat`` clone, an unnamed
#: fusion and copy, a group's pack and the loss's all-reduce.
LAYER_HLO = """\
HloModule jit_body, entry_computation_layout={()->()}

%fused_computation.7 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(f32[8]{0} %param_0), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp(attention)/exp" source_file="layers.py"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.7
  %dot.2 = f32[8]{0} dot(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp(fwd_seg0))/dot_general"}
  %copy-start.9 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %dot.2)
  %copy-done.9 = f32[8]{0} copy-done((f32[8]{0}, f32[8]{0}, u32[]) %copy-start.9)
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp(fwd_seg0))/jvp()/checkpoint/rematted_computation/attention/dot_general"}
  %fusion.3.remat = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp(fwd_seg0))/transpose(jvp(attention))/mul"}
  %sub.4 = f32[8]{0} subtract(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(body)/shard_map/optimizer/sub"}
  %copy.5 = f32[8]{0} copy(f32[8]{0} %p)
  %custom-call.6 = f32[24]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/wfbp_group0_l1_1/comm_pack_pack"}
  %all-reduce.10 = f32[] all-reduce(f32[] %p), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(body)/shard_map/psum"}
  ROOT %neg.8 = f32[8]{0} negate(f32[8]{0} %p), metadata={op_name="jit(body)/shard_map/jvp(fwd_model)/neg"}
}
"""


@pytest.mark.parametrize("inst,want,attention", [
    ("fusion.1", "forward", True),  # the scope of the computation it calls
    ("dot.2", "backward", False),
    ("fusion.3", "remat", True),
    ("fusion.3.remat", "backward", True),  # XLA's clone keeps its original's path
    ("sub.4", "optimizer", False),
    ("copy.5", "unscoped", False),  # a copy of a parameter: no scope to take
    ("copy-done.9", "backward", False),  # XLA's copy takes its operand's scope
    ("custom-call.6", "wire", False),  # a group's pack
    ("all-reduce.10", "wire", False),  # the loss's mean: an all-reduce outside any group
    ("neg.8", "forward", False),  # the post step's forward: jvp(fwd_model)
])
def test_each_op_gets_its_layer(inst, want, attention):
    path = profiler.hlo_op_names(LAYER_HLO).get(inst, "")
    allreduce = inst in profiler.hlo_allreduces(LAYER_HLO)
    assert profiler.op_layer(path, allreduce) == want
    assert profiler.is_attention(path) == attention


def layer_ops():
    """Device 0 runs one op per layer back to back (ns); device 1 runs
    three of them, the forward and backward side by side."""
    d0 = [("fusion.1", 0, 10), ("dot.2", 10, 30), ("fusion.3", 30, 35),
          ("fusion.3.remat", 35, 40), ("sub.4", 40, 42), ("copy.5", 42, 45),
          ("custom-call.6", 45, 50), ("neg.8", 50, 60)]
    d1 = [("fusion.1", 0, 20), ("dot.2", 5, 25), ("copy.5", 30, 40)]
    return ([(0, 0, s, e, n) for n, s, e in d0] + [(1, 0, s, e, n) for n, s, e in d1])


def test_layer_split_by_hand():
    got = profiler.layer_split(layer_ops(), LAYER_HLO)
    ms = 1e-6 / 2  # ns summed over two devices -> ms per step
    # device 1: forward 0..20 and backward 5..25 side by side: the overlap
    # goes to backward (first in LAYERS), forward keeps 0..5
    assert got["forward_ms"] == pytest.approx((10 + 10 + 5) * ms)
    assert got["backward_ms"] == pytest.approx((20 + 5 + 20) * ms)
    assert got["remat_ms"] == pytest.approx(5 * ms)
    assert got["optimizer_ms"] == pytest.approx(2 * ms)
    assert got["wire_ms"] == pytest.approx(5 * ms)
    assert got["unscoped_ms"] == pytest.approx((3 + 10) * ms)
    assert got["busy_ms"] == pytest.approx((60 + 35) * ms)
    assert sum(got[f"{k}_ms"] for k in profiler.LAYERS) == pytest.approx(got["busy_ms"])
    # attention: fusion.1, fusion.3, fusion.3.remat on device 0; fusion.1 on 1
    assert got["attention_ms"] == pytest.approx((10 + 5 + 5 + 20) * ms)


FLASH_HLO = """\
HloModule jit_body, entry_computation_layout={()->()}

ENTRY %main (p: bf16[24,512,128]) -> bf16[24,512,128] {
  %p = bf16[24,512,128]{2,1,0} parameter(0)
  %flash_fwd.1 = (bf16[24,512,128]{2,1,0}, f32[24,512,128]{2,1,0}) custom-call(bf16[24,512,128]{2,1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/attention/flash_fwd/pallas_call"}
  %transpose.2 = bf16[24,512,128]{2,1,0} transpose(bf16[24,512,128]{2,1,0} %p), dimensions={0,1,2}, metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/attention/transpose"}
  %flash_fwd.3 = (bf16[24,512,128]{2,1,0}, f32[24,512,128]{2,1,0}) custom-call(bf16[24,512,128]{2,1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/flash_fwd/pallas_call"}
  %flash_bwd.4 = (bf16[24,512,128]{2,1,0}, bf16[2,512,128]{2,1,0}, bf16[2,512,128]{2,1,0}) custom-call(bf16[24,512,128]{2,1,0} %p, bf16[2,128,512]{2,1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp())/while/body/closed_call/checkpoint/attention/flash_bwd/pallas_call"}
  ROOT %dot.6 = bf16[24,512,128]{2,1,0} dot(bf16[24,512,128]{2,1,0} %p, bf16[24,512,128]{2,1,0} %p), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/dot_general"}
}
"""


def test_layer_split_counts_the_flash_kernels():
    """``attention_flash_ms`` is the attention time inside the flash
    kernels, forward, recompute and backward; attention's other ops (the
    layout changes around them) count in ``attention_ms`` alone."""
    ops = [(0, 0, s, e, n) for n, s, e in [
        ("flash_fwd.1", 0, 10), ("transpose.2", 10, 12), ("dot.6", 12, 20),
        ("flash_fwd.3", 20, 30), ("flash_bwd.4", 30, 65)]]
    got = profiler.layer_split(ops, FLASH_HLO)
    ms = 1e-6
    assert got["attention_ms"] == pytest.approx((10 + 2 + 10 + 35) * ms)
    assert got["attention_flash_ms"] == pytest.approx((10 + 10 + 35) * ms)
    assert got["forward_ms"] == pytest.approx(20 * ms)
    assert got["remat_ms"] == pytest.approx(10 * ms)
    assert got["backward_ms"] == pytest.approx(35 * ms)


MOE_HLO = """\
HloModule jit_body, entry_computation_layout={()->()}

ENTRY %main (p: bf16[1024,256]) -> bf16[1024,256] {
  %p = bf16[1024,256]{1,0} parameter(0)
  %sort.1 = s32[1024]{0} sort(s32[1024]{0} %p), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/moe/moe_dispatch/sort"}
  %gather.2 = bf16[1024,256]{1,0} gather(bf16[1024,256]{1,0} %p), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/moe/moe_dispatch/jit(dispatch)/gather"}
  %moe_gmm.3 = bf16[1024,256]{1,0} custom-call(bf16[1024,256]{1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/moe/moe_experts/moe_gmm/pallas_call"}
  %moe_gmm.4 = bf16[1024,256]{1,0} custom-call(bf16[1024,256]{1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp())/while/body/closed_call/checkpoint/moe/moe_experts/moe_gmm/pallas_call"}
  %moe_tgmm.5 = bf16[16,256,256]{2,1,0} custom-call(bf16[1024,256]{1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp())/while/body/closed_call/checkpoint/moe/moe_experts/moe_tgmm/pallas_call"}
  %dot.7 = f32[1024,128]{1,0} dot(bf16[1024,256]{1,0} %p, bf16[1024,256]{1,0} %p), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/moe/moe_route/dot_general"}
  %moe_rows_sum.8 = bf16[1024,256]{1,0} custom-call(bf16[1024,256]{1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/moe/moe_combine/moe_rows_sum/pallas_call"}
  %moe_rows_gather.9 = bf16[1024,256]{1,0} custom-call(bf16[1024,256]{1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shard_map/bwd_seg0/transpose(jvp())/while/body/closed_call/checkpoint/moe/moe_combine/moe_rows_gather/pallas_call"}
  ROOT %dot.6 = bf16[1024,256]{1,0} dot(bf16[1024,256]{1,0} %p, bf16[1024,256]{1,0} %p), metadata={op_name="jit(body)/shard_map/fwd_seg0/jvp()/while/body/closed_call/dot_general"}
}
"""


def test_layer_split_counts_the_moe_layer_and_its_kernels():
    """``moe_ms`` is the time of every op under the ``moe`` scope, split
    by scope into routing, dispatch, experts and combine; ``moe_gmm_ms``
    its part inside the grouped-matmul kernels, ``moe_rows_ms`` inside the
    row kernels, forward and backward."""
    ops = [(0, 0, s, e, n) for n, s, e in [
        ("sort.1", 0, 4), ("gather.2", 4, 10), ("moe_gmm.3", 10, 30), ("dot.6", 30, 40),
        ("moe_gmm.4", 40, 60), ("moe_tgmm.5", 60, 85), ("dot.7", 85, 88),
        ("moe_rows_sum.8", 88, 95), ("moe_rows_gather.9", 95, 97)]]
    got = profiler.layer_split(ops, MOE_HLO)
    ms = 1e-6
    assert got["moe_ms"] == pytest.approx((4 + 6 + 20 + 20 + 25 + 3 + 7 + 2) * ms)
    assert got["moe_gmm_ms"] == pytest.approx((20 + 20 + 25) * ms)
    assert got["moe_rows_ms"] == pytest.approx((7 + 2) * ms)
    assert got["moe_route_ms"] == pytest.approx(3 * ms)
    assert got["moe_dispatch_ms"] == pytest.approx((4 + 6) * ms)
    assert got["moe_experts_ms"] == pytest.approx((20 + 20 + 25) * ms)
    assert got["moe_combine_ms"] == pytest.approx((7 + 2) * ms)
    assert got["attention_ms"] == 0
    assert got["forward_ms"] == pytest.approx((40 + 3 + 7) * ms)
    assert got["backward_ms"] == pytest.approx((45 + 2) * ms)


def moe_step(arch: str):
    """One DAG step of ``arch --reduced`` through the launcher: its
    compiled module and its metrics."""
    argv = ["--arch", arch, "--reduced", "--batch", "2", "--seq", "32", "--policy", "wfbp",
            "--fuse", "arena", "--optimizer", "sgd", "--replan-every", "0",
            "--issue-order", "dag"]
    ts = train.setup(train.parse_args(argv))
    params = init_params(jax.random.PRNGKey(0), ts.cfg)
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, ts.cfg.vocab),
             "targets": jax.random.randint(key, (2, 32), 0, ts.cfg.vocab)}
    with jax.set_mesh(ts.mesh):
        compiled = ts.train_step(ts.engine()).lower(params, ts.opt.init(params), batch).compile()
    _, _, metrics = compiled(params, ts.opt.init(params), batch)
    return ts.cfg, compiled, {k: float(v) for k, v in metrics.items()}


def test_moe_step_carries_the_moe_scopes_and_counts_its_rows():
    """The reduced SDAR step (16 of 128 experts, top-8) names the MoE
    layer's four parts; its metrics count the rows routed to the held
    experts over the layers and the largest load of one of them."""
    cfg, compiled, m = moe_step("sdar-30b-a3b")
    parts = {profiler._core(c) for c in components(op_names(compiled))}
    assert {scopes.MOE, scopes.MOE_ROUTE, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS,
            scopes.MOE_COMBINE} <= parts
    tokens, k = 2 * 32, cfg.moe.top_k
    assert 0 < m["moe_held_rows"] <= cfg.n_layers * tokens * k
    assert 0 < m["moe_max_expert_rows"] <= tokens


def test_moe_counters_of_a_model_holding_every_expert():
    """Holding all its experts, the reduced mixtral takes every token's
    top-2 rows in every layer; a dense model's step has no counters."""
    cfg, _, m = moe_step("mixtral-8x7b")
    assert m["moe_held_rows"] == cfg.n_layers * 2 * 32 * cfg.moe.top_k
    assert 2 * 32 * cfg.moe.top_k / cfg.moe.n_experts <= m["moe_max_expert_rows"] <= 2 * 32
    _, _, dense = moe_step("tinyllama-1.1b")
    assert set(dense) == {"loss"}


def test_layer_split_of_a_program_without_scopes_is_none():
    bare = "\n".join(line.split(", metadata=")[0] for line in LAYER_HLO.splitlines())
    assert profiler.layer_split(layer_ops(), bare) is None
    assert profiler.layer_split([], LAYER_HLO) is None


def test_traced_step_splits_into_layers(dag, tmp_path):
    """Two traced runs of the tiny DAG step on the CPU: every train-step
    layer and attention read, and the layers add up to the busy time."""
    ts, _, compiled, (params, batch) = dag
    params = jax.tree.map(jnp.copy, params)  # the step donates its state
    state = params, ts.opt.init(params)
    state = jax.block_until_ready(compiled(*state, batch)[:2])
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        state = jax.block_until_ready(compiled(*state, batch)[:2])
    jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    hlo = compiled.as_text()
    got = profiler.scope_layers(xplane, hlo)
    ops = profiler.trace_ops(xplane, re.search(r"^HloModule ([\w.\-]+)", hlo).group(1))
    runs = {(d, r) for d, r, *_ in ops}
    assert len(runs) == 2
    busy, end = 0.0, {}  # the union of each run's op intervals
    for d, r, s, e, _ in sorted(ops, key=lambda op: op[2]):
        busy += max(0, e - max(s, end.get((d, r), s)))
        end[(d, r)] = max(e, end.get((d, r), e))
    busy *= 1e-6 / len(runs)
    assert got["busy_ms"] == pytest.approx(busy, rel=1e-9)
    assert sum(got[f"{k}_ms"] for k in profiler.LAYERS) == pytest.approx(busy, rel=1e-9)
    for k in ("forward", "backward", "remat", "optimizer"):
        assert got[f"{k}_ms"] > 0, k
    assert 0 < got["attention_ms"] < got["forward_ms"] + got["backward_ms"] + got["remat_ms"]
    assert got["unscoped_ms"] < 0.5 * busy

DRYRUN = ["--arch", "starcoder2-3b", "--reduced", "--batch", "4", "--seq", "64",
          "--policy", "wfbp", "--fuse", "arena", "--optimizer", "sgd", "--replan-every", "0",
          "--dryrun", "2"]
N_DEV = 4


@pytest.fixture(scope="module")
def dryruns(tmp_path_factory):
    """``launch/train.py --dryrun`` of both issue orders on 4 virtual CPU
    devices (the group all-reduces exist only between replicas): each
    order's ``[dryrun]`` report line, its plan, and its trace directory."""
    tmp = tmp_path_factory.mktemp("dryrun")
    env = dict(SUBPROC_ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count={N_DEV}",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    procs = {
        issue: subprocess.Popen(
            [sys.executable, "-m", "repro.launch.train", *DRYRUN, "--issue-order", issue,
             "--trace-out", str(tmp / issue)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env, cwd=REPO_ROOT)
        for issue in ("dag", "post")
    }
    out = {}
    for issue, p in procs.items():
        stdout, stderr = p.communicate(timeout=600)
        assert p.returncode == 0, stderr[-3000:]
        lines = [ln for ln in stdout.splitlines() if ln.startswith("[dryrun] {")]
        (layers,) = [ln for ln in stdout.splitlines() if ln.startswith("[dryrun] layers ")]
        out[issue] = (json.loads(lines[-1][len("[dryrun] "):]), tmp / issue,
                      json.loads(layers[len("[dryrun] layers "):]))
    ts = train.setup(train.parse_args(ARGV + ["--issue-order", "dag"]))
    eng = ts.engine()
    return out, len(eng.schedule.groups), len(eng.segments)


def test_dag_overlap_from_the_device_trace(dryruns):
    out, n_groups, n_seg = dryruns
    rep = out["dag"][0]
    assert rep["n_devices"] == N_DEV
    # every group's all-reduce on every device, one backward span per event
    assert rep["comm_span_devices"] == {str(g): list(range(N_DEV)) for g in range(n_groups)}
    assert rep["n_comm_spans"] == n_groups * N_DEV
    assert rep["n_bwd_spans"] == (n_seg + 2) * N_DEV
    assert rep["overlap_fraction"] > 0
    assert rep["n_overlapped_starts"] > 0


def test_post_backward_is_one_span_per_device(dryruns):
    """The post order's backward is one ``transpose(jvp(fwd_model))`` span a
    device.  Its stacked layers' gradients leave the scan's backward
    together, at its end, so it starts fewer all-reduces inside backward
    than the DAG order.  Not none: XLA:CPU runs each op once its operands
    are ready, and the head's group is ready before the scan's backward.
    (The fractions are not compared: on XLA:CPU an all-reduce span holds
    the device's wait for the slowest device.)"""
    out, n_groups, _ = dryruns
    dag, post = out["dag"][0], out["post"][0]
    assert post["n_bwd_spans"] == N_DEV
    assert post["comm_span_devices"] == dag["comm_span_devices"]
    assert post["n_overlapped_starts"] < dag["n_overlapped_starts"]


def test_dryrun_prints_the_report_of_its_trace(dryruns):
    out, _, _ = dryruns
    for rep, trace_dir, _ in out.values():
        assert rep["n_comm_spans"] > 0
        assert glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)


def test_dryrun_prints_the_layers_of_its_trace(dryruns):
    """Each order's layer split: the layers add up to the busy time, and
    with four replicas the wire has time of its own."""
    out, _, _ = dryruns
    for issue, (_, _, layers) in out.items():
        assert set(layers) == {f"{k}_ms" for k in profiler.LAYERS} | {
            "attention_ms", "attention_flash_ms", "moe_ms", "moe_gmm_ms", "moe_rows_ms",
            "busy_ms"} | {f"{p}_ms" for p in profiler.MOE_PARTS}
        assert layers["moe_ms"] == layers["moe_gmm_ms"] == layers["moe_rows_ms"] == 0  # dense
        assert layers["attention_flash_ms"] == 0  # the CPU runs the jnp attention
        assert sum(layers[f"{k}_ms"] for k in profiler.LAYERS) == pytest.approx(layers["busy_ms"])
        for k in ("wire", "backward", "forward", "optimizer"):
            assert layers[f"{k}_ms"] > 0, (issue, k)
