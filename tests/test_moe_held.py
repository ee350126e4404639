"""The held-expert, dropless MoE layer (``models/moe.held_moe_block``) and
its grouped-matmul kernels, against the plain SDAR reference
(``bench/refs/sdar_moe.py``), at ``sdar-30b-a3b``'s reduced size on
seeded random weights:

  * the layer gives the reference's held-expert part, and the parts of
    the eight shares of the 128 experts add up to the uncut layer;
  * no (token, choice) pair is dropped: every token routed to one held
    expert is computed;
  * ``moe_gmm`` and ``moe_tgmm`` in interpret mode give the jnp path's
    products, with empty groups, uneven groups and rows past the last
    group, and the custom VJP over them the jnp path's gradients;
  * the row kernels (``moe_rows_pack``, ``moe_rows_gather``,
    ``moe_rows_sum``) in interpret mode move the same rows as the jnp
    gathers, bit for bit, and give their sums, never reading a buffer row
    past the routed ones; the layer's gradients through them are the jnp
    path's;
  * the whole reduced SDAR train step, through the launcher's path
    (``repro.launch.train``) and the benchmark's harness, gives the
    reference's losses, per-leaf gradients and SGD update;
  * the reduced mixtral-8x7b and dbrx-132b still train.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.kernels.grouped_matmul import (gmm, grouped_matmul, held_lists, rows_gather, rows_pack,
                                          rows_sum, tgmm)
from repro.kernels.grouped_matmul.rows import RowTiles
from repro.models import moe as moe_layer
from repro.models.moe import held_moe_block, sort_rows

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import correct, harness  # noqa: E402
from bench.refs import sdar_moe as ref  # noqa: E402

B, S = 2, 32


def ref_config(cfg, layers: int = 1, held: int | None = None, first: int = 0) -> dict:
    """The reference's configuration file for the program's ``cfg``."""
    att, moe = cfg.attention, cfg.moe
    return {
        "num_hidden_layers": layers, "hidden_size": cfg.d_model,
        "moe_intermediate_size": cfg.d_ff, "num_attention_heads": att.n_heads,
        "num_key_value_heads": att.n_kv_heads, "head_dim": att.head_dim,
        "vocab_size": cfg.vocab, "num_experts": held or moe.n_held, "first_expert": first,
        "num_experts_per_tok": moe.top_k, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "rope_theta": att.rope_theta, "tie_word_embeddings": False,
        "sliding_window": None, "router_aux_loss_coef": 0.0, "param_dtype": "float32",
        "published": {"num_experts": moe.n_experts, "num_hidden_layers": layers},
    }


def program(held: int | None = None, first: int = 0):
    cfg = dataclasses.replace(get_reduced("sdar-30b-a3b"), param_dtype=jnp.float32)
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=held or cfg.moe.n_held,
                                                            first=first))


def layer_weights(cfg, held: int, first: int, seed: int = 0) -> dict:
    """One layer's MoE weights of a share: the uncut layer's router and
    the share's slice of its experts."""
    whole = ref.init(ref_config(cfg, held=cfg.moe.n_experts), jax.random.PRNGKey(seed))
    p = jax.tree.map(lambda a: a[0], whole["stages"][ref.STAGE]["moe"])
    return {k: v if k == "router" else v[first:first + held] for k, v in p.items()}


def hidden(cfg, seed: int = 1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model), jnp.float32)


def test_held_layer_matches_the_reference():
    """The program's layer on the first 16 of 128 experts against the
    reference's dense formulation.  Both are float32; the program's
    matmuls run at the CPU's default precision (f32) where the
    reference's run at ``highest``: 1e-5 leaves room for summation order
    alone."""
    cfg = program()
    p = layer_weights(cfg, 16, 0)
    x = hidden(cfg)
    got, stats = held_moe_block(p, x, cfg)
    want = ref.experts(p, x, ref_config(cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert 0 < float(stats["held_rows"]) <= B * S * cfg.moe.top_k
    assert float(stats["max_expert_rows"]) <= B * S


def test_shares_add_up_to_the_whole_layer():
    """The eight shares' parts (experts 0-15, 16-31, ...) add up to what
    the uncut reference gives for the whole layer: nothing is counted
    twice or left out."""
    cfg = program()
    x = hidden(cfg)
    parts = []
    for first in range(0, 128, 16):
        share = program(16, first)
        out, stats = held_moe_block(layer_weights(cfg, 16, first), x, share)
        parts.append(out)
    whole = ref.experts(layer_weights(cfg, 128, 0), x, ref_config(cfg, held=128))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_no_token_is_dropped_when_all_route_to_one_held_expert():
    """Every token's first choice is held expert 3: the expert takes all
    B·S rows (a capacity of T·k·1.25/E would keep 20 of them), and the
    layer still gives the reference's output for every token."""
    cfg = program()
    p = layer_weights(cfg, 16, 0)
    common = jax.random.normal(jax.random.PRNGKey(7), (cfg.d_model,), jnp.float32)
    x = common + 0.1 * hidden(cfg)
    p["router"] = p["router"].at[:, 3].set(common * 10.0)
    got, stats = held_moe_block(p, x, cfg)
    want = ref.experts(p, x, ref_config(cfg))
    assert float(stats["max_expert_rows"]) == B * S
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_reference_refuses_gates_left_unnormalised():
    """The program always renormalises the top-k gates, so a configuration
    file that says otherwise is refused before any step, not compared."""
    cfg = program()
    spec = ref_config(cfg)
    assert ref.program_config(spec, cfg).moe == cfg.moe
    with pytest.raises(ValueError, match="renormalises"):
        ref.program_config({**spec, "norm_topk_prob": False}, cfg)


def test_rows_are_sorted_by_held_expert():
    """The buffer holds the held experts' rows first, in expert order,
    each pair at its own slot; pairs of absent experts get no slot."""
    cfg = program(4, 2)
    idx = jnp.array([[2, 9], [5, 3], [3, 0], [1, 2]], jnp.int32)  # held: 2, 3, 4, 5
    pair_of, slot_of, sizes = sort_rows(idx, cfg.moe)
    assert sizes.tolist() == [2, 2, 0, 1]
    assert pair_of.shape == (4 * 2,)
    M = 8
    held = [(t, j) for t in range(4) for j in range(2) if 2 <= int(idx[t, j]) < 6]
    assert sorted(int(slot_of[t, j]) for t, j in held) == list(range(5))
    assert all(int(slot_of[t, j]) == M for t in range(4) for j in range(2) if (t, j) not in held)
    for t, j in held:
        assert int(pair_of[int(slot_of[t, j])]) == t * 2 + j
    experts = [int(idx[t, j]) for t, j in sorted(held, key=lambda tj: int(slot_of[tj]))]
    assert experts == sorted(experts)


# --- kernels -----------------------------------------------------------------

M_ROWS, K, N = 512, 256, 384
SIZES = {
    "uneven": [100, 0, 37, 200, 50],
    "one_group": [0, 0, 512, 0, 0],
    "tile_aligned": [128, 128, 0, 128, 128],
    "rows_past_the_end": [300, 0, 0, 0, 12],
    "empty": [0, 0, 0, 0, 0],
}


def operands(seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (M_ROWS, K), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (5, K, N), jnp.float32).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[2], (M_ROWS, N), jnp.float32).astype(jnp.bfloat16)
    return x, w, dy


@pytest.mark.parametrize("name", sorted(SIZES))
def test_kernels_match_the_jnp_path(name):
    """``moe_gmm`` forward and against ``w`` transposed, and ``moe_tgmm``,
    in interpret mode at 128-row tiles, against ``jax.lax.ragged_dot`` and
    per-group products in float32, on the rows the groups hold.  Both
    accumulate in f32 and round to bf16 once: half a bf16 unit of the
    largest result is the room."""
    sizes = jnp.array(SIZES[name], jnp.int32)
    n = int(sizes.sum())
    x, w, dy = operands()
    tiles = (128, 128, 128)
    want = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    got = gmm(x, w, sizes, tiling=tiles, interpret=True).astype(jnp.float32)
    tol = float(jnp.max(jnp.abs(want))) * 2.0 ** -8 + 1e-6
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]), atol=tol)

    want_dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), sizes,
                                 preferred_element_type=jnp.float32)
    got_dx = gmm(dy, w, sizes, tiling=tiles, transpose_rhs=True, interpret=True)
    tol = float(jnp.max(jnp.abs(want_dx))) * 2.0 ** -8 + 1e-6
    np.testing.assert_allclose(np.asarray(got_dx[:n], np.float32), np.asarray(want_dx[:n]),
                               atol=tol)

    off = np.concatenate([[0], np.cumsum(SIZES[name])])
    xf, dyf = np.asarray(x, np.float32), np.asarray(dy, np.float32)
    want_dw = np.stack([xf[off[g]:off[g + 1]].T @ dyf[off[g]:off[g + 1]] for g in range(5)])
    got_dw = np.asarray(tgmm(x, dy, sizes, tiling=tiles, interpret=True), np.float32)
    tol = float(np.max(np.abs(want_dw))) * 2.0 ** -8 + 1e-6
    np.testing.assert_allclose(got_dw, want_dw, atol=tol)
    for g in np.flatnonzero(np.asarray(SIZES[name]) == 0):
        assert not got_dw[g].any()  # an empty group's gradient is written, as zeros


def test_custom_vjp_matches_the_jnp_gradients():
    """The kernels' VJP (``moe_gmm`` against ``w`` transposed for ``dx``,
    ``moe_tgmm`` for ``dw``) against the jnp path's own gradients, on the
    rows the groups hold."""
    sizes = jnp.array(SIZES["uneven"], jnp.int32)
    n = int(sizes.sum())
    x, w, dy = operands(1)
    mask = (jnp.arange(M_ROWS) < n)[:, None]

    def loss(use, x, w):
        y = grouped_matmul(x, w, sizes, use_kernels=use, interpret=True).astype(jnp.float32)
        return jnp.sum(jnp.where(mask, y * dy.astype(jnp.float32), 0.0))

    (gx, gw), (wx, ww) = (jax.grad(loss, (1, 2))(u, x, w) for u in (True, False))
    for got, want in ((gx[:n], wx[:n]), (gw, ww)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got, want, atol=float(np.max(np.abs(want))) * 2.0 ** -7)


# --- row kernels -------------------------------------------------------------

ROW_TILES = RowTiles(tm=128, tt=128, ti=128)
ROW_T, ROW_K = 128, 4  # 512 (token, choice) pairs for the buffer's M_ROWS rows


def routing(name: str, seed: int = 0):
    """A buffer of ``M_ROWS`` rows filled as ``SIZES[name]`` says: the
    routed rows' pairs drawn at random from the ``ROW_T · ROW_K``, the
    rest of the buffer holding the unheld pairs.  ``(pair_of, slot_of,
    n_routed)``."""
    n = sum(SIZES[name])
    pair_of = np.random.default_rng(seed).permutation(ROW_T * ROW_K).astype(np.int32)
    slot = np.full(ROW_T * ROW_K, M_ROWS, np.int32)
    slot[pair_of[:n]] = np.arange(n)
    return jnp.asarray(pair_of), jnp.asarray(slot.reshape(ROW_T, ROW_K)), n


def row_operands(seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def bf(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    gates = jax.random.uniform(ks[4], (ROW_T, ROW_K), jnp.float32)
    return (bf(ks[0], (ROW_T, K)), bf(ks[1], (M_ROWS, K)), bf(ks[2], (M_ROWS, K)),
            bf(ks[3], (ROW_T, K)), gates)


def bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))


def close_to(got, want):
    """Half a bf16 unit of the largest of ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=float(np.max(np.abs(want))) * 2.0 ** -8 + 1e-6)


def row_sums(buf, slot_of, n, w=None, plus=None):
    """``moe_rows_sum`` over rows ``0 .. n - 1`` of ``buf`` (+ ``plus``)."""
    packed = rows_pack(buf, n, plus, tm=ROW_TILES.tm, interpret=True)
    return rows_sum(packed, slot_of, held_lists(slot_of, M_ROWS, ROW_TILES.tt), w,
                    dtype=buf.dtype, tiling=ROW_TILES, interpret=True)


@pytest.mark.parametrize("name", sorted(SIZES))
def test_row_kernels_match_the_jnp_path(name):
    """In interpret mode at 128-row tiles: ``moe_rows_gather`` moves the
    jnp gathers' rows bit for bit (the dispatch's, and the combine
    gradient's, scaled by each row's gate in f32), and its dot products
    give the jnp path's gate gradients; ``moe_rows_sum`` gives the
    combine (gated) and the dispatch's gradient (the two cotangents
    added), each within half a bf16 unit of the largest."""
    pair_of, slot_of, n = routing(name)
    token_of = pair_of // ROW_K
    x, ys, dxu, dout, gates = row_operands()
    src = rows_pack(x, ROW_T, tm=ROW_TILES.tm, interpret=True)
    xs = rows_gather(src, token_of, n, dtype=x.dtype, tiling=ROW_TILES, interpret=True)
    np.testing.assert_array_equal(bits(xs[:n]), bits(moe_layer.dispatch(x, token_of, slot_of)[:n]))

    want_dy, want_dgates, *_ = moe_layer._combine_bwd((ys, gates, token_of, slot_of), dout)
    src = rows_pack(dout, ROW_T, tm=ROW_TILES.tm, interpret=True)
    dy, dot = rows_gather(src, token_of, n, gates.reshape(-1)[pair_of], ys, dtype=ys.dtype,
                          tiling=ROW_TILES, interpret=True)
    np.testing.assert_array_equal(bits(dy[:n]), bits(want_dy[:n]))
    dgates = jnp.where(slot_of < M_ROWS, jnp.take(dot, slot_of, mode="clip"), 0.0)
    close_to(dgates, want_dgates)

    close_to(row_sums(ys, slot_of, n, gates), moe_layer.combine(ys, gates, token_of, slot_of))
    want_dx, *_ = moe_layer._dispatch_bwd(slot_of, ys + dxu)
    close_to(row_sums(ys, slot_of, n, plus=dxu), want_dx)


@pytest.mark.parametrize("name", ["rows_past_the_end", "uneven", "tile_aligned"])
def test_row_sums_never_read_rows_past_the_routed_ones(name):
    """Buffer rows at and past the routed ones hold NaN (the grouped
    matmuls leave them unwritten): the combine's and the dispatch
    gradient's sums stay finite and equal the jnp path's."""
    pair_of, slot_of, n = routing(name, seed=1)
    token_of = pair_of // ROW_K
    _, ys, dxu, _, gates = row_operands(1)
    ys, dxu = ys.at[n:].set(jnp.nan), dxu.at[n:].set(jnp.nan)
    out = row_sums(ys, slot_of, n, gates)
    dx = row_sums(ys, slot_of, n, plus=dxu)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.isfinite(np.asarray(dx, np.float32)).all()
    close_to(out, moe_layer.combine(ys, gates, token_of, slot_of))
    close_to(dx, moe_layer._dispatch_bwd(slot_of, ys + dxu)[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_gradients_through_the_row_kernels(dtype):
    """``held_moe_block`` with the row kernels forced on (interpret mode,
    128-row tiles) against the jnp path (the CPU's default): the output, ``dx``, the router's
    gradient (through the gates' gradient) and the experts' gradients."""
    cfg = program()
    cfg = dataclasses.replace(cfg, param_dtype=dtype)
    p = jax.tree.map(lambda a: a.astype(dtype) if a.ndim == 3 else a,
                     layer_weights(cfg, 16, 0))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, ROW_T // 2, cfg.d_model)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(4), x.shape, jnp.float32)

    def loss(p, x, tiles):
        out, _ = held_moe_block(p, x, cfg, row_tiles=tiles, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * c), out

    (_, got), g_got = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x, ROW_TILES)
    (_, want), g_want = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, x, None)
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    for a, b in [(got, want), *zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want))]:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=float(np.max(np.abs(b))) * tol)


# --- the whole train step ----------------------------------------------------

TINY_SDAR = dict(num_hidden_layers=2, hidden_size=64, moe_intermediate_size=32,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
                 param_dtype="float32")
SEED = 2**31 + 4321


def tiny_sdar_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout with one tiny cell of ``sdar-30b-a3b``: its reduced
    widths, 2 of 2 layers, 16 of 128 experts, float32."""
    shutil.copytree(ROOT / "bench", tmp / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/sdar-30b-a3b.json").read_text())
    cfg.update(TINY_SDAR, name="tiny")
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic/train.seq8192.rows2.json").read_text())
    traffic["seq"] = 64
    (tmp / "bench/traffic/tiny.json").write_text(json.dumps(traffic))
    spec = json.loads((ROOT / "bench/cells/sdar-30b-a3b.train.dp1.json").read_text())
    spec["lr"] = 0.01
    (tmp / "bench/cells/tiny.cell.json").write_text(json.dumps(spec))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"] = [{"name": "tiny", "source": cfg["source"], "file": "bench/configs/tiny.json",
                     "reduced": [], "why": "test"}]
    m["workloads"] = [{"name": "tiny.cell", "config": "tiny", "traffic": "tiny", "chips": 1,
                       "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    return tmp


def test_train_step_matches_the_reference(tmp_path, monkeypatch):
    """The reduced SDAR step built through ``launch/train.setup`` ->
    ``TrainSetup.engine`` -> ``train_step`` (DAG issue order, arena wire,
    SGD) against the reference over three steps, in float32 on both
    sides.  The limits are round-off's: the losses within 1e-4 (relative),
    every leaf's first-step gradient and the three steps' update within
    1e-3 of the larger of its own and the median leaf's norm.  A route
    that flipped on a rounding tie would move one expert's rows by a
    whole gate; none does at these seeds."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    cell = harness.load_cell("tiny.cell", tiny_sdar_root(tmp_path))
    b = harness.build(cell, jax.devices())
    prog, params, _, _ = harness.first_steps(b, cell, SEED)
    assert set(jax.tree.leaves(jax.tree.map(lambda a: a.dtype, params))) == {jnp.dtype("float32")}
    got = correct.numbers(prog, harness.reference_readings(cell, SEED, b))
    assert got["loss_gap"] < 1e-4, got
    assert got["grad_gap"] < 1e-3, got
    assert got["update_gap"] < 1e-3, got


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "dbrx-132b"])
def test_other_moe_models_still_train(arch):
    """The reduced mixtral-8x7b and dbrx-132b, holding all their experts,
    train through the launcher on the dropless layer: the loss is finite
    and falls over a few steps."""
    from repro.launch import train

    out = train.main(["--arch", arch, "--reduced", "--steps", "6", "--batch", "2",
                      "--seq", "32", "--policy", "mg_wfbp", "--optimizer", "sgd",
                      "--lr", "0.5", "--replan-every", "0"])
    assert all(np.isfinite(out.losses)), out.losses
    assert out.losses[-1] < out.losses[0], out.losses
