"""Mixture-of-Experts FFN: the held-expert, dropless layer.

The router spans all ``n_experts``, and this device computes the part of
the layer that its ``held`` experts (ids ``first .. first + held - 1``)
give, for every (token, choice) pair routed to them.  Under expert
parallelism the other experts lie on further devices; their part is left
out here, and the partial result goes on to the next layer.  Training,
prefill and decode all run :func:`held_moe_block`, whose steps are each
under their own scope:

1. ``moe_route``: router logits in float32 over all experts, softmax,
   top-k, the top-k gates renormalised to sum to 1;
2. ``moe_dispatch``: the (token, choice) pairs sorted by expert, the held
   experts' first, and their tokens gathered into a buffer of
   T·min(k, held) rows: the most that can land on the held experts, so
   no pair is dropped;
3. ``moe_experts``: the held experts' SwiGLU as grouped matmuls over
   those rows (``kernels/grouped_matmul``: Pallas kernels on a TPU, whose
   grids stop at the last routed row);
4. ``moe_combine``: each token's expert rows summed back, weighted by
   their gates.

The gathers of steps 2 and 4 carry their own gradients, which are
gathers too: the transpose of each is the other's pattern, so backward
scatters nothing.  On a TPU, where the shapes tile
(``grouped_matmul.row_tiling``), steps 2 and 4 and their gradients run
the row kernels (``kernels/grouped_matmul/rows``), which read and write
only the routed rows (:func:`dispatch_rows`, :func:`combine_rows`);
elsewhere the jnp gathers below (:func:`dispatch`, :func:`combine`)
run over the whole buffer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import scopes
from ..kernels.grouped_matmul import grouped_matmul, held_lists, row_tiling
from ..kernels.grouped_matmul.ops import gather_rows, pack_rows, sum_rows
from ..kernels.grouped_matmul.rows import RowTiles
from .common import ArchConfig, MoE, truncated_normal

#: The per-layer statistics of the held-expert layer, summed over layers
#: (``max_expert_rows``: the largest, see :func:`add_aux`).
AUX_KEYS = ("loss", "held_rows", "max_expert_rows")


def init_moe(key, cfg: ArchConfig) -> dict:
    moe = cfg.moe
    d, f, e = cfg.d_model, cfg.d_ff, moe.n_held
    ks = jax.random.split(key, 4)
    std_in, std_out = d ** -0.5, f ** -0.5
    return {
        "router": truncated_normal(ks[0], (d, moe.n_experts), jnp.float32, std_in),
        "w_gate": truncated_normal(ks[1], (e, d, f), cfg.param_dtype, std_in),
        "w_up": truncated_normal(ks[2], (e, d, f), cfg.param_dtype, std_in),
        "w_down": truncated_normal(ks[3], (e, f, d), cfg.param_dtype, std_out),
    }


# ---------------------------------------------------------------------------
# Held-expert, dropless layer
# ---------------------------------------------------------------------------


def route(p: dict, x: jax.Array, moe: MoE) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``x`` (T, D) -> the top-k gates (T, k) f32, expert ids (T, k) and
    the load-balancing term (0 where ``aux_coef`` is 0)."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
    gates, idx = jax.lax.top_k(probs, moe.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    if moe.aux_coef == 0:
        return gates, idx, jnp.zeros((), jnp.float32)
    frac = jnp.mean(jnp.sum(jax.nn.one_hot(idx, moe.n_experts, dtype=jnp.float32), axis=1), 0)
    return gates, idx, moe.n_experts * jnp.sum(frac * jnp.mean(probs, 0))


def sort_rows(idx: jax.Array, moe: MoE):
    """The buffer order of the (token, choice) pairs ``idx`` (T, k) routes.

    Returns ``(pair_of, slot_of, sizes)``: for each of the buffer's
    T·min(k, held) rows the (token, choice) pair it holds, as
    ``token · k + choice`` (rows past the held ones hold some pair, and
    are never read back); for each pair its row, or the buffer's length
    where its expert is not held; and the rows of each held expert
    (int32), which fill the buffer from its start, in expert order."""
    T, k = idx.shape
    H = moe.n_held
    M = T * min(k, H)
    local = idx.reshape(-1) - moe.first
    held = (local >= 0) & (local < H)
    key = jnp.where(held, local, H)
    order = jnp.argsort(key, stable=True)[:M]
    slot = jnp.zeros(T * k, jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))
    slot_of = jnp.where(held, slot, M).reshape(T, k)
    sizes = jnp.sum(key[:, None] == jnp.arange(H)[None, :], axis=0, dtype=jnp.int32)
    return order.astype(jnp.int32), slot_of, sizes


def _rows_of(y: jax.Array, slot_of: jax.Array) -> jax.Array:
    """(T, k, D): row ``slot_of`` of ``y``, zeros where it is ``len(y)``."""
    g = jnp.take(y, slot_of, axis=0, mode="clip")
    return jnp.where((slot_of < y.shape[0])[..., None], g, 0)


@jax.custom_vjp
def dispatch(x: jax.Array, token_of: jax.Array, slot_of: jax.Array) -> jax.Array:
    """The buffer: row ``r`` is token ``token_of[r]`` of ``x`` (T, D)."""
    return jnp.take(x, token_of, axis=0, mode="clip")


def _dispatch_fwd(x, token_of, slot_of):
    return dispatch(x, token_of, slot_of), slot_of


def _dispatch_bwd(slot_of, dxs):
    # each token's gradient: the sum of its held rows' gradients
    dx = jnp.sum(_rows_of(dxs, slot_of).astype(jnp.float32), axis=1)
    return dx.astype(dxs.dtype), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y: jax.Array, gates: jax.Array, token_of: jax.Array, slot_of: jax.Array):
    """(T, D): each token's held rows of ``y`` (M, D), weighted by their
    ``gates`` (T, k) and summed in f32, in ``y``'s dtype."""
    rows = _rows_of(y, slot_of).astype(jnp.float32)
    return jnp.sum(rows * gates[..., None], axis=1).astype(y.dtype)


def _combine_fwd(y, gates, token_of, slot_of):
    return combine(y, gates, token_of, slot_of), (y, gates, token_of, slot_of)


def _combine_bwd(res, dout):
    y, gates, token_of, slot_of = res
    M = y.shape[0]
    # the gate of each buffer row (0 past the held rows), then the rows'
    # gradient: its token's output gradient times its gate
    row_gate = jnp.zeros(M + 1, jnp.float32).at[slot_of.reshape(-1)].set(
        gates.reshape(-1))[:M]
    dy = (jnp.take(dout, token_of, axis=0, mode="clip").astype(jnp.float32)
          * row_gate[:, None])
    dgates = jnp.sum(_rows_of(y, slot_of).astype(jnp.float32)
                     * dout.astype(jnp.float32)[:, None, :], axis=-1)
    return dy.astype(y.dtype), dgates, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# The same two steps through the row kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def dispatch_rows(x, pair_of, slot_of, lists, n_rows, tiling, interpret):
    """:func:`dispatch` through the row kernels: rows ``0 .. n_rows - 1``
    of the buffer, the rest left unwritten.  The buffer is returned twice,
    once for each grouped matmul that reads it, so that backward gets the
    two cotangents apart and adds only their held rows."""
    k = slot_of.shape[1]
    src = pack_rows(x, x.shape[0], tiling=tiling, interpret=interpret)
    xs = gather_rows(src, pair_of // k, n_rows, dtype=x.dtype, tiling=tiling,
                     interpret=interpret)
    return xs, xs


def _dispatch_rows_fwd(x, pair_of, slot_of, lists, n_rows, tiling, interpret):
    return dispatch_rows(x, pair_of, slot_of, lists, n_rows, tiling, interpret), (
        slot_of, lists, n_rows)


def _dispatch_rows_bwd(tiling, interpret, res, cts):
    slot_of, lists, n_rows = res
    dxg, dxu = cts
    # the two cotangents added over the routed rows alone, then each
    # token's held rows summed in f32
    both = pack_rows(dxg, n_rows, dxu, tiling=tiling, interpret=interpret)
    dx = sum_rows(both, slot_of, lists, dtype=dxg.dtype, tiling=tiling, interpret=interpret)
    return dx, None, None, None, None


dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def combine_rows(y, gates, pair_of, slot_of, lists, n_rows, tiling, interpret):
    """:func:`combine` through the row kernels: only the held rows of
    ``y``'s first ``n_rows`` are read."""
    rows = pack_rows(y, n_rows, tiling=tiling, interpret=interpret)
    return sum_rows(rows, slot_of, lists, gates, dtype=y.dtype, tiling=tiling,
                    interpret=interpret)


def _combine_rows_fwd(y, gates, pair_of, slot_of, lists, n_rows, tiling, interpret):
    out = combine_rows(y, gates, pair_of, slot_of, lists, n_rows, tiling, interpret)
    return out, (y, gates, pair_of, slot_of, n_rows)


def _combine_rows_bwd(tiling, interpret, res, dout):
    y, gates, pair_of, slot_of, n_rows = res
    M, k = y.shape[0], slot_of.shape[1]
    # each buffer row: its token's output gradient times the row's gate,
    # and the row's dot product with that gradient, the gate's gradient
    src = pack_rows(dout, dout.shape[0], tiling=tiling, interpret=interpret)
    row_gate = gates.reshape(-1)[pair_of]
    dy, dot = gather_rows(src, pair_of // k, n_rows, row_gate, y, dtype=y.dtype,
                          tiling=tiling, interpret=interpret)
    dgates = jnp.where(slot_of < M, jnp.take(dot, slot_of, mode="clip"), 0.0)
    return dy, dgates, None, None, None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def held_moe_block(p: dict, x: jax.Array, cfg: ArchConfig, *, row_tiles: RowTiles | None = None,
                   interpret: bool = False) -> tuple[jax.Array, dict]:
    """x: (B, S, D) -> (the held experts' part of the layer, its
    statistics: the load-balancing term, the rows routed to held experts,
    the most rows one held expert took).  The row kernels run at
    ``row_tiles``, by default the tiling ``row_tiling`` gives (None off a
    TPU: the jnp path); tests give a tiling of their own, and
    ``interpret`` runs the kernels in Pallas's interpreter."""
    moe = cfg.moe
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    tiling = row_tiles or row_tiling(B * S, D, moe.top_k, x.dtype)
    with jax.named_scope(scopes.MOE):
        with jax.named_scope(scopes.MOE_ROUTE):
            gates, idx, aux = route(p, xt, moe)
        with jax.named_scope(scopes.MOE_DISPATCH):
            pair_of, slot_of, sizes = sort_rows(idx, moe)
            if tiling is None:
                xg = xu = dispatch(xt, pair_of // moe.top_k, slot_of)
            else:
                n_rows = jnp.sum(sizes)
                lists = held_lists(slot_of, pair_of.shape[0], tiling.tt)
                xg, xu = dispatch_rows(xt, pair_of, slot_of, lists, n_rows, tiling, interpret)
        with jax.named_scope(scopes.MOE_EXPERTS):
            h = jax.nn.silu(grouped_matmul(xg, p["w_gate"], sizes))
            h = h * grouped_matmul(xu, p["w_up"], sizes)
            ys = grouped_matmul(h, p["w_down"], sizes)
        with jax.named_scope(scopes.MOE_COMBINE):
            if tiling is None:
                out = combine(ys, gates, pair_of // moe.top_k, slot_of)
            else:
                out = combine_rows(ys, gates, pair_of, slot_of, lists, n_rows, tiling, interpret)
    stats = {"loss": aux, "held_rows": jnp.sum(sizes).astype(jnp.float32),
             "max_expert_rows": jnp.max(sizes).astype(jnp.float32)}
    return out.reshape(B, S, D), stats


# ---------------------------------------------------------------------------
# The layers' statistics through the stage scans
# ---------------------------------------------------------------------------


def zero_aux(cfg: ArchConfig):
    """A layer's statistics where it has no experts: the dense models'
    scalar 0, a dict of zeros (:data:`AUX_KEYS`) in an MoE model."""
    if cfg.moe is None:
        return jnp.zeros((), jnp.float32)
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def add_aux(a, b):
    """Two layers' statistics together: sums, ``max_expert_rows`` the
    larger."""
    if not isinstance(a, dict):
        return a + b
    return {k: jnp.maximum(a[k], b[k]) if k == "max_expert_rows" else a[k] + b[k] for k in a}


def reduce_aux(aux):
    """The statistics of layers stacked on a leading axis (a scan's)."""
    if not isinstance(aux, dict):
        return jnp.sum(aux)
    return {k: jnp.max(v) if k == "max_expert_rows" else jnp.sum(v) for k, v in aux.items()}


def sum_aux(parts: list):
    """The statistics of several scans and tails together."""
    if not isinstance(parts[0], dict):
        return sum(parts)
    out = parts[0]
    for p in parts[1:]:
        out = add_aux(out, p)
    return out
