"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

TPU mapping
-----------
grid = (batch * q_heads, num_q_blocks, num_kv_blocks); the last grid axis
is sequential on TPU ("arbitrary"), so fp32 scratch accumulators persist
across KV blocks of one (head, q-block):

  acc (block_q, hd)   running unnormalized output
  m   (block_q, 128)  running row max (lane-replicated)
  l   (block_q, 128)  running row sum

The MXU takes q, k, v and p in the operands' own dtype (bf16 in training)
and accumulates in f32; the softmax statistics and the exponentials stay
f32.  Block sizes come from the shapes (:func:`block_sizes`).

GQA is expressed in the BlockSpec index maps: the KV block index maps the
query head h to KV head h // group, so no KV replication is materialized.

Causal and window masking work at two levels.  A (q-block, k-block) tile
that lies wholly above the diagonal or wholly outside the window is
skipped (``pl.when``), and the K/V index map is clamped to the blocks a
q block needs, so the pipeline issues no DMA for a skipped tile (the
block index does not change).  The element mask is applied only on the
tiles the diagonal or the window edge crosses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes

NEG_INF = -1e30
LANES = 128


def block_sizes(Sq: int, Sk: int, hd: int) -> tuple[int, int]:
    """``(block_q, block_k)`` for a ``(Sq, Sk)`` grid of ``hd``-wide heads:
    1024 x 512 tiles (512 x 256 for 256-wide heads, to bound their VMEM),
    halved until they divide the sequence.  On a v5e at 4096 tokens,
    24 x 128 heads, the forward kernel takes 1.13 ms there against 1.25 ms
    at 512 x 512; the backward kernel takes 2.15 ms there."""
    bq, bk = (1024, 512) if hd <= 128 else (512, 256)
    bq, bk = min(bq, Sq), min(bk, Sk)
    while Sq % bq:
        bq //= 2
    while Sk % bk:
        bk //= 2
    return bq, bk


def effective_window(window: int | None, Sq: int) -> int | None:
    """``window``, or None where it masks nothing (``qpos - kpos`` never
    reaches it)."""
    return window if window is not None and window < Sq else None


def kv_range(iq, block_q, block_k, nk, causal, window):
    """First and last k block that q block ``iq`` needs (traced ints)."""
    hi = jnp.minimum(((iq + 1) * block_q - 1) // block_k, nk - 1) if causal else nk - 1
    lo = jnp.maximum(iq * block_q - window + 1, 0) // block_k if window is not None else 0
    return lo, hi


def q_range(ik, block_q, block_k, nq, causal, window):
    """First and last q block that k block ``ik`` is needed by."""
    lo = (ik * block_k) // block_q if causal else 0
    hi = (
        jnp.minimum((ik * block_k + block_k - 1 + window - 1) // block_q, nq - 1)
        if window is not None else nq - 1
    )
    return lo, hi


def tile_flags(iq, ik, block_q, block_k, nk, causal, window):
    """``(needed, edge)`` of tile (iq, ik): whether any of its elements is
    unmasked, and whether the diagonal or the window edge crosses it (so
    it needs the element mask)."""
    lo, hi = kv_range(iq, block_q, block_k, nk, causal, window)
    needed = (ik >= lo) & (ik <= hi)
    edge = jnp.bool_(False)
    if causal:  # some kpos > qpos
        edge |= ik * block_k + block_k - 1 > iq * block_q
    if window is not None:  # some qpos - kpos >= window
        edge |= iq * block_q + block_q - 1 - ik * block_k >= window
    return needed, edge


def element_mask(iq, ik, block_q, block_k, causal, window, transposed=False):
    """The (block_q, block_k) mask of tile (iq, ik); ``transposed``: the
    (block_k, block_q) mask of the transposed tile."""
    shape, qdim = ((block_k, block_q), 1) if transposed else ((block_q, block_k), 0)
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, qdim)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qdim)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return mask


def lanes(x, width: int):
    """A lane-replicated (rows, LANES) value widened or cut to ``width``
    columns: whole-vreg copies where ``width`` is a multiple of LANES."""
    if width % LANES == 0:
        return jnp.tile(x, (1, width // LANES))
    return x[:, :width] if width < LANES else jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def scores(q, k, sm_scale, softcap):
    """``(s, dcap)``: the f32 scores of a q tile against a k tile, and the
    softcap's derivative d s / d raw (None without softcap)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if softcap is None:
        return s, None
    t = jnp.tanh(s / softcap)
    return t * softcap, 1.0 - t * t


def _fwd_kernel(
    q_ref,  # (block_q, hd)
    k_ref,  # (block_k, hd)
    v_ref,  # (block_k, hd)
    o_ref,  # (block_q, hd)
    lse_ref,  # (block_q, LANES) out: row logsumexp (bwd residual)
    acc_ref,  # scratch (block_q, hd) f32
    m_ref,  # scratch (block_q, LANES) f32
    l_ref,  # scratch (block_q, LANES) f32
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked: bool):
        v = v_ref[...]
        s, _ = scores(q_ref[...], k_ref[...], sm_scale, softcap)  # (block_q, block_k)
        if masked:
            mask = element_mask(iq, ik, block_q, block_k, causal, window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]  # (block_q, LANES), lane-replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - lanes(m_new, block_k))
        if masked:  # a row masked so far has m_new = NEG_INF, so exp(0)
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = correction * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * lanes(correction, v.shape[1]) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    needed, edge = tile_flags(iq, ik, block_q, block_k, num_kv_blocks, causal, window)
    pl.when(needed & edge)(lambda: step(True))
    pl.when(needed & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = lanes(l_ref[...], o_ref.shape[1])
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[...] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def to_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, hd) -> (B·H, S, hd), the kernels' layout."""
    B, S, H, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)


def from_heads(x: jax.Array, B: int) -> jax.Array:
    """(B·H, S, hd) -> (B, S, H, hd)."""
    BH, S, hd = x.shape
    return x.reshape(B, BH // B, S, hd).transpose(0, 2, 1, 3)


def fwd_heads(
    qt: jax.Array,  # (B·Hq, Sq, hd)
    kt: jax.Array,  # (B·Hkv, Sk, hd)
    vt: jax.Array,
    *,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The forward kernel in its own layout: the output (B·Hq, Sq, hd) and
    the row logsumexp (B·Hq, Sq, 128) f32, lane-replicated, which
    :func:`~.kernel_bwd.bwd_heads` takes back.  Query head ``bh`` reads
    KV head ``bh // G``."""
    BHq, Sq, hd = qt.shape
    Sk = kt.shape[1]
    G = BHq // kt.shape[0]
    auto_q, auto_k = block_sizes(Sq, Sk, hd)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    window = effective_window(window, Sq)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        lo, hi = kv_range(iq, block_q, block_k, nk, causal, window)
        return (bh // G, jnp.minimum(jnp.maximum(ik, lo), hi), 0)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=hd**-0.5,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_kv_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        name=scopes.FLASH_FWD,
        grid=(BHq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map),
            pl.BlockSpec((None, block_k, hd), kv_map),
            pl.BlockSpec((None, block_k, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, hd), q_map),
            pl.BlockSpec((None, block_q, LANES), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, Sq, hd), qt.dtype),
            jax.ShapeDtypeStruct((BHq, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_k", "interpret",
        "return_lse",
    ),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, Sq, Hq, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    return_lse: bool = False,
):
    """Attention output (B, Sq, Hq, hd); with ``return_lse`` also the row
    logsumexp (B, Sq, Hq).  Block sizes default to :func:`block_sizes`."""
    B, Sq, Hq, _ = q.shape
    out, lse = fwd_heads(
        to_heads(q), to_heads(k), to_heads(v), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    o = from_heads(out, B)
    if return_lse:
        return o, lse[..., 0].reshape(B, Hq, Sq).transpose(0, 2, 1)
    return o
