"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

TPU mapping
-----------
grid = (batch * q_heads, num_q_blocks, num_kv_blocks); the last grid axis
is sequential on TPU ("arbitrary"), so fp32 scratch accumulators persist
across KV blocks of one (head, q-block):

  acc (block_q, hd)   running unnormalized output
  m   (block_q, 128)  running row max (lane-replicated)
  l   (block_q, 128)  running row sum

Block shapes are MXU-aligned: block_q x hd and block_k x hd tiles with
hd ∈ {64, 128, 256} and block_{q,k} multiples of 128 (sublane-packed for
bf16).  VMEM footprint per program ≈ (block_q + 2·block_k) · hd · 2B +
block_q · hd · 4B + 2 · block_q · 512B — e.g. ~0.6 MB at 256/512/128,
far under the ~16 MB v5e budget, leaving room for double buffering.

GQA is expressed in the BlockSpec index maps: the KV block index maps the
query head h to KV head h // group, so no KV replication is materialized.
Causal/window skipping is done with block-level masks (correctness) —
skipped-block *scheduling* (not issuing the dot at all) is a grid-mapping
refinement noted in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128


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

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (block_q, block_k)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (block_q, block_k)
    correction = jnp.exp(m_prev - m_new)  # (block_q, 1)

    l_ref[...] = correction * l_ref[...] + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_ref.shape
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    v = v_ref[...].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[...] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))).astype(
            lse_ref.dtype
        )


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
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    return_lse: bool = False,
) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    while Sq % block_q:
        block_q //= 2
    while Sk % block_k:
        block_k //= 2
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    # layout: fold (B, H) into the first grid axis; heads-minor
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ik, 0)

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
    from jax.experimental.pallas import tpu as pltpu

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
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
            jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Sq, LANES), jnp.float32),
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
    o = out.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
    if return_lse:
        return o, lse[..., 0].reshape(B, Hq, Sq).transpose(0, 2, 1)
    return o
