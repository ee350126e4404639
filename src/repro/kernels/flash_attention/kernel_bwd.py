"""Pallas TPU flash attention backward: dQ / dK / dV with recomputation.

Standard two-kernel decomposition (FlashAttention-2 style):

  * ``_dq_kernel``  — grid (B·Hq, nq, nk), KV axis sequential; fp32
    dQ accumulator (block_q, hd) persists across KV blocks;
  * ``_dkv_kernel`` — grid (B·Hq, nk, nq), Q axis sequential; fp32
    dK/dV accumulators (block_k, hd) persist across Q blocks.  Gradients
    are produced per *query* head and group-summed to KV heads outside
    (GQA), trading G× transient memory for perfectly regular tiles.

Both recompute p = exp(s − L) from the forward's saved row logsumexp
L = m + log l — no S×S residuals are ever written to HBM.  Softcap
backward chains d tanh = 1 − (s/cap)².  VMEM per program ≈
(q + k + v + dO + dQ) blocks ≈ 5·block·hd·4B ≲ 1 MB at 256×128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _scores(q, k, sm_scale, softcap):
    s_raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if softcap is not None:
        t = jnp.tanh(s_raw / softcap)
        return t * softcap, (1.0 - t * t)  # value, d(softcap)/d(raw)
    return s_raw, None


def _mask(iq, ik, block_q, block_k, causal, window):
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    m = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        m &= qpos >= kpos
    if window is not None:
        m &= (qpos - kpos) < window
    return m


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, sm_scale, causal, window, softcap, block_q, block_k, num_kv_blocks,
):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...].astype(jnp.float32)[:, :1]  # (block_q, 1)
    delta = delta_ref[...].astype(jnp.float32)[:, :1]

    s, dcap = _scores(q, k, sm_scale, softcap)
    mask = _mask(iq, ik, block_q, block_k, causal, window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    ds = ds * sm_scale
    acc_ref[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == num_kv_blocks - 1)
    def _done():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, window, softcap, block_q, block_k, num_q_blocks,
):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...].astype(jnp.float32)[:, :1]
    delta = delta_ref[...].astype(jnp.float32)[:, :1]

    s, dcap = _scores(q, k, sm_scale, softcap)
    mask = _mask(iq, ik, block_q, block_k, causal, window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    ds = ds * sm_scale
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(iq == num_q_blocks - 1)
    def _done():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention_bwd(
    q: jax.Array,  # (B, Sq, Hq, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,
    o: jax.Array,  # forward output
    lse: jax.Array,  # (B, Sq, Hq) row logsumexp from forward
    do: jax.Array,  # cotangent of o
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    while Sq % block_q:
        block_q //= 2
    while Sk % block_k:
        block_k //= 2
    nq, nk = Sq // block_q, Sk // block_k

    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    dot = do.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    ot = o.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    lset = lse.transpose(0, 2, 1).reshape(B * Hq, Sq)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)

    LANES = 128
    lse2 = jnp.broadcast_to(lset[..., None], lset.shape + (LANES,))
    delta2 = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    def q_map_q(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map_q(bh, iq, ik):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ik, 0)

    common = dict(
        sm_scale=hd**-0.5, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k,
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv_blocks=nk, **common),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map_q),
            pl.BlockSpec((None, block_k, hd), kv_map_q),
            pl.BlockSpec((None, block_k, hd), kv_map_q),
            pl.BlockSpec((None, block_q, hd), q_map_q),
            pl.BlockSpec((None, block_q, LANES), q_map_q),
            pl.BlockSpec((None, block_q, LANES), q_map_q),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), q_map_q),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, dot, lse2, delta2)

    def k_map(bh, ik, iq):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ik, 0)

    def q_map_k(bh, ik, iq):
        return (bh, iq, 0)

    dk_e, dv_e = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, **common),
        grid=(B * Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map_k),
            pl.BlockSpec((None, block_k, hd), k_map),
            pl.BlockSpec((None, block_k, hd), k_map),
            pl.BlockSpec((None, block_q, hd), q_map_k),
            pl.BlockSpec((None, block_q, LANES), q_map_k),
            pl.BlockSpec((None, block_q, LANES), q_map_k),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((None, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, Sk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, dot, lse2, delta2)

    # group-sum the per-q-head dK/dV back to KV heads
    dk = dk_e.reshape(B, Hkv, G, Sk, hd).sum(axis=2).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_e.reshape(B, Hkv, G, Sk, hd).sum(axis=2).transpose(0, 2, 1, 3).astype(v.dtype)
    dq_out = dq.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
    return dq_out, dk, dv
