"""Pallas TPU flash attention backward: dQ / dK / dV with recomputation.

Standard two-kernel decomposition (FlashAttention-2 style):

  * ``_dq_kernel``  — grid (B·Hq, nq, nk), KV axis sequential; fp32
    dQ accumulator (block_q, hd) persists across KV blocks;
  * ``_dkv_kernel`` — grid (B·Hkv, nk, G·nq), the (query head of the
    group, Q block) axis sequential; fp32 dK/dV accumulators (block_k, hd)
    persist across the G query heads that share the KV head (GQA) and
    their Q blocks, so dK/dV leave the kernel already summed per KV head.
    It works on transposed (block_k, block_q) tiles, so no tile is
    transposed on the way into the MXU.

Both recompute p = exp(s − L) from the forward's saved row logsumexp
L = m + log l — no S×S residuals are ever written to HBM.  As in the
forward, the MXU takes the operands' own dtype (p and ds are cast to it),
accumulates in f32, and the tiles the causal mask or the window leaves
empty are skipped, with their index maps clamped so no DMA is issued for
them; the element mask runs only on the tiles an edge crosses.  Softcap
backward chains d tanh = 1 − (s/cap)².
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from .kernel import (
    LANES,
    block_sizes,
    effective_window,
    element_mask,
    kv_range,
    lanes,
    q_range,
    scores,
    tile_flags,
)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, sm_scale, causal, window, softcap, block_q, block_k, num_kv_blocks,
):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        k = k_ref[...]
        s, dcap = scores(q_ref[...], k, sm_scale, softcap)  # (block_q, block_k)
        p = jnp.exp(s - lanes(lse_ref[...], block_k))
        if masked:
            p = jnp.where(element_mask(iq, ik, block_q, block_k, causal, window), p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - lanes(delta_ref[...], block_k))
        if dcap is not None:
            ds = ds * dcap
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    needed, edge = tile_flags(iq, ik, block_q, block_k, num_kv_blocks, causal, window)
    pl.when(needed & edge)(lambda: step(True))
    pl.when(needed & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(ik == num_kv_blocks - 1)
    def _done():
        dq_ref[...] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, window, softcap, block_q, block_k, num_q_blocks,
    num_kv_blocks, group,
):
    """Works on the transposed tile (block_k, block_q), so every matmul
    takes its operands as they lie: s^T = k q^T, dV += p^T dO,
    dp^T = v dO^T, dK += ds^T q.  ``lse_ref`` and ``delta_ref`` hold the
    q block's statistics along lanes, (1, block_q)."""
    ik, j = pl.program_id(1), pl.program_id(2)
    iq = j % num_q_blocks

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q, do = q_ref[...], do_ref[...]
        st, dcap = scores(k_ref[...], q, sm_scale, softcap)  # (block_k, block_q)
        pt = jnp.exp(st - lse_ref[...])
        if masked:
            mask = element_mask(iq, ik, block_q, block_k, causal, window, transposed=True)
            pt = jnp.where(mask, pt, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_ref[...], do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - delta_ref[...])
        if dcap is not None:
            dst = dst * dcap
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    needed, edge = tile_flags(iq, ik, block_q, block_k, num_kv_blocks, causal, window)
    pl.when(needed & edge)(lambda: step(True))
    pl.when(needed & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(j == group * num_q_blocks - 1)
    def _done():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def bwd_heads(
    qt: jax.Array,  # (B·Hq, Sq, hd)
    kt: jax.Array,  # (B·Hkv, Sk, hd)
    vt: jax.Array,
    ot: jax.Array,  # forward output, (B·Hq, Sq, hd)
    lse: jax.Array,  # (B·Hq, Sq, 128) f32: the forward's row logsumexp
    dot: jax.Array,  # cotangent of ot
    *,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """dQ, dK, dV in the kernels' layout (that of ``qt``, ``kt``, ``vt``)."""
    BHq, Sq, hd = qt.shape
    BHkv, Sk = kt.shape[:2]
    G = BHq // BHkv
    auto_q, auto_k = block_sizes(Sq, Sk, hd)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    nq, nk = Sq // block_q, Sk // block_k
    window = effective_window(window, Sq)

    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)  # (B·Hq, Sq)
    lanes = (BHq, Sq, LANES)  # the dQ kernel's: per q row, lane-replicated
    delta_rows = jnp.broadcast_to(delta[..., None], lanes)
    lse_t, delta_t = lse[:, None, :, 0], delta[:, None, :]  # the dK/dV kernel's: (B·Hq, 1, Sq)

    common = dict(
        sm_scale=hd**-0.5, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk,
    )
    params = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))

    def q_map_q(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map_q(bh, iq, ik):
        lo, hi = kv_range(iq, block_q, block_k, nk, causal, window)
        return (bh // G, jnp.minimum(jnp.maximum(ik, lo), hi), 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        name=scopes.FLASH_DQ,
        grid=(BHq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map_q),
            pl.BlockSpec((None, block_k, hd), kv_map_q),
            pl.BlockSpec((None, block_k, hd), kv_map_q),
            pl.BlockSpec((None, block_q, hd), q_map_q),
            pl.BlockSpec((None, block_q, LANES), q_map_q),
            pl.BlockSpec((None, block_q, LANES), q_map_q),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), q_map_q),
        out_shape=jax.ShapeDtypeStruct((BHq, Sq, hd), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta_rows)

    def kv_map_k(bkv, ik, j):
        return (bkv, ik, 0)

    def q_map_k(bkv, ik, j):
        lo, hi = q_range(ik, block_q, block_k, nq, causal, window)
        return (bkv * G + j // nq, jnp.minimum(jnp.maximum(j % nq, lo), hi), 0)

    def q_row_map_k(bkv, ik, j):
        h, iq, _ = q_map_k(bkv, ik, j)
        return (h, 0, iq)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, group=G, **common),
        name=scopes.FLASH_DKV,
        grid=(BHkv, nk, G * nq),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map_k),
            pl.BlockSpec((None, block_k, hd), kv_map_k),
            pl.BlockSpec((None, block_k, hd), kv_map_k),
            pl.BlockSpec((None, block_q, hd), q_map_k),
            pl.BlockSpec((None, 1, block_q), q_row_map_k),
            pl.BlockSpec((None, 1, block_q), q_row_map_k),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, hd), kv_map_k),
            pl.BlockSpec((None, block_k, hd), kv_map_k),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Sk, hd), kt.dtype),
            jax.ShapeDtypeStruct((BHkv, Sk, hd), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(qt, kt, vt, dot, lse_t, delta_t)
    return dq, dk, dv
