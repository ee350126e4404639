"""Pallas TPU flash attention backward: dQ, dK and dV in one kernel.

``flash_bwd`` walks the grid (B·Hkv, G, nk, nq): a KV head, one query head
of its group, a k block, a q block.  Each tile the masks keep is
recomputed once — s = qkᵀ, p = exp(s − L) from the forward's saved row
logsumexp L = m + log l, dp = dO·vᵀ, ds = p ∘ (dp − δ) — and feeds all
three gradients:

  * dV += pᵀ dO and dK += dsᵀ q, into f32 (Sk, hd) accumulators that
    persist across the G query heads sharing the KV head (GQA) and their
    q blocks, so dK/dV leave the kernel already summed per KV head;
  * dQᵀ += kᵀ dsᵀ, into an f32 (hd, Sq) accumulator of the query head
    that persists across the k blocks, summed in ascending k order; at
    the last k block each q block's dQ is transposed and written.

The kernel works on transposed (block_k, block_q) tiles and takes K
pre-transposed, (B·Hkv, hd, Sk), so every matmul takes its operands as
they lie and no tile is transposed on the way into the MXU.  Every axis
an accumulator sums over is sequential; on a one-core chip (v5e) the grid
runs in order anyway.  The accumulators and the gradient blocks stay in
VMEM for a whole head, which bounds the sequence (:func:`vmem_bytes`):
on a v5e at hd 128 the kernel takes Sq = Sk up to 26,624.

No S×S residual is ever written to HBM.  As in the forward, the MXU takes
the operands' own dtype (p and ds are cast to it), accumulates in f32,
and the tiles the causal mask or the window leaves empty are skipped,
with the q-side index maps clamped so no DMA is issued for them; the
element mask runs only on the tiles an edge crosses.  Softcap backward
chains d tanh = 1 − (s/cap)².
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from .kernel import block_sizes, effective_window, element_mask, q_range, scores, tile_flags

#: VMEM the kernel may take: of a v5e's 128 MiB, what leaves Mosaic room
#: for its own scratch.
VMEM_BUDGET = 96 * 2**20


def vmem_bytes(Sq: int, Sk: int, hd: int, itemsize: int = 2) -> int:
    """VMEM the backward kernel takes at this shape, with ``itemsize``-byte
    operands: the f32 dQᵀ, dK and dV accumulators, the double-buffered
    dQ, dK and dV blocks of a whole head, the double-buffered input tiles
    and the f32 (block_k, block_q) temporaries of one tile."""
    block_q, block_k = block_sizes(Sq, Sk, hd)
    heads = hd * (Sq + 2 * Sk)
    tiles = itemsize * hd * (2 * block_q + 3 * block_k) + 2 * 8 * block_q * 4
    return 4 * heads + 2 * itemsize * heads + 2 * tiles + 8 * 4 * block_q * block_k


def _bwd_kernel(
    q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,  # in
    dq_ref, dk_ref, dv_ref,  # out: a whole head's (S, hd) blocks
    dqt_acc, dk_acc, dv_acc,  # f32 (nq, hd, block_q), (nk, block_k, hd) x 2
    *, sm_scale, causal, window, softcap, block_q, block_k, num_q_blocks,
    num_kv_blocks, group,
):
    """Works on the transposed tile (block_k, block_q): s^T = k q^T,
    dV += p^T dO, dp^T = v dO^T, dK += ds^T q, dQ^T += k^T ds^T.
    ``lse_ref`` and ``delta_ref`` hold the q block's statistics along
    lanes, (1, block_q)."""
    g, ik, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init_dq():
        dqt_acc[iq] = jnp.zeros(dqt_acc.shape[1:], jnp.float32)

    @pl.when((g == 0) & (iq == 0))
    def _init_dkv():
        dk_acc[ik] = jnp.zeros(dk_acc.shape[1:], jnp.float32)
        dv_acc[ik] = jnp.zeros(dv_acc.shape[1:], jnp.float32)

    def step(masked: bool):
        q, do = q_ref[...], do_ref[...]
        st, dcap = scores(k_ref[...], q, sm_scale, softcap)  # (block_k, block_q)
        pt = jnp.exp(st - lse_ref[...])
        if masked:
            mask = element_mask(iq, ik, block_q, block_k, causal, window, transposed=True)
            pt = jnp.where(mask, pt, 0.0)
        dv_acc[ik] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_ref[...], do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - delta_ref[...])
        if dcap is not None:
            dst = dst * dcap
        dst = dst.astype(q.dtype)
        dk_acc[ik] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dqt_acc[iq] += jax.lax.dot_general(
            kt_ref[...], dst, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    needed, edge = tile_flags(iq, ik, block_q, block_k, num_kv_blocks, causal, window)
    pl.when(needed & edge)(lambda: step(True))
    pl.when(needed & jnp.logical_not(edge))(lambda: step(False))

    @pl.when(ik == num_kv_blocks - 1)
    def _done_dq():
        rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
        dq_ref[rows, :] = (dqt_acc[iq].T * sm_scale).astype(dq_ref.dtype)

    @pl.when((g == group - 1) & (iq == num_q_blocks - 1))
    def _done_dkv():
        rows = pl.ds(pl.multiple_of(ik * block_k, block_k), block_k)
        dk_ref[rows, :] = (dk_acc[ik] * sm_scale).astype(dk_ref.dtype)
        dv_ref[rows, :] = dv_acc[ik].astype(dv_ref.dtype)


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
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """dQ, dK, dV in the kernel's layout (that of ``qt``, ``kt``, ``vt``).
    Raises ValueError where a head's accumulators exceed
    :data:`VMEM_BUDGET`."""
    BHq, Sq, hd = qt.shape
    BHkv, Sk = kt.shape[:2]
    G = BHq // BHkv
    block_q, block_k = block_sizes(Sq, Sk, hd)
    nq, nk = Sq // block_q, Sk // block_k
    window = effective_window(window, Sq)
    need = vmem_bytes(Sq, Sk, hd, qt.dtype.itemsize)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"flash attention backward at Sq={Sq}, Sk={Sk}, hd={hd} needs {need / 2**20:.0f} "
            f"MiB of VMEM, more than its {VMEM_BUDGET / 2**20:.0f} MiB")

    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)  # (B·Hq, Sq)
    lse_t, delta_t = lse[:, None, :, 0], delta[:, None, :]  # (B·Hq, 1, Sq)
    ktt = jnp.swapaxes(kt, 1, 2)  # (B·Hkv, hd, Sk): k^T, as the dQ matmul takes it

    def q_map(bkv, g, ik, iq):
        lo, hi = q_range(ik, block_q, block_k, nq, causal, window)
        return (bkv * G + g, jnp.minimum(jnp.maximum(iq, lo), hi), 0)

    def row_map(bkv, g, ik, iq):
        h, iq, _ = q_map(bkv, g, ik, iq)
        return (h, 0, iq)

    def kv_map(bkv, g, ik, iq):
        return (bkv, ik, 0)

    def kvt_map(bkv, g, ik, iq):
        return (bkv, 0, ik)

    def q_head(bkv, g, ik, iq):
        return (bkv * G + g, 0, 0)

    def kv_head(bkv, g, ik, iq):
        return (bkv, 0, 0)

    kernel = functools.partial(
        _bwd_kernel, sm_scale=hd**-0.5, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, num_q_blocks=nq, num_kv_blocks=nk, group=G,
    )
    return pl.pallas_call(
        kernel,
        name=scopes.FLASH_BWD,
        grid=(BHkv, G, nk, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), q_map),
            pl.BlockSpec((None, block_k, hd), kv_map),
            pl.BlockSpec((None, hd, block_k), kvt_map),
            pl.BlockSpec((None, block_k, hd), kv_map),
            pl.BlockSpec((None, block_q, hd), q_map),
            pl.BlockSpec((None, 1, block_q), row_map),
            pl.BlockSpec((None, 1, block_q), row_map),
        ],
        out_specs=[
            pl.BlockSpec((None, Sq, hd), q_head),
            pl.BlockSpec((None, Sk, hd), kv_head),
            pl.BlockSpec((None, Sk, hd), kv_head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHq, Sq, hd), qt.dtype),
            jax.ShapeDtypeStruct((BHkv, Sk, hd), kt.dtype),
            jax.ShapeDtypeStruct((BHkv, Sk, hd), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, hd, block_q), jnp.float32),
            pltpu.VMEM((nk, block_k, hd), jnp.float32),
            pltpu.VMEM((nk, block_k, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=need + 16 * 2**20,
        ),
        interpret=interpret,
    )(qt, kt, ktt, vt, dot, lse_t, delta_t)
