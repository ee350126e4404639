"""Pallas TPU kernel for the RWKV6 WKV recurrence (chunked-parallel form).

TPU mapping
-----------
grid = (B * H, T / chunk); the chunk axis is sequential ("arbitrary") so
the (K, K) fp32 state scratch persists across chunks of one (batch, head).
Per chunk everything is (chunk, K) resident in VMEM:

  intra-chunk:  (chunk x chunk) strictly-lower-triangular matmul — MXU
  inter-chunk:  r̃ @ S — MXU
  state update: diag-decay + k̃ᵀ @ v — MXU

K = 64 (RWKV6 head size) packs one fp32 state tile of 16 KB; chunk = 128
keeps every operand MXU-aligned.  VMEM per program ≈ 6 · chunk·K·4B +
K·K·4B ≈ 0.2 MB.  This is the same algorithm as models/rwkv6.wkv_chunked,
so kernel-vs-chunked-vs-sequential all cross-validate (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref, k_ref, v_ref, w_ref,  # (chunk, K)
    u_ref,  # (1, K)
    s0_ref,  # (K, K) initial state for this (b, h)
    o_ref,  # (chunk, K)
    s_out_ref,  # (K, K) final state
    s_ref,  # scratch (K, K) f32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (1, K)

    logw = jnp.log(jnp.maximum(w, 1e-30))
    clw = jnp.cumsum(logw, axis=0)
    w_prev = jnp.exp(clw - logw)  # decay up to t-1
    w_inc = jnp.exp(clw)
    w_end = w_inc[-1:, :]  # (1, K)

    r_t = r * w_prev
    k_t = k / jnp.maximum(w_inc, 1e-30)

    S = s_ref[...]
    inter = jax.lax.dot_general(
        r_t, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    A = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (chunk, chunk)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(ii > jj, A, 0.0)  # strictly lower triangular
    intra = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    cur = jnp.sum(r * (u * k), axis=1, keepdims=True) * v
    o_ref[...] = (inter + intra + cur).astype(o_ref.dtype)

    kw = k_t * w_end  # (chunk, K)
    s_new = S * w_end.T + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[...] = s_new

    @pl.when(ic == num_chunks - 1)
    def _finish():
        s_out_ref[...] = s_new.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # (H, K)
    s0: jax.Array | None = None,  # (B, H, K, K)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, T, H, K = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    if s0 is None:
        s0 = jnp.zeros((B, H, K, K), jnp.float32)

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, T, K)

    rt, kt, vt, wt = map(to_bh, (r, k, v, w))
    ub = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)
    s0t = s0.reshape(B * H, K, K)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, num_chunks=nc)
    out, s_final = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, chunk, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, chunk, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, chunk, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, 1, K), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((None, K, K), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((None, K, K), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, K), jnp.float32),
            jax.ShapeDtypeStruct((B * H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rt, kt, vt, wt, ub, s0t)
    return (
        out.reshape(B, H, T, K).transpose(0, 2, 1, 3),
        s_final.reshape(B, H, K, K),
    )
