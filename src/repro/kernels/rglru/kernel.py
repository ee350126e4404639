"""Pallas TPU kernel for the RG-LRU diagonal recurrence.

TPU mapping
-----------
grid = (B, W / block_w, T / chunk) — batch and width are parallel, the
time-chunk axis is sequential so the (1, block_w) fp32 state persists in
VMEM scratch.  Within a chunk the recurrence is a log₂(chunk)-step
Blelloch-style doubling entirely on VPU registers/VMEM:

    (a, g) ∘ (a', g') = (a·a', a'·g + g')

i.e. after k doubling steps row t holds the composition of rows
(t-2ᵏ, t]; chunk=128, block_w=512 → 7 doubling steps over a (128, 512)
fp32 tile ≈ 0.25 MB VMEM.  HBM traffic is exactly 2 reads + 1 write of
the sequence — the kernel exists to avoid XLA's materialized
associative_scan intermediates (log T extra HBM round-trips).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(
    a_ref, g_ref,  # (chunk, block_w)
    h0_ref,  # (1, block_w)
    h_ref,  # out (chunk, block_w)
    hT_ref,  # out (1, block_w) final state
    state_ref,  # scratch (1, block_w) f32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)

    # inclusive associative scan over the chunk (doubling)
    step = 1
    while step < chunk:
        a_shift = jnp.roll(a, step, axis=0)
        g_shift = jnp.roll(g, step, axis=0)
        rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        valid = rows >= step
        g = jnp.where(valid, a * g_shift + g, g)
        a = jnp.where(valid, a * a_shift, a)
        step *= 2

    # fold in carried state: h_t = a_{1..t} * h0 + g_t
    h = a * state_ref[...] + g
    h_ref[...] = h.astype(h_ref.dtype)
    state_ref[...] = h[-1:, :]

    @pl.when(ic == num_chunks - 1)
    def _finish():
        hT_ref[...] = h[-1:, :].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_pallas(
    a: jax.Array,  # (B, T, W)
    g: jax.Array,
    h0: jax.Array | None = None,  # (B, W)
    *,
    chunk: int = 128,
    block_w: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, T, W = a.shape
    chunk = min(chunk, T)
    block_w = min(block_w, W)
    assert T % chunk == 0 and W % block_w == 0, (T, chunk, W, block_w)
    nc, nw = T // chunk, W // block_w
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    h0 = h0.reshape(B, 1, W)

    kernel = functools.partial(_rglru_kernel, chunk=chunk, num_chunks=nc)
    h, hT = pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, block_w), lambda b, iw, ic: (b, ic, iw)),
            pl.BlockSpec((None, chunk, block_w), lambda b, iw, ic: (b, ic, iw)),
            pl.BlockSpec((None, 1, block_w), lambda b, iw, ic: (b, 0, iw)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, block_w), lambda b, iw, ic: (b, ic, iw)),
            pl.BlockSpec((None, 1, block_w), lambda b, iw, ic: (b, 0, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, g, h0)
    return h, hT.reshape(B, W)
