"""Gradient compression with error feedback.

Two honest wire formats (DESIGN.md §2 — a TPU psum cannot carry sub-16-bit
payloads, so int8 uses a reduce-scatter + quantized all-gather split):

* bf16 psum     — grads cast to bf16 on the wire (2x vs fp32); handled by
  ``core.sync.SyncConfig(compression='bf16')``.  ``bf16_ef_encode`` is
  the error-feedback variant: the rounding error of the cast stays in a
  local f32 residual and is re-added next step, so the *expected* update
  is unbiased.  ``core.sync``'s arena wire path
  (``SyncConfig(fuse='arena', compression='bf16_ef')``) fuses exactly
  this encode into the ``kernels/comm_pack`` pack kernel — these
  functions are its semantics oracle.
* int8 RS+AG    — ``compressed_psum_rs_ag``: reduce-scatter the fp grads
  (each device owns a 1/N shard of the sum), quantize the shard to int8
  with a per-shard fp32 scale, all-gather the int8 payload (4x smaller
  than an fp32 all-gather half), dequantize.  Quantization error stays
  local in an error-feedback accumulator and is re-added next step —
  the EF-SGD convergence trick [Karimireddy et al., 2019; paper's ref
  class [5][6][7]].

Total wire bytes per element: RS 4B/N·(N-1)≈4B + AG 1B·(N-1)/N ≈ 5B vs
plain fp32 all-reduce ≈ 8B — a 1.6x cut, or 3.2x against the bf16 path's
4B when combined (bf16 RS + int8 AG).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


Pytree = Any


@dataclasses.dataclass
class ErrorFeedbackState:
    residual: Pytree  # local quantization error, fp32


def ef_init(grads_like: Pytree) -> ErrorFeedbackState:
    return ErrorFeedbackState(
        residual=jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
    )


def bf16_ef_encode(
    g: jax.Array, residual: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Error-feedback bf16 wire encode: ``(wire, new_residual)``.

    ``wire = bf16(g + residual)`` and the new residual is what the cast
    dropped — the EF-SGD accumulate/quantize/carry step at fp32/bf16
    granularity.  Reference semantics for the fused arena pack.
    """
    acc = g.astype(jnp.float32) + residual.astype(jnp.float32)
    wire = acc.astype(jnp.bfloat16)
    return wire, acc - wire.astype(jnp.float32)


def bf16_ef_decode(wire: jax.Array, dtype: Any, scale=1.0) -> jax.Array:
    """Inverse of the wire encode with the DP averaging scale fused."""
    return (wire.astype(jnp.float32) * scale).astype(dtype)


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_rs_ag(
    g: jax.Array,
    axis: str | tuple[str, ...],
    residual: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """int8-wire gradient sum over a *manual* (shard_map) mesh axis.

    Returns (summed gradient replicated over ``axis``, new residual).
    Must be called inside shard_map with ``axis`` manual.  The reduce-
    scatter half runs at full precision (sums must not saturate); only
    the broadcast half is quantized, which is where the (N-1)/N of the
    volume lives.
    """
    orig_shape = g.shape
    gf = g.astype(jnp.float32)
    if residual is not None:
        gf = gf + residual

    axis_size = jax.lax.axis_size(axis)
    pad = (-gf.size) % axis_size
    flat = jnp.pad(gf.reshape(-1), (0, pad))
    # reduce-scatter: each rank owns shard i of the full sum
    shard = jax.lax.psum_scatter(
        flat.reshape(axis_size, -1), axis, scatter_dimension=0, tiled=False
    )
    q, scale = _quantize_int8(shard)
    deq_local = q.astype(jnp.float32) * scale  # what the others will see
    # all-gather the int8 payload + scales
    q_all = jax.lax.all_gather(q, axis, axis=0)
    s_all = jax.lax.all_gather(scale, axis, axis=0)
    full = (q_all.astype(jnp.float32) * s_all[:, None]).reshape(-1)[: gf.size]
    full = full.reshape(orig_shape)

    # error feedback: the part of MY shard the quantizer dropped
    my_err = (shard - deq_local).reshape(-1)
    # scatter back into the flat layout: residual only covers our shard;
    # keep it in shard layout broadcast to full size for simplicity
    idx = jax.lax.axis_index(axis)
    err_full = jnp.zeros_like(flat).reshape(axis_size, -1).at[idx].set(my_err)
    new_residual = err_full.reshape(-1)[: gf.size].reshape(orig_shape)
    return full, new_residual
