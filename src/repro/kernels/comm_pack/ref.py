"""Pure-jnp oracle for the gradient-arena pack/unpack pair.

Pack writes each (flattened) gradient part into its slot of one flat wire
arena with ``dynamic_update_slice`` — no ``concatenate`` in the lowering,
which is the whole point of the arena wire layout (``core/sync.py``
``fuse='arena'``): XLA updates the preallocated buffer in place instead
of materializing a second copy of every group's gradients.

The wire-dtype cast is fused into the pack; optionally so is the
error-feedback residual (``runtime/compression.py``): the carried
quantization error is re-added *before* the cast and the new residual is
whatever the cast dropped.  Unpack fuses the inverse cast and the DP
averaging scale.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp


def encode_part(
    part: jax.Array,  # 1-D gradient span
    residual: jax.Array | None,  # 1-D f32, same size, or None
    comm_dtype: Any,
) -> tuple[jax.Array, jax.Array | None]:
    """(wire, new_residual) of one span: the cast, with the carried
    quantization error re-added first when ``residual`` is given."""
    if residual is None:
        return part.astype(comm_dtype), None
    acc = part.astype(jnp.float32) + residual.astype(jnp.float32)
    wire = acc.astype(comm_dtype)
    return wire, acc - wire.astype(jnp.float32)


def decode_part(seg: jax.Array, dtype: Any, scale: jax.Array | float) -> jax.Array:
    """Decompress one reduced span and apply the DP averaging factor."""
    return (seg.astype(jnp.float32) * scale).astype(dtype)


def pack_arena_ref(
    parts: Sequence[jax.Array],  # flattened 1-D gradient parts
    offsets: Sequence[int],  # element offset of each part in the arena
    size: int,  # total arena elements (== sum of part sizes)
    comm_dtype: Any,
    residuals: Sequence[jax.Array] | None = None,  # 1-D f32, same sizes
) -> tuple[jax.Array, list[jax.Array] | None]:
    """(arena, new_residuals) — residuals None for the stateless cast."""
    arena = jnp.zeros((size,), comm_dtype)
    new_res: list[jax.Array] | None = None if residuals is None else []
    for i, (p, off) in enumerate(zip(parts, offsets)):
        wire, r = encode_part(p, None if residuals is None else residuals[i], comm_dtype)
        if new_res is not None:
            new_res.append(r)
        arena = jax.lax.dynamic_update_slice(arena, wire, (off,))
    return arena, new_res


def unpack_arena_ref(
    arena: jax.Array,  # 1-D reduced wire buffer
    slots: Sequence[tuple[int, int]],  # (offset, size) per part
    dtypes: Sequence[Any],  # destination dtype per part
    scale: jax.Array | float = 1.0,  # DP averaging factor (1/world)
) -> list[jax.Array]:
    """Static slices out of the reduced arena, decompress + scale fused."""
    return [
        decode_part(jax.lax.slice(arena, (off,), (off + n,)), dt, scale)
        for (off, n), dt in zip(slots, dtypes)
    ]
