"""Pure-jnp oracle for the gradient-arena pack/unpack pair.

Pack writes each (flattened) gradient part into its slot of one flat wire
arena with ``dynamic_update_slice`` — no ``concatenate`` in the lowering,
which is the whole point of the arena wire (``core/sync.py``): XLA
updates the preallocated buffer in place instead of materializing a
second copy of every group's gradients.

The wire-dtype cast is fused into the pack; unpack fuses the inverse cast
and the DP averaging scale.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp


def encode_part(part: jax.Array, comm_dtype: Any) -> jax.Array:
    """The wire value of one 1-D gradient span: its cast."""
    return part.astype(comm_dtype)


def decode_part(seg: jax.Array, dtype: Any, scale: jax.Array | float) -> jax.Array:
    """Decompress one reduced span and apply the DP averaging factor."""
    return (seg.astype(jnp.float32) * scale).astype(dtype)


def pack_arena_ref(
    parts: Sequence[jax.Array],  # flattened 1-D gradient parts
    offsets: Sequence[int],  # element offset of each part in the arena
    size: int,  # total arena elements (== sum of part sizes)
    comm_dtype: Any,
) -> jax.Array:
    """The group's wire arena: each part cast into its slot."""
    arena = jnp.zeros((size,), comm_dtype)
    for p, off in zip(parts, offsets):
        arena = jax.lax.dynamic_update_slice(arena, encode_part(p, comm_dtype), (off,))
    return arena


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
