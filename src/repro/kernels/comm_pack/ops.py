"""Public ops for the gradient-arena wire path: Pallas on TPU, the
``dynamic_update_slice``/``slice`` oracle otherwise.  Both paths lower
with ZERO concatenate ops — the oracle is not just a test double, it is
the production CPU/GPU layout (XLA turns the update-slice chain into
in-place writes on the preallocated buffer).

Parts may be arbitrary-shaped gradient leaves / scan slices; flattening
to the 1-D wire layout happens here so the kernels only see flat spans.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..per_device import per_device
from .kernel import pack_arena_pallas, unpack_arena_pallas
from .ref import pack_arena_ref, unpack_arena_ref


def _use_pallas(use_pallas: bool | None) -> bool:
    return jax.default_backend() == "tpu" if use_pallas is None else use_pallas


def pack_arena(
    parts: Sequence[jax.Array],
    offsets: Sequence[int],
    size: int,
    comm_dtype: Any,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
    chunk: int | None = None,
) -> jax.Array:
    """Pack one group's parts into its flat wire arena, the wire-dtype
    cast fused in.  ``chunk`` overrides the staging-buffer length
    (elements) on the Pallas path — tests shrink it to force the
    multi-chunk DMA pipeline.
    """
    flat = [p.reshape(-1) for p in parts]
    if _use_pallas(use_pallas) or interpret:
        kw = {} if chunk is None else {"chunk": chunk}
        return per_device(
            lambda flat: pack_arena_pallas(
                flat, offsets, size, comm_dtype, interpret=interpret, **kw
            )
        )(flat)
    return pack_arena_ref(flat, offsets, size, comm_dtype)


def unpack_arena(
    arena: jax.Array,
    slots: Sequence[tuple[int, int]],  # (offset, size) per part
    shapes: Sequence[tuple[int, ...]],
    dtypes: Sequence[Any],
    scale: jax.Array | float = 1.0,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
    chunk: int | None = None,
) -> list[jax.Array]:
    """Slice the reduced arena back into parts (decompress + DP-average
    fused); parts come back in their original shapes/dtypes."""
    if _use_pallas(use_pallas) or interpret:
        kw = {} if chunk is None else {"chunk": chunk}
        out = per_device(
            lambda arena, scale: unpack_arena_pallas(
                arena, slots, dtypes, scale, interpret=interpret, **kw
            )
        )(arena, jnp.asarray(scale, jnp.float32).reshape(1))
    else:
        out = unpack_arena_ref(arena, slots, dtypes, scale)
    return [p.reshape(s) for p, s in zip(out, shapes)]
