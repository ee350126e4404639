"""Pallas TPU kernels for the gradient-arena wire path.

One ``pallas_call`` per schedule group, both directions.  The gradient
parts and the arena live in ``ANY`` (compiler-placed, HBM at these
sizes); the kernel streams each part through small VMEM staging buffers
with explicit async copies:

    pack    part[c:c+m] ──DMA──► VMEM ──cast──► VMEM ──DMA──► arena[off+c:]
    unpack  arena[off+c:] ──DMA──► VMEM ──cast·scale──► VMEM ──DMA──► part[c:]

so the bf16 (or any wire-dtype) cast costs zero extra HBM round-trips:
exactly one read of the gradients and one write of the arena, where a
concatenate of the parts would pay a full extra copy each way.

Slot offsets are exact-packed (element granularity; the wire buffer holds
exactly the group's gradient elements, no padding).  A TPU DMA, however, only
moves whole HBM tiles of a 1-D array (``ALIGN`` elements, offset and
length alike), so the kernel takes each part's *aligned body*: the part
must start on a tile boundary of the arena, and the kernel moves its
leading ``(n // ALIGN) * ALIGN`` elements.  What is left — a part's
ragged tail, or a whole part that starts mid-tile — goes through the
``ref.py`` encode/decode and is written into the kernel's output in
place (``dynamic_update_slice``).  Real models are tile-aligned
throughout (every tinyllama-1.1b part is a multiple of d_model = 2048),
so there the remainder is empty.

Each part's chunk loop is a ``fori_loop`` and the staging is
double-buffered: every staging buffer exists once per slot (two separate
VMEM scratch buffers, so no DMA slices a single row out of a tiled
dimension) with a two-entry DMA semaphore array.  The first inbound copy
is warmed up before the loop; at chunk ``k`` the kernel starts the
inbound copy for chunk ``k+1`` into slot ``(k+1) % 2`` before waiting on
chunk ``k``'s, so the next HBM read is in flight while the current chunk
is cast (and the previous chunk's arena write drains).  Slot reuse is
fenced by waiting chunk ``k-1``'s *outbound* copy before starting chunk
``k+1``'s inbound one, which shares its slot.  Every chunk is ``chunk``
elements long; the last one is pulled back to end exactly at the body
(it re-does part of its predecessor, writing the same values), so a
part needs one staging shape and no masking.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from .ref import decode_part, encode_part

#: Staging-buffer length in elements (f32: 256 KiB — comfortably inside
#: VMEM next to its wire-dtype twin).
DEFAULT_CHUNK = 1 << 16

#: Elements in one HBM tile of a 1-D array (8 sublanes x 128 lanes): the
#: granularity of every DMA offset and length the kernel issues.
ALIGN = 1024

_ANY = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)


def aligned_body(offset: int, size: int) -> int:
    """Elements of a part at ``offset`` in the arena that the kernel moves."""
    return (size // ALIGN) * ALIGN if offset % ALIGN == 0 else 0


def _check_chunk(chunk: int) -> None:
    if chunk <= 0 or chunk % ALIGN:
        raise ValueError(f"chunk must be a positive multiple of {ALIGN}, got {chunk}")


def _pipeline(
    body: int,
    ck: int,
    in_copies: Callable[[Any, int], list],
    out_copies: Callable[[Any, int], list],
    compute: Callable[[int], None],
) -> None:
    """Double-buffered chunk loop over ``body`` elements in ``ck`` chunks.

    ``in_copies(c0, s)`` / ``out_copies(c0, s)`` build the DMA
    descriptors of the chunk starting at element ``c0`` staged in slot
    ``s``; ``compute(s)`` transforms slot ``s`` in VMEM.
    """
    n = -(-body // ck)

    def start(k):  # the last chunk ends exactly at the body
        if isinstance(k, int):
            return min(k * ck, body - ck)
        return pl.multiple_of(jnp.minimum(k * ck, body - ck), ALIGN)

    for cp in in_copies(0, 0):  # warm-up: the first chunk's inbound copies
        cp.start()

    def step(k, carry):
        for s in (0, 1):

            @pl.when(k % 2 == s)
            def _():
                @pl.when(k >= 1)
                def _():
                    # drain chunk k-1's outbound copies: they share slot
                    # 1 - s with chunk k+1's inbound ones
                    for cp in out_copies(start(k - 1), 1 - s):
                        cp.wait()

                @pl.when(k + 1 < n)
                def _():
                    for cp in in_copies(start(k + 1), 1 - s):
                        cp.start()

                for cp in in_copies(start(k), s):
                    cp.wait()
                compute(s)
                for cp in out_copies(start(k), s):
                    cp.start()

        return carry

    jax.lax.fori_loop(0, n, step, 0)
    for cp in out_copies(start(n - 1), (n - 1) % 2):
        cp.wait()


def _pack_kernel(
    *refs,
    bodies: tuple[int, ...],
    offsets: tuple[int, ...],
    chunk: int,
    comm_dtype: Any,
):
    parts, arena = refs[:-1], refs[-1]

    for i, body in enumerate(bodies):
        ck = min(chunk, body)

        def part(src0, src1, wire0, wire1, in_sem, out_sem, i=i, body=body, ck=ck):
            src, wire = (src0, src1), (wire0, wire1)

            def in_copies(c0, s):
                return [pltpu.make_async_copy(
                    parts[i].at[pl.ds(c0, ck)], src[s], in_sem.at[s])]

            def out_copies(c0, s):
                return [pltpu.make_async_copy(
                    wire[s], arena.at[pl.ds(offsets[i] + c0, ck)], out_sem.at[s])]

            def compute(s):
                wire[s][...] = src[s][...].astype(jnp.float32).astype(comm_dtype)

            _pipeline(body, ck, in_copies, out_copies, compute)

        pl.run_scoped(
            part,
            src0=pltpu.VMEM((ck,), parts[i].dtype),
            src1=pltpu.VMEM((ck,), parts[i].dtype),
            wire0=pltpu.VMEM((ck,), comm_dtype),
            wire1=pltpu.VMEM((ck,), comm_dtype),
            in_sem=pltpu.SemaphoreType.DMA((2,)),
            out_sem=pltpu.SemaphoreType.DMA((2,)),
        )


def pack_arena_pallas(
    parts: Sequence[jax.Array],  # flattened 1-D gradient parts
    offsets: Sequence[int],
    size: int,
    comm_dtype: Any,
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Fused pack(+cast) of one group's wire arena."""
    _check_chunk(chunk)
    offsets = tuple(int(o) for o in offsets)
    sizes = tuple(int(p.size) for p in parts)
    bodies = tuple(aligned_body(o, n) for o, n in zip(offsets, sizes))
    kin = [i for i, b in enumerate(bodies) if b]
    if kin:
        kernel = functools.partial(
            _pack_kernel,
            bodies=tuple(bodies[i] for i in kin),
            offsets=tuple(offsets[i] for i in kin),
            chunk=chunk,
            comm_dtype=comm_dtype,
        )
        (arena,) = pl.pallas_call(
            kernel,
            name=scopes.COMM_PACK_PACK,
            in_specs=[_ANY] * len(kin),
            out_specs=[_ANY],
            out_shape=[jax.ShapeDtypeStruct((size,), comm_dtype)],
            interpret=interpret,
        )(*[parts[i] for i in kin])
    else:
        arena = jnp.zeros((size,), comm_dtype)
    # the remainder: ragged tails, and parts that start mid-tile
    for i, (off, n, b) in enumerate(zip(offsets, sizes, bodies)):
        if b < n:
            arena = jax.lax.dynamic_update_slice(
                arena, encode_part(parts[i][b:], comm_dtype), (off + b,))
    return arena


def _unpack_kernel(
    arena,
    scale_ref,  # (1,) f32 in SMEM: the DP averaging factor
    *outs,
    slots: tuple[tuple[int, int], ...],  # (offset, aligned body) per part
    dtypes: tuple[Any, ...],
    chunk: int,
):
    for i, (off, body) in enumerate(slots):
        ck = min(chunk, body)

        def part(wire0, wire1, dst0, dst1, in_sem, out_sem, i=i, off=off, body=body, ck=ck):
            wire, dst = (wire0, wire1), (dst0, dst1)

            def in_copies(c0, s):
                return [pltpu.make_async_copy(
                    arena.at[pl.ds(off + c0, ck)], wire[s], in_sem.at[s])]

            def out_copies(c0, s):
                return [pltpu.make_async_copy(
                    dst[s], outs[i].at[pl.ds(c0, ck)], out_sem.at[s])]

            def compute(s):
                x = wire[s][...].astype(jnp.float32) * scale_ref[0]
                dst[s][...] = x.astype(dtypes[i])

            _pipeline(body, ck, in_copies, out_copies, compute)

        pl.run_scoped(
            part,
            wire0=pltpu.VMEM((ck,), arena.dtype),
            wire1=pltpu.VMEM((ck,), arena.dtype),
            dst0=pltpu.VMEM((ck,), dtypes[i]),
            dst1=pltpu.VMEM((ck,), dtypes[i]),
            in_sem=pltpu.SemaphoreType.DMA((2,)),
            out_sem=pltpu.SemaphoreType.DMA((2,)),
        )


def unpack_arena_pallas(
    arena: jax.Array,
    slots: Sequence[tuple[int, int]],  # (offset, size) per part
    dtypes: Sequence[Any],
    scale: jax.Array,  # shape-(1,) f32
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> list[jax.Array]:
    """Fused unpack(+decompress+average) of one reduced arena."""
    _check_chunk(chunk)
    slots = tuple((int(o), int(s)) for o, s in slots)
    dtypes = tuple(dtypes)
    scale = scale.astype(jnp.float32).reshape(1)
    bodies = tuple(aligned_body(o, n) for o, n in slots)
    kin = [i for i, b in enumerate(bodies) if b]
    out: dict[int, jax.Array] = {}
    if kin:
        kernel = functools.partial(
            _unpack_kernel,
            slots=tuple((slots[i][0], bodies[i]) for i in kin),
            dtypes=tuple(dtypes[i] for i in kin),
            chunk=chunk,
        )
        res = pl.pallas_call(
            kernel,
            name=scopes.COMM_PACK_UNPACK,
            in_specs=[_ANY, pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM)],
            out_specs=[_ANY] * len(kin),
            out_shape=[jax.ShapeDtypeStruct((slots[i][1],), dtypes[i]) for i in kin],
            interpret=interpret,
        )(arena, scale)
        out = dict(zip(kin, res))
    # the remainder: ragged tails, and parts that start mid-tile
    for i, ((off, n), b) in enumerate(zip(slots, bodies)):
        if b < n:
            seg = decode_part(jax.lax.slice(arena, (off + b,), (off + n,)), dtypes[i], scale[0])
            out[i] = seg if not b else jax.lax.dynamic_update_slice(out[i], seg, (b,))
    return [out[i] for i in range(len(slots))]
