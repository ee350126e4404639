"""Pallas TPU kernels that move the routed rows of the MoE layer between
token order and the held experts' buffer, in proportion to the rows
routed rather than to the buffer's T·min(k, held) rows.

A DMA on the TPU addresses whole tiles of an array's layout: 8 or 16 rows
of a 2-D array, never one row.  So the rows a kernel fetches by id are
first written in *row layout*: an (n, 1, w) uint32 array that XLA lays out
as one (1, 128)-tiled row per index, the ``d`` values of each row as
``w`` 32-bit words (a float32 row as it is; a bfloat16 row as ``d / 2``
words, word ``c`` holding column ``c`` in its low half and column
``c + d/2`` in its high half).  Unpacked, every value is exact in float32.

* :func:`rows_pack` (``moe_rows_pack``): the first ``n_rows`` rows of an
  (n, d) array in row layout, optionally the sum of two arrays rounded to
  their dtype; tiles past ``n_rows`` get no grid step;
* :func:`rows_gather` (``moe_rows_gather``): buffer row ``r`` =
  ``scale[r]`` · token row ``token_of[r]``, for ``r < n_rows``, one DMA a
  row; optionally also each row's dot product with a buffer-order array,
  ``dot[r] = <src[token_of[r]], ys[r]>``;
* :func:`rows_sum` (``moe_rows_sum``): token row ``t`` = the sum over its
  held slots of ``w[t, j]`` · buffer row ``slot_of[t, j]``, in float32,
  rounded once; a token's held slots come from a per-tile list sorted
  held-first (:func:`held_lists`), so an unheld (token, choice) pair
  costs no read.

Index vectors reach each grid step as SMEM blocks; the row counts as
scalar prefetch.  Every result is at least 2-D.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes

_HI = 0xFFFF0000
#: Scoped VMEM the row kernels may use (a v5e core has 128 MiB).
_VMEM_LIMIT = 48 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class RowTiles:
    """``tm`` rows per step of the pack and gather kernels, ``tt`` tokens
    per step of the sum kernel, ``ti`` entries per SMEM block of the
    gather's row ids (a multiple of ``tm``; the sum's blocks hold the
    ``tt * k`` pairs of its tokens)."""

    tm: int
    tt: int
    ti: int


def tiles(t: int, d: int, k: int, dtype) -> RowTiles | None:
    """The TPU tiling of the row kernels for ``t`` tokens of width ``d``
    in ``dtype``, ``k`` choices each, or None where the shapes do not
    tile: ``t`` a multiple of 1024, each row a whole number of 128-word
    lanes, and the sum's SMEM blocks (``tt * k`` entries) a multiple of
    the 1024 that XLA tiles a 1-D int32 array by."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    w = words(d, dtype)
    tt = next((tt for tt in (128, 256, 512) if (tt * k) % 1024 == 0), None)
    if t % 1024 or d % (128 * (4 // dtype.itemsize)) or tt is None:
        return None
    if k * tt * w * 4 > 16 * 1024 * 1024:  # the sum's row scratch
        return None
    return RowTiles(tm=256, tt=tt, ti=1024)


def words(d: int, dtype) -> int:
    """32-bit words of one row of ``d`` values of ``dtype``."""
    return d * jnp.dtype(dtype).itemsize // 4


def _to_words(v):
    """(n, d) float32 or bfloat16 -> (n, words) uint32."""
    bits = lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    if v.dtype == jnp.float32:
        return bits
    w = v.shape[1] // 2
    return (bits[:, :w] >> 16) | (bits[:, w:] & jnp.uint32(_HI))


def _from_words(w, dtype) -> list:
    """(n, words) uint32 -> the row's values in float32, as the column
    blocks that the words hold: all ``d`` columns for float32, the two
    halves for bfloat16."""
    if jnp.dtype(dtype) == jnp.float32:
        return [lax.bitcast_convert_type(w, jnp.float32)]
    return [lax.bitcast_convert_type(w << 16, jnp.float32),
            lax.bitcast_convert_type(w & jnp.uint32(_HI), jnp.float32)]


def _store(out_ref, parts):
    """Column blocks ``parts`` (float32) into ``out_ref`` in its dtype."""
    w = parts[0].shape[1]
    for i, p in enumerate(parts):
        out_ref[:, i * w:(i + 1) * w] = p.astype(out_ref.dtype)


def _split(v, n_parts: int) -> list:
    w = v.shape[1] // n_parts
    return [v[:, i * w:(i + 1) * w].astype(jnp.float32) for i in range(n_parts)]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _count(n_rows) -> jax.Array:
    return jnp.asarray(n_rows, jnp.int32).reshape(1)


def rows_pack(a, n_rows, b=None, *, tm: int, interpret: bool = False):
    """Rows ``0 .. n_rows - 1`` of ``a`` (n, d), or of ``a + b`` rounded
    to ``a``'s dtype, in row layout: (n, 1, words) uint32.  Tiles past
    ``n_rows`` are left unwritten."""
    n, d = a.shape
    w = words(d, a.dtype)
    count = _count(n_rows)
    n_tiles = (count[0] + tm - 1) // tm

    def kernel(n_ref, *refs):
        *ins, out_ref = refs
        v = ins[0][...]
        if len(ins) == 2:
            v = (v.astype(jnp.float32) + ins[1][...].astype(jnp.float32)).astype(v.dtype)
        out_ref[...] = _to_words(v).reshape(tm, 1, w)

    ins = [a] if b is None else [a, b]
    return pl.pallas_call(
        kernel,
        name=scopes.MOE_ROWS_PACK,
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, d), lambda i, n: (i, 0)) for _ in ins],
            out_specs=pl.BlockSpec((tm, 1, w), lambda i, n: (i, 0, 0)),
        ),
        compiler_params=_params(),
        interpret=interpret,
    )(count, *ins)


def rows_gather(src, token_of, n_rows, scale=None, ys=None, *, dtype, tiling: RowTiles,
                interpret: bool = False):
    """Buffer rows from token rows.  ``src`` (t, 1, words) the tokens in
    row layout (:func:`rows_pack`), ``token_of`` (m,) int32: (m, d) in
    ``dtype``, row ``r < n_rows`` = token ``token_of[r]`` times
    ``scale[r]`` ((m,) float32, in float32 then rounded); rows past
    ``n_rows`` are left unwritten.  With ``ys`` (m, d) also returns
    (m,) float32: each row's dot product with row ``r`` of ``ys``
    (without the scale), in float32.  Per-row scalars travel lane-dense,
    as (m / tm, 1, tm) blocks."""
    w = src.shape[2]
    m = token_of.shape[0]
    d = w * 4 // jnp.dtype(dtype).itemsize
    tm, ti = tiling.tm, tiling.ti
    per = ti // tm
    count = _count(n_rows)
    n_tiles = (count[0] + tm - 1) // tm

    def kernel(n_ref, tok_ref, src_ref, *refs):
        refs = list(refs)
        scale_ref = refs.pop(0) if scale is not None else None
        ys_ref = refs.pop(0) if ys is not None else None
        out_ref, *dot_ref = refs[:-2]
        scr, sem = refs[-2:]
        i = pl.program_id(0)
        base = (i % per) * tm
        rows = jnp.minimum(n_ref[0] - i * tm, tm)

        def copy(j, t):
            return pltpu.make_async_copy(src_ref.at[t], scr.at[j], sem)

        def start(j, c):
            copy(j, tok_ref[base + j]).start()
            return c

        def wait(j, c):
            copy(0, 0).wait()
            return c

        lax.fori_loop(0, rows, start, 0)
        lax.fori_loop(0, rows, wait, 0)
        parts = _from_words(scr[...].reshape(tm, w), dtype)
        if ys_ref is not None:
            y = _split(ys_ref[...], len(parts))
            dot_ref[0][...] = sum(jnp.sum(p * q, axis=1) for p, q in zip(parts, y))[None, :]
        if scale_ref is not None:
            col = scale_ref[...].reshape(tm, 1)
            parts = [p * col for p in parts]
        _store(out_ref, parts)

    in_specs = [pl.BlockSpec((ti,), lambda i, n: (i // per,), memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    ins = [token_of, src]
    if scale is not None:
        in_specs.append(pl.BlockSpec((None, 1, tm), lambda i, n: (i, 0, 0)))
        ins.append(scale.reshape(m // tm, 1, tm))
    if ys is not None:
        in_specs.append(pl.BlockSpec((tm, d), lambda i, n: (i, 0)))
        ins.append(ys)
    out_shape = [jax.ShapeDtypeStruct((m, d), dtype)]
    out_specs = [pl.BlockSpec((tm, d), lambda i, n: (i, 0))]
    if ys is not None:
        out_shape.append(jax.ShapeDtypeStruct((m // tm, 1, tm), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, tm), lambda i, n: (i, 0, 0)))
    out = pl.pallas_call(
        kernel,
        name=scopes.MOE_ROWS_GATHER,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tm, 1, w), jnp.uint32), pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=_params(),
        interpret=interpret,
    )(count, *ins)
    return (out[0], out[1].reshape(m)) if ys is not None else out[0]


def held_lists(slot_of, m: int, tt: int):
    """Per tile of ``tt`` tokens, the (token, choice) pairs whose slot is
    held (``slot_of < m``), first: ``(dest, slot, counts)``.  ``dest``
    and ``slot`` are (t·k,) int32, tile by tile of ``tt·k`` entries: the
    pair's place in the sum kernel's row scratch (``j·tt + token in the
    tile``) and its buffer row, the tile's held pairs before the rest;
    ``counts`` (t / tt,) int32 the held pairs of each tile."""
    t, k = slot_of.shape
    held = slot_of < m
    dest = (jnp.arange(k, dtype=jnp.int32)[None, :] * tt
            + (jnp.arange(t, dtype=jnp.int32) % tt)[:, None])
    key = jnp.where(held, dest, k * tt).reshape(t // tt, tt * k)
    key, slot = lax.sort((key, slot_of.reshape(t // tt, tt * k)), dimension=1, num_keys=1)
    counts = jnp.sum(held.reshape(t // tt, tt * k), axis=1, dtype=jnp.int32)
    return key.reshape(-1), slot.reshape(-1), counts


def rows_sum(rows, slot_of, lists, w=None, *, dtype, tiling: RowTiles,
             interpret: bool = False):
    """Token rows from buffer rows.  ``rows`` (m, 1, words) the buffer in
    row layout (:func:`rows_pack`), ``slot_of`` (t, k) int32 each pair's
    buffer row (``m`` where its expert is not held), ``lists`` its
    :func:`held_lists`, ``w`` (t, k) float32 weights (1 where None):
    (t, d) in ``dtype``, each token's sum over its held pairs of ``w`` times
    the pair's row, in float32, rounded once.  Only the held pairs' rows
    are read."""
    m, _, nw = rows.shape
    t, k = slot_of.shape
    d = nw * 4 // jnp.dtype(dtype).itemsize
    tt = tiling.tt
    dest, slot, counts = lists

    def kernel(cnt_ref, dest_ref, slot_ref, slotv_ref, *refs):
        refs = list(refs)
        w_ref = refs.pop(0) if w is not None else None
        rows_ref, out_ref, scr, sem = refs
        n = cnt_ref[pl.program_id(0)]

        def copy(q, s):
            return pltpu.make_async_copy(rows_ref.at[s], scr.at[q], sem)

        def start(q, c):
            copy(dest_ref[q], slot_ref[q]).start()
            return c

        def wait(q, c):
            copy(0, 0).wait()
            return c

        lax.fori_loop(0, n, start, 0)
        lax.fori_loop(0, n, wait, 0)
        acc = None
        for j in range(k):
            held = slotv_ref[:, j:j + 1] < m
            parts = _from_words(scr[j * tt:(j + 1) * tt].reshape(tt, nw), dtype)
            if w_ref is not None:
                parts = [p * w_ref[:, j:j + 1] for p in parts]
            parts = [jnp.where(held, p, 0.0) for p in parts]
            acc = parts if acc is None else [a + p for a, p in zip(acc, parts)]
        _store(out_ref, acc)

    in_specs = [pl.BlockSpec((tt * k,), lambda i, c: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec((tt * k,), lambda i, c: (i,), memory_space=pltpu.SMEM),
                pl.BlockSpec((tt, k), lambda i, c: (i, 0))]
    ins = [dest, slot, slot_of]
    if w is not None:
        in_specs.append(pl.BlockSpec((tt, k), lambda i, c: (i, 0)))
        ins.append(w)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    ins.append(rows)
    return pl.pallas_call(
        kernel,
        name=scopes.MOE_ROWS_SUM,
        out_shape=jax.ShapeDtypeStruct((t, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // tt,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, d), lambda i, c: (i, 0)),
            scratch_shapes=[pltpu.VMEM((k * tt, 1, nw), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=_params(),
        interpret=interpret,
    )(counts, *ins)
