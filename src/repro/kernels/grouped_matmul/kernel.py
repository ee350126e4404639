"""Pallas TPU grouped matmuls over rows sorted by group (the experts of
an MoE layer), adapted from the megablox design bundled with JAX
(``jax.experimental.pallas.ops.tpu.megablox``).

``lhs`` holds ``m`` rows, sorted so that group ``g`` owns rows
``offsets[g] .. offsets[g + 1] - 1`` (``offsets`` = the running sum of
``group_sizes``).  Rows past the last group are buffer space: no kernel
reads them into a result, and :func:`gmm` leaves its output there
unwritten.

TPU mapping
-----------
The grid walks ``tm``-row tiles of ``lhs`` in order.  A tile that two
groups share is visited once for each of them, back to back, so its
output block stays in VMEM between the visits and each visit stores only
its own group's rows.  The tile list is built on the host side of the
kernel from ``group_sizes`` (:func:`group_metadata`) and reaches the
kernel as scalar prefetch; its length, and so the grid, is the number of
tiles the groups cover: the work follows the rows routed, not the buffer.

* :func:`gmm`: ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (or
  ``rhs[g].T``), grid ``(n tiles, row tiles, k tiles)``, an f32 VMEM
  accumulator per output tile;
* :func:`tgmm`: ``out[g] = lhs[rows of g].T @ rhs[rows of g]``, grid
  ``(k tiles, n tiles, row tiles)``; rows of other groups are masked out
  of each tile, and an empty group's block is written as zeros.

The MXU takes the operands in their own dtype (bf16 in training) and
accumulates in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes


def group_metadata(group_sizes: jax.Array, m: int, tm: int, visit_empty: bool):
    """``((offsets, group_ids, tile_ids), n_tiles)`` for ``m`` rows in
    ``tm``-row tiles.  Grid step ``i < n_tiles`` works on row tile
    ``tile_ids[i]`` for group ``group_ids[i]``; tiles past the last group
    get no step.  ``visit_empty`` gives an empty group one step (the
    weight gradient must still write its zeros)."""
    G = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(jnp.int32)
    # tiles each group touches: from its first row's tile to its last's
    first_tile, end_tile = starts // tm, (ends + tm - 1) // tm
    group_tiles = jnp.where(group_sizes == 0, 0, end_tile - first_tile)
    if visit_empty:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    n_steps = tiles_m + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), group_tiles,
                           total_repeat_length=n_steps)
    # a tile is visited once by the group owning its first row, and once
    # more by each group that starts inside it (or is empty there)
    starts_inside = (starts % tm != 0) & (group_sizes > 0)
    if visit_empty:
        starts_inside = starts_inside | (group_sizes == 0)
    extra = jnp.where(starts_inside, jnp.minimum(starts // tm, tiles_m - 1), tiles_m)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[extra].add(1)[:tiles_m] + 1
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                          total_repeat_length=n_steps)
    return (offsets, group_ids, tile_ids), jnp.sum(group_tiles)


def _row_mask(meta, i, tm: int, width: int):
    offsets, group_ids, tile_ids = meta
    g = group_ids[i]
    row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + tile_ids[i] * tm
    return (row >= offsets[g]) & (row < offsets[g + 1])


def gmm(lhs, rhs, group_sizes, *, tiling: tuple[int, int, int], transpose_rhs: bool = False,
        interpret: bool = False):
    """``lhs`` (m, k) by group into ``rhs`` (G, k, n) — or (G, n, k) with
    ``transpose_rhs`` — giving (m, n) in ``lhs``'s dtype."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (m, k, n, tiling)
    meta, n_tiles = group_metadata(group_sizes, m, tm, visit_empty=False)
    nk = k // tk

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        i, ik = pl.program_id(1), pl.program_id(2)

        @pl.when(ik == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                        preferred_element_type=jnp.float32)

        @pl.when(ik == nk - 1)
        def _():
            keep = _row_mask(meta, i, tm, tn)
            out_ref[...] = jnp.where(keep, acc_ref[...],
                                     out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    def lhs_map(jn, i, ik, meta):
        return meta[2][i], ik

    def rhs_map(jn, i, ik, meta):
        return (meta[1][i], jn, ik) if transpose_rhs else (meta[1][i], ik, jn)

    def out_map(jn, i, ik, meta):
        return meta[2][i], jn

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        kernel,
        name=scopes.MOE_GMM,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, n_tiles, nk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, lhs, rhs)


def tgmm(lhs, rhs, group_sizes, *, tiling: tuple[int, int, int], interpret: bool = False):
    """Per group, ``lhs`` (m, k) rows transposed into ``rhs`` (m, n) rows:
    (G, k, n) in ``lhs``'s dtype (zeros for an empty group)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    G = group_sizes.shape[0]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (m, k, n, tiling)
    meta, n_tiles = group_metadata(group_sizes, m, tm, visit_empty=True)

    def kernel(meta, lhs_ref, rhs_ref, out_ref, acc_ref):
        offsets, group_ids, _ = meta
        i, last = pl.program_id(2), pl.num_programs(2) - 1
        g = group_ids[i]
        prev, nxt = group_ids[jnp.maximum(i - 1, 0)], group_ids[jnp.minimum(i + 1, last)]

        @pl.when((i == 0) | (prev != g))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _():
            # the rows transposed in f32, as Mosaic transposes 32-bit tiles
            x = jnp.where(_row_mask(meta, i, tm, tk), lhs_ref[...].astype(jnp.float32), 0.0)
            dy = jnp.where(_row_mask(meta, i, tm, tn), rhs_ref[...], 0)
            acc_ref[...] += jnp.dot(x.T.astype(lhs_ref.dtype), dy,
                                    preferred_element_type=jnp.float32)

        @pl.when((i == last) | (nxt != g))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(jk, jn, i, meta):
        return meta[2][i], jk

    def rhs_map(jk, jn, i, meta):
        return meta[2][i], jn

    def out_map(jk, jn, i, meta):
        return meta[1][i], jk, jn

    return pl.pallas_call(
        kernel,
        name=scopes.MOE_TGMM,
        out_shape=jax.ShapeDtypeStruct((G, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, n_tiles),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map), pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(meta, lhs, rhs)


def tile(dim: int, prefer: tuple[int, ...]) -> int | None:
    """The first of ``prefer`` that divides ``dim``, else None."""
    return next((t for t in prefer if dim % t == 0), None)


@functools.lru_cache(maxsize=None)
def tilings(m: int, k: int, n: int) -> tuple[tuple[int, int, int], ...] | None:
    """Tiles of the three kernels of one ``(m, k) x (G, k, n)`` product:
    forward ``gmm``, the ``lhs`` gradient (``gmm`` against ``rhs``
    transposed: contracts over ``n``, gives ``k``) and the ``rhs``
    gradient (``tgmm``: contracts over rows, gives ``(k, n)``).  512-row
    tiles; the other two dimensions at most 1024 wide, so that the
    double-buffered blocks and the f32 accumulator stay within 16 MiB of
    VMEM.  None where a dimension does not tile (multiples of 128)."""
    tm = tile(m, (512, 256, 128))
    tk, tn = tile(k, (1024, 768, 512, 256, 128)), tile(n, (1024, 768, 512, 256, 128))
    if None in (tm, tk, tn):
        return None
    return (tm, tk, tn), (tm, tn, tk), (tm, tk, tn)
