"""The grouped matmul of the MoE layer: ``grouped_matmul(x, w, sizes)``,
row block ``g`` of ``x`` (m, k) times ``w[g]`` (k, n), differentiable;
and the row kernels that move the layer's routed rows (``rows.py``):
:func:`row_tiling` says where they run, :func:`pack_rows`,
:func:`gather_rows` and :func:`sum_rows` run them once per device.

Dispatch: on a TPU, where :func:`.kernel.tilings` tiles the shapes
(every dimension a multiple of 128), the Pallas kernels run under a
custom VJP: forward ``moe_gmm``; backward ``moe_gmm`` against ``w``
transposed for ``dx`` and ``moe_tgmm`` for ``dw``.  They run once per
device where the mesh leaves axes to the compiler (``per_device``).
Elsewhere (the CPU, shapes that do not tile) ``jax.lax.ragged_dot``
computes the same product and its own gradients.  Both take the operands
in their dtype and accumulate in f32.  Rows past the last group are zeros
on the jnp path and left unwritten by the kernels: no caller reads them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..per_device import per_device
from . import rows
from .kernel import gmm, tgmm, tilings


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernels(x, w, sizes, tiles, interpret):
    return per_device(functools.partial(gmm, tiling=tiles[0], interpret=interpret))(x, w, sizes)


def _kernels_fwd(x, w, sizes, tiles, interpret):
    return _kernels(x, w, sizes, tiles, interpret), (x, w, sizes)


def _kernels_bwd(tiles, interpret, res, dy):
    x, w, sizes = res
    dx = per_device(functools.partial(gmm, tiling=tiles[1], transpose_rhs=True,
                                      interpret=interpret))(dy, w, sizes)
    dw = per_device(functools.partial(tgmm, tiling=tiles[2], interpret=interpret))(x, dy, sizes)
    return dx, dw, None


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   use_kernels: bool | None = None, interpret: bool = False) -> jax.Array:
    """``x`` (m, k) rows sorted by group, ``w`` (G, k, n), ``sizes`` (G,)
    int32 rows per group: (m, n) in ``x``'s dtype.  ``use_kernels``
    defaults to running on a TPU; ``interpret`` runs the kernels in
    Pallas's interpreter (tests)."""
    m, k = x.shape
    tiles = tilings(m, k, w.shape[2])
    if use_kernels is None:
        use_kernels = _on_tpu()
    if use_kernels and tiles is not None:
        return _kernels(x, w, sizes, tiles, interpret)
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32).astype(x.dtype)


def row_tiling(t: int, d: int, k: int, dtype) -> rows.RowTiles | None:
    """The tiling of the row kernels for ``t`` tokens of width ``d`` with
    ``k`` choices each, or None where the jnp path runs instead: off a
    TPU, and where the shapes do not tile (:func:`.rows.tiles`: decode's
    few tokens, widths not a multiple of 128 lanes)."""
    return rows.tiles(t, d, k, dtype) if _on_tpu() else None


def pack_rows(a, n_rows, b=None, *, tiling: rows.RowTiles, interpret: bool = False):
    def f(a, n_rows, b):
        return rows.rows_pack(a, n_rows, b, tm=tiling.tm, interpret=interpret)

    return per_device(f)(a, n_rows, b)


def gather_rows(src, token_of, n_rows, scale=None, ys=None, *, dtype, tiling: rows.RowTiles,
                interpret: bool = False):
    def f(src, token_of, n_rows, scale, ys):
        return rows.rows_gather(src, token_of, n_rows, scale, ys, dtype=dtype, tiling=tiling,
                                interpret=interpret)

    return per_device(f)(src, token_of, n_rows, scale, ys)


def sum_rows(buf_rows, slot_of, lists, w=None, *, dtype, tiling: rows.RowTiles,
             interpret: bool = False):
    def f(buf_rows, slot_of, lists, w):
        return rows.rows_sum(buf_rows, slot_of, lists, w, dtype=dtype, tiling=tiling,
                             interpret=interpret)

    return per_device(f)(buf_rows, slot_of, lists, w)
