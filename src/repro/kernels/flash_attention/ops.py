"""The differentiable training op over the Pallas flash kernels.

``flash_attention_train`` is a custom VJP whose forward saves only the
kernels' operands, (o, lse) — no S×S residuals in HBM — and whose
backward runs the one Pallas kernel that computes dQ, dK and dV
(kernel_bwd.py).  Both rules work in the kernels' (B·H, S, hd) layout
and run the kernels once per device where the mesh leaves axes to the
compiler (``per_device``).
"""

from __future__ import annotations

import functools

import jax

from ..per_device import per_device
from .kernel import fwd_heads, from_heads, to_heads
from .kernel_bwd import bwd_heads


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_train(
    q, k, v,  # (B, S, Hq, hd), (B, S, Hkv, hd), (B, S, Hkv, hd)
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool = False,
):
    return _fat_fwd(q, k, v, causal, window, softcap, interpret)[0]


def _fat_fwd(q, k, v, causal, window, softcap, interpret):
    qt, kt, vt = to_heads(q), to_heads(k), to_heads(v)
    fwd = functools.partial(fwd_heads, causal=causal, window=window, softcap=softcap,
                            interpret=interpret)
    ot, lse = per_device(fwd)(qt, kt, vt)
    return from_heads(ot, q.shape[0]), (qt, kt, vt, ot, lse)


def _fat_bwd(causal, window, softcap, interpret, res, do):
    qt, kt, vt, ot, lse = res
    bwd = functools.partial(bwd_heads, causal=causal, window=window, softcap=softcap,
                            interpret=interpret)
    grads = per_device(bwd)(qt, kt, vt, ot, lse, to_heads(do))
    return tuple(from_heads(g, do.shape[0]) for g in grads)


flash_attention_train.defvjp(_fat_fwd, _fat_bwd)
