"""Arithmetic shared by the plain references: float32 at ``highest``
matmul precision, and the control's rounding to the precision below
the stated one."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn


def below(dtype) -> jnp.dtype:
    """The nearest precision below ``dtype``: bfloat16 -> float8 e4m3,
    float32 -> bfloat16."""
    return jnp.dtype(jnp.bfloat16) if jnp.dtype(dtype) == jnp.float32 else jnp.dtype(F8)


def lower(x, lowp: bool):
    """``x`` as float32; with ``lowp`` first rounded below its own dtype."""
    if lowp:
        x = x.astype(below(x.dtype))
    return x.astype(jnp.float32)


def mm(eq: str, a, b, lowp: bool):
    """``einsum`` in float32 at ``highest`` precision.  With ``lowp`` both
    operands are first rounded to float8 e4m3, the precision below the
    bfloat16 compute that the configurations state."""
    if lowp:
        a, b = a.astype(F8), b.astype(F8)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def trunc_normal(key, shape, std: float, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)
