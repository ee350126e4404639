"""Operations and bytes a training step needs, from the configuration's
shapes alone (the benchmark's own arithmetic, not the program's)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def model_flops_per_token(fam, cfg: dict, seq: int) -> float:
    """Forward and backward FLOPs per token: 2 per multiply-add, 3 passes
    (forward, and backward to activations and to weights) over every
    weight a token multiplies through, plus the family's sequence mixer
    (attention or recurrence).  Recomputation is not counted."""
    return 6.0 * fam.matmul_params(cfg) + fam.mixer_flops_per_token(cfg, seq)


def comm_pack_bytes(fam, cfg: dict, wire_itemsize: int) -> int:
    """HBM bytes the arena wire forces per step on one chip: each
    gradient read and written back in its weight's dtype, and written to
    and read from the wire in the wire dtype."""
    shapes = jax.eval_shape(lambda k: fam.init(cfg, k), jax.random.PRNGKey(0))
    return sum(x.size * (2 * jnp.dtype(x.dtype).itemsize + 2 * wire_itemsize)
               for x in jax.tree.leaves(shapes))
