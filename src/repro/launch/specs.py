"""ShapeDtypeStruct stand-ins for every model input of every (arch, shape)
cell — weak-type-correct, shardable, no device allocation."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..configs.shapes import SHAPES, ShapeSpec
from ..models import init_caches
from ..models.common import ArchConfig
from ..models.transformer import init_params
from ..optim.optimizers import Optimizer

Pytree = Any


def sds(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def param_specs(cfg: ArchConfig) -> Pytree:
    return sds(jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))


def opt_state_specs(cfg: ArchConfig, optimizer: Optimizer) -> Pytree:
    p = param_specs(cfg)
    return sds(jax.eval_shape(optimizer.init, p))


def cache_specs(cfg: ArchConfig, batch: int, max_seq: int) -> Pytree:
    return sds(
        jax.eval_shape(lambda: init_caches(cfg, batch=batch, max_seq=max_seq))
    )


def batch_input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """Training/prefill inputs: tokens or stub frontend embeddings."""
    B, S = shape.global_batch, shape.seq_len
    out = {"targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.input_mode == "embeds":
        out["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return out


def decode_input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    B = shape.global_batch
    if cfg.input_mode == "embeds":
        return {"embeds": jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)}
    return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}


def arch_config_for_shape(arch: str, shape_name: str, cost_mode: bool = False) -> ArchConfig:
    """Config tuned per shape: chunk sizes that bound dry-run memory in
    'map' mode, or 'unroll' for exact cost accounting in segments."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    q_chunk = 512 if shape.kind == "train" else 2048
    overrides = dict(
        q_chunk=min(q_chunk, shape.seq_len),
        chunk_impl="unroll" if cost_mode else "map",
        rec_chunk=128,
        remat="full" if shape.kind == "train" else "none",
    )
    return dataclasses.replace(cfg, **overrides)
