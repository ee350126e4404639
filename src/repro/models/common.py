"""Shared model configuration and initialization helpers."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Attention:
    """Attention block options."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int | None = None  # sliding-window size (None = full causal)
    softcap: float | None = None  # attention-logit softcap (gemma2)
    rope_theta: float = 10000.0
    rope: str = "rope"  # 'rope' | 'mrope' | 'sinusoidal' | 'none'
    mrope_sections: tuple[int, int, int] | None = None  # qwen2-vl
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class MoE:
    """Mixture-of-experts options (None on the config = dense FFN).

    The router spans all ``n_experts``; this device holds ``held`` of them
    (all by default), ids ``first .. first + held - 1``: under expert
    parallelism the others lie on further devices, and each layer
    computes only the held experts' part (``models/moe``).  The top-k
    gates are renormalised to sum to 1."""

    n_experts: int
    top_k: int
    held: int | None = None
    first: int = 0
    aux_coef: float = 0.01  # weight of the load-balancing term in the loss

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held is None else self.held


@dataclasses.dataclass(frozen=True)
class Recurrent:
    """RG-LRU / RWKV-style recurrent block options."""

    kind: str  # 'rglru' | 'rwkv6'
    conv_width: int = 4  # temporal conv in the Griffin recurrent block
    lru_width: int | None = None  # defaults to d_model
    head_dim: int = 64  # rwkv6 wkv head size


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture from the assigned pool (or a reduced smoke variant)."""

    name: str
    family: str  # audio|dense|moe|ssm|hybrid|vlm
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    attention: Attention | None
    # repeating block pattern making up one scan stage, e.g. ('attn',) or
    # ('attn_local', 'attn_global') or ('rec', 'rec', 'attn_local');
    # n_layers = len(pattern) * n_stages + len(tail_pattern)
    pattern: tuple[str, ...] = ("attn",)
    tail_pattern: tuple[str, ...] = ()
    moe: MoE | None = None
    recurrent: Recurrent | None = None
    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm' | 'rmsnorm_gemma'
    post_norm: bool = False  # gemma2 adds post-block norms
    mlp: str = "swiglu"  # 'swiglu' | 'geglu' | 'gelu' | 'rwkv_cmix'
    tie_embeddings: bool = False
    logit_softcap: float | None = None
    input_mode: str = "tokens"  # 'tokens' | 'embeds' (audio/vlm stub frontends)
    param_dtype: Any = jnp.bfloat16
    # local-attention window used by '*_local' pattern entries
    local_window: int = 4096
    # implementation knobs (not architecture):
    q_chunk: int = 256  # query chunk for the jnp attention fallback
    rec_chunk: int = 128  # time chunk for chunked linear recurrences
    chunk_impl: str = "map"  # 'map' (memory-realistic) | 'unroll' (exact cost)
    remat: str = "full"  # 'full' | 'dots' | 'none'

    @property
    def n_stages(self) -> int:
        body = self.n_layers - len(self.tail_pattern)
        assert body % len(self.pattern) == 0, (
            f"{self.name}: {self.n_layers} layers do not tile by pattern "
            f"{self.pattern} + tail {self.tail_pattern}"
        )
        return body // len(self.pattern)

    def block_kinds(self) -> list[str]:
        """Per-layer kinds, length n_layers."""
        return list(self.pattern) * self.n_stages + list(self.tail_pattern)


def truncated_normal(key, shape, dtype, stddev: float):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev).astype(
        dtype
    )


def param_count(tree: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def cast_tree(tree: Any, dtype) -> Any:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )
