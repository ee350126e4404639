"""Pallas TPU kernels for the compute hot-spots the MG-WFBP schedule
overlaps against — flash attention, the MoE layer's grouped matmuls,
RWKV6 WKV, RG-LRU — plus the communication-side pack/unpack pair behind
the arena wire layout (``core/sync.py`` ``fuse='arena'``).

Each kernel package ships kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (dispatching wrapper) and ref.py (pure-jnp oracle; the
grouped matmul's oracle is ``jax.lax.ragged_dot``); tests sweep
shapes/dtypes in interpret mode against the oracles.
"""

from .comm_pack import pack_arena, pack_arena_ref, unpack_arena, unpack_arena_ref
from .flash_attention import attention_ref, flash_attention_fwd, flash_attention_train
from .rglru import rglru, rglru_pallas, rglru_ref
from .rwkv6_wkv import wkv, wkv_pallas, wkv_ref

__all__ = [
    "attention_ref",
    "pack_arena",
    "pack_arena_ref",
    "unpack_arena",
    "unpack_arena_ref",
    "flash_attention_fwd",
    "flash_attention_train",
    "rglru",
    "rglru_pallas",
    "rglru_ref",
    "wkv",
    "wkv_pallas",
    "wkv_ref",
]
