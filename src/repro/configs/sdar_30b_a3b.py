"""SDAR-30B-A3B [hf:JetLM/SDAR-30B-A3B-Chat, config.json; model_type
sdar_moe].

48L d_model=2048 32H (GQA kv=4) head_dim=128, per-head RMSNorm on q and
k before the rotary (the Qwen3-MoE attention the family is built on; not
a key of config.json), rope_theta=1e6, RMSNorm eps 1e-6; every layer an
MoE of 128 experts of width 768 (SwiGLU), top-8 with the top-k gates
renormalised, no shared expert; vocab=151936, untied head.  SDAR
generates by diffusion over blocks; the program trains the decoder with
the next-token objective of the autoregressive stage it starts from, and
without the load-balancing term (``aux_coef`` 0).
"""

from repro.models.common import ArchConfig, Attention, MoE


def config() -> ArchConfig:
    return ArchConfig(
        name="sdar-30b-a3b",
        family="moe",
        n_layers=48,
        d_model=2048,
        d_ff=768,  # moe_intermediate_size: the expert width
        vocab=151936,
        attention=Attention(n_heads=32, n_kv_heads=4, head_dim=128, qk_norm=True,
                            rope_theta=1e6, window=None),
        pattern=("moe",),
        moe=MoE(n_experts=128, top_k=8, aux_coef=0.0),
        norm="rmsnorm",
        mlp="swiglu",
        tie_embeddings=False,
    )


def reduced() -> ArchConfig:
    """Small widths for the CPU tests; the router keeps 128 experts and
    top-8, and this device holds the first 16 of them (one share of
    eight, as the benchmark's chip does)."""
    import dataclasses

    return dataclasses.replace(
        config(),
        name="sdar-30b-a3b-reduced",
        n_layers=2,
        d_model=64,
        d_ff=32,
        vocab=512,
        attention=Attention(n_heads=4, n_kv_heads=2, head_dim=16, qk_norm=True, rope_theta=1e6),
        moe=MoE(n_experts=128, top_k=8, held=16, first=0, aux_coef=0.0),
        q_chunk=32,
    )
