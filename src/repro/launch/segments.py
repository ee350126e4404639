"""Segment cost accounting — exact roofline inputs on a CPU-only host.

``compiled.cost_analysis()`` counts a ``lax.scan`` body once (verified in
prototyping), so whole-program numbers undercount the layer loop.  We
therefore lower three *segments* with production shardings and 'unroll'
chunk mode (exact flops) and recompose:

    total = head_segment + stage_segment * n_stages (+ tail_segment)

Collective traffic per segment comes from the compiled HLO text
(core.profiler.parse_collectives).  All numbers are per device.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.profiler import CollectiveStats, parse_collectives
from ..models.common import ArchConfig
from ..models.transformer import apply_stage, init_params
from ..models.layers import apply_norm, softcap_logits
from ..parallel.context import activation_sharding, from_rules
from ..parallel.sharding import (
    ShardingRules,
    batch_specs,
    param_pspecs,
    cache_pspecs,
)
from .specs import cache_specs, param_specs

Pytree = Any


@dataclasses.dataclass
class SegCost:
    name: str
    flops: float
    bytes_accessed: float
    coll_counts: dict[str, int]
    coll_bytes: dict[str, int]

    @property
    def coll_total_bytes(self) -> int:
        return sum(self.coll_bytes.values())


def _cost_of(name: str, compiled) -> SegCost:
    ca = compiled.cost_analysis() or {}
    st = parse_collectives(compiled.as_text())
    return SegCost(
        name=name,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_counts=st.counts,
        coll_bytes=st.bytes_by_kind,
    )


def _stage_tree_and_specs(cfg: ArchConfig, rules: ShardingRules, mesh):
    """(stage param ShapeDtypeStructs, NamedShardings) for ONE stage."""
    full = param_specs(cfg)
    pspecs = param_pspecs(full, rules)
    stage_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), full["stages"]
    )
    stage_specs = jax.tree.map(
        lambda s: P(*tuple(s)[1:]) if len(tuple(s)) > 0 else P(),
        pspecs["stages"],
        is_leaf=lambda x: isinstance(x, P),
    )
    stage_sh = jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), stage_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return stage_shapes, stage_sh


def _x_sharding(rules: ShardingRules, mesh, batch: int):
    return jax.NamedSharding(mesh, P(rules.batch_axes(batch), None, None))


def stage_train_segment(
    cfg: ArchConfig, rules: ShardingRules, mesh, batch: int, seq: int,
    pattern: tuple[str, ...] | None = None,
) -> SegCost:
    """One stage forward+backward at training shape."""
    pattern = pattern or cfg.pattern
    stage_shapes, stage_sh = _stage_tree_and_specs(cfg, rules, mesh)
    if pattern is cfg.tail_pattern or pattern == cfg.tail_pattern:
        full = param_specs(cfg)
        pspecs = param_pspecs(full, rules)
        stage_shapes = full["tail"]
        stage_sh = jax.tree.map(
            lambda s: jax.NamedSharding(mesh, s), pspecs["tail"],
            is_leaf=lambda x: isinstance(x, P),
        )
    x_spec = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.param_dtype)
    x_sh = _x_sharding(rules, mesh, batch)

    def seg(stage_p, x, dy):
        pos_shape = (3, batch, seq) if (cfg.attention and cfg.attention.rope == "mrope") else (batch, seq)
        pos = jnp.broadcast_to(jnp.arange(seq), pos_shape)

        def f(sp, xx):
            prefer = "tp" if rules.reserve_model else "fsdp"
            with activation_sharding(from_rules(rules, batch, prefer=prefer)):
                y, _, aux = apply_stage(sp, xx, cfg, pattern, positions=pos)
            return y, aux

        (y, aux), vjp = jax.vjp(f, stage_p, x)
        dsp, dx = vjp((dy, jax.tree.map(jnp.zeros_like, aux)))
        return y, dsp, dx

    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(seg, in_shardings=(stage_sh, x_sh, x_sh))
            .lower(stage_shapes, x_spec, x_spec)
            .compile()
        )
    return _cost_of("stage_train", compiled)


def stage_fwd_segment(
    cfg: ArchConfig, rules: ShardingRules, mesh, batch: int, seq: int,
    caches: Pytree | None = None, cache_sh: Pytree | None = None,
    pos_value: int = 0,
    pattern: tuple[str, ...] | None = None,
) -> SegCost:
    """One stage forward (prefill / decode)."""
    pattern = pattern or cfg.pattern
    stage_shapes, stage_sh = _stage_tree_and_specs(cfg, rules, mesh)
    x_spec = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.param_dtype)
    x_sh = _x_sharding(rules, mesh, batch)

    def seg(stage_p, x, cache):
        pos_shape = (3, batch, seq) if (cfg.attention and cfg.attention.rope == "mrope") else (batch, seq)
        pos = jnp.broadcast_to(jnp.arange(seq) + pos_value, pos_shape)
        if caches is not None:
            prefer = "fsdp"  # decode: caches carry the TP
        else:
            prefer = "tp" if rules.reserve_model else "seq_tp"
        with activation_sharding(from_rules(rules, batch, prefer=prefer)):
            y, new_cache, _ = apply_stage(
                stage_p, x, cfg, pattern,
                positions=pos, caches=cache, q_offset=pos_value,
            )
        return y, new_cache

    args = (stage_shapes, x_spec, caches)
    shardings = (stage_sh, x_sh, cache_sh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(seg, in_shardings=shardings).lower(*args).compile()
    return _cost_of("stage_fwd", compiled)


def head_train_segment(
    cfg: ArchConfig, rules: ShardingRules, mesh, batch: int, seq: int
) -> SegCost:
    """Embed lookup + final norm + head matmul + CE, forward+backward."""
    full = param_specs(cfg)
    pspecs = param_pspecs(full, rules)
    keys = ["embed", "final_norm"] + ([] if cfg.tie_embeddings else ["head"])
    hp_shapes = {k: full[k] for k in keys}
    hp_sh = jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), {k: pspecs[k] for k in keys},
        is_leaf=lambda x: isinstance(x, P),
    )
    ba = rules.batch_axes(batch)
    x_sh = _x_sharding(rules, mesh, batch)
    tok_sh = jax.NamedSharding(mesh, P(ba, None))
    vocab_ax = "model" if cfg.vocab % rules.model_size == 0 else None
    if ba and rules.model_axis in ba:
        vocab_ax = None

    def seg(hp, batch_in, x_mid):
        if cfg.input_mode == "embeds":
            x = batch_in["embeds"].astype(cfg.param_dtype)
        else:
            x = hp["embed"][batch_in["tokens"]].astype(cfg.param_dtype)
        x = x + x_mid  # stand-in for the stage stack output
        x = apply_norm(cfg, hp["final_norm"], x)
        head = hp["embed"].T.astype(cfg.param_dtype) if cfg.tie_embeddings else hp["head"]
        logits = (x @ head).astype(jnp.float32)
        logits = jax.lax.with_sharding_constraint(logits, P(ba, None, vocab_ax))
        logits = softcap_logits(logits, cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch_in["targets"][..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)

    bspecs = batch_specs(cfg, rules, batch, seq)
    batch_in = {
        "targets": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.input_mode == "embeds":
        batch_in["embeds"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16)
    else:
        batch_in["tokens"] = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    b_sh = jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), bspecs, is_leaf=lambda x: isinstance(x, P)
    )
    x_spec = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.param_dtype)

    def seg_grad(hp, batch_in, x_mid):
        return jax.value_and_grad(seg)(hp, batch_in, x_mid)

    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(seg_grad, in_shardings=(hp_sh, b_sh, x_sh))
            .lower(hp_shapes, batch_in, x_spec)
            .compile()
        )
    return _cost_of("head_train", compiled)


def head_fwd_segment(
    cfg: ArchConfig, rules: ShardingRules, mesh, batch: int, seq: int
) -> SegCost:
    """Embed + final norm + head, forward only (serving)."""
    full = param_specs(cfg)
    pspecs = param_pspecs(full, rules)
    keys = ["embed", "final_norm"] + ([] if cfg.tie_embeddings else ["head"])
    hp_shapes = {k: full[k] for k in keys}
    hp_sh = jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s), {k: pspecs[k] for k in keys},
        is_leaf=lambda x: isinstance(x, P),
    )
    ba = rules.batch_axes(batch)
    x_sh = _x_sharding(rules, mesh, batch)
    vocab_ax = "model" if cfg.vocab % rules.model_size == 0 else None
    if ba and rules.model_axis in ba:
        vocab_ax = None

    def seg(hp, x):
        x = apply_norm(cfg, hp["final_norm"], x)
        head = hp["embed"].T.astype(cfg.param_dtype) if cfg.tie_embeddings else hp["head"]
        logits = (x @ head).astype(jnp.float32)
        logits = jax.lax.with_sharding_constraint(logits, P(ba, None, vocab_ax))
        return softcap_logits(logits, cfg.logit_softcap)

    x_spec = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.param_dtype)
    with jax.set_mesh(mesh):
        compiled = jax.jit(seg, in_shardings=(hp_sh, x_sh)).lower(hp_shapes, x_spec).compile()
    return _cost_of("head_fwd", compiled)
