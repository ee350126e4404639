"""Decoder-only LM over heterogeneous block patterns, with the
bucket-segmented layer scan that makes MG-WFBP's merge schedule a
structural property of the compiled program.

Parameters
----------
::

    params = {
      'embed':  (vocab, d)                      # tokens mode
      'stages': pytree stacked on a leading n_stages axis; each stage holds
                one param set per pattern element, keyed '<kind>_<i>'
      'tail':   like one stage, for tail_pattern (or absent)
      'final_norm': {...}
      'head':   (d, vocab)                      # absent when tie_embeddings
    }

The train/serve step functions take ``segments`` — ``(start, stop)`` stage
ranges produced by the MG-WFBP schedule (``core.bucketing``); each segment
is scanned separately so its gradient message is an independent HLO value
that the sync engine all-reduces as one merged (variadic) collective which
XLA can overlap with the previous segment's backward compute.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .common import ArchConfig, param_count, truncated_normal
from .layers import (
    apply_norm,
    attention_block,
    init_attention,
    init_mlp,
    init_norm,
    mlp_block,
    sinusoidal_embedding,
    softcap_logits,
)
from .moe import (
    add_aux,
    held_moe_block,
    init_moe,
    reduce_aux,
    sum_aux,
    zero_aux,
)
from .rglru import init_rglru_block, init_rglru_state, rglru_block
from .rwkv6 import init_rwkv6_block, init_rwkv6_state, rwkv6_block

Pytree = Any

#: The load-balancing weight the dense models' (zero) statistic is added
#: with, as it always was: their compiled step stays as it is.  An MoE
#: model weighs its term by its own ``MoE.aux_coef``.
MOE_AUX_COEF = 0.01


def with_aux(cfg: ArchConfig, ce: jax.Array, aux) -> tuple[jax.Array, dict]:
    """The training loss from the token cross-entropy and the layers'
    statistics (``models/moe``), and its metrics; an MoE model's carry
    the rows routed to its held experts and the largest expert load."""
    if cfg.moe is None:
        return ce + MOE_AUX_COEF * aux, {"ce": ce, "moe_aux": aux}
    return ce + cfg.moe.aux_coef * aux["loss"], {
        "ce": ce, "moe_aux": aux["loss"], "moe_held_rows": aux["held_rows"],
        "moe_max_expert_rows": aux["max_expert_rows"]}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_sublayer(key, cfg: ArchConfig, kind: str) -> dict:
    if kind == "rwkv":
        return init_rwkv6_block(key, cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict = {"norm1": init_norm(cfg, cfg.d_model), "norm2": init_norm(cfg, cfg.d_model)}
    if cfg.post_norm:
        p["post_norm1"] = init_norm(cfg, cfg.d_model)
        p["post_norm2"] = init_norm(cfg, cfg.d_model)
    if kind == "rec":
        p["mix"] = init_rglru_block(k1, cfg)
    else:  # attn / attn_local / attn_global / moe
        p["attn"] = init_attention(k1, cfg, cfg.attention)
    if kind == "moe":
        p["moe"] = init_moe(k2, cfg)
    else:
        p["mlp"] = init_mlp(k3, cfg)
    return p


def init_params(key, cfg: ArchConfig) -> Pytree:
    ks = jax.random.split(key, 8)
    params: dict = {}
    params["embed"] = truncated_normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32, 1.0)

    def init_stage(k):
        sub = {}
        kk = jax.random.split(k, len(cfg.pattern))
        for i, kind in enumerate(cfg.pattern):
            sub[f"{kind}_{i}"] = _init_sublayer(kk[i], cfg, kind)
        return sub

    stage_keys = jax.random.split(ks[1], cfg.n_stages)
    params["stages"] = jax.vmap(init_stage)(stage_keys)

    if cfg.tail_pattern:
        tail = {}
        kk = jax.random.split(ks[2], len(cfg.tail_pattern))
        for i, kind in enumerate(cfg.tail_pattern):
            tail[f"{kind}_{i}"] = _init_sublayer(kk[i], cfg, kind)
        params["tail"] = tail

    params["final_norm"] = init_norm(cfg, cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = truncated_normal(
            ks[3], (cfg.d_model, cfg.vocab), cfg.param_dtype, cfg.d_model ** -0.5
        )
    return params


# ---------------------------------------------------------------------------
# Stage application
# ---------------------------------------------------------------------------


def _window_for(cfg: ArchConfig, kind: str) -> int | None:
    if kind == "attn_local":
        return cfg.local_window
    if kind in ("attn", "moe") and cfg.attention and cfg.attention.window:
        return cfg.attention.window
    return None


def apply_sublayer(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    kind: str,
    *,
    positions: jax.Array,
    cache: Pytree | None = None,
    q_offset: jax.Array | int = 0,
) -> tuple[jax.Array, Pytree | None, jax.Array]:
    """Returns (x, new_cache, aux): the layer's statistics (:func:`zero_aux`)."""
    aux = zero_aux(cfg)
    if kind == "rwkv":
        x, new_state = rwkv6_block(p, x, cfg, cache)
        return x, new_state, aux

    if kind == "rec":
        h = apply_norm(cfg, p["norm1"], x)
        h, new_state = rglru_block(p["mix"], h, cfg, cache)
        if cfg.post_norm:
            h = apply_norm(cfg, p["post_norm1"], h)
        x = x + h
    else:
        h = apply_norm(cfg, p["norm1"], x)
        h, new_state = attention_block(
            p["attn"], h, cfg, cfg.attention,
            positions=positions,
            window=_window_for(cfg, kind),
            kv_cache=cache,
            q_offset=q_offset,
        )
        if cfg.post_norm:
            h = apply_norm(cfg, p["post_norm1"], h)
        x = x + h

    h = apply_norm(cfg, p["norm2"], x)
    if kind == "moe":
        h, aux = held_moe_block(p["moe"], h, cfg)
    else:
        h = mlp_block(p["mlp"], h, cfg)
    if cfg.post_norm:
        h = apply_norm(cfg, p["post_norm2"], h)
    return x + h, new_state, aux


def _remat_wrap(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.remat(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.remat(fn)


def apply_stage(
    stage_p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    pattern: tuple[str, ...],
    *,
    positions: jax.Array,
    caches: Pytree | None = None,
    q_offset: jax.Array | int = 0,
) -> tuple[jax.Array, Pytree | None, jax.Array]:
    aux_total = zero_aux(cfg)
    new_caches = {} if caches is not None else None
    for i, kind in enumerate(pattern):
        key = f"{kind}_{i}"
        cache = caches[key] if caches is not None else None
        x, nc, aux = apply_sublayer(
            stage_p[key], x, cfg, kind,
            positions=positions, cache=cache, q_offset=q_offset,
        )
        aux_total = add_aux(aux_total, aux)
        if new_caches is not None:
            new_caches[key] = nc
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# Forward (training / prefill / decode share this body)
# ---------------------------------------------------------------------------


def forward(
    params: Pytree,
    cfg: ArchConfig,
    *,
    tokens: jax.Array | None = None,  # (B, S) int32
    embeds: jax.Array | None = None,  # (B, S, D) — audio/vlm stub frontends
    positions: jax.Array | None = None,
    segments: tuple[tuple[int, int], ...] | None = None,
    caches: Pytree | None = None,  # stacked per-stage caches for serving
    q_offset: jax.Array | int = 0,
    act_sharding_constraint=None,  # callable x -> x, applied between stages
    return_hidden: bool = False,  # skip the head (chunked-CE path)
) -> tuple[jax.Array, Pytree | None, jax.Array]:
    """Returns (logits fp32 — or final hidden states when
    ``return_hidden`` — , new_caches, moe_aux)."""
    if embeds is None:
        x = params["embed"][tokens].astype(cfg.param_dtype)
        if cfg.tie_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.param_dtype)  # gemma scaling
    else:
        x = embeds.astype(cfg.param_dtype)
    B, S = x.shape[:2]

    if positions is None:
        base = jnp.arange(S)[None, :] + q_offset
        if cfg.attention and cfg.attention.rope == "mrope":
            positions = jnp.broadcast_to(base, (3, B, S))
        else:
            positions = jnp.broadcast_to(base, (B, S))

    if cfg.attention and cfg.attention.rope == "sinusoidal":
        pos0 = q_offset if isinstance(q_offset, int) else 0
        pe = sinusoidal_embedding(S, cfg.d_model, offset=pos0).astype(x.dtype)
        x = x + pe[None]

    if segments is None:
        segments = ((0, cfg.n_stages),)
    constrain = act_sharding_constraint or (lambda a: a)

    aux_total = zero_aux(cfg)
    new_stage_caches = None

    def stage_body(x, stage_p_and_cache):
        stage_p, cache = stage_p_and_cache
        x = constrain(x)
        fn = _remat_wrap(
            cfg,
            lambda sp, xx, cc: apply_stage(
                sp, xx, cfg, cfg.pattern,
                positions=positions, caches=cc, q_offset=q_offset,
            ),
        )
        x, new_cache, aux = fn(stage_p, x, cache)
        return x, (new_cache, aux)

    collected_caches = []
    aux_parts = []
    for (start, stop) in segments:
        seg_params = jax.tree.map(lambda a: a[start:stop], params["stages"])
        seg_caches = (
            jax.tree.map(lambda a: a[start:stop], caches["stages"])
            if caches is not None
            else None
        )
        x, (seg_new_caches, seg_aux) = jax.lax.scan(
            stage_body, x, (seg_params, seg_caches)
        )
        aux_parts.append(reduce_aux(seg_aux))
        if caches is not None:
            collected_caches.append(seg_new_caches)

    if caches is not None:
        new_stage_caches = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *collected_caches
        ) if len(collected_caches) > 1 else collected_caches[0]

    new_caches = None
    if cfg.tail_pattern:
        tail_cache = caches["tail"] if caches is not None else None
        x = constrain(x)
        fn = _remat_wrap(
            cfg,
            lambda sp, xx, cc: apply_stage(
                sp, xx, cfg, cfg.tail_pattern,
                positions=positions, caches=cc, q_offset=q_offset,
            ),
        )
        x, new_tail_cache, aux = fn(params["tail"], x, tail_cache)
        aux_parts.append(aux)
        if caches is not None:
            new_caches = {"stages": new_stage_caches, "tail": new_tail_cache}
    elif caches is not None:
        new_caches = {"stages": new_stage_caches}

    aux_total = sum_aux(aux_parts) if aux_parts else aux_total

    x = apply_norm(cfg, params["final_norm"], x)
    if return_hidden:
        return x, new_caches, aux_total
    head = params["embed"].T.astype(cfg.param_dtype) if cfg.tie_embeddings else params["head"]
    logits = (x @ head).astype(jnp.float32)
    logits = softcap_logits(logits, cfg.logit_softcap)
    return logits, new_caches, aux_total


# ---------------------------------------------------------------------------
# Losses / steps
# ---------------------------------------------------------------------------


CHUNKED_CE_VOCAB = 64000  # big-vocab archs never materialize full logits
CE_SEQ_CHUNK = 512


def _ce_from_hidden(
    cfg: ArchConfig,
    head: jax.Array,
    x: jax.Array,
    targets: jax.Array,
    mask: jax.Array | None = None,
    logits_sharding_constraint=None,
) -> jax.Array:
    """Cross-entropy from post-final-norm hidden states.

    The one CE implementation both the monolithic ``loss_fn`` and the
    DAG step's staged head closure run — shared so the two steps compute
    the same floats.  ``head`` is the (d, vocab) projection (already
    transposed when embeddings are tied).

    Chunked path: the (B, S, V) fp32 logits of a 100k–256k vocab
    dominate training memory when the model axis is consumed by the
    batch; computing the loss per sequence chunk under remat bounds the
    transient to (B, CE_SEQ_CHUNK, V) and recomputes it in backward.
    (``mask`` is a standard-path feature; the chunked archs train
    unmasked.)
    """
    seq = targets.shape[1]
    use_chunked = (
        cfg.vocab >= CHUNKED_CE_VOCAB
        and seq > CE_SEQ_CHUNK
        and seq % CE_SEQ_CHUNK == 0
    )
    if use_chunked:

        @jax.remat
        def ce_chunk(x_c, t_c):
            logits = (x_c @ head).astype(jnp.float32)
            if logits_sharding_constraint is not None:
                logits = logits_sharding_constraint(logits)
            logits = softcap_logits(logits, cfg.logit_softcap)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - ll)

        n_chunks = seq // CE_SEQ_CHUNK

        def body(ci):
            x_c = jax.lax.dynamic_slice_in_dim(x, ci * CE_SEQ_CHUNK, CE_SEQ_CHUNK, 1)
            t_c = jax.lax.dynamic_slice_in_dim(targets, ci * CE_SEQ_CHUNK, CE_SEQ_CHUNK, 1)
            return ce_chunk(x_c, t_c)

        if cfg.chunk_impl == "unroll":
            total_nll = sum(body(i) for i in range(n_chunks))
        else:
            total_nll = jnp.sum(jax.lax.map(body, jnp.arange(n_chunks)))
        return total_nll / (targets.shape[0] * seq)

    logits = (x @ head).astype(jnp.float32)
    logits = softcap_logits(logits, cfg.logit_softcap)
    if logits_sharding_constraint is not None:
        logits = logits_sharding_constraint(logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(lse - ll)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(
    params: Pytree,
    batch: dict,
    cfg: ArchConfig,
    segments: tuple[tuple[int, int], ...] | None = None,
    act_sharding_constraint=None,
    logits_sharding_constraint=None,
) -> tuple[jax.Array, dict]:
    x, _, aux = forward(
        params, cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        segments=segments,
        act_sharding_constraint=act_sharding_constraint,
        return_hidden=True,
    )
    head = (
        params["embed"].T.astype(cfg.param_dtype)
        if cfg.tie_embeddings
        else params["head"]
    )
    ce = _ce_from_hidden(
        cfg, head, x, batch["targets"], mask=batch.get("mask"),
        logits_sharding_constraint=logits_sharding_constraint,
    )
    return with_aux(cfg, ce, aux)


def staged_loss_fns(
    cfg: ArchConfig,
    batch: dict,
    segments: tuple[tuple[int, int], ...],
    act_sharding_constraint=None,
    logits_sharding_constraint=None,
):
    """Split the training loss into per-unit closures for the DAG step.

    Returns ``(embed_fn, seg_fns, tail_fn, head_fn)``:

    * ``embed_fn(embed_p) -> x`` — token lookup (+ gemma scaling +
      sinusoidal PE), or the input cast in ``embeds`` mode;
    * ``seg_fns[j](seg_params, x) -> (x, aux)`` — one scan over the
      stages of ``segments[j]`` (caller slices the stacked params);
    * ``tail_fn(tail_p, x) -> (x, aux)`` or ``None``;
    * ``head_fn(head_p, embed_p, x, aux) -> (loss, metrics)`` —
      final-norm + projection + CE (``head_p`` holds ``final_norm`` and,
      untied, ``head``; tied embeddings read ``embed_p`` so its vjp
      carries the tied d_embed contribution).

    Chained with ``jax.vjp`` these compute the same loss as ``loss_fn``
    over the same ``segments`` (shared ``apply_stage`` bodies, shared
    ``_ce_from_hidden``); the split exists so the train step can walk
    the pullbacks in backward order and issue each schedule group's
    all-reduce at the event where its last gradient lands.
    """
    targets = batch["targets"]
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    B, S = (tokens.shape if embeds is None else embeds.shape[:2])

    base = jnp.arange(S)[None, :]
    if cfg.attention and cfg.attention.rope == "mrope":
        positions = jnp.broadcast_to(base, (3, B, S))
    else:
        positions = jnp.broadcast_to(base, (B, S))
    constrain = act_sharding_constraint or (lambda a: a)

    def embed_fn(embed_p):
        if embeds is None:
            x = embed_p[tokens].astype(cfg.param_dtype)
            if cfg.tie_embeddings:
                x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.param_dtype)
        else:
            x = embeds.astype(cfg.param_dtype)
        if cfg.attention and cfg.attention.rope == "sinusoidal":
            pe = sinusoidal_embedding(S, cfg.d_model, offset=0).astype(x.dtype)
            x = x + pe[None]
        return x

    def _stage_apply(pattern):
        def apply(p, v):
            y, _, aux = apply_stage(p, v, cfg, pattern, positions=positions)
            return y, aux

        return _remat_wrap(cfg, apply)

    def make_seg_fn():
        stage_fn = _stage_apply(cfg.pattern)

        def seg_fn(seg_params, x):
            def body(xx, sp):
                return stage_fn(sp, constrain(xx))

            x, auxs = jax.lax.scan(body, x, seg_params)
            return x, reduce_aux(auxs)

        return seg_fn

    seg_fns = tuple(make_seg_fn() for _ in segments)

    tail_fn = None
    if cfg.tail_pattern:
        tail_stage_fn = _stage_apply(cfg.tail_pattern)

        def tail_fn(tail_p, x):
            return tail_stage_fn(tail_p, constrain(x))

    def head_fn(head_p, embed_p, x, aux):
        x = apply_norm(cfg, head_p["final_norm"], x)
        head = (
            embed_p.T.astype(cfg.param_dtype)
            if cfg.tie_embeddings
            else head_p["head"]
        )
        ce = _ce_from_hidden(
            cfg, head, x, targets, mask=batch.get("mask"),
            logits_sharding_constraint=logits_sharding_constraint,
        )
        return with_aux(cfg, ce, aux)

    return embed_fn, seg_fns, tail_fn, head_fn


# ---------------------------------------------------------------------------
# Serving: KV/state caches
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Pytree:
    """Empty decode caches for all stages (+tail)."""
    att = cfg.attention

    def cache_for(kind: str):
        if kind == "rwkv":
            return init_rwkv6_state(cfg, batch)
        if kind == "rec":
            return init_rglru_state(cfg, batch)
        window = _window_for(cfg, kind)
        T = min(max_seq, window) if window else max_seq
        shape = (batch, T, att.n_kv_heads, att.head_dim)
        return (
            jnp.zeros(shape, dtype),
            jnp.zeros(shape, dtype),
            jnp.full((T,), 2**30, jnp.int32),  # slot -> absolute position
        )

    def stage_cache():
        return {f"{kind}_{i}": cache_for(kind) for i, kind in enumerate(cfg.pattern)}

    one = stage_cache()
    stages = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_stages,) + a.shape), one
    )
    out = {"stages": stages}
    if cfg.tail_pattern:
        out["tail"] = {
            f"{kind}_{i}": cache_for(kind) for i, kind in enumerate(cfg.tail_pattern)
        }
    return out


def describe_params(cfg: ArchConfig, params: Pytree) -> str:
    n = param_count(params)
    return f"{cfg.name}: {n / 1e9:.3f}B params ({cfg.n_layers} layers, d={cfg.d_model})"
