"""Transformer building blocks: norms, RoPE/M-RoPE, GQA attention (full /
sliding-window / softcap), dense & gated MLPs, logit softcap.

``gqa_attention`` runs self-attention over freshly projected K/V (training,
and prefill without a cache) through the Pallas flash kernels
(``kernels.flash_attention.flash_attention_train``) on a TPU, where the
shapes suit them and no tensor parallelism splits the heads: no S×S
scores reach HBM.  Everything else — the CPU, decode and attention
against a KV cache, the TP and sequence-TP paths — runs the jnp path:
query-chunked masked attention with bounded score memory.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import scopes
from ..kernels.flash_attention import flash_attention_train
from ..parallel.context import constrain, tp_active, tp_size
from .common import ArchConfig, Attention, truncated_normal

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ArchConfig, dim: int) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((dim,), cfg.param_dtype), "bias": jnp.zeros((dim,), cfg.param_dtype)}
    return {"scale": jnp.zeros((dim,), cfg.param_dtype) if cfg.norm == "rmsnorm_gemma" else jnp.ones((dim,), cfg.param_dtype)}


def apply_norm(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + 1e-6)
    scale = p["scale"].astype(jnp.float32)
    if cfg.norm == "rmsnorm_gemma":
        scale = scale + 1.0  # gemma stores scale-1
    return (y * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE, M-RoPE, sinusoidal)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,  # (3, ..., S): (t, h, w) streams
    theta: float,
    sections: tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are split into three
    sections rotated by temporal / height / width positions respectively.
    For text-only tokens the three streams coincide and M-RoPE reduces to
    standard RoPE (tested)."""
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)  # (half,)
    # section id per half-dim
    sec = np.concatenate([np.full((s,), i) for i, s in enumerate(sections)])
    pos_per_dim = jnp.stack([positions[i] for i in range(3)], axis=0)  # (3, ..., S)
    # select stream per half-dim: (..., S, half)
    ang = jnp.einsum("k...s,kf->...sf", pos_per_dim.astype(jnp.float32),
                     jnp.asarray((sec[None, :] == np.arange(3)[:, None]), jnp.float32) * freqs)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(seq_len: int, dim: int, offset: int = 0) -> jax.Array:
    """MusicGen-style additive sinusoidal positions."""
    pos = np.arange(offset, offset + seq_len, dtype=np.float64)[:, None]
    freqs = np.exp(-np.log(10000.0) * np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = pos * freqs[None, :]
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, att: Attention) -> dict:
    d = cfg.d_model
    qd, kvd = att.n_heads * att.head_dim, att.n_kv_heads * att.head_dim
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": truncated_normal(ks[0], (d, qd), cfg.param_dtype, std),
        "wk": truncated_normal(ks[1], (d, kvd), cfg.param_dtype, std),
        "wv": truncated_normal(ks[2], (d, kvd), cfg.param_dtype, std),
        "wo": truncated_normal(ks[3], (qd, d), cfg.param_dtype, (qd) ** -0.5),
    }
    if att.qk_norm:
        p["q_norm"] = jnp.ones((att.head_dim,), cfg.param_dtype)
        p["k_norm"] = jnp.ones((att.head_dim,), cfg.param_dtype)
    return p


def _chunk_iter(fn, n_chunks: int, mode: str):
    """Run ``fn(i)`` for i in range(n_chunks), stacked on axis 0.

    mode='map'    -> lax.map (one body in HLO; memory-realistic, used for
                     full-program dry-runs)
    mode='unroll' -> python loop (exact cost_analysis; segment lowering)
    """
    if mode == "unroll":
        return jnp.stack([fn(jnp.asarray(i)) for i in range(n_chunks)], axis=0)
    return jax.lax.map(fn, jnp.arange(n_chunks))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_applies(S: int, T: int, hd: int, q_offset, kpos) -> bool:
    """Whether the flash kernels take this attention call: self-attention
    over freshly projected K/V (no cache positions, no query offset) on a
    TPU, at shapes the kernels tile, with no tensor parallelism (GSPMD
    cannot partition a Mosaic kernel)."""
    return (
        _on_tpu()
        and kpos is None
        and isinstance(q_offset, int) and q_offset == 0
        and S == T
        and S % 128 == 0
        and hd in (64, 128, 256)
        and not tp_active()
    )


def gqa_attention(
    q: jax.Array,  # (B, S, Hq, hd) — rope already applied
    k: jax.Array,  # (B, T, Hkv, hd)
    v: jax.Array,  # (B, T, Hkv, hd)
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,  # absolute position of q[0] (decode)
    window: int | None = None,
    softcap: float | None = None,
    q_chunk: int = 256,
    chunk_impl: str = "map",
    kpos: jax.Array | None = None,  # absolute key positions (ring caches)
) -> jax.Array:
    """Masked GQA attention; returns (B, S, Hq, hd).

    On a TPU, self-attention over fresh K/V runs the flash kernels
    (:func:`_flash_applies`).  Otherwise: sequence-TP prefill, or
    query-chunked attention with bounded score memory (full softmax per
    row — each chunk sees every key, so no online rescaling is needed).
    ``kpos`` carries absolute key positions for ring-buffer KV caches;
    unwritten slots hold a large sentinel so the causal mask hides them.
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    scale = hd ** -0.5

    # Sequence-TP (prefill on non-EP archs): the model axis is otherwise
    # idle (batch < chips), so q is reshaped into model_size row-blocks
    # sharded over 'model' — every device computes 1/16 of the score rows
    # against the (replicated) K/V.  Context-parallel without all-to-alls.
    from ..parallel.context import current as _ctx_current

    ctx = _ctx_current()
    # peak-memory guard: seq_tp materializes the whole (S/16, S) score
    # block per shard; for many-head archs that exceeds the budget and the
    # chunked-loop path stays the better trade (measured: starcoder2-7b
    # 41.7 GiB vs 13.6 GiB — see EXPERIMENTS.md §Perf It-3b).
    _seq_tp_bytes = 0
    if ctx is not None and ctx.model_axis is not None and ctx.batch_axes:
        _b_loc = max(1, B // ctx.data_size)
        _seq_tp_bytes = _b_loc * Hq * (S // ctx.model_size) * T * 4
    seq_tp = (
        ctx is not None
        and ctx.prefer == "seq_tp"
        and ctx.model_axis is not None
        and S % (ctx.model_size) == 0
        and S > 1
        and S == T  # self-attention prefill only
        and 0 < _seq_tp_bytes < 8 * 2**30
    )
    if not seq_tp and _flash_applies(S, T, hd, q_offset, kpos):
        return flash_attention_train(q, k, v, causal, window, softcap)

    if kpos is None:
        kpos = jnp.arange(T)
    if seq_tp:
        nc = ctx.model_size
        chunk = S // nc
        qb = constrain(qg.reshape(B, nc, chunk, Hkv, G, hd), {0: "batch", 1: "model"})
        scores = jnp.einsum("bnckgh,btkh->bnkgct", qb, k).astype(jnp.float32) * scale
        if softcap is not None:
            scores = jnp.tanh(scores / softcap) * softcap
        qpos = (
            q_offset
            + (jnp.arange(nc) * chunk)[:, None]
            + jnp.arange(chunk)[None, :]
        )  # (nc, chunk)
        mask = jnp.ones((nc, chunk, T), bool)
        if causal:
            mask &= qpos[..., None] >= kpos[None, None, :]
        if window is not None:
            mask &= (qpos[..., None] - kpos[None, None, :]) < window
        scores = jnp.where(mask[None, :, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        o = jnp.einsum("bnkgct,btkh->bnckgh", p, v)
        return o.reshape(B, S, Hq, hd)

    def one_chunk(ci):
        start = ci * q_chunk
        qs = jax.lax.dynamic_slice_in_dim(qg, start, q_chunk, axis=1)
        scores = jnp.einsum("bskgh,btkh->bkgst", qs, k).astype(jnp.float32) * scale
        if softcap is not None:
            scores = jnp.tanh(scores / softcap) * softcap
        qpos = q_offset + start + jnp.arange(q_chunk)
        mask = jnp.ones((q_chunk, T), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgst,btkh->bskgh", p, v)

    if S <= q_chunk:
        # single chunk (decode / short prefill)
        q_chunk = S
        out = one_chunk(0)
        return out.reshape(B, S, Hq, hd)

    assert S % q_chunk == 0, (S, q_chunk)
    chunks = _chunk_iter(one_chunk, S // q_chunk, chunk_impl)
    out = jnp.moveaxis(chunks, 0, 1).reshape(B, S, Hkv, G, hd)
    return out.reshape(B, S, Hq, hd)


def attention_block(
    p: dict,
    x: jax.Array,  # (B, S, D)
    cfg: ArchConfig,
    att: Attention,
    *,
    positions: jax.Array,  # (B, S) or (3, B, S) for mrope
    causal: bool = True,
    window: int | None = None,
    kv_cache: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    q_offset: jax.Array | int = 0,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array, jax.Array] | None]:
    """Full attention block: project, position-encode, attend, out-project.

    ``kv_cache`` is ``(k, v, kpos)`` where ``kpos`` (T,) int32 holds the
    absolute position stored in each slot (ring buffer for windowed
    layers; a large sentinel marks unwritten slots).

    * prefill (S > 1): attention runs over the freshly projected k/v; the
      last min(S, T_cache) positions are then written into the cache.
    * decode (S == 1): the new k/v is written at ``q_offset % T_cache``
      and attention runs against the whole cache using stored positions.
    """
    B, S, D = x.shape
    x = constrain(x, {0: "batch"})
    # Under TP (EP archs: the batch must leave the model axis to experts)
    # heads shard over 'model'.  GQA K/V are expanded to the full head
    # count first so the (KV, G) split never fights the head sharding —
    # Megatron-style, at the cost of G× K/V reads (noted in DESIGN.md).
    q = constrain(
        (x @ p["wq"]).reshape(B, S, att.n_heads, att.head_dim), {0: "batch", 2: "model"}
    )
    k = (x @ p["wk"]).reshape(B, S, att.n_kv_heads, att.head_dim)
    v = (x @ p["wv"]).reshape(B, S, att.n_kv_heads, att.head_dim)
    if tp_active() and kv_cache is None and att.n_heads % tp_size() == 0:
        grp = att.n_heads // att.n_kv_heads
        k = jnp.repeat(k, grp, axis=2)
        v = jnp.repeat(v, grp, axis=2)
    k = constrain(k, {0: "batch", 2: "model"})
    v = constrain(v, {0: "batch", 2: "model"})

    if att.qk_norm:
        q = q * jax.lax.rsqrt(jnp.mean(jnp.square(q.astype(jnp.float32)), -1, keepdims=True) + 1e-6).astype(q.dtype) * p["q_norm"]
        k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k.astype(jnp.float32)), -1, keepdims=True) + 1e-6).astype(k.dtype) * p["k_norm"]

    if att.rope == "rope":
        q = apply_rope(q, positions, att.rope_theta)
        k = apply_rope(k, positions, att.rope_theta)
    elif att.rope == "mrope":
        q = apply_mrope(q, positions, att.rope_theta, att.mrope_sections)
        k = apply_mrope(k, positions, att.rope_theta, att.mrope_sections)
    # 'sinusoidal' positions are added at the embedding level; 'none' = NoPE.

    new_cache = None
    kpos = None
    if kv_cache is not None:
        ck, cv, ckpos = kv_cache
        Tc = ck.shape[1]
        if S == 1:
            # decode: ring-buffer write, attend against the cache
            idx = q_offset % Tc if window else q_offset
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), idx, axis=1)
            pos_val = jnp.reshape(jnp.asarray(q_offset, ckpos.dtype), (1,))
            ckpos = jax.lax.dynamic_update_slice_in_dim(ckpos, pos_val, idx, axis=0)
            k, v, kpos = ck, cv, ckpos
        else:
            # prefill: attend over own k/v, then store the trailing window
            keep = min(S, Tc)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k[:, S - keep :].astype(ck.dtype), 0, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v[:, S - keep :].astype(cv.dtype), 0, axis=1
            )
            ckpos = jnp.where(
                jnp.arange(Tc) < keep,
                jnp.arange(Tc) + (S - keep) + q_offset,
                2**30,
            ).astype(ckpos.dtype)
        new_cache = (ck, cv, ckpos)

    with jax.named_scope(scopes.ATTENTION):
        o = gqa_attention(
            q, k, v,
            causal=causal,
            q_offset=q_offset,
            window=window,
            softcap=att.softcap,
            q_chunk=cfg.q_chunk,
            chunk_impl=cfg.chunk_impl,
            kpos=kpos,
        )
    out = (o.reshape(B, S, -1).astype(x.dtype)) @ p["wo"]
    return constrain(out.astype(x.dtype), {0: "batch"}), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    std_in, std_out = d ** -0.5, f ** -0.5
    if cfg.mlp in ("swiglu", "geglu"):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": truncated_normal(k1, (d, f), cfg.param_dtype, std_in),
            "w_up": truncated_normal(k2, (d, f), cfg.param_dtype, std_in),
            "w_down": truncated_normal(k3, (f, d), cfg.param_dtype, std_out),
        }
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_up": truncated_normal(k1, (d, f), cfg.param_dtype, std_in),
        "w_down": truncated_normal(k2, (f, d), cfg.param_dtype, std_out),
    }


def mlp_block(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Megatron-style TP: the hidden dim shards over 'model'; the w_down
    contraction then reduces over 'model' and the output is batch-sharded."""
    x = constrain(x, {0: "batch"})
    tp = {0: "batch", 2: "model"}
    if cfg.mlp == "swiglu":
        h = constrain(jax.nn.silu(x @ p["w_gate"]), tp) * constrain(x @ p["w_up"], tp)
    elif cfg.mlp == "geglu":
        h = constrain(jax.nn.gelu(x @ p["w_gate"], approximate=True), tp) * constrain(
            x @ p["w_up"], tp
        )
    else:
        h = constrain(jax.nn.gelu(x @ p["w_up"], approximate=True), tp)
    return constrain(h @ p["w_down"], {0: "batch"})


def softcap_logits(logits: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return logits
    return jnp.tanh(logits / cap) * cap
