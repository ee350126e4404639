"""Plain reference of the StarCoder2 decoder as the benchmark runs it,
and the benchmark's own weights for it.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one
layer at a time; it imports nothing of the program.  It follows
arXiv:2402.19173 (pre-LayerNorm blocks, grouped-query attention with
rotary positions, a GELU MLP) with the departures that the program's
decoder forces, which the configuration file states under ``assumed``:
no bias terms in the attention and MLP projections, and the embedding
lookup scaled by ``embed_multiplier`` (the program scales a tied
embedding by the square root of the width, in bfloat16).  The rotary
base, the sliding window and the tied output head are the file's.

``lowp=True`` is the control: every matmul operand and every stored
weight is rounded to the precision below the one the configuration
states (float8 e4m3 for bfloat16, bfloat16 for float32).

Parameters use the program's layout (one stacked leading layer axis)::

    embed (V, D) f32; stages.attn_0.{norm1,norm2}.{scale,bias} (L, D);
    stages.attn_0.attn.{wq (L,D,H*hd), wk, wv (L,D,Hkv*hd), wo (L,H*hd,D)};
    stages.attn_0.mlp.{w_up (L,D,F), w_down (L,F,D)};
    final_norm.{scale,bias} (D,); head (D, V), absent where the head is
    tied to the embedding
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.common import lower, mm, trunc_normal

STAGE = "attn_0"
Q_CHUNK = 1024


def dims(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "V": cfg["vocab_size"]}


def program_config(cfg: dict, arch):
    """The program's ``ArchConfig`` (``arch``, from its registry) set to
    this file's sizes; ``ValueError`` where its block is of another kind."""
    import dataclasses

    if arch.norm != "layernorm" or arch.mlp != "gelu" or arch.pattern != ("attn",):
        raise ValueError(f"{arch.name} is not a StarCoder2-style decoder")
    d = dims(cfg)
    scale = float(jnp.asarray(d["D"] ** 0.5, cfg["param_dtype"])) \
        if cfg["tie_word_embeddings"] else 1.0
    if cfg["embed_multiplier"] != scale:
        raise ValueError(f"the program scales this embedding by {scale}, the file says "
                         f"{cfg['embed_multiplier']}")
    att = dataclasses.replace(arch.attention, n_heads=d["H"], n_kv_heads=d["Hkv"],
                              head_dim=d["hd"], rope_theta=cfg["rope_theta"],
                              window=cfg["sliding_window"])
    return dataclasses.replace(arch, n_layers=d["L"], d_model=d["D"], d_ff=d["F"],
                               vocab=d["V"], attention=att,
                               tie_embeddings=cfg["tie_word_embeddings"],
                               param_dtype=jnp.dtype(cfg["param_dtype"]))


def init(cfg: dict, key) -> dict:
    """Weights from ``key`` in the program's layout and stored dtypes."""
    d = dims(cfg)
    L, D, F, H, Hkv, hd, V = (d[k] for k in ("L", "D", "F", "H", "Hkv", "hd", "V"))
    pd = jnp.dtype(cfg["param_dtype"])
    ks = iter(jax.random.split(key, 8))

    def norm(*lead):
        return {"scale": jnp.ones((*lead, D), pd), "bias": jnp.zeros((*lead, D), pd)}

    params = {
        # the lookup, times the multiplier, has unit spread; a tied head
        # then has the spread D ** -0.5 of an untied one
        "embed": trunc_normal(next(ks), (V, D), 1.0 / cfg["embed_multiplier"], jnp.float32),
        "stages": {STAGE: {
            "norm1": norm(L), "norm2": norm(L),
            "attn": {"wq": trunc_normal(next(ks), (L, D, H * hd), D ** -0.5, pd),
                     "wk": trunc_normal(next(ks), (L, D, Hkv * hd), D ** -0.5, pd),
                     "wv": trunc_normal(next(ks), (L, D, Hkv * hd), D ** -0.5, pd),
                     "wo": trunc_normal(next(ks), (L, H * hd, D), (H * hd) ** -0.5, pd)},
            "mlp": {"w_up": trunc_normal(next(ks), (L, D, F), D ** -0.5, pd),
                    "w_down": trunc_normal(next(ks), (L, F, D), F ** -0.5, pd)},
        }},
        "final_norm": norm(),
    }
    if not cfg["tie_word_embeddings"]:
        params["head"] = trunc_normal(next(ks), (D, V), D ** -0.5, pd)
    return params


def layer_norm(p, x, lowp):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + 1e-5)
    return y * lower(p["scale"], lowp) + lower(p["bias"], lowp)


def rope(x, theta):
    """Rotary positions on (B, S, heads, hd), halves rotated together."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, lowp):
    """Causal softmax attention over the last ``window`` positions (all
    of them where ``window`` is None), grouped queries, in query blocks."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    chunk = min(Q_CHUNK, S)

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, 1)
        s = mm("bqkgh,btkh->bkgqt", qs, k, lowp) / np.sqrt(hd)
        qpos = i * chunk + jnp.arange(chunk)
        back = qpos[:, None] - jnp.arange(S)[None, :]
        seen = (back >= 0) if window is None else (back >= 0) & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
        return mm("bkgqt,btkh->bqkgh", jax.nn.softmax(s, -1), v, lowp)

    out = jax.lax.map(block, jnp.arange(S // chunk))  # (n, B, chunk, Hkv, G, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def layer(p, x, cfg, lowp=False):
    """One decoder layer on f32 activations ``x`` (B, S, D)."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = layer_norm(p["norm1"], x, lowp)
    a = p["attn"]
    q = rope(mm("bsd,de->bse", h, a["wq"], lowp).reshape(B, S, H, hd), cfg["rope_theta"])
    k = rope(mm("bsd,de->bse", h, a["wk"], lowp).reshape(B, S, Hkv, hd), cfg["rope_theta"])
    v = mm("bsd,de->bse", h, a["wv"], lowp).reshape(B, S, Hkv, hd)
    x = x + mm("bse,ed->bsd", attention(q, k, v, cfg["sliding_window"], lowp), a["wo"], lowp)
    h = layer_norm(p["norm2"], x, lowp)
    u = jax.nn.gelu(mm("bsd,df->bsf", h, p["mlp"]["w_up"], lowp), approximate=True)
    return x + mm("bsf,fd->bsd", u, p["mlp"]["w_down"], lowp)


def embed(table, tokens, cfg, lowp=False):
    return lower(table, lowp)[tokens] * cfg["embed_multiplier"]


def final_hidden(p, x, cfg, lowp=False):
    """``x`` after the final norm; ``p`` holds ``final_norm``."""
    return layer_norm(p["final_norm"], x, lowp)


def head_weight(p):
    """The output head (D, V); the embedding's transpose where tied."""
    return p["head"] if "head" in p else p["embed"].T


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through (layers and output head)."""
    d = dims(cfg)
    per_layer = d["D"] * (d["H"] + 2 * d["Hkv"]) * d["hd"] + d["H"] * d["hd"] * d["D"] \
        + 2 * d["D"] * d["F"]
    return d["L"] * per_layer + d["D"] * d["V"]


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of causal attention per token: QK and PV,
    2 flops per multiply-add, over the mean causal context seq/2."""
    d = dims(cfg)
    return 3.0 * d["L"] * 2 * 2 * d["H"] * d["hd"] * seq / 2
