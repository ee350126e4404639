"""Plain reference of the SDAR-30B-A3B decoder (``sdar_moe``) as the
benchmark trains it, and the benchmark's own weights for it.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one
layer at a time; it imports nothing of the program.  Each layer:
RMSNorm, grouped-query attention with per-head RMSNorm on queries and
keys before the rotary positions (halves rotated together), causal over
the whole sequence; RMSNorm, then the mixture of experts: the router
(float32) over all ``published.num_experts`` experts, softmax, the top
``num_experts_per_tok``, renormalised (``norm_topk_prob``), and a SwiGLU
expert of width ``moe_intermediate_size``.  The file's ``num_experts``
are the experts this chip holds, ids ``first_expert ..``: each is
computed densely over every token and weighted by its gate, 0 where the
token did not choose it, a formulation independent of the program's
sorted rows and grouped matmuls.  The absent experts' part is left out,
as in the program.  The loss is the next-token cross-entropy, with no
load-balancing term.

``lowp=True`` is the control: every matmul operand and every stored
weight is rounded to the precision below the one the configuration
states (float8 e4m3 for bfloat16, bfloat16 for float32).

Parameters use the program's layout (one stacked leading layer axis)::

    embed (V, D) f32; stages.moe_0.{norm1,norm2}.scale (L, D);
    stages.moe_0.attn.{wq (L,D,H*hd), wk, wv (L,D,Hkv*hd), wo (L,H*hd,D),
    q_norm, k_norm (L, hd)}; stages.moe_0.moe.{router (L,D,E) f32,
    w_gate, w_up (L,X,D,F), w_down (L,X,F,D)}; final_norm.scale (D,);
    head (D, V)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from bench.refs.common import lower, mm, trunc_normal
from bench.refs.starcoder2 import rope

STAGE = "moe_0"
#: Query rows of one attention block, and tokens of one block of the
#: experts: at 2 x 8192 tokens a block's float32 scores take 1 GiB, and
#: its backward keeps only the block's inputs.
Q_CHUNK = 512
TOKEN_CHUNK = 2048


def dims(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "F": cfg["moe_intermediate_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"], "V": cfg["vocab_size"],
            "E": cfg["published"]["num_experts"], "X": cfg["num_experts"],
            "first": cfg["first_expert"], "k": cfg["num_experts_per_tok"]}


def program_config(cfg: dict, arch):
    """The program's ``ArchConfig`` (``arch``, from its registry) set to
    this file's sizes and held experts; ``ValueError`` where its block is
    of another kind."""
    import dataclasses

    if (arch.norm != "rmsnorm" or arch.mlp != "swiglu" or arch.pattern != ("moe",)
            or arch.moe is None or not arch.attention.qk_norm):
        raise ValueError(f"{arch.name} is not an SDAR-style MoE decoder")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["tie_word_embeddings"] or cfg["sliding_window"]:
        raise ValueError("the program's decoder has RMSNorm eps 1e-6, an untied head and "
                         "full attention here")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k gates")
    d = dims(cfg)
    att = dataclasses.replace(arch.attention, n_heads=d["H"], n_kv_heads=d["Hkv"],
                              head_dim=d["hd"], rope_theta=cfg["rope_theta"], window=None)
    moe = dataclasses.replace(arch.moe, n_experts=d["E"], top_k=d["k"], held=d["X"],
                              first=d["first"], aux_coef=cfg["router_aux_loss_coef"])
    return dataclasses.replace(arch, n_layers=d["L"], d_model=d["D"], d_ff=d["F"],
                               vocab=d["V"], attention=att, moe=moe, tie_embeddings=False,
                               param_dtype=jnp.dtype(cfg["param_dtype"]))


def init(cfg: dict, key) -> dict:
    """Weights from ``key`` in the program's layout and stored dtypes."""
    d = dims(cfg)
    L, D, F, H, Hkv, hd, V, E, X = (d[k] for k in ("L", "D", "F", "H", "Hkv", "hd", "V", "E", "X"))
    pd = jnp.dtype(cfg["param_dtype"])
    ks = iter(jax.random.split(key, 10))

    def ones(*shape):
        return jnp.ones(shape, pd)

    return {
        # the lookup has unit spread, as the first norm leaves it
        "embed": trunc_normal(next(ks), (V, D), 1.0, jnp.float32),
        "stages": {STAGE: {
            "norm1": {"scale": ones(L, D)}, "norm2": {"scale": ones(L, D)},
            "attn": {"wq": trunc_normal(next(ks), (L, D, H * hd), D ** -0.5, pd),
                     "wk": trunc_normal(next(ks), (L, D, Hkv * hd), D ** -0.5, pd),
                     "wv": trunc_normal(next(ks), (L, D, Hkv * hd), D ** -0.5, pd),
                     "wo": trunc_normal(next(ks), (L, H * hd, D), (H * hd) ** -0.5, pd),
                     "q_norm": ones(L, hd), "k_norm": ones(L, hd)},
            "moe": {"router": trunc_normal(next(ks), (L, D, E), D ** -0.5, jnp.float32),
                    "w_gate": trunc_normal(next(ks), (L, X, D, F), D ** -0.5, pd),
                    "w_up": trunc_normal(next(ks), (L, X, D, F), D ** -0.5, pd),
                    "w_down": trunc_normal(next(ks), (L, X, F, D), F ** -0.5, pd)},
        }},
        "final_norm": {"scale": ones(D)},
        "head": trunc_normal(next(ks), (D, V), D ** -0.5, pd),
    }


def rms_norm(scale, x, lowp, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * lower(scale, lowp)


def attention(q, k, v, lowp):
    """Causal softmax attention over the whole sequence, grouped queries,
    in query blocks."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    chunk = min(Q_CHUNK, S)

    @jax.checkpoint
    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, 1)
        s = mm("bqkgh,btkh->bkgqt", qs, k, lowp) / np.sqrt(hd)
        seen = (i * chunk + jnp.arange(chunk))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(seen, s, -jnp.inf)
        return mm("bkgqt,btkh->bqkgh", jax.nn.softmax(s, -1), v, lowp)

    out = jax.lax.map(block, jnp.arange(S // chunk))  # (n, B, chunk, Hkv, G, hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def experts(p, h, cfg, lowp=False):
    """The held experts' part of the MoE on ``h`` (B, S, D) f32, in
    blocks of tokens."""
    d = dims(cfg)
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    probs = jax.nn.softmax(mm("td,de->te", flat, p["router"], lowp), -1)
    gates, idx = jax.lax.top_k(probs, d["k"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    ids = d["first"] + jnp.arange(d["X"])
    chunk = min(TOKEN_CHUNK, B * S)

    @jax.checkpoint
    def block(args):
        hb, gb, ib = args

        @jax.checkpoint
        def one(out, e):
            wg, wu, wd, eid = e
            gate = jnp.sum(jnp.where(ib == eid, gb, 0.0), -1)  # 0 where not chosen
            u = jax.nn.silu(mm("td,df->tf", hb, wg, lowp)) * mm("td,df->tf", hb, wu, lowp)
            return out + gate[:, None] * mm("tf,fd->td", u, wd, lowp), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(hb),
                              (p["w_gate"], p["w_up"], p["w_down"], ids))
        return out

    n = B * S // chunk
    out = jax.lax.map(block, (flat.reshape(n, chunk, D), gates.reshape(n, chunk, -1),
                              idx.reshape(n, chunk, -1)))
    return out.reshape(B, S, D)


def layer(p, x, cfg, lowp=False):
    """One decoder layer on f32 activations ``x`` (B, S, D)."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = rms_norm(p["norm1"]["scale"], x, lowp, eps)
    q = rms_norm(a["q_norm"], mm("bsd,de->bse", h, a["wq"], lowp).reshape(B, S, H, hd), lowp, eps)
    k = rms_norm(a["k_norm"], mm("bsd,de->bse", h, a["wk"], lowp).reshape(B, S, Hkv, hd), lowp,
                 eps)
    v = mm("bsd,de->bse", h, a["wv"], lowp).reshape(B, S, Hkv, hd)
    o = attention(rope(q, theta), rope(k, theta), v, lowp)
    x = x + mm("bse,ed->bsd", o, a["wo"], lowp)
    return x + experts(p["moe"], rms_norm(p["norm2"]["scale"], x, lowp, eps), cfg, lowp)


def embed(table, tokens, cfg, lowp=False):
    return lower(table, lowp)[tokens]


def final_hidden(p, x, cfg, lowp=False):
    """``x`` after the final norm; ``p`` holds ``final_norm``."""
    return rms_norm(p["final_norm"]["scale"], x, lowp, cfg["rms_norm_eps"])


def head_weight(p):
    """The output head (D, V)."""
    return p["head"]


def held_share(cfg: dict) -> float:
    """Held experts a token visits on average, top_k x held / E, where
    routing spreads evenly over the experts."""
    d = dims(cfg)
    return d["k"] * d["X"] / d["E"]


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through (layers and output head): the
    attention projections, the router, the held experts' expected share
    of one expert's three matrices under even routing, and the head.  The
    experts are about a tenth of it; from a fresh router the rows routed
    to the held experts run from 0.78 to 1.49 times the even share."""
    d = dims(cfg)
    attn = d["D"] * (d["H"] + 2 * d["Hkv"]) * d["hd"] + d["H"] * d["hd"] * d["D"]
    per_layer = attn + d["D"] * d["E"] + held_share(cfg) * 3 * d["D"] * d["F"]
    return int(d["L"] * per_layer + d["D"] * d["V"])


def mixer_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of causal attention per token: QK and PV,
    2 flops per multiply-add, over the mean causal context seq/2."""
    d = dims(cfg)
    return 3.0 * d["L"] * 2 * 2 * d["H"] * d["hd"] * seq / 2

