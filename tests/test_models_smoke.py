"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, output shapes + finiteness; decode-vs-forward consistency; full-config
parameter counts validated via eval_shape (no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.models import forward, init_caches, init_params, loss_fn, param_count
from repro.models.transformer import init_params as _init

B, S = 2, 64


def make_batch(cfg, key, batch=B, seq=S):
    k1, k2 = jax.random.split(key)
    tgt = jax.random.randint(k2, (batch, seq), 0, cfg.vocab)
    if cfg.input_mode == "embeds":
        return {
            "embeds": jax.random.normal(k1, (batch, seq, cfg.d_model), jnp.float32) * 0.02,
            "targets": tgt,
        }
    return {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab), "targets": tgt}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    logits, _, aux = forward(params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"))
    assert logits.shape == (B, S, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg), has_aux=True
    )(params)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_segmented_forward_matches_single_scan(arch):
    """Bucket-segmented scan must be numerically identical to one scan."""
    cfg = get_reduced(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    n = cfg.n_stages
    if n < 2:
        pytest.skip("single-stage model")
    one, _, _ = forward(params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
                        segments=((0, n),))
    two, _, _ = forward(params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
                        segments=((0, n // 2), (n // 2, n)))
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "arch",
    ["tinyllama-1.1b", "gemma2-2b", "mixtral-8x7b", "recurrentgemma-9b", "rwkv6-7b",
     "sdar-30b-a3b"],
)
def test_decode_matches_forward(arch):
    """Prefill + incremental decode logits == full-forward logits.

    Runs in fp32 so the check isolates cache/masking logic from bf16
    rounding (bf16 reorder noise is ~1e-2 on O(1) logits)."""
    import dataclasses

    cfg = dataclasses.replace(get_reduced(arch), param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    seq = 32
    batch = make_batch(cfg, jax.random.PRNGKey(1), batch=1, seq=seq)
    kwargs = (
        {"embeds": batch["embeds"]} if cfg.input_mode == "embeds" else {"tokens": batch["tokens"]}
    )
    full_logits, _, _ = forward(params, cfg, **kwargs)

    # prefill on the first seq-8 positions, then decode 8 tokens
    split = seq - 8
    caches = init_caches(cfg, batch=1, max_seq=seq, dtype=jnp.float32)
    if cfg.input_mode == "embeds":
        pre = {"embeds": batch["embeds"][:, :split]}
    else:
        pre = {"tokens": batch["tokens"][:, :split]}
    logits_pre, caches, _ = forward(params, cfg, **pre, caches=caches)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, -1]), np.asarray(full_logits[:, split - 1]),
        rtol=2e-3, atol=2e-3,
    )

    for t in range(split, seq):
        if cfg.input_mode == "embeds":
            step_in = {"embeds": batch["embeds"][:, t : t + 1]}
        else:
            step_in = {"tokens": batch["tokens"][:, t : t + 1]}
        logits_t, caches, _ = forward(params, cfg, **step_in, caches=caches, q_offset=t)
        np.testing.assert_allclose(
            np.asarray(logits_t[:, 0]), np.asarray(full_logits[:, t]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"decode step t={t}",
        )


EXPECTED_PARAMS_B = {
    "musicgen-large": (1.4, 2.6),
    "tinyllama-1.1b": (1.0, 1.2),
    "starcoder2-7b": (6.4, 7.8),
    "gemma2-2b": (2.0, 3.2),
    "starcoder2-3b": (2.7, 3.5),
    "mixtral-8x7b": (44.0, 49.0),
    "dbrx-132b": (125.0, 138.0),
    "rwkv6-7b": (6.5, 8.2),
    "recurrentgemma-9b": (8.0, 10.5),
    "qwen2-vl-2b": (1.2, 1.8),
    "sdar-30b-a3b": (29.5, 31.5),
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_param_count(arch):
    """Full configs hit the advertised parameter counts (eval_shape only —
    nothing is allocated)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: _init(k, cfg), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    lo, hi = EXPECTED_PARAMS_B[arch]
    assert lo <= n / 1e9 <= hi, f"{arch}: {n / 1e9:.2f}B params outside [{lo}, {hi}]"


def test_mrope_reduces_to_rope_for_text():
    """Qwen2-VL M-RoPE with equal (t,h,w) streams == standard RoPE."""
    from repro.models.layers import apply_mrope, apply_rope

    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (2, 16, 4, 24), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    mpos = jnp.broadcast_to(pos[None], (3, 2, 16))
    a = apply_rope(x, pos, 1e6)
    b = apply_mrope(x, mpos, 1e6, (4, 4, 4))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_sliding_window_masks_distant_tokens():
    """A windowed arch must ignore keys beyond the window."""
    import dataclasses

    cfg = get_reduced("mixtral-8x7b")
    att = dataclasses.replace(cfg.attention, window=8)
    cfg = dataclasses.replace(cfg, attention=att, local_window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab)
    base, _, _ = forward(params, cfg, tokens=tokens)
    # perturb a token far outside the window of the last position
    tokens2 = tokens.at[0, 2].set((tokens[0, 2] + 1) % cfg.vocab)
    pert, _, _ = forward(params, cfg, tokens=tokens2)
    np.testing.assert_allclose(
        np.asarray(base[:, -1]), np.asarray(pert[:, -1]), rtol=1e-4, atol=1e-4
    )
