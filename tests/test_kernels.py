"""Pallas kernel validation in interpret mode: shape/dtype sweeps and
hypothesis property tests against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.kernels import (
    attention_ref,
    flash_attention_fwd,
    rglru_pallas,
    rglru_ref,
    wkv_pallas,
    wkv_ref,
)
from repro.models.rwkv6 import wkv_chunked


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,causal,window,softcap",
    [
        (2, 256, 4, 2, 64, True, None, None),
        (1, 512, 8, 8, 128, True, None, None),
        (2, 256, 4, 1, 64, True, 128, None),
        (1, 256, 2, 2, 64, True, None, 50.0),
        (1, 256, 4, 2, 64, False, None, None),
        (1, 384, 6, 2, 128, True, 256, 30.0),  # everything at once
        (1, 128, 4, 4, 256, True, None, None),  # gemma head_dim
    ],
)
def test_flash_attention_matches_ref(B, S, Hq, Hkv, hd, causal, window, softcap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32).astype(dtype)
    out = flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=128, block_k=128, interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


@settings(max_examples=12, deadline=None)
@given(
    bq=st.sampled_from([64, 128, 256]),
    bk=st.sampled_from([64, 128]),
    seed=st.integers(0, 2**31 - 1),
    causal=st.booleans(),
)
def test_flash_attention_block_shape_invariance(bq, bk, seed, causal):
    """Output must not depend on the tiling."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# WKV6
# ---------------------------------------------------------------------------


def _wkv_inputs(key, B, T, H, K, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, T, H, K), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, H, K), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, K), jnp.float32).astype(dtype)
    # decays in ~(0.63, 0.999) like trained RWKV models
    w = jnp.exp(-jnp.exp(jax.random.uniform(ks[3], (B, T, H, K), minval=-6.0, maxval=-0.8)))
    u = jax.random.normal(ks[4], (H, K), jnp.float32) * 0.5
    return r, k, v, w.astype(jnp.float32), u


@pytest.mark.parametrize("B,T,H,K,chunk", [
    (2, 64, 2, 32, 16),
    (1, 128, 4, 64, 32),
    (1, 256, 1, 64, 128),
    (2, 96, 2, 32, 32),  # T not a multiple of a power-of-two chunk count
])
def test_wkv_pallas_matches_sequential_ref(B, T, H, K, chunk):
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(1), B, T, H, K)
    out_ref, s_ref = wkv_ref(r, k, v, w, u)
    out_pl, s_pl = wkv_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref), rtol=2e-4, atol=2e-4)


def test_wkv_chunked_jnp_matches_sequential_ref():
    """The model's chunked jnp path (training fallback) is also exact."""
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(2), 2, 128, 2, 32)
    out_ref, s_ref = wkv_ref(r, k, v, w, u)
    out_c, s_c = wkv_chunked(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_ref), rtol=2e-4, atol=2e-4)


def test_wkv_initial_state_threading():
    """Splitting a sequence across two kernel calls == one call (serving)."""
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(3), 1, 128, 2, 32)
    out_full, s_full = wkv_pallas(r, k, v, w, u, chunk=32, interpret=True)
    h = 64
    out_a, s_a = wkv_pallas(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u, chunk=32, interpret=True)
    out_b, s_b = wkv_pallas(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s_a, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_full[:, h:]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(s_full), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), chunk=st.sampled_from([8, 16, 32, 64]))
def test_wkv_chunk_invariance(seed, chunk):
    """WKV output must not depend on the chunk size (associativity)."""
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(seed), 1, 64, 2, 32)
    out_ref, s_ref = wkv_ref(r, k, v, w, u)
    out, s = wkv_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


def _rglru_inputs(key, B, T, W):
    k1, k2 = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, T, W)) * 2.0 + 2.0)  # (0,1)
    g = jax.random.normal(k2, (B, T, W)) * 0.5
    return a, g


@pytest.mark.parametrize("B,T,W,chunk,block_w", [
    (2, 64, 128, 16, 128),
    (1, 128, 256, 32, 128),
    (1, 256, 512, 128, 256),
])
def test_rglru_pallas_matches_ref(B, T, W, chunk, block_w):
    a, g = _rglru_inputs(jax.random.PRNGKey(0), B, T, W)
    h_ref, hT_ref = rglru_ref(a, g)
    h, hT = rglru_pallas(a, g, chunk=chunk, block_w=block_w, interpret=True)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), rtol=1e-5, atol=1e-5)


def test_rglru_state_threading():
    a, g = _rglru_inputs(jax.random.PRNGKey(1), 1, 128, 128)
    h_full, hT_full = rglru_pallas(a, g, chunk=32, block_w=128, interpret=True)
    h_a, s_a = rglru_pallas(a[:, :64], g[:, :64], chunk=32, block_w=128, interpret=True)
    h_b, s_b = rglru_pallas(a[:, 64:], g[:, 64:], s_a, chunk=32, block_w=128, interpret=True)
    np.testing.assert_allclose(np.asarray(h_b), np.asarray(h_full[:, 64:]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_b), np.asarray(hT_full), rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_rglru_associative_scan_fallback_matches_ref(seed):
    from repro.kernels.rglru.ops import rglru

    a, g = _rglru_inputs(jax.random.PRNGKey(seed), 2, 64, 64)
    h_ref, hT_ref = rglru_ref(a, g)
    h, hT = rglru(a, g, use_pallas=False)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention backward (dQ/dK/dV Pallas kernels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,causal,window,softcap",
    [
        (1, 256, 4, 2, 64, True, None, None),
        (1, 256, 4, 4, 64, False, None, None),
        (1, 256, 2, 1, 64, True, 128, None),
        (1, 256, 2, 2, 64, True, None, 50.0),
        (1, 384, 6, 2, 128, True, 256, 30.0),
    ],
)
def test_flash_attention_bwd_matches_ref_grads(B, S, Hq, Hkv, hd, causal, window, softcap):
    from repro.kernels.flash_attention.ops import flash_attention_train

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    w = jax.random.normal(ks[3], (B, S, Hq, hd), jnp.float32)  # loss weights

    def loss_kernel(q, k, v):
        o = flash_attention_train(q, k, v, causal, window, softcap, True)
        return jnp.sum(o * w)

    def loss_ref(q, k, v):
        o = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
        return jnp.sum(o * w)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=name
        )


def test_flash_attention_fwd_lse():
    from repro.kernels.flash_attention import flash_attention_fwd

    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    o, lse = flash_attention_fwd(q, k, v, causal=True, block_q=64, block_k=64,
                                 interpret=True, return_lse=True)
    # reference lse
    s = jnp.einsum("bsqh,btqh->bqst", q, k) * 64**-0.5
    mask = jnp.tril(jnp.ones((128, 128), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    lse_ref = jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)  # (B, S, H)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention in bf16: several q and k blocks, so skipped tiles, clamped
# index maps and diagonal / window-edge tiles all run
# ---------------------------------------------------------------------------


def _bf16_qkv(seed, B, S, Hq, Hkv, hd):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, (B, S, h, hd), jnp.float32).astype(jnp.bfloat16)
                  for kk, h in zip(ks, (Hq, Hkv, Hkv, Hq)))
    return q, k, v, w


def assert_bf16_close(got, want, name=""):
    """Within bf16 rounding: every element to 2e-2 of the reference's
    largest magnitude, and the whole to 1e-2 in norm."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2 * scale, err_msg=name)
    assert np.linalg.norm(got - want) <= 1e-2 * np.linalg.norm(want), name


@pytest.mark.parametrize(
    "S,Hq,Hkv,hd,causal,window,bq,bk",
    [
        (512, 4, 2, 64, True, None, 128, 128),  # 4x4 tiles, 6 skipped
        (512, 4, 1, 128, True, None, 256, 128),  # MQA, q blocks larger than k
        (512, 6, 2, 64, True, None, 128, 256),  # k blocks larger than q
        (512, 4, 2, 64, True, 200, 128, 128),  # window: tiles skipped on both sides
        (512, 4, 4, 64, False, 160, 128, 128),  # window without causal
        (384, 6, 2, 128, False, None, 128, 128),  # no skipping at all
    ],
)
def test_flash_attention_fwd_bf16(S, Hq, Hkv, hd, causal, window, bq, bk):
    q, k, v, _ = _bf16_qkv(11, 1, S, Hq, Hkv, hd)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window, block_q=bq,
                              block_k=bk, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
                        causal=causal, window=window)
    assert_bf16_close(out, ref)


@pytest.mark.parametrize(
    "S,Hq,Hkv,hd,causal,window,softcap",
    [
        (1536, 4, 2, 64, True, None, None),  # 3x3 blocks of 512: 3 tiles skipped
        (1536, 4, 2, 64, True, 256, None),  # and the window skips tile (2, 0)
        (1024, 6, 2, 128, True, 700, 30.0),  # G = 3, window edge and softcap
        (1024, 2, 1, 64, False, None, None),  # nothing skipped, GQA sum
    ],
)
def test_flash_attention_train_bf16_grads(S, Hq, Hkv, hd, causal, window, softcap):
    from repro.kernels.flash_attention import flash_attention_train

    q, k, v, w = _bf16_qkv(12, 1, S, Hq, Hkv, hd)
    wf = w.astype(jnp.float32)

    def loss_kernel(q, k, v):
        o = flash_attention_train(q, k, v, causal, window, softcap, True)
        return jnp.sum(o.astype(jnp.float32) * wf)

    def loss_ref(q, k, v):
        o = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
        return jnp.sum(o * wf)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, name in zip(gk, gr, ("dq", "dk", "dv")):
        assert a.dtype == jnp.bfloat16, name
        assert_bf16_close(a, b, name)
