"""Which attention path a call takes: the Pallas flash kernels for
self-attention over fresh K/V on a TPU, the jnp path for everything else.

The backend predicate is patched to the TPU's; the kernels then run in
interpret mode, so the kernel path is checked on the CPU against the jnp
path it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_train
from repro.launch.mesh import make_mesh
from repro.models import layers
from repro.models.common import ArchConfig, Attention
from repro.parallel.context import ActSharding, activation_sharding

ATT = Attention(n_heads=4, n_kv_heads=2, head_dim=64)
CFG = ArchConfig(name="tiny", family="dense", n_layers=1, d_model=128, d_ff=256, vocab=64,
                 attention=ATT, q_chunk=64)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)


def params():
    return layers.init_attention(jax.random.PRNGKey(0), CFG, ATT)


def x_of(S, B=1):
    return jax.random.normal(jax.random.PRNGKey(1), (B, S, CFG.d_model)).astype(jnp.bfloat16)


def block(p, x, **kw):
    S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S), x.shape[:2])
    return layers.attention_block(p, x, CFG, ATT, positions=pos, **kw)[0]


def has_kernel(f, *args) -> bool:
    # a new function each time: a traced function is cached by identity,
    # and the patched predicate is not part of the cache key
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: f(*a))(*args))


def test_training_shape_takes_the_kernel(on_tpu):
    assert has_kernel(block, params(), x_of(256))
    assert has_kernel(lambda p, x: block(p, x, window=128), params(), x_of(256))


def test_prefill_into_a_cache_takes_the_kernel(on_tpu):
    """Prefill attends over its own fresh K/V, then writes the cache."""
    cache = (jnp.zeros((1, 256, 2, 64), jnp.bfloat16),) * 2 + (jnp.zeros((256,), jnp.int32),)
    assert has_kernel(lambda p, x: block(p, x, kv_cache=cache), params(), x_of(256))


def test_cpu_takes_the_jnp_path():
    assert not has_kernel(block, params(), x_of(256))


def test_decode_against_a_cache_takes_the_jnp_path(on_tpu):
    cache = (jnp.zeros((1, 256, 2, 64), jnp.bfloat16),) * 2 + (jnp.zeros((256,), jnp.int32),)
    assert not has_kernel(lambda p, x: block(p, x, kv_cache=cache, q_offset=jnp.int32(7)),
                          params(), x_of(1))


def test_cache_positions_take_the_jnp_path(on_tpu):
    """``kpos`` (a ring cache's stored positions), or a query offset."""
    q = jnp.zeros((1, 256, 4, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    assert has_kernel(lambda q, k: layers.gqa_attention(q, k, k, causal=True), q, kv)
    assert not has_kernel(
        lambda q, k: layers.gqa_attention(q, k, k, causal=True, kpos=jnp.arange(256)), q, kv)
    assert not has_kernel(
        lambda q, k: layers.gqa_attention(q, k, k, causal=True, q_offset=jnp.int32(0)), q, kv)


@pytest.mark.parametrize("S,hd", [(192, 64), (256, 96)])
def test_shapes_the_kernel_does_not_tile_take_the_jnp_path(on_tpu, S, hd):
    q = jnp.zeros((1, S, 4, hd), jnp.bfloat16)
    kv = jnp.zeros((1, S, 2, hd), jnp.bfloat16)
    assert not has_kernel(lambda q, k: layers.gqa_attention(q, k, k, causal=True), q, kv)


def test_tensor_parallel_takes_the_jnp_path(on_tpu):
    mesh = make_mesh((1, 1), ("data", "model"))
    tp = ActSharding(batch_axes=("data",), model_axis="model", data_size=1, model_size=1,
                     prefer="tp")
    with jax.set_mesh(mesh), activation_sharding(tp):
        assert not has_kernel(block, params(), x_of(256))


@pytest.mark.parametrize("window", [None, 96])
def test_kernel_path_matches_the_jnp_path(monkeypatch, window):
    """The block's output and its weight gradients, through the kernel (in
    interpret mode) and through the jnp path, agree within bf16 rounding."""
    p, x = params(), x_of(256, B=2)
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 256, CFG.d_model))

    def loss(p):
        return jnp.sum(block(p, x, window=window).astype(jnp.float32) * w)

    jnp_out = block(p, x, window=window)
    jnp_loss, jnp_grads = jax.value_and_grad(loss)(p)
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(layers, "flash_attention_train",
                        lambda q, k, v, c, wi, s: flash_attention_train(q, k, v, c, wi, s, True))
    assert has_kernel(loss, p)
    out = block(p, x, window=window)
    kernel_loss, grads = jax.value_and_grad(loss)(p)

    def close(a, b, name):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b), name
        np.testing.assert_allclose(a, b, rtol=0, atol=4e-2 * np.max(np.abs(b)), err_msg=name)

    close(out, jnp_out, "out")
    assert float(kernel_loss) == pytest.approx(float(jnp_loss), rel=2e-2)
    for name in ("wq", "wk", "wv", "wo"):
        close(grads[name], jnp_grads[name], name)
