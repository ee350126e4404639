"""Chunked-CE equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.models import init_params, loss_fn
from repro.models import transformer as tf_mod


class TestChunkedCE:
    def test_chunked_matches_full(self, monkeypatch):
        """Sequence-chunked CE must equal the full-logits CE (values and
        gradients) — it is a pure memory transformation."""
        cfg = dataclasses.replace(
            get_reduced("tinyllama-1.1b"), param_dtype=jnp.float32
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        B, S = 2, 64
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab),
        }
        full_loss, _ = loss_fn(params, batch, cfg)
        g_full = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)

        monkeypatch.setattr(tf_mod, "CHUNKED_CE_VOCAB", 1)
        monkeypatch.setattr(tf_mod, "CE_SEQ_CHUNK", 16)
        chunk_loss, _ = loss_fn(params, batch, cfg)
        g_chunk = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)

        np.testing.assert_allclose(float(full_loss), float(chunk_loss), rtol=1e-6)
        diffs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g_full, g_chunk
        )
        assert max(jax.tree.leaves(diffs)) < 1e-5
