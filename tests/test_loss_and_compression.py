"""Chunked-CE equivalence and int8 error-feedback compression."""

import dataclasses
import json
import subprocess
import sys
import textwrap
from _env import REPO_ROOT, SUBPROC_ENV  # shared subprocess env

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


COMPRESSION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.runtime.compression import compressed_psum_rs_ag

    mesh = make_mesh((8,), ("dp",))

    def body(g, res):
        return compressed_psum_rs_ag(g, "dp", res)

    f = jax.jit(jax.shard_map(body, mesh=mesh, axis_names={"dp"},
                 in_specs=(P("dp"), P("dp")), out_specs=(P("dp"), P("dp")),
                 check_vma=False))

    key = jax.random.PRNGKey(0)
    # per-device distinct gradients: (8, n) rows = one per device
    g = jax.random.normal(key, (8, 1024), jnp.float32)
    res = jnp.zeros_like(g)
    with jax.set_mesh(mesh):
        out, new_res = f(g, res)
    exact = jnp.sum(g, axis=0)
    out_rows = np.asarray(out)
    # every device row should hold (approximately) the exact sum
    err = float(np.max(np.abs(out_rows - np.asarray(exact)[None, :])))
    scale = float(np.max(np.abs(np.asarray(exact))))
    # error feedback: residual captures the quantization error
    res_norm = float(np.max(np.abs(np.asarray(new_res))))

    # second round with error feedback reduces accumulated bias:
    with jax.set_mesh(mesh):
        out2, res2 = f(g, new_res)
    two_step = np.asarray(out) + np.asarray(out2)
    exact2 = 2 * np.asarray(exact)
    err2 = float(np.max(np.abs(two_step - exact2[None, :])))

    print(json.dumps({"err": err, "scale": scale, "res_norm": res_norm,
                      "err2_accum": err2}))
""")


def test_int8_rs_ag_compression():
    out = subprocess.run(
        [sys.executable, "-c", COMPRESSION_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env=SUBPROC_ENV,
        cwd=REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # int8 quantization error bounded by ~scale/127 per shard
    assert rec["err"] <= rec["scale"] / 127 * 3 + 1e-6, rec
    # residual is nonzero (error feedback captured something)
    assert rec["res_norm"] > 0, rec
    # with EF, two accumulated steps stay within ~the same bound (no drift)
    assert rec["err2_accum"] <= rec["scale"] / 127 * 6 + 1e-6, rec
