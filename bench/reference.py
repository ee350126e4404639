"""Plain SGD steps of a reference model, one layer at a time.

Drives a family module of ``bench/refs`` (``embed``, ``layer``,
``final_hidden``, ``head_weight``) through the same steps the program
takes: forward layer by layer keeping each layer's input, the loss
(mean token cross-entropy, in sequence blocks), then backward layer by
layer, each layer's weights updated in place as soon as its gradient
exists (SGD: ``p - lr * g`` in float32, stored back in the weight's own
dtype).  So only one layer's gradient is alive at a time, and the
whole step fits next to one copy of the weights.

Batches placed on several devices with their rows split across them
give a data-parallel step: the compiler sums the gradient over rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.refs.common import below, mm

CE_CHUNK = 1024


def _at(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _store(p, g, lr, lowp):
    """SGD update of one leaf, kept in the leaf's dtype (with ``lowp``
    first rounded below it, as the control stores weights)."""
    new = p.astype(jnp.float32) - lr * g
    if lowp:
        new = new.astype(below(p.dtype))
    return new.astype(p.dtype)


class Reference:
    """Jitted pieces of one family's reference at one configuration."""

    def __init__(self, fam, cfg: dict, lowp: bool = False):
        self.fam, self.cfg, self.lowp = fam, cfg, lowp
        self.n_layers = cfg["num_hidden_layers"]
        stage = fam.STAGE

        def layer(stages, i, x):
            return fam.layer(_at(stages[stage], i), x, cfg, lowp)

        def nll_sum(hp, x, targets, weights):
            h = fam.final_hidden(hp, x, cfg, lowp)
            w = fam.head_weight(hp)
            S = x.shape[1]
            chunk = min(CE_CHUNK, S)

            @jax.checkpoint
            def block(i):
                hs = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, 1)
                ts = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, 1)
                ws = jax.lax.dynamic_slice_in_dim(weights, i * chunk, chunk, 1)
                logits = mm("bsd,dv->bsv", hs, w, lowp)
                ll = jnp.take_along_axis(logits, ts[..., None], -1)[..., 0]
                return jnp.sum((jax.nn.logsumexp(logits, -1) - ll) * ws)

            return jnp.sum(jax.lax.map(block, jnp.arange(S // chunk)))

        def head_grads(hp, x, targets, weights, lr):
            def loss(hp, x):
                return nll_sum(hp, x, targets, weights) / jnp.sum(weights)

            l, (g, dx) = jax.value_and_grad(loss, argnums=(0, 1))(hp, x)
            new = jax.tree.map(lambda p, gg: _store(p, gg, lr, lowp), hp, g)
            return l, new, dx

        def layer_back(stages, i, x, dx, lr):
            p = _at(stages[stage], i)
            _, pull = jax.vjp(lambda p, x: fam.layer(p, x, cfg, lowp), p, x)
            g, dx_in = pull(dx)
            new = jax.tree.map(lambda a, gg: _store(a, gg, lr, lowp), p, g)
            st = jax.tree.map(lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, i, 0),
                              stages[stage], new)
            return {**stages, stage: st}, dx_in

        def embed_back(table, tokens, dx, lr):
            _, pull = jax.vjp(lambda t: fam.embed(t, tokens, cfg, lowp), table)
            (g,) = pull(dx)
            return _store(table, g, lr, lowp)

        self._embed = jax.jit(lambda t, tok: fam.embed(t, tok, cfg, lowp))
        self._layer = jax.jit(layer)
        self._head = jax.jit(head_grads, donate_argnums=(0,))
        self._layer_back = jax.jit(layer_back, donate_argnums=(0,))
        self._embed_back = jax.jit(embed_back, donate_argnums=(0,))

    def step(self, params: dict, batch: dict, lr: float) -> tuple[dict, float]:
        """One SGD step on ``batch`` (``tokens``, ``targets`` and the
        per-token loss ``weights``); returns the new weights (the old
        ones are donated) and the loss before the update."""
        # a tied head takes the embedding with it: the head's gradient is
        # applied first, the lookup's after the layers
        tied = "head" not in params
        hp_keys = [k for k in params if k != "stages" and (tied or k != "embed")]
        x = self._embed(params["embed"], batch["tokens"])
        xs = []
        with jax.default_matmul_precision("highest"):
            for i in range(self.n_layers):
                xs.append(x)
                x = self._layer(params["stages"], jnp.int32(i), x)
            hp = {k: params.pop(k) for k in hp_keys}
            loss, hp, dx = self._head(hp, x, batch["targets"], batch["weights"], lr)
            params.update(hp)
            stages = params.pop("stages")
            for i in reversed(range(self.n_layers)):
                stages, dx = self._layer_back(stages, jnp.int32(i), xs.pop(), dx, lr)
            params["stages"] = stages
            params["embed"] = self._embed_back(params.pop("embed"), batch["tokens"], dx, lr)
        return params, float(loss)


@functools.lru_cache(maxsize=None)
def _leaf_norms_fn(treedef, stacked: tuple[bool, ...]):
    def norms(new, old):
        out = []
        for a, b, s in zip(jax.tree.leaves(new), jax.tree.leaves(old), stacked):
            d = jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))
            out.append(jnp.sqrt(jnp.sum(d.reshape(d.shape[0] if s else 1, -1), 1)))
        return out

    return jax.jit(norms)


def change_norms(new: dict, old: dict) -> dict[str, float]:
    """Per leaf, the float32 norm of ``new - old``, keyed by path; a leaf
    of ``stages`` (one slice per layer on its leading axis) gives one
    norm per layer, keyed ``<path>[<layer>]``."""
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(new)]
    stacked = tuple(p.startswith("['stages']") for p in paths)
    vals = _leaf_norms_fn(jax.tree.structure(new), stacked)(new, old)
    out = {}
    for path, s, v in zip(paths, stacked, vals):
        for i, x in enumerate(np.asarray(v)):
            out[f"{path}[{i}]" if s else path] = float(x)
    return out
