"""Sharding rules: DP / FSDP / TP / EP / SP as PartitionSpec pytrees.

Axes
----
``('data', 'model')`` single-pod, ``('pod', 'data', 'model')`` multi-pod.
Batch shards over the data axes; parameters shard FSDP-style:

  * the largest weight dim divisible by |model| shards over ``'model'``
    (expert-stacked weights prefer the expert dim — true EP — when
    divisible, e.g. dbrx 16e on a 16-way model axis);
  * optionally (``fsdp_data=True``, the beyond-paper memory optimization)
    a second dim shards over the data axes, ZeRO-3 style.  The
    paper-faithful baseline keeps parameters replicated across data so
    the gradient synchronization is a pure all-reduce — exactly the
    operation MG-WFBP schedules.

KV caches shard batch over data when divisible, else sequence (SP — the
long_500k single-request regime), and head_dim over ``'model'``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from ..models.common import ArchConfig

Pytree = Any

MOE_LEAF_NAMES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved sharding policy for one (arch, mesh) pair."""

    data_axes: tuple[str, ...]
    model_axis: str
    mesh_shape: dict[str, int]
    # False: params sharded over 'model' only (paper-faithful: DP grads are
    #        pure all-reduces).  True: second dim over the data axes
    #        (ZeRO-3).  'experts_only': serving mode — dense weights stay
    #        model-only (no per-token gathers) while the big expert tables
    #        keep the data dim (they are consumed shard-local under EP).
    fsdp_data: bool | str = False
    # EP archs reserve the model axis for experts: the batch must not
    # shard over it (the MoE all-to-all runs G@data <-> E@model).
    reserve_model: bool = False

    @property
    def data_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh_shape[a]
        return n

    @property
    def model_size(self) -> int:
        return self.mesh_shape[self.model_axis]

    def _axes_size(self, axes: tuple[str, ...]) -> int:
        n = 1
        for a in axes:
            n *= self.mesh_shape[a]
        return n

    def batch_axes(self, batch: int) -> tuple[str, ...] | None:
        """Maximal mesh-axis combination that divides the batch.

        train_4k's 256 rows == one pod's 256 chips, so the batch shards
        over *every* axis (pure 256-way DP; ZeRO-3 gathers the FSDP
        weights).  Smaller batches fall back to fewer axes; batch-1 decode
        returns None and sequence-parallel cache sharding carries the
        parallelism instead.
        """
        candidates = [
            self.data_axes + (self.model_axis,),
            self.data_axes,
            self.data_axes[-1:],
        ]
        if self.reserve_model:
            candidates = candidates[1:]
        for axes in candidates:
            if axes and batch % self._axes_size(axes) == 0:
                return axes
        return None


def rules_for_mesh(mesh: jax.sharding.Mesh, fsdp_data: bool = False) -> ShardingRules:
    names = tuple(mesh.axis_names)
    shape = dict(zip(names, mesh.devices.shape))
    data_axes = tuple(a for a in names if a != "model")
    return ShardingRules(
        data_axes=data_axes, model_axis="model", mesh_shape=shape, fsdp_data=fsdp_data
    )


def rules_for_arch(cfg: ArchConfig, mesh: jax.sharding.Mesh, fsdp_data: bool = False) -> ShardingRules:
    """Arch-aware rules: EP archs reserve the model axis for experts."""
    rules = rules_for_mesh(mesh, fsdp_data)
    ep = cfg.moe is not None and cfg.moe.n_held % rules.model_size == 0
    return dataclasses.replace(rules, reserve_model=ep)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _leaf_spec(path_names: list[str], shape: tuple[int, ...], rules: ShardingRules) -> P:
    """FSDP spec for one parameter leaf."""
    in_stages = "stages" in path_names
    dims = list(enumerate(shape))
    if in_stages:
        dims = dims[1:]  # leading n_stages axis stays replicated (scan axis)
    if len(dims) < 2:
        return P()  # 1-D (norm scales, biases, lambdas): replicate

    spec: list[str | None] = [None] * len(shape)
    is_moe = any(n in MOE_LEAF_NAMES for n in path_names)
    model_dim = None
    if is_moe:
        e_axis, e_size = dims[0]
        if e_size % rules.model_size == 0:
            model_dim = e_axis
    if model_dim is None:
        for ax, size in sorted(dims, key=lambda t: -t[1]):
            if size % rules.model_size == 0:
                model_dim = ax
                break
    if model_dim is not None:
        spec[model_dim] = rules.model_axis

    want_data = rules.fsdp_data is True or (
        rules.fsdp_data == "experts_only" and is_moe
    )
    if want_data:
        for ax, size in sorted(dims, key=lambda t: -t[1]):
            if ax != model_dim and size % rules.data_size == 0:
                spec[ax] = rules.data_axes
                break

    return P(*spec)


def param_pspecs(param_shapes: Pytree, rules: ShardingRules) -> Pytree:
    """PartitionSpec pytree matching a params (shape) pytree."""

    def spec(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        return _leaf_spec(names, tuple(leaf.shape), rules)

    return jax.tree_util.tree_map_with_path(spec, param_shapes)


# ---------------------------------------------------------------------------
# Activations / batch / caches
# ---------------------------------------------------------------------------


def _batch_axes(rules: ShardingRules, batch: int) -> tuple[str, ...] | None:
    return rules.batch_axes(batch)


def batch_specs(cfg: ArchConfig, rules: ShardingRules, batch: int, seq: int) -> Pytree:
    ba = _batch_axes(rules, batch)
    out = {"targets": P(ba, None)}
    if cfg.input_mode == "embeds":
        out["embeds"] = P(ba, None, None)
    else:
        out["tokens"] = P(ba, None)
    return out


def act_constraint(cfg: ArchConfig, rules: ShardingRules, batch: int):
    """Between-stage activation constraint: batch over data axes."""
    ba = _batch_axes(rules, batch)

    def constrain(x):
        if ba is None:
            return x
        return jax.lax.with_sharding_constraint(x, P(ba, None, None))

    return constrain


def logits_constraint(cfg: ArchConfig, rules: ShardingRules, batch: int):
    ba = _batch_axes(rules, batch)
    vocab_ax = rules.model_axis if cfg.vocab % rules.model_size == 0 else None
    if ba and rules.model_axis in ba:
        vocab_ax = None  # model axis already consumed by the batch

    def constrain(x):
        return jax.lax.with_sharding_constraint(x, P(ba, None, vocab_ax))

    return constrain


def cache_pspecs(cfg: ArchConfig, rules: ShardingRules, cache_shapes: Pytree, batch: int) -> Pytree:
    """Decode cache shardings.

    KV leaves are (n_stages?, B, T, Hkv, hd) (+ kpos (n_stages?, T));
    recurrent states are (n_stages?, B, ...).  Batch shards over data when
    divisible.  The KV *sequence* axis shards over 'model'
    (flash-decoding style): scores contract locally per shard and only the
    per-row softmax statistics and the (B, H, hd) partial outputs cross
    the wire — no weight or cache gathers.  Recurrent state width shards
    over 'model' (elementwise recurrences are embarrassingly parallel
    across width).
    """
    ba = rules.batch_axes(batch)
    if ba and rules.model_axis in ba:
        ba = tuple(a for a in ba if a != rules.model_axis) or None
    ba_size = rules._axes_size(ba) if ba else 0

    def spec(path, leaf):
        shape = tuple(leaf.shape)
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        staged = "stages" in names
        body = shape[1:] if staged else shape
        lead: list[str | None] = [None] if staged else []
        if len(body) == 1:  # kpos (T,) — replicated with the seq shards
            return P(*lead, None)
        s: list[Any] = [None] * len(body)
        if ba and body[0] % ba_size == 0:
            s[0] = ba
        if len(body) >= 3:
            # KV cache (B, T, Hkv, hd) or wkv state (B, H, K, K):
            # shard T (axis 1) over 'model' when it divides
            if body[1] % rules.model_size == 0:
                s[1] = rules.model_axis
        elif len(body) == 2 and body[-1] % rules.model_size == 0:
            # (B, W) recurrent state: width over model
            s[-1] = rules.model_axis
        return P(*lead, *s)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def named(tree_of_pspecs: Pytree, mesh: jax.sharding.Mesh) -> Pytree:
    return jax.tree.map(
        lambda s: jax.NamedSharding(mesh, s),
        tree_of_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
