"""Batch-slot serving engine: dispatch-free continuous batching.

The engine owns a fixed batch of decode slots and keeps **all** per-step
state — the KV cache arena, per-row positions, next tokens, the
active-slot mask, the per-row token budget, and the sampling key — in a
single fixed-shape device pytree (the ``DecodeState``).  The decode hot
loop is exactly one jitted, buffer-donating call per step
(``jax.jit(step, donate_argnums=...)``): no per-step ``device_put``, no
host-side position bookkeeping feeding the trace, and no retrace when a
sequence joins or leaves — joins and retirements are *data* (masked
device writes), never *shape*.

Admission is bucketed: requests admitted in the same step are spliced
into their slots by one jitted masked-write call selected from a small
set of static batch buckets (powers of two up to ``slots``), so a churny
request stream compiles at most ``log2(slots)+1`` admission executables
ever, and the steady-state decode loop compiles exactly one
(``compile_stats`` exposes the executable counts; the test suite pins
them).  Prefill still runs per-join at the request's prompt length and
its KV rows ride the bucketed splice; a paged KV allocator is the
production refinement and slots behind this API.

Rows at different progress coexist in one batch: the decode step's
scalar ``pos`` is the max active row position (the engine's global
clock), and each layer's ring-buffer cache masks by absolute stored
positions (``models/layers.py``).  For simplicity rows joining
mid-flight re-prefill into a fresh slot-batch of size 1 and are copied
in by the bucketed splice.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..launch.steps import make_decode_step, make_prefill_step
from ..models.common import ArchConfig
from ..models.transformer import init_caches

if TYPE_CHECKING:
    from ..planning.serve import ServePlan
    from .sharded import ServeTimer

Pytree = Any


@dataclasses.dataclass
class Request:
    """One generation request: a prompt, a token budget, and the tokens
    decoded so far.  ``submit`` it to a ``ServingEngine``; the engine
    appends to ``generated`` every step and sets ``done`` when the budget
    (or the engine's ``max_seq``) is reached.

    ``deadline_s`` is an absolute per-request SLO on the serving loop's
    clock (``serving.resilience.resilient_serve_loop``): an *active*
    request past its deadline retires gracefully with the tokens decoded
    so far (``expired=True``, partial ``generated``); a *waiting* request
    whose predicted completion (``ServePlan.predicted_step_time()`` ×
    remaining budget) misses the deadline is never admitted
    (``shed=True``, empty ``generated``) — load shedding at admission
    instead of wasted decode steps.

    ``replica_id``/``retries`` are fleet provenance
    (``serving.fleet.FleetController``): which replica currently owns
    the request and how many times it was failed over.  A request
    submitted with a non-empty ``generated`` list *resumes*: admission
    re-prefills ``prompt + generated[:-1]`` and continues decoding from
    ``generated[-1]``, so a re-routed request keeps every token it
    already produced (its final output is token-identical to its
    partial prefix, and goodput is never double-charged — the tokens
    live on one ``Request``, counted once)."""

    rid: int
    prompt: np.ndarray  # (prompt_len,) int32 token ids
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    deadline_s: float | None = None
    expired: bool = False
    shed: bool = False
    replica_id: int | None = None
    retries: int = 0

    @property
    def remaining_tokens(self) -> int:
        """Decode steps still owed to this request — what fleet admission
        prices against ``ServePlan.predicted_step_time()``."""
        return max(0, self.max_new_tokens - len(self.generated))


def _cache_size(fn) -> int:
    """Number of compiled executables behind a jitted callable (0 before
    the first call) — the compile-count hook the engine tests pin."""
    get = getattr(fn, "_cache_size", None)
    return int(get()) if callable(get) else -1


class ServingEngine:
    """Synchronous-step continuous batching over fixed decode slots.

    ``plan`` is the frozen decode-side ``planning.ServePlan`` the engine
    runs under; ``predicted_step_time`` is the plan's wire timeline plus
    its measured per-step fixed (dispatch+compute) term — see
    ``measure_step_fixed``/``calibrate_plan``.  With ``mesh=`` the
    engine *executes* the plan: the decode step runs under ``shard_map``
    over ``tp_axis`` and issues exactly one fused collective per
    scheduled serve group (``serving.sharded`` — KV all-gathers for
    dense archs, expert all-to-alls for MoE), token-for-token identical
    to the unsharded path.  Either way the whole step — decode,
    sampling, position/budget/mask updates — is ONE jitted call whose
    ``DecodeState`` argument is donated, so the cache arena is updated
    in place and the steady-state loop never retraces.

    ``sample`` may take ``(logits)`` (pure, e.g. the default argmax) or
    ``(logits, key)`` (seeded stochastic sampling; the PRNG key lives in
    the donated state and is split inside the step).  A ``ServeTimer``
    passed as ``timer=`` records per-step wall clock, closing the
    predicted-vs-observed loop (``observed_step_time``); call
    ``warmup()`` before any timing loop so compilation never pollutes
    the samples.

    Token models feed prompts directly; ``input_mode == 'embeds'`` archs
    (audio/VLM stub frontends) route token ids through the model's
    embedding table — the same one-engine code path either way.

    Example::

        plan = build_serve_plan(cfg, param_specs(cfg), "tpu_v5e",
                                {"model": 8}, batch_rows=4)
        eng = ServingEngine(cfg, params, slots=4, plan=plan, mesh=mesh)
        eng.warmup()
        plan = eng.calibrate_plan()     # measured t_step_fixed folded in
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=16))
        done = eng.run_to_completion()
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        slots: int = 4,
        max_seq: int = 512,
        sample: Callable | None = None,
        sample_seed: int = 0,
        plan: "ServePlan | None" = None,
        mesh=None,
        tp_axis: str = "model",
        timer: "ServeTimer | None" = None,
    ):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.plan = plan
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.timer = timer
        self.sample = sample or (lambda logits: jnp.argmax(logits, axis=-1))
        self._keyed_sample = _takes_key(self.sample)
        self._prefill = jax.jit(make_prefill_step(cfg, None, max_seq=max_seq))
        if mesh is not None:
            if plan is None:
                raise ValueError("sharded serving (mesh=) requires a ServePlan")
            from .sharded import sharded_decode_core

            core = sharded_decode_core(cfg, plan, mesh, tp_axis=tp_axis)
        else:
            base = make_decode_step(cfg, None)

            def core(params, caches, batch, pos):
                logits, caches = base(params, caches, batch, pos)
                return logits, caches, ()

        self._step_fn = jax.jit(self._make_step(core), donate_argnums=(1,))
        self._admit_fns: dict[int, Callable] = {}
        caches = init_caches(cfg, batch=slots, max_seq=max_seq, dtype=jnp.float32)
        self._state: Pytree = {
            "caches": caches,
            "row_pos": jnp.zeros((slots,), jnp.int32),
            "next_token": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "budget": jnp.zeros((slots,), jnp.int32),
            "key": jax.random.PRNGKey(sample_seed),
        }
        if mesh is not None:
            # the step runs mirror-compute over the mesh: weights and state
            # ride replicated, committed ONCE here — never again per step
            # (weights left on one device would be broadcast every step)
            sh = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            self.params = jax.device_put(self.params, sh)
            self._state = jax.device_put(self._state, sh)
        self._admit_key = jax.random.PRNGKey(sample_seed + 1)
        self.active: dict[int, Request] = {}  # slot -> request
        self.row_pos = np.zeros((slots,), np.int32)  # host mirror (bookkeeping)
        self.next_token = np.zeros((slots,), np.int32)
        self.waiting: list[Request] = []
        self.completed: list[Request] = []

    # -- the one jitted step ----------------------------------------------

    def _make_step(self, core):
        """Build the whole-step body: decode + sample + masked state
        updates, traced once per (shape, mesh) — the donated hot path."""
        cfg, max_seq = self.cfg, self.max_seq
        sample, keyed = self.sample, self._keyed_sample

        def step_fn(params, state):
            active = state["active"]
            pos = jnp.max(jnp.where(active, state["row_pos"], 0)).astype(jnp.int32)
            tokens = state["next_token"][:, None]
            if cfg.input_mode == "embeds":
                batch = {"embeds": params["embed"][tokens].astype(jnp.float32)}
            else:
                batch = {"tokens": tokens}
            logits, caches, wire = core(params, state["caches"], batch, pos)
            if keyed:
                key, sub = jax.random.split(state["key"])
                sampled = sample(logits, sub)
            else:
                key = state["key"]
                sampled = sample(logits)
            sampled = sampled.astype(jnp.int32)
            row_pos = jnp.where(active, state["row_pos"] + 1, state["row_pos"])
            budget = jnp.where(active, state["budget"] - 1, state["budget"])
            # retirement is a masked device write: a row leaves the batch
            # by flipping its mask bit, never by changing a shape
            still = active & (budget > 0) & (row_pos + 1 < max_seq)
            new_state = {
                "caches": caches,
                "row_pos": row_pos,
                "next_token": jnp.where(active, sampled, state["next_token"]),
                "active": still,
                "budget": budget,
                "key": key,
            }
            return new_state, sampled, wire

        return step_fn

    # -- inputs ------------------------------------------------------------

    def _embed_rows(self, ids: jax.Array) -> jax.Array:
        """Stub frontend for ``input_mode == 'embeds'`` archs: token ids ->
        embedding-table rows (what ``launch/serve.py`` historically did)."""
        return self.params["embed"][ids].astype(jnp.float32)

    def _prefill_input(self, prompt: np.ndarray) -> dict:
        ids = jnp.asarray(prompt[None, :])
        if self.cfg.input_mode == "embeds":
            return {"embeds": self._embed_rows(ids)}
        return {"tokens": ids}

    # -- predicted vs observed --------------------------------------------

    def predicted_step_time(self) -> float | None:
        """Modeled decode-step seconds: the plan's wire timeline plus its
        ``t_step_fixed`` (dispatch+compute) term."""
        return self.plan.predicted_step_time() if self.plan is not None else None

    def observed_step_time(self) -> float | None:
        """Median measured decode-step seconds from the attached
        ``ServeTimer`` (None without a timer or before any clean sample)
        — the measured counterpart of ``predicted_step_time``."""
        return self.timer.median() if self.timer is not None else None

    def warmup(self) -> None:
        """Compile + warm the decode executable on a throwaway state copy
        (all slots marked active) so the first timed step never includes
        compilation.  Run this before any timing loop; the engine's real
        state and submitted requests are untouched."""
        state = _copy_state(self._state)
        state["active"] = jnp.ones_like(state["active"])
        out_state, sampled, _ = self._step_fn(self.params, state)
        jax.block_until_ready((out_state, sampled))

    def probe_step_time(self, repeats: int = 5) -> float:
        """Min-of-``repeats`` wall seconds of the compiled engine step on
        a throwaway state chain (every slot active) — the whole-step
        measurement ``measure_step_fixed`` decomposes.  Compilation is
        warmed first and never timed; samples run through the shared
        outlier-retrying ``planning.costs.min_of_k`` so one GC pause or
        noisy neighbor cannot skew the ``t_step_fixed`` calibration."""
        from ..planning.costs import min_of_k

        state = _copy_state(self._state)
        state["active"] = jnp.ones_like(state["active"])
        state, s, _ = self._step_fn(self.params, state)  # warm
        jax.block_until_ready(s)
        chain = [state]

        def sample() -> float:
            t0 = time.perf_counter()
            new_state, tok, _ = self._step_fn(self.params, chain[0])
            jax.block_until_ready((new_state, tok))
            chain[0] = new_state
            return time.perf_counter() - t0

        return min_of_k(sample, max(1, repeats))

    def measure_step_fixed(self, repeats: int = 5) -> float:
        """The measured per-step *fixed* (dispatch+compute) seconds: the
        probed whole-step time minus the plan's wire timeline — the
        ``a_step`` analogue of the paper's startup term, one level up.
        Probed once (``StepTimer``-style warm-compiled min-of-repeats)
        and folded into ``ServePlan.t_step_fixed`` by
        ``calibrate_plan``; without a plan the whole probe is fixed."""
        probe = self.probe_step_time(repeats=repeats)
        wire = 0.0
        if self.plan is not None and self.plan.schedule.result is not None:
            wire = self.plan.schedule.result.t_iter
        return max(0.0, probe - wire)

    def calibrate_plan(self, repeats: int = 5) -> "ServePlan":
        """Probe the fixed term and install (and return) the calibrated
        plan: ``predicted_step_time`` now reports wire + fixed — the
        honest compute+dispatch serve cost model."""
        if self.plan is None:
            raise ValueError("calibrate_plan requires a ServePlan")
        self.plan = self.plan.with_step_fixed(self.measure_step_fixed(repeats))
        return self.plan

    def install_plan(self, plan: "ServePlan") -> None:
        """Swap in a (re)built ``ServePlan`` — the degraded-fabric replan
        hook.  On an unsharded engine the plan is advisory (predictions,
        shedding); on a sharded engine the decode step *executes* the
        plan's merge schedule, so the step function is rebuilt and
        recompiles on the next step — acceptable for a rare replan, and
        the only way the wire actually changes shape."""
        self.plan = plan
        if self.mesh is not None:
            from .sharded import sharded_decode_core

            core = sharded_decode_core(self.cfg, plan, self.mesh,
                                       tp_axis=self.tp_axis)
            self._step_fn = jax.jit(self._make_step(core), donate_argnums=(1,))

    def retire(self, slot: int, *, expired: bool = False,
               requeue: bool = False) -> Request:
        """Retire an active row before its budget is spent: the request
        keeps its partial ``generated`` output, the slot's device mask
        bit flips off (a masked write, never a reshape), and the slot
        frees for the next admission.

        With ``requeue=True`` the request is *evicted*, not finished: it
        is returned not-done and joins no queue — the fleet failover
        path re-submits it elsewhere and resume admission continues it
        from its partial prefix.  Otherwise it lands in ``completed``
        (``expired=`` marks a deadline expiry)."""
        req = self.active.pop(slot)
        state = dict(self._state)
        state["active"] = state["active"].at[slot].set(False)
        self._state = state
        if requeue:
            return req
        req.done = True
        req.expired = expired
        self.completed.append(req)
        return req

    def drain_requests(self) -> list[Request]:
        """Evict every in-flight and waiting request (active rows first,
        in slot order) — what the fleet controller calls on a dead
        replica to fail its work over to healthy peers.  Each request
        keeps its partial ``generated`` output; the engine is left
        empty."""
        out = [self.retire(slot, requeue=True)
               for slot in sorted(self.active)]
        out.extend(self.waiting)
        self.waiting.clear()
        return out

    # -- snapshot / restore -------------------------------------------------

    def snapshot(self, step: int = 0) -> "Any":
        """Host-side ``EngineSnapshot`` of the full decode state — the
        cache arena, row positions, next tokens, masks, budgets, both
        PRNG keys — plus the pending/in-flight/completed request queues
        (``serving.resilience.snapshot_engine``).  Save it with
        ``serving.resilience.save_snapshot`` (the checkpoint subsystem's
        atomic-rename machinery) and resume with ``restore_snapshot``:
        decoding continues token-for-token identical to an uninterrupted
        run — the serve-side analogue of ``RunState.checkpoint_tree()``."""
        from .resilience import snapshot_engine

        return snapshot_engine(self, step)

    def restore_snapshot(self, snap: "Any") -> None:
        """Install an ``EngineSnapshot``: device state re-placed (under
        the engine's mesh sharding when sharded), request queues and host
        mirrors rebuilt.  The engine must have been constructed with the
        same config/slots/max_seq the snapshot was taken under (the
        snapshot carries them for validation).  After a restore the next
        ``step()`` continues exactly where the snapshot left off."""
        snap.validate_against(self)
        state = jax.tree.map(jnp.asarray, snap.state)
        if self.mesh is not None:
            sh = jax.NamedSharding(self.mesh, jax.sharding.PartitionSpec())
            state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
        self._state = state
        self._admit_key = jnp.asarray(snap.admit_key)
        from .resilience import requests_from_snapshot

        self.active, self.waiting, self.completed = requests_from_snapshot(snap)
        self.row_pos = np.asarray(snap.row_pos, np.int32).copy()
        self.next_token = np.asarray(snap.next_token, np.int32).copy()

    def compile_stats(self) -> dict[str, Any]:
        """Executable counts per engine entry point: ``decode`` (the one
        donated step), ``admit`` (one per batch bucket used), ``prefill``
        (one per distinct prompt length).  The steady-state invariant the
        tests pin is ``decode == 1`` across joins, leaves, and slot
        reuse."""
        return {
            "decode": _cache_size(self._step_fn),
            "admit": {m: _cache_size(f) for m, f in self._admit_fns.items()},
            "prefill": _cache_size(self._prefill),
        }

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.waiting.append(req)

    def _bucket(self, k: int) -> int:
        """Static admission bucket: next power of two ≥ k, ≤ slots."""
        m = 1 << max(0, k - 1).bit_length()
        return min(m, self.slots)

    def _admit_fn(self, m: int) -> Callable:
        if m not in self._admit_fns:
            self._admit_fns[m] = jax.jit(
                self._make_admit(m), donate_argnums=(0,)
            )
        return self._admit_fns[m]

    def _make_admit(self, m: int):
        """Bucketed splice: write ``m`` stacked 1-row prefill cache trees
        into their slots as masked device writes (invalid lanes rewrite
        the slot's own row — a no-op), plus the per-row scalar state.
        One executable per bucket size, reused forever."""
        cfg, slots = self.cfg, self.slots

        def admit(state, fresh, slot_idx, valid, tok0, pos0, budget0):
            def put(c, f):
                # same leaf-dispatch rule as the historical eager splice:
                # stacked stage caches splice axis 1, slot-batched leaves
                # axis 0, shared (kpos) leaves keep the engine's copy
                for i in range(m):
                    s = slot_idx[i]
                    if c.ndim >= 2 and c.shape[0] == cfg.n_stages:
                        if c.ndim >= 3 and c.shape[1] == slots:
                            cur = jax.lax.dynamic_slice_in_dim(c, s, 1, axis=1)
                            row = jnp.where(valid[i], f[i].astype(c.dtype), cur)
                            c = jax.lax.dynamic_update_slice_in_dim(c, row, s, axis=1)
                            continue
                    if c.ndim >= 1 and c.shape[0] == slots:
                        cur = jax.lax.dynamic_slice_in_dim(c, s, 1, axis=0)
                        row = jnp.where(valid[i], f[i].astype(c.dtype), cur)
                        c = jax.lax.dynamic_update_slice_in_dim(c, row, s, axis=0)
                return c

            caches = jax.tree.map(put, state["caches"], fresh)
            row_pos, next_token = state["row_pos"], state["next_token"]
            active, budget = state["active"], state["budget"]
            for i in range(m):
                s = slot_idx[i]
                row_pos = row_pos.at[s].set(
                    jnp.where(valid[i], pos0[i], row_pos[s]))
                next_token = next_token.at[s].set(
                    jnp.where(valid[i], tok0[i], next_token[s]))
                budget = budget.at[s].set(
                    jnp.where(valid[i], budget0[i], budget[s]))
                active = active.at[s].set(valid[i] | active[s])
            return {
                **state, "caches": caches, "row_pos": row_pos,
                "next_token": next_token, "active": active, "budget": budget,
            }

        return admit

    def _admit(self) -> None:
        free = [s for s in range(self.slots) if s not in self.active]
        entries: list[tuple[int, Pytree, int, int, int]] = []
        while free and self.waiting:
            slot = free.pop(0)
            req = self.waiting.pop(0)
            if req.generated:
                # resume (fleet failover re-route): re-prefill everything
                # up to the last already-sampled token, then decode that
                # token next — the request continues from its partial
                # prefix, no admission sample, no token double-charged
                if req.remaining_tokens == 0:
                    req.done = True
                    self.completed.append(req)
                    free.insert(0, slot)
                    continue
                ids = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(req.generated[:-1], np.int32)]
                )
                _, fresh = self._prefill(self.params, self._prefill_input(ids))
                tok = int(req.generated[-1])
                pos0 = len(req.prompt) + len(req.generated) - 1
            else:
                logits, fresh = self._prefill(
                    self.params, self._prefill_input(req.prompt)
                )
                if self._keyed_sample:
                    self._admit_key, sub = jax.random.split(self._admit_key)
                    tok = int(np.asarray(self.sample(logits, sub))[0])
                else:
                    tok = int(np.asarray(self.sample(logits))[0])
                req.generated.append(tok)
                pos0 = len(req.prompt)
            if self.mesh is not None:
                sh = jax.NamedSharding(self.mesh, jax.sharding.PartitionSpec())
                fresh = jax.tree.map(lambda x: jax.device_put(x, sh), fresh)
            self.active[slot] = req
            self.row_pos[slot] = pos0
            self.next_token[slot] = tok
            entries.append((slot, fresh, tok, pos0,
                            req.max_new_tokens - len(req.generated)))
        if not entries:
            return
        n_real = len(entries)
        m = self._bucket(n_real)
        while len(entries) < m:  # pad the bucket with masked-off lanes
            entries.append(entries[0])
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[e[1] for e in entries])
        self._state = self._admit_fn(m)(
            self._state,
            stacked,
            jnp.asarray([e[0] for e in entries], jnp.int32),
            jnp.asarray([i < n_real for i in range(m)], bool),
            jnp.asarray([e[2] for e in entries], jnp.int32),
            jnp.asarray([e[3] for e in entries], jnp.int32),
            jnp.asarray([e[4] for e in entries], jnp.int32),
        )

    # -- stepping ----------------------------------------------------------

    def step(self) -> int:
        """Admit, decode one token for every active row; returns #active.

        With no active rows this is a guaranteed no-op: no compile, no
        dispatch, no collective (the empty-bucket invariant the tests
        pin)."""
        self._admit()
        if not self.active:
            return 0
        t0 = time.perf_counter() if self.timer is not None else 0.0
        new_state, sampled, _wire = self._step_fn(self.params, self._state)
        self._state = new_state
        if self.timer is not None:
            jax.block_until_ready((new_state, sampled))
            self.timer.observe(time.perf_counter() - t0)
        sampled_np = np.asarray(sampled)  # the step's one device->host read
        for slot, req in list(self.active.items()):
            tok = int(sampled_np[slot])
            req.generated.append(tok)
            self.row_pos[slot] += 1
            self.next_token[slot] = tok
            if len(req.generated) >= req.max_new_tokens or self.row_pos[slot] + 1 >= self.max_seq:
                req.done = True
                self.completed.append(req)
                del self.active[slot]
        return len(self.active)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            if not self.active and not self.waiting:
                break
            self.step()
        return self.completed


def _takes_key(sample: Callable) -> bool:
    """Whether ``sample`` is the keyed two-arg form ``(logits, key)``."""
    try:
        n = len(inspect.signature(sample).parameters)
    except (TypeError, ValueError):
        return False
    return n >= 2


def _copy_state(state: Pytree) -> Pytree:
    """Deep-copy a ``DecodeState`` into fresh buffers (same shardings) so
    a donated probe/warmup call can never consume the engine's state."""
    return jax.tree.map(jnp.copy, state)
