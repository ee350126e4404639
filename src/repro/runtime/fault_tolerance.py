"""Fault tolerance: restart loop, straggler mitigation, elasticity hooks.

On a real multi-pod deployment each of these hooks binds to the cluster
manager (GKE/Borg preemption signals, ICI health counters).  The logic —
what to do when — lives here and is deterministic and unit-tested; the
signal sources are injectable callables so the tests (and this CPU
container) simulate failures exactly.

* ``resilient_loop`` — run train steps; on failure restore the latest
  complete checkpoint and continue.  Tolerates the checkpointed step
  being mid-write (atomic rename guarantees a complete older one).
* ``StragglerMonitor`` — deadline-based detection over per-step
  durations: a step slower than ``factor`` x rolling median flags a
  straggler; after ``patience`` consecutive flags it requests remediation
  (re-shard / hot-spare swap at the cluster layer).  This implements the
  synchronous-SGD-side mitigation MG-WFBP needs: merged buckets make
  all-reduces fewer and larger, so one slow participant stalls the whole
  step — detection must be cheap and fast.
* elasticity — on restart with a different device count the MG-WFBP
  plan is recomputed (checkpoint layout is schedule-agnostic; see
  checkpoint.restore_rebucketed).  ``resilient_loop`` exposes this as
  the ``on_restart`` hook: the launcher re-runs the planning pipeline
  (``planning.replan_if_drifted`` or a fresh policy run at the new N)
  and swaps in the new train step before the loop resumes.
"""

from __future__ import annotations

import dataclasses
import logging
import statistics
import time
from typing import Any, Callable

import jax

from ..checkpoint import AsyncCheckpointer, latest_step, restore

Pytree = Any

log = logging.getLogger(__name__)


@dataclasses.dataclass
class RunState:
    step: int
    params: Pytree
    opt_state: Pytree
    restarts: int = 0

    def checkpoint_tree(self) -> dict:
        return {"params": self.params, "opt_state": self.opt_state}


class StragglerMonitor:
    """Deadline-based straggler detection on per-step wall times."""

    def __init__(self, factor: float = 2.0, patience: int = 3, window: int = 32):
        self.factor = factor
        self.patience = patience
        self.window = window
        self.durations: list[float] = []
        self.consecutive_slow = 0
        self.remediations = 0

    def observe(self, duration_s: float) -> bool:
        """Record one step; returns True when remediation should trigger."""
        if len(self.durations) >= 8:
            med = statistics.median(self.durations[-self.window :])
            if duration_s > self.factor * med:
                self.consecutive_slow += 1
            else:
                self.consecutive_slow = 0
        self.durations.append(duration_s)
        if self.consecutive_slow >= self.patience:
            self.consecutive_slow = 0
            self.remediations += 1
            return True
        return False


def resilient_loop(
    *,
    num_steps: int,
    init_state: Callable[[], RunState],
    train_step: Callable[[RunState, int], RunState],
    checkpoint_dir: str,
    checkpoint_every: int = 50,
    max_restarts: int = 5,
    backoff_base_s: float = 0.05,
    sleep_fn: Callable[[float], None] = time.sleep,
    fault_injector: Callable[[int], None] | None = None,
    straggler: StragglerMonitor | None = None,
    on_straggler: Callable[[RunState], RunState] | None = None,
    on_restart: Callable[[RunState], RunState] | None = None,
    plan_provider: Callable[[], Any] | None = None,
    tuner_provider: Callable[[], Any] | None = None,
) -> RunState:
    """Checkpoint/restart training loop.

    ``fault_injector(step)`` may raise to simulate a node failure;
    the loop restores the latest complete checkpoint and resumes.  The
    data pipeline needs no state file — batches are pure functions of the
    step (data/pipeline.py), so restored step ⇒ restored stream.

    ``on_restart(state)`` runs after every restore (including restarts
    from scratch) — the elasticity hook where the launcher re-plans the
    gradient-merge schedule for the post-failure cluster shape.

    Failure handling: every failure logs the full traceback with the
    failing step before the restore; ``KeyboardInterrupt``/``SystemExit``
    are never swallowed (an operator Ctrl-C must stop the run, not
    restart it); restarts back off exponentially
    (``backoff_base_s * 2**(restarts-1)``, ``sleep_fn`` injectable so
    tests pin the schedule without sleeping); and the ``restarts``
    counter saved in every checkpoint's ``extra`` dict is folded back in
    on restore, so the count — and the ``max_restarts`` budget — survive
    process death instead of resetting with each new process.

    ``plan_provider()`` returns the *currently active* ``planning.Plan``
    (or None); it is called at every checkpoint so the plan JSON lands
    beside the weights (``checkpoint.load_plan`` reads it back) — a
    callable rather than a value because online re-planning swaps the
    plan mid-run.  ``tuner_provider()`` is the same contract for the
    auto-tuner's state (``checkpoint.load_tuner_state`` /
    ``planning.Tuner.load_state``): sweep history and comm observations
    resume across restarts instead of restarting the online loop cold.
    """
    ckpt = AsyncCheckpointer(checkpoint_dir)
    state = init_state()
    restarts = 0

    while state.step < num_steps:
        try:
            t0 = time.monotonic()
            if fault_injector is not None:
                fault_injector(state.step)
            state = train_step(state, state.step)
            state.step += 1
            dt = time.monotonic() - t0
            if straggler is not None and straggler.observe(dt):
                if on_straggler is not None:
                    state = on_straggler(state)
            if state.step % checkpoint_every == 0:
                with jax.profiler.TraceAnnotation("train.checkpoint"):
                    ckpt.save(
                        state.step,
                        state.checkpoint_tree(),
                        extra={"restarts": restarts},
                        plan=plan_provider() if plan_provider is not None else None,
                        tuner=tuner_provider() if tuner_provider is not None else None,
                    )
        except (KeyboardInterrupt, SystemExit):
            raise  # operator interrupts stop the run, never restart it
        except Exception:
            log.exception(
                "train step %d failed; restart %d/%d from latest checkpoint",
                state.step, restarts + 1, max_restarts,
            )
            restarts += 1
            if restarts > max_restarts:
                raise
            if backoff_base_s > 0:
                sleep_fn(backoff_base_s * 2 ** (restarts - 1))
            ckpt.wait()
            step = latest_step(checkpoint_dir)
            if step is None:
                state = init_state()
                state.restarts = restarts
                if on_restart is not None:
                    state = on_restart(state)
                continue
            fresh = init_state()
            tree, extra = restore(checkpoint_dir, step, fresh.checkpoint_tree())
            # restart counts survive process death: the checkpoint's saved
            # counter (+1 for the failure just handled) floors this
            # session's count, so max_restarts budgets the run, not the
            # process
            restarts = max(restarts, int(extra.get("restarts", 0)) + 1)
            state = RunState(
                step=step,
                params=tree["params"],
                opt_state=tree["opt_state"],
                restarts=restarts,
            )
            if on_restart is not None:
                state = on_restart(state)
    ckpt.wait()
    state.restarts = restarts
    return state
