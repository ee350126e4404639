"""Cost sources: where the scheduler's per-layer time vector comes from.

The paper seeds Algorithm 1 with *benchmarked* backward times ("the first
several iterations"); our repo historically only had the analytic Eq. 18
path.  This module makes the source pluggable:

  * ``AnalyticCosts``  — the Eq. 18 / roofline estimate (flops and bytes
    per unit converted to seconds by a ``Hardware`` preset);
  * ``MeasuredCosts``  — wall-clock observations: per-unit times from HLO
    segment profiling (``core/profiler.py``), or a whole-step timing that
    rescales the analytic compute model.  Measured times are expressed
    against ``MEASURED_HW`` (unit hardware: 1 flop == 1 second) so the
    scheduler math is unchanged.

``replan_if_drifted`` is the journal version's online re-planning: when a
live cost measurement drifts from the vector a plan was built with, the
same policy reruns on the measured vector and a successor plan is
emitted.  The training loop and the fault-tolerant restart path both call
it (see ``launch/train.py``).

The *communication* side has the same analytic/measured split:
``MeasuredComm`` times real psums over a size sweep and least-squares
fits the (α, β) of Eq. 9 per mesh axis (journal §V-A Fig. 5(b), online)
— the measured counterpart of ``core.comm_model``'s analytic
``tpu_psum_model``.  Its ``fit()`` is an ordinary ``AllReduceModel``, so
plans and every registered policy consume measured comm models
transparently.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Protocol, runtime_checkable

import numpy as np

from ..core.bucketing import layer_buckets_for_scan
from ..core.comm_model import AllReduceModel, fit_affine
from ..core.cost_model import Hardware, LayerCost, TPU_V5E
from .plan import Plan
from .registry import build_schedule, resolve_policy_name

#: Unit hardware: costs carry wall-clock seconds directly in ``bwd_flops``
#: / ``fwd_flops`` (1 FLOP == 1 s, no memory term).
MEASURED_HW = Hardware(
    name="measured_wallclock", peak_flops=1.0, hbm_bw=1.0, mxu_eff=1.0, hbm_eff=1.0
)


@runtime_checkable
class CostSource(Protocol):
    """A producer of the scheduler's per-layer cost vector."""

    name: str
    hw: Hardware

    def layer_costs(self) -> list[LayerCost]: ...


@dataclasses.dataclass(frozen=True)
class AnalyticCosts:
    """Eq. 18-style analytic cost vector (today's default path)."""

    costs: tuple[LayerCost, ...]
    hw: Hardware = TPU_V5E
    name: str = "analytic"

    def layer_costs(self) -> list[LayerCost]:
        return list(self.costs)


@dataclasses.dataclass(frozen=True)
class MeasuredCosts:
    """Wall-clock per-unit cost vector (seconds, against ``MEASURED_HW``)."""

    costs: tuple[LayerCost, ...]
    hw: Hardware = MEASURED_HW
    name: str = "measured"

    def layer_costs(self) -> list[LayerCost]:
        return list(self.costs)

    @classmethod
    def from_unit_times(
        cls,
        base: list[LayerCost],
        bwd_seconds: list[float],
        fwd_seconds: list[float] | None = None,
        name: str = "measured",
    ) -> "MeasuredCosts":
        """Directly measured per-unit backward (and optional forward) times.

        Message sizes and param counts are carried over from ``base`` —
        measurement changes *times*, never payloads.
        """
        if len(bwd_seconds) != len(base):
            raise ValueError(f"{len(bwd_seconds)} times for {len(base)} units")
        if fwd_seconds is not None and len(fwd_seconds) != len(base):
            raise ValueError(f"{len(fwd_seconds)} fwd times for {len(base)} units")
        out = []
        for i, c in enumerate(base):
            out.append(
                LayerCost(
                    name=c.name,
                    params=c.params,
                    grad_bytes=c.grad_bytes,
                    bwd_flops=float(bwd_seconds[i]),
                    fwd_flops=float(fwd_seconds[i]) if fwd_seconds is not None else 0.0,
                )
            )
        return cls(costs=tuple(out), name=name)

    @classmethod
    def from_step_timing(
        cls,
        base: list[LayerCost],
        base_hw: Hardware,
        measured_t_iter: float,
        modeled_t_iter: float,
        name: str = "measured_step",
    ) -> "MeasuredCosts":
        """Whole-step wall-clock calibration (cheapest online signal).

        One measured iteration time rescales every analytic compute time by
        ``measured / modeled`` — the single-free-parameter fit the paper
        itself uses to calibrate Eq. 18 constants.  Comm (α–β) stays fixed,
        so the compute/comm overlap balance — and hence the optimal merge
        set — genuinely shifts.
        """
        if modeled_t_iter <= 0 or measured_t_iter <= 0:
            raise ValueError("step times must be positive")
        scale = measured_t_iter / modeled_t_iter
        bwd = [c.t_b(base_hw) * scale for c in base]
        fwd = [c.t_f(base_hw) * scale for c in base]
        return cls.from_unit_times(base, bwd, fwd, name=name)

    @classmethod
    def from_segment_times(
        cls,
        base: list[LayerCost],
        base_hw: Hardware,
        unit_seconds: dict[str, float],
        name: str = "measured_segments",
    ) -> "MeasuredCosts":
        """Per-unit overrides from HLO segment profiling.

        ``unit_seconds`` maps unit names (``embed``, ``stage_0``, ...,
        ``head``) to measured backward seconds; unmeasured units keep their
        analytic time.  This is the compiled-segment analogue of the
        paper's first-iterations benchmark (see ``core/profiler.py``).
        """
        bwd = [unit_seconds.get(c.name, c.t_b(base_hw)) for c in base]
        fwd = [c.t_f(base_hw) for c in base]
        return cls.from_unit_times(base, bwd, fwd, name=name)


#: A timed probe this many times slower than the running min is treated
#: as an outlier (GC pause, noisy neighbor) and re-taken rather than
#: recorded — see ``min_of_k``.
PROBE_OUTLIER_FACTOR = 10.0


def min_of_k(
    sample_fn: Callable[[], float],
    repeats: int,
    *,
    outlier_factor: float = PROBE_OUTLIER_FACTOR,
    max_retries: int | None = None,
) -> float:
    """Min of ``repeats`` samples with an outlier retry.

    A sample exceeding ``outlier_factor`` × the running min is discarded
    and re-taken (a GC pause or noisy neighbor would otherwise burn one
    of the ``repeats`` slots and, with small ``repeats``, silently skew
    the calibration the sample feeds — ``t_step_fixed``, (α, β) fits).
    Retries are bounded by ``max_retries`` (default ``repeats``) so a
    *genuine* sustained slowdown is reported, not spun on: once the
    budget is spent every sample counts.  Shared by
    ``time_collective_call`` and ``ServingEngine.probe_step_time``.
    """
    repeats = max(1, repeats)
    budget = repeats if max_retries is None else max(0, max_retries)
    best = float("inf")
    taken = retried = 0
    while taken < repeats:
        t = float(sample_fn())
        if t > outlier_factor * best and retried < budget:
            retried += 1
            continue
        best = min(best, t)
        taken += 1
    return best


def time_collective_call(
    f, x, repeats: int = 3, warmup: int = 1,
    clock: Callable[[], float] = time.perf_counter,
) -> float:
    """Run ``warmup`` discarded calls (the first compiles — compile time
    must NEVER reach a timed sample, it would poison every (α, β) fit
    min-of-N merely hides) and return the min of ``repeats`` timed calls
    — the one latency estimator shared by ``MeasuredComm.time_psums``
    (train psums) and ``planning.serve.measure_serve_comm`` (serve
    gathers/all-to-alls), so compute- and comm-side measured costs stay
    directly comparable.  Samples run through ``min_of_k``: a probe 10×
    slower than the running min is re-taken, so one scheduler hiccup
    cannot poison a 3-sample calibration.  ``clock`` is injectable (the
    FakeClock pattern) so tests never assert on real wall-clock deltas."""
    import jax

    for _ in range(max(1, warmup)):  # at least one: compile + warm
        jax.block_until_ready(f(x))

    def sample() -> float:
        t0 = clock()
        jax.block_until_ready(f(x))
        return clock() - t0

    return min_of_k(sample, repeats)


#: Default psum size sweep: 4 KiB … 16 MiB in ×8 steps — small enough to
#: expose α, large enough to pin β (the journal sweeps the same decades).
DEFAULT_COMM_SWEEP = tuple(4 * 1024 * 8**i for i in range(6))

#: Amortized re-fit sweep: one small, one mid, one large size.  Three
#: timed psums per drift check keep the online comm monitor cheap while
#: still moving both ends of the affine fit (α from the small size, β
#: from the large one).
SLIM_COMM_SWEEP = (DEFAULT_COMM_SWEEP[0], DEFAULT_COMM_SWEEP[2], DEFAULT_COMM_SWEEP[5])


@dataclasses.dataclass(frozen=True)
class MeasuredComm:
    """Measured (α, β) all-reduce model for one set of mesh axes.

    Raw observations are kept (sizes in bytes, wall seconds) so the fit
    is reproducible and re-fittable; ``fit()`` returns the affine
    ``AllReduceModel`` every policy/plan already consumes.
    """

    sizes_bytes: tuple[int, ...]
    times_s: tuple[float, ...]
    axes: tuple[str, ...] = ("data",)
    name: str = "measured_comm"

    def fit(self) -> AllReduceModel:
        return fit_affine(
            self.sizes_bytes, self.times_s,
            name=f"{self.name}[{'+'.join(self.axes)}]",
        )

    def update(
        self,
        sizes_bytes: tuple[int, ...] | list[int],
        times_s: tuple[float, ...] | list[float],
        weight: float = 0.5,
    ) -> "MeasuredComm":
        """Fold fresh observations into the sweep (returns a new record).

        Re-observed sizes are exponentially weighted (``new = (1-w)·old +
        w·fresh``) so a transient spike does not whiplash the (α, β) fit,
        while sustained congestion converges in a few checks; unseen sizes
        are appended.  This is the amortized online fit of the journal
        version: a slim ``SLIM_COMM_SWEEP`` re-probe per check instead of
        the full startup sweep.
        """
        if not 0.0 < weight <= 1.0:
            raise ValueError(f"EWMA weight must be in (0, 1], got {weight}")
        obs = dict(zip(self.sizes_bytes, self.times_s))
        for s, t in zip(sizes_bytes, times_s):
            s = int(s)
            obs[s] = (1.0 - weight) * obs[s] + weight * float(t) if s in obs else float(t)
        items = sorted(obs.items())
        return dataclasses.replace(
            self,
            sizes_bytes=tuple(s for s, _ in items),
            times_s=tuple(t for _, t in items),
        )

    @classmethod
    def time_psums(
        cls,
        mesh,
        axes: tuple[str, ...] = ("data",),
        sizes_bytes: tuple[int, ...] = DEFAULT_COMM_SWEEP,
        dtype=None,
        repeats: int = 3,
        name: str = "measured_comm",
    ) -> "MeasuredComm":
        """Time real psums over a size sweep on ``mesh``'s ``axes``.

        One jitted ``shard_map`` psum per size; the first (compiling)
        call is discarded and the min of ``repeats`` timed calls is kept
        — the standard latency estimator, robust to scheduler noise.
        """
        import jax
        import jax.numpy as jnp

        dtype = jnp.float32 if dtype is None else dtype
        P = jax.sharding.PartitionSpec
        axis_arg = axes if len(axes) > 1 else axes[0]
        times = []
        for nb in sizes_bytes:
            n = max(1, int(nb) // np.dtype(dtype).itemsize)
            x = jnp.ones((n,), dtype)

            def body(v):
                return jax.lax.psum(v, axis_arg)

            f = jax.jit(
                jax.shard_map(
                    body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                    axis_names=set(axes), check_vma=False,
                )
            )
            times.append(time_collective_call(f, x, repeats))
        return cls(
            sizes_bytes=tuple(int(s) for s in sizes_bytes),
            times_s=tuple(times), axes=tuple(axes), name=name,
        )


def measure_comm_models(
    mesh, axes: tuple[str, ...] | None = None, **kwargs
) -> dict[str, AllReduceModel]:
    """Per-mesh-axis measured (α, β) fits — one ``MeasuredComm`` sweep
    and fit per axis (plus every axis jointly when there are several,
    under the ``'+'``-joined key), so hierarchical meshes get per-stage
    measured constants the way ``TpuInterconnect.psum_model`` composes
    analytic ones."""
    axes = tuple(mesh.axis_names) if axes is None else tuple(axes)
    out = {ax: MeasuredComm.time_psums(mesh, (ax,), **kwargs).fit() for ax in axes}
    if len(axes) > 1:
        out["+".join(axes)] = MeasuredComm.time_psums(mesh, axes, **kwargs).fit()
    return out


def cost_drift(plan: Plan, measured: CostSource) -> float:
    """Max relative per-unit backward-time deviation of measured vs plan.

    0.0 == identical; 0.5 == some layer's measured backward time is 50%
    away from what the plan was scheduled with.
    """
    base = [c.t_b(plan.hw) for c in plan.costs]
    new = [c.t_b(measured.hw) for c in measured.layer_costs()]
    if len(base) != len(new):
        raise ValueError(f"measured {len(new)} units, plan has {len(base)}")
    worst = 0.0
    for b, n in zip(base, new):
        denom = max(abs(b), 1e-12)
        worst = max(worst, abs(n - b) / denom)
    return worst


def replan_if_drifted(
    plan: Plan,
    measured: CostSource,
    threshold: float = 0.15,
    policy: str | None = None,
) -> tuple[Plan, bool]:
    """Re-run the plan's policy on measured costs when drift exceeds
    ``threshold``; returns ``(plan, replanned)``.

    The successor plan keeps the layout and α–β model, swaps in the
    measured cost vector and its hardware basis, and records the drift and
    cost source in provenance.  Below threshold the original plan is
    returned untouched — re-planning recompiles the train step (new scan
    segments), so it must be rare and deliberate.
    """
    drift = cost_drift(plan, measured)
    if drift <= threshold:
        return plan, False
    policy = resolve_policy_name(policy or plan.policy)
    costs = measured.layer_costs()
    schedule = build_schedule(
        policy, costs, plan.ar_model, hw=measured.hw, **plan.policy_opts
    )
    segments = (
        layer_buckets_for_scan(schedule, plan.n_scan_stages)
        if plan.n_scan_stages is not None
        else None
    )
    prov = dict(plan.provenance)
    prov.update(
        {
            "policy": policy,
            "cost_source": measured.name,
            "replanned_from": plan.provenance.get("cost_source", "?"),
            "drift": f"{drift:.4f}",
        }
    )
    new_plan = dataclasses.replace(
        plan,
        costs=tuple(costs),
        hw=measured.hw,
        schedule=schedule,
        segments=segments,
        provenance=prov,
    )
    return new_plan, True


def comm_drift(old: AllReduceModel, new: AllReduceModel) -> float:
    """Max relative deviation of the fitted (α, β) pair vs a reference.

    0.0 == identical constants; 9.0 == one of α/β moved ×10 (congestion,
    a degraded link).  Denominators are floored so a near-zero reference
    constant does not turn measurement noise into infinite drift.
    """
    da = abs(new.a - old.a) / max(abs(old.a), 1e-9)
    db = abs(new.b - old.b) / max(abs(old.b), 1e-15)
    return max(da, db)


def replan_if_comm_drifted(
    plan: Plan,
    new_model: AllReduceModel,
    threshold: float = 0.25,
    policy: str | None = None,
) -> tuple[Plan, bool]:
    """The comm-side analogue of ``replan_if_drifted``: re-run the plan's
    policy under a freshly fitted (α, β) model when it drifts past
    ``threshold``; returns ``(plan, replanned)``.

    The successor plan keeps the cost vector and layout, swaps in the
    measured all-reduce model, and records the drift in provenance.  α is
    the merge gain itself (Eq. 10), so a drifted α directly moves the
    optimal merge set — this is what completes the journal version's
    online loop (arXiv:1912.09268 Fig. 5(b)) for the wire side.
    """
    drift = comm_drift(plan.ar_model, new_model)
    if drift <= threshold:
        return plan, False
    policy = resolve_policy_name(policy or plan.policy)
    costs = list(plan.costs)
    schedule = build_schedule(policy, costs, new_model, hw=plan.hw, **plan.policy_opts)
    segments = (
        layer_buckets_for_scan(schedule, plan.n_scan_stages)
        if plan.n_scan_stages is not None
        else None
    )
    prov = dict(plan.provenance)
    prov.update(
        {
            "policy": policy,
            "comm_source": new_model.name,
            "replanned_from_comm": plan.ar_model.name,
            "comm_drift": f"{drift:.4f}",
        }
    )
    new_plan = dataclasses.replace(
        plan,
        ar_model=new_model,
        schedule=schedule,
        segments=segments,
        provenance=prov,
    )
    return new_plan, True
