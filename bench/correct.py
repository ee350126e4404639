"""The comparison that decides ``correct`` for a training cell.

Set-up drives the compiled train step through its first steps; the
plain reference (``bench/reference.py``) takes the same steps from the
same weights on the same rows.  Compared, each against a limit of its
own (the cell file's ``limits``):

``loss_gap``
    the largest gap between the program's and the reference's loss over
    those steps (forward and loss; after the first step the update too),
    relative to the reference's loss, or in nats where that is under 1:
    a batch of the seed's repeated motif is learnt within a step, and
    its loss can fall to nought;
``grad_gap``
    per weight leaf, and per layer for the leaves stacked over layers,
    the norm of the first step's change divided by the learning rate:
    the gradient as the optimizer received it, after backward and the
    gradient wire.  The gap between the program's norm and the
    reference's, relative to the larger of the reference's norm of that
    leaf and of the median leaf; the worst leaf counts.  Every leaf
    counts, the LayerNorm scales among them, whose bfloat16 values near 1
    one step moves by a rounding unit at a few elements or none;
``grad_median_gap``
    the median of those per-leaf gaps: where a leaf's gradient is off by
    round-off alone the median stays at the sound run's, and where the
    whole gradient is off (half of the batch left out) it moves with it;
``update_gap``
    per leaf (the layers of a stacked leaf taken together), the gap of
    the norms of the change over all the steps, relative to the larger
    of the reference's norm of that leaf and of the median leaf, leaving
    out the leaves whose reference gradient is under a thousandth of the
    median leaf's (they move by round-off alone); the worst leaf counts;
``replica_mismatch``
    on several chips, the number of weight leaves whose copies on the
    chips are not bit-identical after the window (limit 0).
"""

from __future__ import annotations

import math
import re
import statistics

#: The layer index that ends the key of one layer of a stacked leaf.
LAYER = re.compile(r"\[\d+\]$")


def leaf_gaps(prog: dict[str, float], ref: dict[str, float], keep) -> list[float]:
    """``|prog - ref| / max(ref, median ref)`` of each leaf in ``keep``;
    infinite where any leaf is not finite."""
    if not all(math.isfinite(v) for v in [*prog.values(), *ref.values()]):
        return [math.inf]
    floor = statistics.median(ref.values())
    return [abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keep]


def stacked(norms: dict[str, float]) -> dict[str, float]:
    """Per-layer norms (keys ending in ``[<layer>]``) joined into one norm
    per stacked leaf."""
    out: dict[str, float] = {}
    for k, v in norms.items():
        k = LAYER.sub("", k)
        out[k] = math.hypot(out.get(k, 0.0), v)
    return out


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """The compared numbers of one run from the program's and the
    reference's readings (``losses``, ``grad``, ``update``)."""
    loss_gap = max(
        (abs(p - r) / max(abs(r), 1.0) if math.isfinite(p) else math.inf)
        for p, r in zip(prog["losses"], ref["losses"], strict=True))
    grad = leaf_gaps(prog["grad"], ref["grad"], ref["grad"])
    g = stacked(ref["grad"])
    moved = [k for k, r in g.items() if r >= 1e-3 * statistics.median(g.values())]
    out = {"loss_gap": loss_gap, "grad_gap": max(grad), "grad_median_gap": statistics.median(grad),
           "update_gap": max(leaf_gaps(stacked(prog["update"]), stacked(ref["update"]), moved))}
    if "replica_mismatch" in prog:
        out["replica_mismatch"] = float(prog["replica_mismatch"])
    return out


def judge(nums: dict[str, float], limits: dict[str, float]) -> tuple[bool, list[list]]:
    """``(correct, [[name, number, limit], ...])``: correct when every
    number is at most its limit."""
    rows = [[k, v, limits[k]] for k, v in nums.items()]
    return all(v <= lim for _, v, lim in rows), rows
