"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

The JAX profiler writes one XSpace per run.  On a TPU each chip is a
plane ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per
executed HLO operation (start and duration in nanoseconds, on the same
clock as the host's events), named by the instruction's HLO text.  A
loop or call is an event that holds its body's events; only the
innermost events are kept, so no time is counted twice.  On the CPU
backend the operations run on host threads and carry an ``hlo_op`` stat
instead; they are read as device 0's, so the same code can be tested
without a chip.  The benchmark's own host spans
(``bench.batch``, ``bench.dispatch``, ``bench.wait``) come from
``/host:CPU``.

Everything here is plain interval arithmetic on those events: busy time
is the union of a device's operation intervals, idle time the rest of
the traced window, and a collective's exposed time the part of its
interval in which no other operation runs on that device.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("bench.batch", "bench.dispatch", "bench.wait")


_ALLREDUCE = re.compile(r"(^%?all-reduce|\sall-reduce(-start|-done)?\()")
#: A Mosaic kernel whose result is one flat array (a pack into the wire
#: arena), or whose operands are one flat array and the f32[1] scale (an
#: unpack of it).
_PACK = re.compile(r"^%?\S+ = \w+\[\d+\]\{[^}]*\} custom-call\(")
_UNPACK = re.compile(r"custom-call\(\w+\[\d+\]\{[^}]*\} %\S+, f32\[1\]\{")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float  # ns
    end: float
    name: str
    module: str = ""
    scope: str = ""  # the op's name-scope path, where the trace gives it


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged ``intervals`` cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


@dataclasses.dataclass
class Reduced:
    ops: dict[int, list[Op]]  # by device
    host: list[tuple[float, float, str]]
    t0: float  # the traced window, ns
    t1: float

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy(self, dev: int) -> list[tuple[float, float]]:
        return union((max(o.start, self.t0), min(o.end, self.t1))
                     for o in self.ops[dev] if o.end > self.t0 and o.start < self.t1)

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        return sum(covered(self.busy(d), self.t0, self.t1) for d in self.ops) \
            * 1e-9 / max(1, len(self.ops))

    def op_seconds(self, dev: int, pred) -> float:
        """Summed durations of ``dev``'s operations that ``pred`` selects."""
        return sum(min(o.end, self.t1) - max(o.start, self.t0)
                   for o in self.ops[dev] if pred(o) and o.end > self.t0
                   and o.start < self.t1) * 1e-9

    def exposed_seconds(self, dev: int, pred) -> float:
        """Time in which an operation that ``pred`` selects runs on
        ``dev`` and no other operation does."""
        sel = union((o.start, o.end) for o in self.ops[dev] if pred(o))
        rest = union((o.start, o.end) for o in self.ops[dev] if not pred(o))
        tot = 0.0
        for s, e in sel:
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                tot += (e - s) - covered(rest, s, e)
        return tot * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed by name,
        averaged over devices), and the longest idle gaps named by the
        host span they fall in."""
        per: dict[str, float] = {}
        for dev, ops in self.ops.items():
            for o in ops:
                if o.end > self.t0 and o.start < self.t1:
                    per[o.name] = per.get(o.name, 0.0) + (o.end - o.start) * 1e-9 / len(self.ops)
        gaps = []
        for dev in self.ops:
            b = self.busy(dev)
            edges = [self.t0] + [x for iv in b for x in iv] + [self.t1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s, e))
        gaps.sort(reverse=True)

        def host_at(s, e):
            best, name = 0.0, "no host span"
            for hs, he, hn in self.host:
                ov = min(he, e) - max(hs, s)
                if ov > best:
                    best, name = ov, hn
            return name

        return {
            "device_ops": [[label(n), v]
                           for n, v in sorted(per.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[host_at(s, e), g * 1e-9] for g, s, e in gaps[:top]],
        }


def label(name: str, width: int = 120) -> str:
    """A short label of an operation: its HLO text, cut to ``width``."""
    return name.lstrip("%")[:width]


def innermost(ops: list[Op]) -> list[Op]:
    """The events that hold no other event (drops loops and calls whose
    bodies are listed too)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    parent = [False] * len(ops)
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and ops[stack[-1]].end >= o.end and o.end > o.start:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (AttributeError, TypeError):
        return {}


def reduce_file(path: str, device_ids) -> Reduced:
    """Read one ``.xplane.pb``; ``device_ids`` are the chips of the run."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops: dict[int, list[Op]] = {d: [] for d in device_ids}
    host = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in ops:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    ops[dev].append(Op(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                                       str(st.get("hlo_module", "")),
                                       str(st.get("tf_op", st.get("name", "")))))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                        continue
                    st = _stats(ev)
                    if "hlo_op" in st and int(st.get("device_ordinal", 0)) in ops:
                        ops[int(st.get("device_ordinal", 0))].append(
                            Op(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                               str(st.get("hlo_module", ""))))
    ops = {d: innermost(v) for d, v in ops.items()}
    if host:
        t0, t1 = min(h[0] for h in host), max(h[1] for h in host)
    else:
        allops = [o for v in ops.values() for o in v]
        t0, t1 = min(o.start for o in allops), max(o.end for o in allops)
    return Reduced(ops=ops, host=host, t0=t0, t1=t1)


def reduce_dir(tdir: str, device_ids) -> Reduced:
    """The run's trace under the profiler's output directory ``tdir``."""
    files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return reduce_file(files[-1], device_ids)


def is_allreduce(op: Op) -> bool:
    """An all-reduce (or a part of an asynchronous one)."""
    return bool(_ALLREDUCE.search(op.name))


def is_comm_pack(op: Op) -> bool:
    """A comm_pack pack or unpack kernel: a Mosaic kernel
    (``tpu_custom_call``) that writes one flat wire arena or reads one
    back with its scale."""
    return 'custom_call_target="tpu_custom_call"' in op.name and bool(
        _PACK.match(op.name) or _UNPACK.search(op.name))
