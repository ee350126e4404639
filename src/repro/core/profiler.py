"""HLO-based cost extraction — the JAX analogue of the paper's warm-up
benchmarking (Algorithm 1 'initializes ... with system settings and
benchmarks in the first several iterations').

On real hardware MG-WFBP measures per-layer backward times; in this
CPU-only container we extract exact FLOPs / bytes from compiled HLO
*segments* and convert them to times with ``core.cost_model.Hardware``.

Why segments: ``compiled.cost_analysis()`` counts a ``lax.scan`` body ONCE
(verified during prototyping), so whole-program numbers undercount layer
loops.  Lowering (embed, one layer, head) separately with production
shardings gives exact per-segment costs; totals recompose analytically.

Also here: the collective-traffic parser used by the roofline analysis —
it walks compiled HLO text, sums operand bytes of every collective op, and
multiplies ops inside `while` loops by their trip count.  It also reads
*lowered* StableHLO (pre-optimization): on CPU the compiled module
upcasts bf16 collectives to f32, so wire-dtype truth — what the
wire-layout benchmark and the bf16/arena byte assertions need — only
exists before compilation.

Trace-first overlap verification (the DAG-step proof obligation)
----------------------------------------------------------------
The train step names its layers with ``jax.named_scope`` (the names of
``repro.scopes``: ``fwd_*``, ``bwd_*``, ``optimizer``, ``attention``, and
the sync engine's ``wfbp_group*``).  XLA keeps each scope path in the
compiled module's ``op_name`` metadata, so :func:`scope_spans` reads a
``jax.profiler`` trace, joins each executed op to its scope by
instruction name, and emits one :class:`Span` per ``bwd_*`` scope and
one per ``wfbp_group*`` scope's all-reduce, per device and step, on the
device's own clock.
:func:`overlap_report` then computes the measured overlap fraction (comm
time inside the backward window / total comm time) and the structural
DAG property: a non-final ``wfbp_group*`` span starting before the last
backward span ends.  :func:`parse_trace_spans` reads Chrome-trace JSON
(the committed fixture under ``tests/data/``) into the same spans.
:func:`scope_layers` reads the same trace by layer: device time per step
of the wire, remat recompute, backward, forward, optimizer and attention.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib
import re
import time

from .. import scopes

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

#: StableHLO op name -> compiled-HLO kind (the parser's canonical keys).
_STABLEHLO_COLLECTIVES = {
    "all_reduce": "all-reduce",
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "all_to_all": "all-to-all",
    "collective_permute": "collective-permute",
}


@dataclasses.dataclass
class CollectiveStats:
    """Aggregated collective traffic of one compiled module (per device).

    ``concat_ops`` counts ``concatenate`` ops — not a collective, but the
    tell-tale of the copy-based merged-buffer wire layout: the arena
    layout (``core/sync.py`` ``fuse='arena'``) must lower with zero of
    them, and the wire-layout benchmark reports them per fuse mode.  It
    is kept out of ``counts``/``total_bytes`` so roofline collective
    traffic is unchanged.
    """

    counts: dict[str, int]
    bytes_by_kind: dict[str, int]
    concat_ops: int = 0

    @property
    def total_ops(self) -> int:
        return sum(self.counts.values())

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape like 'bf16[32,4608]{1,0}' (0 for token etc.)."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str.strip())
    if not m:
        return 0
    dtype, dims = m.group(1), m.group(2)
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_shapes(line: str) -> list[str]:
    """Shapes produced by an HLO op line (handles tuple results)."""
    # '%name = (f32[2,3]{1,0}, f32[4]{0}) all-reduce(...)' or
    # '%name = f32[2,3]{1,0} all-reduce(...)'
    m = re.search(r"=\s*(\([^)]*\)|\S+)\s+[\w-]+\(", line)
    if not m:
        return []
    res = m.group(1)
    if res.startswith("("):
        return [s for s in res[1:-1].split(", ") if s]
    return [res]


def _tensor_bytes(tensor_type: str) -> int:
    """Bytes of one StableHLO tensor type body like '100x32xbf16' / 'f32'."""
    parts = tensor_type.strip().split("x")
    dtype = parts[-1]
    if dtype not in _DTYPE_BYTES:
        # stablehlo integer spellings: i8/i32/ui8... -> s8/s32/u8
        alias = {"i": "s", "ui": "u"}
        m = re.match(r"(ui|i)(\d+)$", dtype)
        dtype = f"{alias[m.group(1)]}{m.group(2)}" if m else dtype
        if dtype not in _DTYPE_BYTES:
            return 0
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _parse_stablehlo(text: str) -> CollectiveStats:
    """Collective stats from lowered (StableHLO) module text.

    Ops with regions (all_reduce) put their type signature on the
    region-closing ``}) : (...) -> ...`` line; the first ``->`` after the
    op start is that signature either way, so a forward scan suffices.

    Counts are *static*: StableHLO ``while`` bodies carry no trip-count
    annotation, so a collective inside a scanned body counts once — use
    compiled-HLO text when loop-multiplied totals matter (the dry-run /
    roofline path does), lowered text when wire dtypes matter.
    """
    counts: dict[str, int] = {}
    nbytes: dict[str, int] = {}
    concat_ops = len(re.findall(r"stablehlo\.concatenate", text))
    for m in re.finditer(r'"?stablehlo\.(\w+)"?[(<]', text):
        kind = _STABLEHLO_COLLECTIVES.get(m.group(1))
        if kind is None:
            continue
        tail = text[m.end() : m.end() + 8000]
        tm = re.search(r"->\s*(\([^)]*\)|tensor<[^>]*>)", tail)
        payload = (
            sum(_tensor_bytes(t) for t in re.findall(r"tensor<([^>]*)>", tm.group(1)))
            if tm
            else 0
        )
        counts[kind] = counts.get(kind, 0) + 1
        nbytes[kind] = nbytes.get(kind, 0) + payload
    return CollectiveStats(counts=counts, bytes_by_kind=nbytes, concat_ops=concat_ops)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Count collective ops and payload bytes in compiled HLO text.

    Lowered StableHLO text is detected and parsed too — use that form
    whenever the *wire dtype* matters (compiled CPU modules upcast bf16
    collectives to f32), but note the while-loop multiplication below is
    compiled-HLO-only (StableHLO has no trip-count annotation, so
    loop-body collectives count once there).

    * operand bytes are taken from the op's *result* shapes (for all-reduce
      result==operand; for all-gather the result is the gathered size which
      upper-bounds wire traffic per device; reduce-scatter result is the
      scattered shard — we use max(result, operands)/2-style accounting
      kept deliberately simple: payload = max(result bytes, operand bytes));
    * ops inside `while` loop bodies are multiplied by the loop trip count
      when XLA printed a known trip count comment, else by the scan length
      inferred from the loop induction comparison.
    """
    if "stablehlo." in hlo_text:
        return _parse_stablehlo(hlo_text)

    counts: dict[str, int] = {}
    nbytes: dict[str, int] = {}
    concat_ops = 0

    # Map computation name -> list of (kind, payload)
    comp_ops: dict[str, list[tuple[str, int]]] = {}
    comp_name = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # data-movement tell-tale (compiled HLO and stablehlo spellings)
        if re.search(r"(?:\s|=\s*)concatenate\(|stablehlo\.concatenate", stripped):
            concat_ops += 1
        m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*\)\s*->", stripped)
        if m and ("{" in stripped or stripped.endswith("{")):
            comp_name = m.group(1)
            comp_ops.setdefault(comp_name, [])
            continue
        for kind in _COLLECTIVES:
            # match 'kind(' or 'kind-start('
            if re.search(rf"\)?\s{kind}(?:-start)?\(", stripped) and "=" in stripped:
                res_shapes = _result_shapes(stripped)
                payload = sum(_shape_bytes(s) for s in res_shapes)
                # all-reduce-done / all-gather-done re-mention the shape; skip
                if re.search(rf"\s{kind}-done\(", stripped):
                    continue
                if comp_name is not None:
                    comp_ops[comp_name].append((kind, payload))
                counts[kind] = counts.get(kind, 0) + 1
                nbytes[kind] = nbytes.get(kind, 0) + payload
                break

    # Account for while-loop trip counts: find while ops and their body
    # computations, then re-add (trip_count - 1) x body collectives.
    for m in re.finditer(r"while\(.*?\)[^\n]*body=%?([\w.\-]+)[^\n]*", hlo_text):
        body = m.group(1)
        line = m.group(0)
        trip = None
        tc = re.search(r"trip_count=(\d+)", line)
        if tc:
            trip = int(tc.group(1))
        if trip is None or body not in comp_ops:
            continue
        for kind, payload in comp_ops[body]:
            counts[kind] = counts.get(kind, 0) + (trip - 1)
            nbytes[kind] = nbytes.get(kind, 0) + payload * (trip - 1)

    return CollectiveStats(counts=counts, bytes_by_kind=nbytes, concat_ops=concat_ops)


@dataclasses.dataclass
class SegmentCost:
    """Exact cost of one lowered program segment (per device)."""

    name: str
    flops: float
    bytes_accessed: float
    collectives: CollectiveStats
    peak_temp_bytes: int = 0


def segment_cost(name: str, compiled) -> SegmentCost:
    """Extract flops / bytes / collectives from one compiled executable."""
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    return SegmentCost(
        name=name,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        collectives=parse_collectives(compiled.as_text()),
        peak_temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
    )


def time_segment(fn, *args, warmup: int = 1, repeats: int = 3, clock=None) -> float:
    """Wall-clock one jitted/compiled segment: discard ``warmup`` calls
    (compilation, caches), keep the min of ``repeats`` timed calls — the
    same latency estimator ``MeasuredComm.time_psums`` uses, so compute-
    and comm-side measured costs are directly comparable.  This is the
    measured counterpart of ``segment_cost``: same segment decomposition,
    seconds instead of flops.  ``clock`` is injectable (FakeClock
    pattern) so tests never sleep or assert on real wall-clock deltas."""
    import jax

    if clock is None:
        clock = time.perf_counter
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = clock()
        jax.block_until_ready(fn(*args))
        best = min(best, clock() - t0)
    return best


# ---------------------------------------------------------------------------
# Named scopes of the train step, and the spans read back from a trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span:
    """One timed scope of one device, in Chrome-trace units (µs)."""

    name: str
    device: int
    start_us: float
    dur_us: float
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us


#: ``wfbp_group{gi}_l{lo}_{hi}`` — the sync engine's per-group scope name.
GROUP_SPAN_RE = re.compile(r"^wfbp_group(\d+)_l(\d+)_(\d+)$")

#: Backward-compute scopes of the train step (``bwd_<event>``).
BWD_SPAN_PREFIX = scopes.BWD_PREFIX

_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: An all-reduce instruction's line, or a part of an asynchronous one.
_ALLREDUCE_OP = re.compile(r"\sall-reduce(-start|-done)?\(")
_TRANSPOSED_FWD = re.compile(r"^transpose\(jvp\(fwd_(\w+)\)\)$")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` scope path, from a compiled
    module's text (``compiled.as_text()``).  An instruction without one
    takes that of the computation it calls (a fusion), else that of its
    first operand that has one, in turn: XLA's copies, prefetches and
    layout changes move a value that a scoped instruction made."""
    names: dict[str, str] = {}
    first_in: dict[str, str] = {}  # computation -> its first op_name
    calls: dict[str, str] = {}  # instruction without op_name -> computation
    operands: dict[str, list[str]] = {}  # instruction without op_name -> operands
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        n = _OP_NAME.search(line)
        if n:
            names[m.group(1)] = n.group(1)
            if comp is not None:
                first_in.setdefault(comp, n.group(1))
            continue
        c = _CALLS.search(line)
        if c:
            calls[m.group(1)] = c.group(1)
        rhs = line.split("=", 1)[1]
        operands[m.group(1)] = _OPERAND.findall(rhs[rhs.find("("):])
    for inst, c in calls.items():
        if c in first_in:
            names[inst] = first_in[c]

    def resolve(inst: str) -> str | None:  # depth first, first operand first
        stack, seen = [inst], set()
        while stack:
            x = stack.pop()
            if x in names:
                return names[x]
            if x not in seen:
                seen.add(x)
                stack.extend(reversed(operands.get(x, ())))
        return None

    for inst in operands:
        if inst not in names and (path := resolve(inst)) is not None:
            names[inst] = path
    return names


def hlo_allreduces(hlo_text: str) -> set[str]:
    """Names of the all-reduce instructions (and the start / done halves
    of asynchronous ones) of a compiled module's text."""
    out = set()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and _ALLREDUCE_OP.search(line):
            out.add(m.group(1))
    return out


def span_scope(op_name: str, allreduce: bool = False) -> str | None:
    """The overlap report's span an op belongs to: for an all-reduce its
    ``wfbp_group*`` scope; otherwise its ``bwd_*`` scope, or ``bwd_<x>``
    for the transposed forward of ``fwd_<x>`` (the ``post`` step's
    backward).  None for anything else, such as a group's pack and
    unpack: a comm span is the group's time on the wire alone."""
    parts = op_name.split("/")
    if allreduce:
        return next((c for c in parts if GROUP_SPAN_RE.match(c)), None)
    if any(GROUP_SPAN_RE.match(c) for c in parts):
        return None
    for c in parts:
        if c.startswith(BWD_SPAN_PREFIX):
            return c
    for c in parts:
        m = _TRANSPOSED_FWD.match(c)
        if m:
            return BWD_SPAN_PREFIX + m.group(1)
    return None


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (AttributeError, TypeError):
        return {}


def _leaves(events):
    """The events of one trace line, ``(start, end, ...)``, that hold no
    other: a loop's or a call's event is dropped where its body's events
    are listed too, so no time is counted twice."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    holds = [False] * len(events)
    stack: list[int] = []
    for i, (start, end, *_) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and events[stack[-1]][1] >= end > start:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(events, holds) if not h]


def trace_ops(xplane_path, module: str) -> list[tuple[int, int, int, int, str]]:
    """``(device, run, start_ns, end_ns, instruction)`` of every executed
    op of the HLO module ``module`` in a ``jax.profiler`` trace, without
    the loops and calls whose bodies' ops are listed (:func:`_leaves`).

    On a TPU each chip's ``XLA Ops`` line names an op by its HLO text
    (``%fusion.12 = ...``) and the ``XLA Modules`` line gives each
    execution of a module; on the CPU each op is a host event with
    ``hlo_op``, ``hlo_module``, ``run_id`` and ``device_ordinal`` stats.
    ``run`` numbers the module's executions in the trace from 0."""
    import bisect

    import jax

    pd = jax.profiler.ProfileData.from_file(str(xplane_path))
    raw: list[tuple[int, int, int, object, str]] = []  # start, end, device, run, op
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            runs = sorted(e.start_ns for e in lines.get("XLA Modules", ())
                          if e.name.startswith(module))
            raw += _leaves(
                (ev.start_ns, ev.start_ns + ev.duration_ns, int(m.group(1)),
                 max(0, bisect.bisect_right(runs, ev.start_ns) - 1),
                 ev.name.split(" = ", 1)[0].strip().lstrip("%"))
                for ev in lines.get("XLA Ops", ()))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                line_ops = []
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_op" in st and str(st.get("hlo_module", module)) == module:
                        line_ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                         int(st.get("device_ordinal", 0)),
                                         int(st.get("run_id", 0)), str(st["hlo_op"])))
                raw += _leaves(line_ops)
    order = {r: i for i, r in enumerate(sorted({ev[3] for ev in raw}))}
    return [(d, order[r], s, e, n) for s, e, d, r, n in raw]


def spans_from_ops(ops, hlo_text: str, group_bytes=()) -> list[Span]:
    """The overlap report's spans from executed ops ``(device, run,
    start_ns, end_ns, instruction)`` (:func:`trace_ops`) of the compiled
    module ``hlo_text``: each op is joined by instruction name to its
    ``op_name`` scope path (:func:`hlo_op_names`).  For each device and
    each run there is one :class:`Span` per ``bwd_*`` scope and per
    ``wfbp_group*`` scope (:func:`span_scope`: a group's span covers its
    all-reduce ops only), from the scope's first op to its last;
    ``args["step"]`` numbers the runs.  ``group_bytes``
    (``sync.group_wire_bytes``) sets each group span's ``args["bytes"]``.
    """
    names = hlo_op_names(hlo_text)
    allreduces = hlo_allreduces(hlo_text)
    bounds: dict[tuple[int, int, str], list[int]] = {}
    for dev, run, start, end, op in ops:
        scope = span_scope(names.get(op, ""), op in allreduces)
        if scope is None:
            continue
        b = bounds.setdefault((dev, run, scope), [start, end])
        b[0], b[1] = min(b[0], start), max(b[1], end)
    spans = []
    for (dev, run, scope), (start, end) in bounds.items():
        args = {"step": run}
        g = GROUP_SPAN_RE.match(scope)
        if g and int(g.group(1)) < len(group_bytes):
            args["bytes"] = int(group_bytes[int(g.group(1))])
        spans.append(Span(name=scope, device=dev, start_us=start / 1e3,
                          dur_us=(end - start) / 1e3, args=args))
    spans.sort(key=lambda s: (s.device, s.start_us))
    return spans


def scope_spans(xplane_path, hlo_text: str, group_bytes=()) -> list[Span]:
    """The overlap report's spans, read from a ``jax.profiler`` trace of
    the step whose compiled module is ``hlo_text``
    (``compiled.as_text()``): :func:`spans_from_ops` of its
    :func:`trace_ops`."""
    return spans_from_ops(_module_ops(xplane_path, hlo_text), hlo_text, group_bytes)


def _module_ops(xplane_path, hlo_text: str):
    m = _HLO_MODULE.search(hlo_text)
    return trace_ops(xplane_path, m.group(1) if m else "")


#: The train step's layers, in the order :func:`op_layer` tries them.
LAYERS = ("wire", "remat", "backward", "forward", "optimizer", "unscoped")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _core(component: str) -> str:
    """A path component without its transformation wrappers:
    ``transpose(jvp(attention))`` -> ``attention``."""
    while (m := _WRAPPED.match(component)) is not None:
        component = m.group(1)
    return component


def op_layer(op_name: str, allreduce: bool = False) -> str:
    """The train-step layer of an op whose scope path is ``op_name``;
    the first rule that matches wins:

    1. ``wire``: an all-reduce, or an op under a ``wfbp_group*`` scope
       (a group's pack, all-reduce, unpack and the slicing around them);
    2. ``remat``: a component ``rematted_computation``, the
       ``jax.checkpoint`` recompute inside a pullback;
    3. ``backward``: a component ``bwd_*`` or ``transpose(...)``;
    4. ``forward``: a component ``fwd_*`` or one that wraps it
       (``jvp(fwd_model)``);
    5. ``optimizer``: a component ``optimizer``;
    6. ``unscoped``: anything else.

    XLA's memory rematerialization clones an instruction under a name
    ending in ``.remat`` with its original's ``op_name``: the clone counts
    in its original's layer."""
    parts = op_name.split("/") if op_name else []
    if allreduce or any(GROUP_SPAN_RE.match(c) for c in parts):
        return "wire"
    if "rematted_computation" in parts:
        return "remat"
    if any(c.startswith((BWD_SPAN_PREFIX, "transpose(")) for c in parts):
        return "backward"
    cores = [_core(c) for c in parts]
    if any(c.startswith(scopes.FWD_PREFIX) for c in cores):
        return "forward"
    if scopes.OPTIMIZER in cores:
        return "optimizer"
    return "unscoped"


def is_attention(op_name: str) -> bool:
    """An op of attention's core: a component ``attention`` or one that
    wraps it (``jvp(attention)``, ``transpose(jvp(attention))``)."""
    return any(_core(c) == scopes.ATTENTION for c in op_name.split("/")) if op_name else False


def is_flash(op_name: str) -> bool:
    """An op of one of the flash attention kernels (``flash_fwd``,
    ``flash_dq``, ``flash_dkv``: the ``name=`` of their ``pallas_call``)."""
    return any(_core(c) in scopes.FLASH_KERNELS for c in op_name.split("/")) if op_name else False


def is_moe(op_name: str) -> bool:
    """An op of the held-expert MoE layer (a component ``moe`` or one that
    wraps it): routing, dispatch, the experts and the combine."""
    return any(_core(c) == scopes.MOE for c in op_name.split("/")) if op_name else False


def is_moe_gmm(op_name: str) -> bool:
    """An op of the MoE grouped-matmul kernels (``moe_gmm``, ``moe_tgmm``:
    the ``name=`` of their ``pallas_call``)."""
    return any(_core(c) in scopes.MOE_KERNELS for c in op_name.split("/")) if op_name else False


def is_moe_rows(op_name: str) -> bool:
    """An op of the MoE row kernels (``moe_rows_pack``,
    ``moe_rows_gather``, ``moe_rows_sum``: the ``name=`` of their
    ``pallas_call``)."""
    return any(_core(c) in scopes.MOE_ROW_KERNELS for c in op_name.split("/")) if op_name else False


#: The MoE layer's parts (``models/moe.held_moe_block``), each read by
#: :func:`layer_split` as ``<part>_ms``.
MOE_PARTS = (scopes.MOE_ROUTE, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS, scopes.MOE_COMBINE)


def moe_part(op_name: str) -> str | None:
    """The part of the MoE layer (:data:`MOE_PARTS`) an op belongs to, by
    its scope path, or None."""
    cores = {_core(c) for c in op_name.split("/")} if op_name else set()
    return next((p for p in MOE_PARTS if p in cores), None)


def layer_split(ops, hlo_text: str) -> dict[str, float] | None:
    """Device milliseconds per step of each of :data:`LAYERS`
    (``<layer>_ms``), of attention (``attention_ms``), of attention inside
    the flash kernels (``attention_flash_ms``), of the MoE layer
    (``moe_ms``), of each of its parts (``moe_route_ms``,
    ``moe_dispatch_ms``, ``moe_experts_ms``, ``moe_combine_ms``), of its
    grouped-matmul kernels (``moe_gmm_ms``) and of its row kernels
    (``moe_rows_ms``), and of all busy time
    (``busy_ms``), averaged over devices and steps, from executed ops
    ``(device, run, start_ns, end_ns, instruction)`` (:func:`trace_ops`) of
    the compiled module ``hlo_text``.  Where ops of several layers run at
    once (XLA:CPU runs them side by side; a chip seldom does) the time
    counts once, for the first of them in :data:`LAYERS`, so the layers add
    up to the busy time.  Attention and the MoE layer cut across the
    layers: the union of their ops' intervals.  None where no op carries a
    train-step scope."""
    names = hlo_op_names(hlo_text)
    allreduces = hlo_allreduces(hlo_text)
    runs: dict[tuple[int, int], list[tuple]] = {}
    for dev, run, start, end, op in ops:
        path = names.get(op, "")
        runs.setdefault((dev, run), []).append(
            (start, end, LAYERS.index(op_layer(path, op in allreduces)), is_attention(path),
             is_flash(path), is_moe(path), is_moe_gmm(path), is_moe_rows(path), moe_part(path)))
    total = [0.0] * len(LAYERS)
    attention = flash = moe = gmm = rows = 0.0
    parts = dict.fromkeys(MOE_PARTS, 0.0)
    for tagged in runs.values():
        edges = sorted([(s, 1, k) for s, e, k, *_ in tagged if e > s]
                       + [(e, -1, k) for s, e, k, *_ in tagged if e > s])
        active, prev = [0] * len(LAYERS), None
        for t, d, k in edges:
            if prev is not None and t > prev:
                first = next((i for i, c in enumerate(active) if c), None)
                if first is not None:
                    total[first] += t - prev
            active[k] += d
            prev = t
        attention += _union_len([(s, e) for s, e, _, a, *_ in tagged if a])
        flash += _union_len([(s, e) for s, e, _, a, f, *_ in tagged if a and f])
        moe += _union_len([(s, e) for s, e, _, _, _, m, *_ in tagged if m])
        gmm += _union_len([(s, e) for s, e, *_, g, _, _ in tagged if g])
        rows += _union_len([(s, e) for s, e, *_, r, _ in tagged if r])
        for part in parts:
            parts[part] += _union_len([(s, e) for s, e, *_, q in tagged if q == part])
    if not any(total[LAYERS.index(k)] for k in ("remat", "backward", "forward", "optimizer")):
        return None
    per = 1e-6 / len(runs)  # ns summed over (device, step) -> ms per step
    out = {f"{k}_ms": v * per for k, v in zip(LAYERS, total)}
    return out | {"attention_ms": attention * per, "attention_flash_ms": flash * per,
                  "moe_ms": moe * per, "moe_gmm_ms": gmm * per, "moe_rows_ms": rows * per,
                  **{f"{k}_ms": v * per for k, v in parts.items()}, "busy_ms": sum(total) * per}


def scope_layers(xplane_path, hlo_text: str) -> dict[str, float] | None:
    """:func:`layer_split` of a ``jax.profiler`` trace of the step whose
    compiled module is ``hlo_text`` (``compiled.as_text()``)."""
    return layer_split(_module_ops(xplane_path, hlo_text), hlo_text)


def parse_trace_spans(trace) -> list[Span]:
    """Parse Chrome-trace ``X`` events into :class:`Span` rows.

    ``trace`` is a dict, a JSON string, or a path to ``.json`` /
    ``.json.gz`` — recorded traces, committed ``tests/data/`` fixtures,
    and real profiler dumps all funnel through here.  ``B``/``E`` event
    pairs are folded into complete spans; events without a duration are
    skipped.  Devices are taken from ``pid``.
    """
    if isinstance(trace, pathlib.PurePath):
        trace = str(trace)
    if isinstance(trace, (str, bytes)) and not str(trace).lstrip().startswith("{"):
        opener = gzip.open if str(trace).endswith(".gz") else open
        with opener(trace, "rt") as f:
            trace = json.load(f)
    elif isinstance(trace, (str, bytes)):
        trace = json.loads(trace)
    events = trace.get("traceEvents", trace) if isinstance(trace, dict) else trace

    spans: list[Span] = []
    open_: dict[tuple[str, int], list[dict]] = {}
    for ev in events:
        ph = ev.get("ph")
        name = ev.get("name")
        if not name:
            continue
        dev = int(ev.get("pid", 0))
        if ph == "X":
            spans.append(
                Span(name=name, device=dev, start_us=float(ev["ts"]),
                     dur_us=float(ev.get("dur", 0.0)), args=dict(ev.get("args", {})))
            )
        elif ph == "B":
            open_.setdefault((name, dev), []).append(ev)
        elif ph == "E":
            stack = open_.get((name, dev))
            if stack:
                b = stack.pop(0)
                spans.append(
                    Span(name=name, device=dev, start_us=float(b["ts"]),
                         dur_us=float(ev["ts"]) - float(b["ts"]),
                         args=dict(b.get("args", {})))
                )
    spans.sort(key=lambda s: (s.device, s.start_us))
    return spans


def _union_len(intervals: list[tuple[float, float]]) -> float:
    total = 0.0
    end = -float("inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _overlap_with_union(lo: float, hi: float, intervals: list[tuple[float, float]]) -> float:
    """Length of [lo, hi] ∩ (∪ intervals)."""
    clipped = [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]
    return _union_len(clipped)


def overlap_report(spans: list[Span]) -> dict:
    """Measured comm/compute overlap from parsed spans.

    Comm spans are the ``wfbp_group{gi}_l{lo}_{hi}`` scopes; backward
    spans are the ``bwd_*`` scopes the DAG step records.  Per device the
    report intersects each comm span with the backward *window* (first
    backward start .. last backward end) and with the union of the
    backward compute spans themselves; aggregated:

    * ``overlap_fraction`` — Σ comm-time-inside-backward-window / Σ comm
      time: the issue-order property the DAG step buys.  Comm placed in
      this window is what an async fabric hides (the paper's WFBP/MG-WFBP
      ratio); the serialized issue order scores ~0 because every group
      issues after the window closes.
    * ``hidden_fraction`` — the stricter Σ comm-time-intersecting-backward
      *compute spans* / Σ comm time: true wall-clock concurrency.  On a
      serial backend (CPU) this can honestly read 0 even under the DAG
      step — issued comm executes in the gaps between backward segments —
      while a real accelerator overlaps it; use ``overlap_fraction`` for
      backend-robust assertions and this for real-fabric measurement.
    * ``n_overlapped_starts`` — comm spans starting strictly before the
      device's last backward span ends (the structural DAG property: a
      merged all-reduce issued *inside* backward);
    * ``groups`` — per-group rows from device 0 (name, layers, bytes,
      start/dur, window/hidden time, the starts-before flag) for tables.

    Returns zeros (not an error) when no comm spans parse — callers
    assert on the fields, so an empty trace fails loudly there.
    """
    by_dev: dict[int, dict[str, list[Span]]] = {}
    for s in spans:
        d = by_dev.setdefault(s.device, {"comm": [], "bwd": []})
        if GROUP_SPAN_RE.match(s.name):
            d["comm"].append(s)
        elif s.name.startswith(BWD_SPAN_PREFIX):
            d["bwd"].append(s)

    total_comm = hidden = windowed = 0.0
    n_overlapped_starts = 0
    n_comm_spans = 0
    groups_out: list[dict] = []
    first_dev = min(by_dev) if by_dev else None
    for dev in sorted(by_dev):
        comm, bwd = by_dev[dev]["comm"], by_dev[dev]["bwd"]
        bwd_iv = [(s.start_us, s.end_us) for s in bwd]
        first_bwd_start = min((s.start_us for s in bwd), default=0.0)
        last_bwd_end = max((s.end_us for s in bwd), default=0.0)
        window = [(first_bwd_start, last_bwd_end)] if bwd else []
        for s in comm:
            h = _overlap_with_union(s.start_us, s.end_us, bwd_iv)
            w = _overlap_with_union(s.start_us, s.end_us, window)
            starts_inside = bool(bwd) and s.start_us < last_bwd_end
            total_comm += s.dur_us
            hidden += h
            windowed += w
            n_comm_spans += 1
            if starts_inside:
                n_overlapped_starts += 1
            if dev == first_dev:
                m = GROUP_SPAN_RE.match(s.name)
                groups_out.append(
                    {
                        "name": s.name,
                        "group": int(m.group(1)),
                        "layers": [int(m.group(2)), int(m.group(3))],
                        "bytes": int(s.args.get("bytes", 0)),
                        "start_us": s.start_us,
                        "dur_us": s.dur_us,
                        "window_us": w,
                        "hidden_us": h,
                        "starts_before_bwd_end": starts_inside,
                    }
                )
    groups_out.sort(key=lambda g: g["group"])
    return {
        "n_devices": len(by_dev),
        "n_comm_spans": n_comm_spans,
        "n_bwd_spans": sum(len(d["bwd"]) for d in by_dev.values()),
        "total_comm_us": total_comm,
        "windowed_comm_us": windowed,
        "hidden_comm_us": hidden,
        "overlap_fraction": (windowed / total_comm) if total_comm > 0 else 0.0,
        "hidden_fraction": (hidden / total_comm) if total_comm > 0 else 0.0,
        "n_overlapped_starts": n_overlapped_starts,
        "groups": groups_out,
    }
