"""Read the program's own spans and named device programs out of a profiler
trace (``.xplane.pb``), beside ``bench/trace_reduce.py``'s reduction.

``read`` returns a ``ProgramTrace`` with three things:

1. the program's spans (``jax.profiler.TraceAnnotation`` names starting with
   ``engine.`` or ``async.``) per host thread, nested;
2. each device op with the program that ran it (the ``XLA Modules`` event
   that encloses it, ``jit_<name>(<fingerprint>)``) and its ``tf_op``
   scope path (the op_name ``jax.named_scope`` writes into), which
   ``jax.profiler.ProfileData`` does not hand out: a small decoder of the
   XSpace fields needed reads it from each device plane's event metadata.
   An op with no op_name of its own takes the scope of the ops it reads
   (``inherit_scopes``);
3. the host-device skew, from the program's ``engine.host_fetch`` ends: a
   fetch cannot return before the last device op it waits on ends.

The readings built on it: device time by program, the decode program's
device time by scope, the traced window's idle put down to the innermost
program span open on the host, and the per-layer quantities
(``decode_attn_roofline``, ``host_step_ms``, ``token_delivery_p95_s``) that
readers in ``bench/metrics/`` take from ``RunRecord.program``. A traced
``harness.run`` reads its trace here before deleting it, and logs
``readings``.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from bench import work
from bench.record import percentile

PROGRAM_PREFIXES = ("engine.", "async.")
SCOPES = ("attention", "mlp", "qkv", "out_proj", "norm", "head", "cache_write")
UNSCOPED = "unscoped"
CONTROL_FLOW = ("while", "cond")  # an op_name ending here names a loop or branch, not an op
NO_SPAN = "outside_spans"  # idle while no program span was open
FETCH_SPAN = "engine.host_fetch"
STEP_SPAN = "engine.step"
DECODE_PROGRAM = "jit_decode_step"
DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


# -- a decoder of the XSpace fields needed (protobuf wire format) ------------


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field number, wire type, value)`` of one message; a length-delimited
    value is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i : i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = buf[i : i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} at byte {i}")
        yield num, wire, v


def _text(buf, span) -> str:
    return bytes(buf[span[0] : span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple:
    key, value = None, None
    for num, _, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def op_metadata(buf) -> dict:
    """``{device plane: {(program id, op text): tf_op}}`` from the event
    metadata of each ``/device:TPU:<n>`` plane of a serialized XSpace.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
    .str_value = 5, .ref_value = 7 (a stat metadata id whose name is the
    string); XStatMetadata.name = 2."""
    out = {}
    for num, _, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for pnum, _, v in _fields(buf, *plane):
            if pnum == 2:
                name = _text(buf, v)
                if not name.startswith(DEVICE_PREFIX):
                    break
            elif pnum == 4:
                events.append(_map_entry(buf, v)[1])
            elif pnum == 5:
                sid, smd = _map_entry(buf, v)
                for snum, _, sv in _fields(buf, *smd):
                    if snum == 2:
                        stat_names[sid] = _text(buf, sv)
        if name is None or not name.startswith(DEVICE_PREFIX):
            continue
        table = out.setdefault(name, {})
        for md in events:
            op, stats = None, []
            for enum, _, ev in _fields(buf, *md):
                if enum == 2:
                    op = _text(buf, ev)
                elif enum == 5:
                    stats.append(ev)
            program, tf_op = None, None
            for st in stats:
                sid, val = None, None
                for snum, _, sv in _fields(buf, *st):
                    if snum == 1:
                        sid = sv
                    elif snum in (3, 4):
                        val = sv
                    elif snum == 5:
                        val = _text(buf, sv)
                    elif snum == 7:
                        val = ("ref", sv)
                key = stat_names.get(sid)
                if key == "program_id":
                    program = val
                elif key == "tf_op":
                    tf_op = stat_names.get(val[1], "") if isinstance(val, tuple) else val
            if op is not None and tf_op is not None:
                table[(program, op)] = tf_op
    return out


# -- what the trace holds ----------------------------------------------------


@dataclass
class Span:
    name: str
    start: float  # ns, host clock of the trace
    end: float
    depth: int
    parent: Optional[int]  # index in the thread's list; parents come first
    thread: str = ""


@dataclass
class Op:
    start: float  # ns, device clock of the trace
    end: float
    name: str  # HLO op name, ``fusion.3``
    program: str  # ``jit_decode_step``
    run: int  # index of the program execution in ``ProgramTrace.runs[device]``, or -1
    tf_op: str  # op_name path, ``jit(decode_step)/while/body/.../attention/dot_general:``
    scope: str  # one of SCOPES, or UNSCOPED
    leaf: bool = True  # holds no other op (a ``while`` holds its body's ops)
    reads: tuple = ()  # names of the ops it reads (its HLO operands)
    module: Optional[int] = None  # the executable's fingerprint: op names are its own
    inherited: bool = False  # scope taken from ``reads`` (no op_name of its own)


@dataclass
class ProgramTrace:
    spans: dict = field(default_factory=dict)  # host thread -> [Span]
    ops: dict = field(default_factory=dict)  # device -> [Op], by start
    runs: dict = field(default_factory=dict)  # device -> [(start, end, program)]
    host: list = field(default_factory=list)  # other host annotations: (start, end, name)
    skew_ns: float = 0.0  # device clock minus host clock

    def all_spans(self) -> list:
        return [s for spans in self.spans.values() for s in spans]


def scope_of(tf_op: str) -> str:
    """The innermost of ``SCOPES`` on an op_name path, else ``UNSCOPED``."""
    for part in reversed(tf_op.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def program_of(module: str) -> tuple:
    """``jit_decode_step(123)`` -> (``jit_decode_step``, 123)."""
    m = re.fullmatch(r"(.*)\((\d+)\)", module)
    return (m.group(1), int(m.group(2))) if m else (module, None)


def hlo_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


# ``%name`` after the ``=`` of an HLO instruction's text, less attribute
# references such as ``calls=%fused_computation``
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def hlo_operands(text: str) -> tuple:
    """Names of the ops an HLO instruction's text reads."""
    _, eq, rest = text.partition(" = ")
    return tuple(_OPERAND.findall(rest)) if eq else ()


def own_op_name(tf_op: str) -> bool:
    """Whether an op's ``tf_op`` names the op itself. The compiler emits
    some ops with no op_name; the trace then gives them none, or, inside a
    loop body, the loop's own (``jit(decode_step)/while:``)."""
    return bool(tf_op) and tf_op.rstrip(":").rsplit("/", 1)[-1] not in CONTROL_FLOW


def inherit_scopes(ops: list) -> None:
    """Give each op with no op_name of its own (``own_op_name``) the scope
    of the ops it reads, where those found in the trace have one scope of
    ``SCOPES`` between them; else it stays ``UNSCOPED``. In the default
    decode, the float32 ``convert``s of the gathered K/V carry none and read
    the ``attention`` gathers. Producers are looked up by name among the ops
    of the same executable (one program name may cover several compilations,
    each naming its ops anew), through others with no op_name of their own."""
    named: dict = {}
    bare: dict = {}
    for o in ops:
        if own_op_name(o.tf_op):
            named.setdefault((o.program, o.module, o.name), o.scope)
        else:
            bare.setdefault((o.program, o.module, o.name), o.reads)
    resolved: dict = {}

    def resolve(key, seen):
        if key in named:
            return named[key]
        if key in resolved:
            return resolved[key]
        if key not in bare or key in seen:
            return None
        seen.add(key)
        found = {resolve(key[:2] + (n,), seen) for n in bare[key]} - {None}
        resolved[key] = found.pop() if len(found) == 1 else UNSCOPED
        return resolved[key]

    for o in ops:
        if not own_op_name(o.tf_op):
            o.scope = resolve((o.program, o.module, o.name), set())
            o.inherited = o.scope != UNSCOPED


def nest(events: list, thread: str = "") -> list:
    """Spans of one thread, nested by time containment, parents first."""
    out: list = []
    stack: list = []
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and out[stack[-1]].end <= start:
            stack.pop()
        parent = stack[-1] if stack else None
        out.append(Span(name, start, end, len(stack), parent, thread))
        stack.append(len(out) - 1)
    return out


def mark_leaves(ops: list) -> None:
    """An op that holds another op (by time) on the same line is no leaf."""
    stack: list = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].leaf = False
        stack.append(op)


def read(path: str, *, host_names=()) -> ProgramTrace:
    """The program's spans, device ops and skew of one trace file.
    ``host_names``: other host annotations to keep (the harness's)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    meta = op_metadata(memoryview(raw))
    data = ProfileData.from_serialized_xspace(raw)
    out = ProgramTrace()
    wanted = set(host_names)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX) :].isdigit():
            table = meta.get(plane.name, {})
            lines = {line.name: line for line in plane.lines}
            runs = []
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    runs.append((ev.start_ns, ev.start_ns + ev.duration_ns) + program_of(ev.name))
            runs.sort()
            starts = [r[0] for r in runs]
            ops = []
            if OPS_LINE in lines:
                for ev in lines[OPS_LINE].events:
                    start, text = ev.start_ns, ev.name
                    i = bisect.bisect_right(starts, start) - 1
                    if i >= 0 and start < runs[i][1]:
                        program, pid = runs[i][2], runs[i][3]
                    else:
                        i, program, pid = -1, "", None
                    tf_op = table.get((pid, text), "")
                    op = Op(start, start + ev.duration_ns, hlo_name(text), program, i, tf_op, scope_of(tf_op))
                    op.reads, op.module = hlo_operands(text), pid
                    ops.append(op)
            ops.sort(key=lambda o: (o.start, -o.end))
            mark_leaves(ops)
            inherit_scopes(ops)
            out.ops[plane.name] = ops
            out.runs[plane.name] = [r[:3] for r in runs]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                thread = f"{k}:{line.name}"  # threads may share a name
                mine = []
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES):
                        mine.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name in wanted:
                        out.host.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                if mine:
                    out.spans[thread] = nest(mine, thread)
    out.skew_ns = skew(out) or 0.0
    return out


# -- arithmetic on it --------------------------------------------------------


def busy_union(ops: list, lo: float = float("-inf"), hi: float = float("inf")) -> list:
    from bench.trace_reduce import union

    return union([(o.start, o.end) for o in ops], lo, hi)


def first_device(pt: ProgramTrace) -> str:
    return sorted(pt.ops, key=lambda n: int(n[len(DEVICE_PREFIX) :]))[0]


def skew(pt: ProgramTrace, fetch: str = FETCH_SPAN) -> Optional[float]:
    """Device clock minus host clock, in ns, or None without fetch spans.

    A fetch returns only once the device work it waits on has ended, and
    that work started before the fetch returned: so for each ``fetch`` span
    the end of the last busy interval that starts before the span's end,
    minus that end, is the skew less the fetch's own latency. The median
    over the fetches stands against one matched to a neighbouring run."""
    if not pt.ops:
        return None
    busy = busy_union(pt.ops[first_device(pt)])
    starts = [a for a, _ in busy]
    returns = [s.end for s in pt.all_spans() if s.name == fetch] + [e for _, e, n in pt.host if n == fetch]
    diffs = []
    for r in returns:
        i = bisect.bisect_left(starts, r) - 1
        if i >= 0:
            diffs.append(busy[i][1] - r)
    return float(np.median(diffs)) if diffs else None


def window(pt: ProgramTrace, name: str = "bench_window") -> tuple:
    """The named host span's bounds on the device clock, else the trace's."""
    spans = [(a, b) for a, b, n in pt.host if n == name]
    if spans:
        return min(a for a, _ in spans) + pt.skew_ns, max(b for _, b in spans) + pt.skew_ns
    every = [(o.start, o.end) for ops in pt.ops.values() for o in ops]
    return min(a for a, _ in every), max(b for _, b in every)


def by_program(pt: ProgramTrace, lo: float, hi: float) -> dict:
    """Seconds of leaf-op device time per program inside ``[lo, hi)``,
    averaged over devices."""
    out: dict = {}
    for ops in pt.ops.values():
        for o in ops:
            if o.leaf and o.end > lo and o.start < hi:
                out[o.program] = out.get(o.program, 0.0) + (min(o.end, hi) - max(o.start, lo)) / 1e9
    return {k: v / len(pt.ops) for k, v in sorted(out.items(), key=lambda x: -x[1])}


def by_scope(pt: ProgramTrace, lo: float, hi: float, program: str = DECODE_PROGRAM) -> dict:
    """One program's leaf-op device time by scope inside ``[lo, hi)``, with
    its executions' own time (``XLA Modules``) under ``runs``, seconds
    averaged over devices."""
    out = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    runs = 0.0
    for dev, ops in pt.ops.items():
        for o in ops:
            if o.leaf and o.program == program and o.end > lo and o.start < hi:
                out[o.scope] += (min(o.end, hi) - max(o.start, lo)) / 1e9
        runs += sum(min(b, hi) - max(a, lo) for a, b, p in pt.runs[dev] if p == program and b > lo and a < hi) / 1e9
    n = len(pt.ops)
    out = {k: v / n for k, v in out.items()}
    out["runs"] = runs / n
    return out


def top_ops(pt: ProgramTrace, lo: float, hi: float, program: str = DECODE_PROGRAM, n: int = 12) -> list:
    """The ``n`` leaf ops of one program with the most device time inside
    ``[lo, hi)``: ``[name, scope, seconds]``, averaged over devices; a scope
    taken from the op's operands (``inherit_scopes``) ends in ``<``."""
    out: dict = {}
    for ops in pt.ops.values():
        for o in ops:
            if o.leaf and o.program == program and o.end > lo and o.start < hi:
                k = (o.name, o.scope + ("<" if o.inherited else ""))
                out[k] = out.get(k, 0.0) + (min(o.end, hi) - max(o.start, lo)) / 1e9 / len(pt.ops)
    return [[name, scope, t] for (name, scope), t in sorted(out.items(), key=lambda x: -x[1])[:n]]


def _innermost_segments(pt: ProgramTrace) -> list:
    """Disjoint ``(start, end, chain)`` pieces of host time (device clock),
    each labelled by the chain of names from the outermost to the innermost
    program span open then (over every thread: the deepest wins)."""
    shift = pt.skew_ns
    marks = []
    for thread, spans in pt.spans.items():
        for i, s in enumerate(spans):
            marks.append((s.start + shift, 1, thread, i))
            marks.append((s.end + shift, 0, thread, i))
    marks.sort()
    open_: dict = {}
    out = []
    prev = None
    for t, kind, thread, i in marks:
        if prev is not None and t > prev and open_:
            th, j = max(open_, key=lambda k: (pt.spans[k[0]][k[1]].depth, pt.spans[k[0]][k[1]].start))
            out.append((prev, t, chain(pt.spans[th], j)))
        if kind:
            open_[(thread, i)] = True
        else:
            open_.pop((thread, i), None)
        prev = t
    return out


def chain(spans: list, i: int) -> tuple:
    names = []
    while i is not None:
        names.append(spans[i].name)
        i = spans[i].parent
    return tuple(reversed(names))


def idle_split(pt: ProgramTrace, lo: float, hi: float, inside: str = "engine_step") -> dict:
    """The first device's idle inside ``[lo, hi)`` (device clock), in
    seconds: ``by_span`` puts each gap down to the innermost program span
    open on the host (``NO_SPAN`` where none was); ``inside`` does the same,
    by the chain of open spans, for the idle that also fell inside the
    harness's ``inside`` spans."""
    from bench.trace_reduce import gaps, union

    dev = first_device(pt)
    idle = gaps(busy_union(pt.ops[dev], lo, hi), lo, hi)
    segs = _innermost_segments(pt)
    within = union([(a + pt.skew_ns, b + pt.skew_ns) for a, b, n in pt.host if n == inside], lo, hi)
    within_ends = [e for _, e in within]
    by_span: dict = {}
    in_step: dict = {}

    def add(table, name, d):
        table[name] = table.get(name, 0.0) + d / 1e9

    def overlap_within(a, b):
        total = 0.0
        for s, e in within[bisect.bisect_right(within_ends, a) :]:
            if s >= b:
                break
            total += min(b, e) - max(a, s)
        return total

    seg_starts = [s[0] for s in segs]
    for a, b in idle:
        covered = covered_within = 0.0
        k = max(bisect.bisect_right(seg_starts, a) - 1, 0)
        while k < len(segs) and segs[k][0] < b:
            s, e, names = segs[k]
            x, y = max(a, s), min(b, e)
            if y > x:
                add(by_span, names[-1], y - x)
                d = overlap_within(x, y)
                if d > 0:
                    add(in_step, "/".join(names), d)
                covered += y - x
                covered_within += d
            k += 1
        if (b - a) - covered > 0:
            add(by_span, NO_SPAN, (b - a) - covered)
        rest = overlap_within(a, b) - covered_within
        if rest > 0:
            add(in_step, NO_SPAN, rest)
    return {
        "idle_s": sum(b - a for a, b in idle) / 1e9,
        "by_span": dict(sorted(by_span.items(), key=lambda x: -x[1])),
        "inside": dict(sorted(in_step.items(), key=lambda x: -x[1])),
    }


def named_child_share(inside: dict, parent: str = STEP_SPAN) -> Optional[float]:
    """Share of ``idle_split(...)["inside"]`` put down to a span nested
    under ``parent`` (not ``parent`` itself, nor no span)."""
    total = sum(inside.values())
    if total <= 0:
        return None
    named = sum(v for k, v in inside.items() if parent in k.split("/") and not k.endswith(parent))
    return named / total


# -- the per-layer quantities ------------------------------------------------


def step_spans(pt: ProgramTrace, rec) -> list:
    """``(StepRecord, engine.step Span)`` for every harness step inside the
    traced span: host times map onto the trace's host clock through the
    ``bench_window`` span's opening, which both record."""
    lo, hi = rec.trace_span
    opened = [a for a, _, n in pt.host if n == "bench_window"]
    if not opened:
        return []
    shift = min(opened) - lo * 1e9
    spans = sorted((s for s in pt.all_spans() if s.name == STEP_SPAN), key=lambda s: s.start)
    starts = [s.start for s in spans]
    out = []
    for st in rec.steps:
        if st.t0 < lo or st.t1 > hi:
            continue
        a, b = st.t0 * 1e9 + shift, st.t1 * 1e9 + shift
        i = bisect.bisect_left(starts, a)
        if i < len(spans) and spans[i].end <= b:
            out.append((st, spans[i]))
    return out


def decode_only(pairs: list) -> list:
    return [(st, sp) for st, sp in pairs if st.produced > 0 and st.prefill_tokens == 0 and st.decode_keys]


def children(pt: ProgramTrace, span: Span, name: str) -> list:
    """Spans called ``name`` nested under ``span`` on its thread."""
    return [
        s
        for s in pt.spans[span.thread]
        if s.name == name and s.depth > span.depth and s.start >= span.start and s.end <= span.end
    ]


def host_step_ms(pt: ProgramTrace, rec) -> Optional[float]:
    """Mean, over the traced span's decode-only ``engine.step`` spans, of the
    span's duration minus its ``engine.host_fetch`` children: the host time
    a step does not spend waiting for the device, in ms."""
    vals = []
    for _, sp in decode_only(step_spans(pt, rec)):
        fetch = sum(c.end - c.start for c in children(pt, sp, FETCH_SPAN))
        vals.append((sp.end - sp.start - fetch) / 1e6)
    return float(np.mean(vals)) if vals else None


def decode_attn_roofline(pt: ProgramTrace, rec) -> Optional[float]:
    """Least time of the decode attention of the traced span's decode-only
    steps (``work.decode_attn_least_s``) over the device time of the leaf ops
    scoped ``attention`` (their own op_name or, without one, the ops they
    read) in the ``jit_decode_step`` executions that start inside those
    steps' ``engine.step`` spans, in %."""
    attn = {dev: {} for dev in pt.ops}  # device -> {decode run: attention ns}
    for dev, ops in pt.ops.items():
        for o in ops:
            if o.leaf and o.scope == "attention" and o.program == DECODE_PROGRAM:
                attn[dev][o.run] = attn[dev].get(o.run, 0.0) + (o.end - o.start)
    least = spent = 0.0
    for st, sp in decode_only(step_spans(pt, rec)):
        a, b = sp.start + pt.skew_ns, sp.end + pt.skew_ns
        t = 0.0
        for dev, runs in pt.runs.items():
            lo = bisect.bisect_left(runs, (a,))
            hi = bisect.bisect_left(runs, (b,))
            t += sum(attn[dev].get(i, 0.0) for i in range(lo, hi) if runs[i][2] == DECODE_PROGRAM)
        if t <= 0:
            continue
        least += work.decode_attn_least_s(rec.shapes, st.decode_keys, rec.peak, rec.chips)
        spent += t / len(pt.ops) / 1e9
    return 100.0 * least / spent if spent > 0 else None


def token_delivery_p95_s(deliveries: list, t_open: float, t_close: float) -> Optional[float]:
    """95th percentile, over the tokens received in ``[t_open, t_close)``, of
    the client's receipt minus the program's emission stamp.
    ``deliveries``: ``(receipt, delay)`` pairs on the host clock."""
    return percentile([d for t, d in deliveries if t_open <= t < t_close], 95)


# -- every reading of one traced run ----------------------------------------


def readings(pt: ProgramTrace) -> dict:
    """The readings of a traced run beside its per-layer metrics, for its
    log: skew, the traced window, device time by program, the decode
    program's time by scope and its top ops, and the idle split by program
    span. Empty without a device plane in the trace."""
    if not pt.ops:
        return {}
    lo, hi = window(pt)
    split = idle_split(pt, lo, hi)
    return {
        "skew_ms": pt.skew_ns / 1e6,
        "window_s": (hi - lo) / 1e9,
        "programs_s": by_program(pt, lo, hi),
        "decode_by_scope_s": by_scope(pt, lo, hi),
        "decode_top_ops": top_ops(pt, lo, hi),
        "idle": split,
        "idle_in_engine_step_named_share": named_child_share(split["inside"]),
    }
