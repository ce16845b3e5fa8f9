"""What the served path records of itself in a profile, beside
``bench.tracing``'s view of the same profile.

The program opens host spans named ``serve.<what>`` (``jax.profiler``
annotations, with host numbers as arguments: ``serve.admission`` carries
``waiting``, ``scanned`` and ``admitted``, ``serve.admit`` its ``rid`` and
``waited_ms``) and gives each device phase of a refinement a
``jax.named_scope``: ``srds.fine``, ``srds.coarse``, ``srds.correct`` and
``srds.init``.  A scope reaches the profile in the ``tf_op`` statistic of
each operation's metadata on the device plane (the op's ``op_name``).
``jax.profiler.ProfileData`` gives the events but not their metadata, so
:func:`op_scopes` reads that one statistic from the ``.xplane.pb`` file's
protobuf encoding itself.

The readers below take a :class:`bench.tracing.Trace` (the window, the
device operations and programs, the harness's ``bench.*`` spans) and a
:class:`ProgramTrace` of the same profile.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench import tracing

SERVE_PREFIX = "serve."
WAIT = "serve.wait"
ADMISSION = "serve.admission"
STEP_PROGRAM = "jit_step_fn"
# ``jit(step_fn)/while/body/closed_call/srds.coarse/...`` -> srds.coarse
SCOPE = re.compile(r"(?:^|/)(srds\.[A-Za-z_]+)")
# an XLA program's execution: ``jit_step_fn(13602961581225950339)``
PROGRAM_ID = re.compile(r"\((\d+)\)$")


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    args: Dict[str, float]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(slots=True)
class ScopedOp:
    """A leaf device operation inside a program execution: its interval,
    its ``srds.*`` scope (``None`` outside every scope) and the program's
    name without its id (``jit_step_fn``)."""
    start_ns: float
    dur_ns: float
    scope: Optional[str]
    program: str


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                    # the program's serve.* spans
    ops: Dict[str, List[ScopedOp]]       # per device


# --------------------------------------------------------------------------
# reading a profile
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """The ``(field number, value)`` pairs of one protobuf message: an int
    for varints and fixed-width values, a memoryview for length-delimited
    ones (strings, bytes, nested messages)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield num, val


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata
# = 5 (maps: key 1, value 2); XEventMetadata.name = 2, .stats = 5;
# XStat.metadata_id = 1, .str_value = 5, .uint64_value = 3,
# .int64_value = 4; XStatMetadata.name = 2
def _map_values(buf) -> Iterator[memoryview]:
    for num, val in fields(buf):
        if num == 2:
            yield val


def op_scopes(xspace: bytes, device_prefix: str = tracing.DEVICE_PREFIX
              ) -> Dict[Tuple[int, str], str]:
    """``(program id, operation text) -> srds.* scope`` for every device
    operation whose metadata carries one, from a serialized ``XSpace``.
    The operation text is the event's name as ``ProfileData`` gives it."""
    out: Dict[Tuple[int, str], str] = {}
    for num, plane in fields(memoryview(xspace)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, val in fields(plane):
            if pnum == 2:
                name = bytes(val).decode()
            elif pnum == 4:
                events.append(val)
            elif pnum == 5:
                for meta in _map_values(val):
                    f = dict(fields(meta))
                    stat_names[f.get(1, 0)] = bytes(f.get(2, b"")).decode()
        if not name.startswith(device_prefix):
            continue
        want = {k for k, v in stat_names.items() if v in ("tf_op",
                                                          "program_id")}
        for entry in events:
            for meta in _map_values(entry):
                text, tf_op, pid = "", None, None
                for fnum, val in fields(meta):
                    if fnum == 2:
                        text = bytes(val).decode()
                    elif fnum == 5:
                        stat = dict(fields(val))
                        sid = stat.get(1)
                        if sid not in want:
                            continue
                        if stat_names[sid] == "tf_op":
                            tf_op = bytes(stat.get(5, b"")).decode()
                        else:
                            pid = stat.get(3, stat.get(4))
                m = SCOPE.search(tf_op or "")
                if m and pid is not None:
                    out[(pid, text)] = m.group(1)
    return out


def scoped_ops(events, modules: List[tracing.Event],
               scopes: Dict[Tuple[int, str], str]) -> List[ScopedOp]:
    """Each leaf operation of ``events`` (``ProfileData`` events, named by
    their whole HLO text) that ran inside one of the program executions
    ``modules``, with its scope, found by the program's id (an
    execution's name ends in it) and the operation's text.  The text is
    not kept: a traced window holds millions of operations."""
    mods = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    progs = []
    for m in mods:
        pid = PROGRAM_ID.search(m.name)
        progs.append((int(pid.group(1)) if pid else None,
                      m.name.split("(", 1)[0]))
    out: List[ScopedOp] = []
    for e in events:
        t0 = float(e.start_ns)
        j = bisect.bisect_right(starts, t0) - 1
        if j < 0 or t0 >= mods[j].end_ns:
            continue
        text = e.name
        if tracing.op_name(tracing.Event(text, 0.0, 0.0)) \
                in tracing.CONTAINERS:
            continue
        pid, program = progs[j]
        out.append(ScopedOp(t0, float(e.duration_ns),
                            scopes.get((pid, text)), program))
    return out


def _number(v) -> float:
    try:
        return float(v)
    except (TypeError, ValueError):
        return float("nan")


def from_profile(profile, xspace: bytes) -> ProgramTrace:
    """Gather a ``jax.profiler.ProfileData`` and its serialized ``XSpace``
    into a :class:`ProgramTrace`."""
    scopes = op_scopes(xspace)
    spans: List[Span] = []
    ops: Dict[str, List[ScopedOp]] = {}
    for plane in profile.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if tracing.OPS_LINE not in lines:
                continue
            mods = [tracing._event(e) for e in
                    lines[tracing.MODULES_LINE].events] \
                if tracing.MODULES_LINE in lines else []
            ops[plane.name] = scoped_ops(lines[tracing.OPS_LINE].events,
                                         mods, scopes)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SERVE_PREFIX):
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.duration_ns),
                                          {k: _number(v)
                                           for k, v in e.stats}))
    return ProgramTrace(spans=spans, ops=ops)


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        xspace = f.read()
    return from_profile(ProfileData.from_serialized_xspace(xspace), xspace)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def in_window(trace: tracing.Trace, spans: List[Span]) -> List[Span]:
    lo, hi = trace.window()
    return [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]


def scope_ns(trace: tracing.Trace, program: ProgramTrace,
             device: str, scope: Optional[str],
             prefix: str = STEP_PROGRAM) -> float:
    """Device nanoseconds in the window of the leaf operations scoped
    ``scope`` (``None``: unscoped) inside the programs named ``prefix``."""
    lo, hi = trace.window()
    return sum(o.dur_ns for o in program.ops.get(device, [])
               if o.program == prefix and o.scope == scope
               and o.start_ns >= lo and o.start_ns + o.dur_ns <= hi)


def scope_device_ms(trace: tracing.Trace, program: ProgramTrace,
                    dispatches: int, scope: str) -> Optional[float]:
    """Milliseconds of device time per dispatched refinement that the step
    programs spent under ``scope``, on the first chip (the divisor of
    ``step_device_ms``); ``None`` where the window holds no scoped step
    operation."""
    dev = tracing.first_device(trace)
    if dev is None or not dispatches or not any(
            o.scope for o in program.ops.get(dev, [])):
        return None
    return 1e-6 * scope_ns(trace, program, dev, scope) / dispatches


def covered_ns(intervals: List[Tuple[float, float]],
               spans: List[Span]) -> float:
    """Nanoseconds of ``intervals`` that lie inside some span."""
    cover = tracing.union([(s.start_ns, s.end_ns) for s in spans])
    total, j = 0.0, 0
    for a, b in sorted(intervals):
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def host_bound_idle_share(trace: tracing.Trace,
                          program: ProgramTrace) -> Optional[float]:
    """Share (%) of the window in which the first chip was idle while the
    host was inside a ``serve.*`` span other than ``serve.wait``."""
    dev = tracing.first_device(trace)
    if dev is None or not program.spans:
        return None
    lo, hi = trace.window()
    work = [s for s in program.spans if s.name != WAIT]
    return 100.0 * covered_ns(tracing.idle_gaps(trace, dev), work) \
        / (hi - lo)


def idle_in_program_share(trace: tracing.Trace,
                          program: ProgramTrace) -> Optional[float]:
    """Share (%) of the first chip's idle time in the window that lies
    inside some ``serve.*`` span, ``serve.wait`` included."""
    dev = tracing.first_device(trace)
    if dev is None or not program.spans:
        return None
    gaps = tracing.idle_gaps(trace, dev)
    idle = sum(b - a for a, b in gaps)
    return 100.0 * covered_ns(gaps, program.spans) / idle if idle else None


def admission_ms(trace: tracing.Trace,
                 program: ProgramTrace) -> Optional[float]:
    """Mean duration of the window's ``serve.admission`` spans."""
    rounds = [s for s in in_window(trace, program.spans)
              if s.name == ADMISSION]
    return 1e-6 * sum(s.dur_ns for s in rounds) / len(rounds) \
        if rounds else None


def admission_scanned(trace: tracing.Trace,
                      program: ProgramTrace) -> Optional[float]:
    """Mean ``scanned`` argument of the window's ``serve.admission``
    spans: waiting requests examined per admission round."""
    vals = [s.args["scanned"] for s in in_window(trace, program.spans)
            if s.name == ADMISSION and "scanned" in s.args]
    return sum(vals) / len(vals) if vals else None


def host_activity(trace: tracing.Trace, program: ProgramTrace,
                  a: float, b: float) -> str:
    """What the host was doing over ``[a, b]``: the harness's span as
    :func:`bench.tracing.host_activity` names it, where one covers half of
    it; else the innermost (shortest) ``serve.*`` span covering half of it;
    else ``host_other``."""
    name = tracing.host_activity(trace, a, b)
    if name != "host_other":
        return name
    best, best_dur = name, float("inf")
    for s in program.spans:
        cover = min(s.end_ns, b) - max(s.start_ns, a)
        if cover >= 0.5 * (b - a) and s.dur_ns < best_dur:
            best, best_dur = s.name, s.dur_ns
    return best


def idle_gaps(trace: tracing.Trace, program: ProgramTrace,
              top: int = 10) -> List[list]:
    """The longest idle gaps over every device, as
    :func:`bench.tracing.breakdown` lists them, named by
    :func:`host_activity`."""
    gaps: List[Tuple[float, float]] = []
    for dev in tracing.devices(trace):
        gaps += tracing.idle_gaps(trace, dev)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [[host_activity(trace, program, a, b), (b - a) * 1e-9]
            for a, b in gaps]
