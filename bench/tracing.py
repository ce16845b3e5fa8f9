"""From a JAX profiler trace to the numbers the benchmark reports.

The harness records host spans named ``bench.<what>`` with
``jax.profiler.TraceAnnotation``: ``bench.window`` around the measured
window, and ``bench.admit``, ``bench.step_dispatch``,
``bench.step_resolve`` and ``bench.policy`` around the calls into the
serving loop's layers.  The device planes (``/device:TPU:<n>``) carry one
event per executed XLA operation (line ``XLA Ops``) and per executed
program (line ``XLA Modules``).  Host and device events share one clock
in the trace, so an idle stretch of the device can be laid against what
the host was doing meanwhile.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


# an array type in HLO text: ``bf16[5,48,64,64]`` -> ("bf16", "5,48,64,64")
ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
CUSTOM_CALL = "custom-call("

Shape = Tuple[str, Tuple[int, ...]]


@dataclasses.dataclass(slots=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    # a kernel call's (results, operands) array types, from its HLO text
    shapes: Optional[Tuple[Tuple[Shape, ...], Tuple[Shape, ...]]] = None

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """The events of one traced window: per device, its operations and
    programs; and the harness's own host spans."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    spans: List[Event]
    _busy: Dict[str, list] = dataclasses.field(default_factory=dict)

    def busy(self, device: str) -> List[Tuple[float, float]]:
        """The union of ``device``'s operation intervals in the window."""
        if device not in self._busy:
            lo, hi = self.window()
            self._busy[device] = union(clip(self.ops.get(device, []),
                                            lo, hi))
        return self._busy[device]

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == SPAN_PREFIX + "window"]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0].start_ns, w[0].end_ns


def _event(e, name: Optional[str] = None) -> Event:
    return Event(e.name if name is None else name, float(e.start_ns),
                 float(e.duration_ns))


def _arrays(text: str) -> Tuple[Shape, ...]:
    return tuple((t, tuple(int(d) for d in dims.split(",") if d))
                 for t, dims in ARRAY.findall(text))


def call_shapes(text: str):
    """``(results, operands)`` of a custom call's HLO text
    (``%k.1 = (bf16[4,8]{..}, f32[4]{..}) custom-call(bf16[4,8]{..} %a),
    custom_call_target=...``), or ``None`` for other operations."""
    head, sep, rest = text.partition(CUSTOM_CALL)
    end = rest.find("custom_call_target")
    if not sep or end < 0:
        return None
    return _arrays(head.partition(" = ")[2]), _arrays(rest[:end])


def _op(e) -> Event:
    """A device operation, named by the head of its HLO text
    (``%srds_flash_fwd.11``), with a kernel call's array types: the whole
    text runs to kilobytes, and a traced window holds millions of
    operations."""
    head = e.name.split(" = ", 1)[0]
    shapes = call_shapes(e.name) if CUSTOM_CALL in e.name else None
    return Event(head, float(e.start_ns), float(e.duration_ns), shapes)


def from_profile(profile) -> Trace:
    """Gather a ``jax.profiler.ProfileData``'s events into a :class:`Trace`."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [_op(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(ops=ops, modules=modules, spans=spans)


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float,
                                                                  float]]:
    """The events' intervals cut to ``[lo, hi]``; empty ones dropped."""
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float,
                                                              float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace, device: str) -> float:
    """Nanoseconds of the window in which an operation ran on ``device``."""
    return sum(b - a for a, b in trace.busy(device))


def devices(trace: Trace) -> List[str]:
    return sorted(d for d, evs in trace.ops.items() if evs)


def idle_gaps(trace: Trace, device: str) -> List[Tuple[float, float]]:
    """The stretches of the window in which nothing ran on ``device``."""
    lo, hi = trace.window()
    gaps, t = [], lo
    for a, b in trace.busy(device):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(trace: Trace, a: float, b: float) -> str:
    """What the host was doing over ``[a, b]``: the harness span (other
    than the window itself) that covers most of it, where that span covers
    at least half of it; else ``host_other`` (the loop's own bookkeeping,
    or its sleep until the next arrival)."""
    best, best_cover = "host_other", 0.5 * (b - a)
    for s in trace.spans:
        if s.name == SPAN_PREFIX + "window":
            continue
        cover = min(s.end_ns, b) - max(s.start_ns, a)
        if cover >= best_cover:
            best, best_cover = s.name[len(SPAN_PREFIX):], cover
    return best


# HLO operations that only contain others: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def op_name(e: Event) -> str:
    """An operation's HLO name without its ``%`` and numeric suffix
    (``%srds_flash_fwd.11 = (bf16[...]) custom-call(...)`` and
    ``%srds_flash_fwd.11`` are both ``srds_flash_fwd``)."""
    head = e.name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def is_kernel(e: Event, kernel: str) -> bool:
    """Whether a device operation is a call of the Pallas kernel
    ``kernel`` (Mosaic names the custom call after the kernel)."""
    return op_name(e) == kernel


def kernel_events(trace: Trace, device: str, kernel: str) -> List[Event]:
    lo, hi = trace.window()
    return [e for e in trace.ops.get(device, [])
            if is_kernel(e, kernel) and e.start_ns >= lo and e.end_ns <= hi]


def kernel_roofline(run, kernel: str, cost) -> Optional[float]:
    """``kernel``'s calls in the traced window, on the first chip, as a
    share (%) of their roofline: the least time the chip could take for
    each call's operations and bytes (``cost(results, operands)`` of the
    call's array types, ``bench.flops``), summed, over the calls' summed
    device time.  ``None`` where the window holds no call of it, or a call
    whose array types the trace does not give."""
    from bench import flops
    dev = first_device(run.trace)
    evs = kernel_events(run.trace, dev, kernel) if dev else []
    if not evs or any(e.shapes is None for e in evs):
        return None
    best = sum(flops.roofline_seconds(*cost(*e.shapes), run.peak)
               for e in evs)
    return 100.0 * best / (1e-9 * sum(e.dur_ns for e in evs))


def module_events(trace: Trace, device: str, prefix: str) -> List[Event]:
    """Executions of the programs whose name starts with ``prefix``."""
    lo, hi = trace.window()
    return [e for e in trace.modules.get(device, [])
            if e.name.startswith(prefix) and e.start_ns >= lo
            and e.end_ns <= hi]


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, summed by name (leaf
    operations only), and the
    longest idle gaps, each named by what the host was doing; over every
    device of the trace."""
    totals: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    lo, hi = trace.window()
    for dev in devices(trace):
        for e in trace.ops[dev]:
            name = op_name(e)
            if e.end_ns > lo and e.start_ns < hi and name not in CONTAINERS:
                totals[name] = totals.get(name, 0.0) + e.dur_ns * 1e-9
        gaps += idle_gaps(trace, dev)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[host_activity(trace, a, b), (b - a) * 1e-9]
                          for a, b in gaps]}


def first_device(trace: Trace) -> Optional[str]:
    devs = devices(trace)
    return devs[0] if devs else None
