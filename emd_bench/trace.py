"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into the neutral
form ``Trace``: the operations of each TPU device (the "XLA Ops" line of
the planes ``/device:TPU:<i>``, whose event names are HLO instructions,
``%name = shape kind(operands), ...``) and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` events whose name starts with
``bench.``). Device and host events share the trace's clock. Everything
after ``load`` is plain arithmetic on intervals, so the tests check it on
a small trace.

Operations nest on the "XLA Ops" line: a ``while`` spans the operations
of its body. Busy time is the union of all of them; the time of an
operation is its self time, its span less what the operations inside it
cover, so nothing is counted twice. A kernel is a custom call to
``tpu_custom_call``, the target every Pallas (Mosaic) kernel lowers to;
every other operation, XLA's own custom calls such as ``TopK`` among
them, is XLA's.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_HLO = re.compile(r"^%?(\S+) = (.+?[\]\}\)]) ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
KERNEL_TARGET = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # HLO instruction name, or the span's name
    start_ns: float
    dur_ns: float
    kind: str = ""          # HLO op kind (fusion, custom-call, while, ...)
    shape: str = ""         # HLO output shape
    target: str = ""        # a custom call's target

    @property
    def is_kernel(self) -> bool:
        return self.target == KERNEL_TARGET

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def label(self) -> str:
        """What the breakdown calls the operation."""
        if not self.kind:
            return self.name
        return f"{self.name} {self.kind} {self.shape}"[:160]


def parse_op(text: str, start_ns: float, dur_ns: float) -> Event:
    m = _HLO.match(text)
    if m is None:
        return Event(text[:160], start_ns, dur_ns)
    t = _TARGET.search(text)
    return Event(m.group(1), start_ns, dur_ns, m.group(3), m.group(2),
                 t.group(1) if t else "")


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Event]]     # device id -> its operations
    spans: list[Event]                  # host spans of the benchmark


def load(profile_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    parse_op(ev.name, ev.start_ns, ev.duration_ns)
                    for ev in line.events)
            elif not m:
                spans.extend(Event(ev.name, ev.start_ns, ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, spans=spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """(event, self time in ns) of nested events: each event's span less
    the spans of the events directly inside it."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack = [], []          # stack of [event, self ns]
    for e in order:
        while stack and e.start_ns >= stack[-1][0].end_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, e.dur_ns])
    out.extend(tuple(s) for s in reversed(stack))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float                    # length of the window span
    busy_s: float                      # device busy time, mean over devices
    op_s: dict[str, float]             # self time per operation label
    name_s: dict[str, float]           # self time per instruction name
    idle_by_span: dict[str, float]     # device idle time by host span
    kernel_s: float = 0.0              # self time of the kernels, mean
    n_devices: int = 1

    def time_of(self, prefix: str) -> float:
        """Seconds of the operations whose instruction name starts with
        ``prefix`` (a kernel's ``pallas_call`` name), mean over
        devices."""
        return sum(s for k, s in self.name_s.items() if k.startswith(prefix))


def reduce(trace: Trace) -> Reduced:
    """Busy time, per-operation self time and idle gaps inside the window
    span (the first ``bench.window`` span of the trace)."""
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not windows or not trace.devices:
        raise ValueError("the trace holds no window span or no device")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    inner = [s for s in trace.spans if s.name != WINDOW_SPAN]
    busy_ns, kernel_ns, op_ns, name_ns, idle = 0.0, 0.0, {}, {}, {}
    for events in trace.devices.values():
        inside = [e for e in events if lo <= e.start_ns and e.end_ns <= hi]
        for e, own in self_times(inside):
            op_ns[e.label] = op_ns.get(e.label, 0.0) + own
            name_ns[e.name] = name_ns.get(e.name, 0.0) + own
            kernel_ns += own if e.is_kernel else 0.0
        busy = union((e.start_ns, e.end_ns) for e in inside)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = _span_at(inner, (s + e) / 2)
                idle[name] = idle.get(name, 0.0) + (e - s)
    nd = len(trace.devices)

    def per_device(d):
        return {k: v / 1e9 / nd for k, v in d.items()}

    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_ns / 1e9 / nd,
                   op_s=per_device(op_ns), name_s=per_device(name_ns),
                   idle_by_span=per_device(idle),
                   kernel_s=kernel_ns / 1e9 / nd, n_devices=nd)


def _span_at(spans: list[Event], t: float) -> str:
    """The innermost (shortest) host span covering time t."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (best is None
                                            or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else WINDOW_SPAN


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The operations that took most device time, and device idle time
    by what the host was doing."""
    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": most(r.op_s), "idle_gaps": most(r.idle_by_span)}
