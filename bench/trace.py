"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` file JAX's profiler writes into plain
events: the operations each device ran (its ``XLA Ops`` line), and the host
events, among them the benchmark's own ``TraceAnnotation`` spans (names
starting ``bench.``).  Everything after that is arithmetic on intervals,
kept free of the profiler so a test can drive it with a synthetic trace:

- busy time: the union of the device-op intervals inside the window;
- idle gaps: the window minus that union, each named by the benchmark span
  it falls in and the most specific host event that covers half of it;
- kernel time: the summed durations of the device ops the kernel's name
  names, and of that the exposed part: the time in which no other op runs
  on the same device (a collective that compute does not hide).  On a TPU
  an op event's name is its HLO instruction
  (``%ell_spmv.5 = f32[1,1048576]{1,0} custom-call(...)``), and a Pallas
  kernel's instruction is named after the kernel; a v5e names the
  exchange of ``DistributedBSPEngine`` ``%all_to_all.10 = ...
  all-to-all(...)``.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

BENCH_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
TOP = 10
# Control-flow instructions span the ops they run; they count for busy
# time but would count their body twice among the top ops.
CONTAINERS = (" while(", " conditional(", " call(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: List[List[Event]]     # per device plane: its op events
    host: List[Event]              # every host event, bench spans included


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [Event(ev.name, float(ev.start_ns), float(ev.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            host.extend(Event(ev.name, float(ev.start_ns),
                              float(ev.duration_ns))
                        for line in plane.lines for ev in line.events)
    return Trace(devices=devices, host=host)


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi)`` that no busy interval covers."""
    out, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < hi:
        out.append((cursor, hi))
    return out


def _overlap(ev: Event, start: float, end: float) -> float:
    return max(0.0, min(ev.end_ns, end) - max(ev.start_ns, start))


def name_gap(start: float, end: float, host: Sequence[Event]) -> str:
    """``<bench span> / <host event>``: the shortest benchmark span that
    holds the gap's midpoint, and the shortest other host event that covers
    at least half of the gap ("-" where none does)."""
    mid = (start + end) / 2
    spans = [e for e in host if e.name.startswith(BENCH_PREFIX)
             and e.start_ns <= mid < e.end_ns]
    span = (min(spans, key=lambda e: e.dur_ns).name if spans
            else "outside bench spans")
    inner = [e for e in host if not e.name.startswith(BENCH_PREFIX)
             and _overlap(e, start, end) >= 0.5 * (end - start)]
    what = min(inner, key=lambda e: e.dur_ns).name if inner else "-"
    return f"{span} / {what}"


def _container(ev: Event) -> bool:
    return any(c in ev.name for c in CONTAINERS)


def _length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(end - start for start, end in intervals)


def op_name(text: str) -> str:
    """``ell_spmv.5`` of ``%ell_spmv.5 = f32[...] custom-call(...)``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def matches(ev: Event, kernel: str) -> bool:
    """Is ``ev`` a call of ``kernel`` (and not an op that reads one)?"""
    name = op_name(ev.name)
    return name == kernel or name.startswith(kernel + ".")


def short(text: str, width: int = 120) -> str:
    """An HLO instruction without layouts and operand names, cut to
    ``width`` characters: what an op is, in one line of a breakdown."""
    text = re.sub(r"\{[^{}]*\}", "", text)
    text = re.sub(r"/\*[^*]*\*/", "", text)
    text = re.sub(r" %[\w.\-]+", "", text)
    return text[:width]


@dataclasses.dataclass
class Summary:
    """A trace reduced over the window ``[lo, hi)`` (ns, host clock)."""

    window_s: float
    busy_s: float                          # mean over the devices
    num_devices: int
    device_ops: List[List[Event]]          # each device's ops in the window
    idle: List[Tuple[str, float]]          # longest gaps, named, seconds

    @property
    def ops(self) -> List[Event]:
        """Every device's ops inside the window, in one list."""
        return [e for ops in self.device_ops for e in ops]

    def kernel_seconds(self, kernel: str) -> float:
        """Device seconds of ``kernel``'s ops, mean over the devices."""
        total = sum(e.dur_ns for e in self.ops if matches(e, kernel))
        return total / 1e9 / self.num_devices

    def kernel_calls(self, kernel: str) -> int:
        """Ops of ``kernel`` per device."""
        return sum(1 for e in self.ops if matches(e, kernel)) \
            // self.num_devices

    def exposed_seconds(self, kernel: str) -> float:
        """Seconds inside ``kernel``'s ops in which no other op runs on the
        same device, control flow left out, mean over the devices."""
        total = 0.0
        for ops in self.device_ops:
            spans = [(e.start_ns, e.end_ns, matches(e, kernel)) for e in ops
                     if not _container(e)]
            other = [(start, end) for start, end, mine in spans if not mine]
            # Exposed: what the kernel's ops add to the union of the rest.
            total += _length(merge(((start, end) for start, end, _ in spans),
                                   -math.inf, math.inf))
            total -= _length(merge(other, -math.inf, math.inf))
        return total / 1e9 / self.num_devices

    def top_ops(self, top: int = TOP) -> List[Tuple[str, float]]:
        """Ops by summed device seconds (over all devices), control flow
        left out."""
        totals: dict = {}
        for e in self.ops:
            if _container(e):
                continue
            key = short(e.name)
            totals[key] = totals.get(key, 0.0) + e.dur_ns / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def summarize(trace: Trace, lo: float, hi: float,
              top: int = TOP) -> Summary:
    """Busy time, ops and the ``top`` longest idle gaps of ``trace`` over
    ``[lo, hi)``."""
    if not trace.devices:
        raise ValueError("the trace holds no device operations")
    busy_total, per_device, idle = 0.0, [], []
    for dev_ops in trace.devices:
        inside = [e for e in dev_ops if e.end_ns > lo and e.start_ns < hi]
        per_device.append(inside)
        busy = merge(((e.start_ns, e.end_ns) for e in inside), lo, hi)
        busy_total += _length(busy)
        idle.extend(gaps(busy, lo, hi))
    idle.sort(key=lambda gap: gap[0] - gap[1])
    named = [(name_gap(start, end, trace.host), (end - start) / 1e9)
             for start, end in idle[:top]]
    n = len(trace.devices)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
                   num_devices=n, device_ops=per_device, idle=named)


def window_of(trace: Trace, span: str) -> Optional[Tuple[float, float]]:
    """``[start, end)`` of the benchmark span named ``span``."""
    found = [e for e in trace.host if e.name == span]
    if not found:
        return None
    return found[0].start_ns, found[0].end_ns
