"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

``reduce_trace`` reads a profiler session's serialized XSpace (what a
``.xplane.pb`` holds) through ``jax.profiler.ProfileData``, and keeps:

- the window: the harness's own span (a ``TraceAnnotation`` whose name
  starts with ``chipbench.``) around the traced call, on the host clock
  the trace shares with the devices;
- per device: the union of the intervals in which an operation ran (busy
  time), the device time per operation name, and the operations
  themselves (HLO text, start, duration), clipped to the window;
- the idle gaps of the first device, each labelled by the innermost host
  event that covers its midpoint.

Device operations are the events of each TPU plane's ``XLA Ops`` line.
An operation that encloses others on that line (a loop, a conditional,
a call) is kept for the union but not counted again in the per-name
times: only the innermost events are summed, so no time is counted
twice.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str          # the HLO instruction's text
    start: int
    dur: int


@dataclass
class Device:
    name: str
    busy_ns: int = 0
    ops: list = field(default_factory=list)     # innermost Op events
    by_name: dict = field(default_factory=dict)  # name -> ns
    gaps: list = field(default_factory=list)     # idle (start, end)


@dataclass
class Trace:
    window: tuple       # (start_ns, end_ns)
    devices: list
    gaps: list          # [(label, ns)] idle gaps of device 0

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def op_ns(self, match, reduce=max) -> float:
        """Device ns of the operations for which ``match(op)`` holds:
        the fullest device's by default."""
        per = [sum(o.dur for o in d.ops if match(o)) for d in self.devices]
        return reduce(per) if per else 0.0


# which device operations a metric reads.  On a TPU the profiler names
# each operation event by its HLO instruction's text, e.g.
# '%maecho_gram_stacked.46 = f32[24,2,2]{...} custom-call(...),
# custom_call_target="tpu_custom_call", ...': a Pallas kernel is a
# Mosaic custom call named after the function that called pallas_call.
def is_kernel(op) -> bool:
    return "tpu_custom_call" in op.name


def is_maecho_kernel(op) -> bool:
    return is_kernel(op) and op.name.startswith("%maecho_")


def is_decode_attention(op) -> bool:
    return is_kernel(op) and op.name.startswith("%decode_attention")


def is_all_reduce(op) -> bool:
    head = op.name.split("(", 1)[0]
    return "all-reduce" in head


_SHORT = re.compile(r"^(%[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def short_name(text: str) -> str:
    """'%copy.67 bf16[1,64,1280,2,64] copy' from an HLO instruction's
    text; the part before ' = ' where the result is a tuple."""
    m = _SHORT.match(text)
    if m:
        return " ".join(m.groups())
    return text.split(" = ", 1)[0][:120]


def _union(intervals):
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label_gaps(gaps, host_events):
    """Label each idle gap by the shortest host event that covers its
    midpoint (a sweep over both, sorted by time)."""
    import heapq

    host = sorted(host_events)
    out, active, i = [], [], 0
    for gs, ge in sorted(gaps):
        mid = (gs + ge) // 2
        while i < len(host) and host[i][0] <= mid:
            s, d, name = host[i]
            heapq.heappush(active, (s + d, d, name))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        label = min(((d, n) for _, d, n in active),
                    default=(0, "no host event"))[1]
        out.append((label, ge - gs))
    return out


def _innermost(events):
    """Events that enclose no other event of the same line."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    keep = []
    for i, (s, d, *_rest) in enumerate(evs):
        j = i + 1
        encloses = j < len(evs) and evs[j][0] < s + d and \
            evs[j][0] + evs[j][1] <= s + d
        if not encloses:
            keep.append(evs[i])
    return keep


def reduce_trace(xspace: bytes, span: str) -> Trace | None:
    """Reduce the serialized trace ``xspace`` to the window of the
    harness span named ``span``.  ``None`` where the trace holds no
    device operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(xspace)
    host_events, window = [], None
    dev_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host_events.append((ev.start_ns, ev.duration_ns, ev.name))
                if ev.name == span and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        return None
    lo, hi = window
    devices = []
    for plane in dev_planes:
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns,
                                                 hi)
                if e > s:
                    evs.append((s, e - s, ev.name))
        if not evs:
            continue
        dev = Device(plane.name)
        spans = [(s, s + d) for s, d, *_ in evs]
        dev.busy_ns = _union(spans)
        dev.gaps = _gaps(spans, lo, hi)
        for s, d, name in _innermost(evs):
            dev.ops.append(Op(name, s, d))
            dev.by_name[name] = dev.by_name.get(name, 0) + d
        devices.append(dev)
    if not devices:
        return None
    return Trace(window, devices, _label_gaps(devices[0].gaps, host_events))


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time (fullest device) and the idle gaps summed by
    what the host was doing, in seconds."""
    dev = max(tr.devices, key=lambda d: d.busy_ns)
    ops = sorted(dev.by_name.items(), key=lambda kv: -kv[1])[:top]
    by_label: dict = {}
    for label, ns in tr.gaps:
        by_label[label] = by_label.get(label, 0) + ns
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(n), ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}

