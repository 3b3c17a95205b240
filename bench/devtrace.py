"""Reduction of one profiler trace to the numbers the per-layer readers
and the `breakdown` report.

`events_of` turns the `.xplane.pb` the JAX profiler writes into plain
records; everything after it is pure arithmetic on those records, so a
test can feed it a synthetic trace.

  device ops     events on the TPU planes' "XLA Ops" lines: each executed
                 HLO op, with its start and length in nanoseconds
  kernels        the device ops that are Pallas kernels. Mosaic lowers a
                 `pallas_call` to a custom call with the target
                 "tpu_custom_call", which the op's HLO text (its event name
                 on a TPU) or its stats carry. Matched on that, never on a
                 function name, so a rename cannot hide a kernel
  host spans     the harness's own `jax.profiler.TraceAnnotation` spans
                 ("bench.*") on the host plane

Device busy time is the union of the device ops' intervals, averaged over
the devices used; the idle share is 1 - busy / window, the window being
the traced stretch between the first and last harness span.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import List, Optional

HOST_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    """One trace event: where it ran, what it was, when, and its stats."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


def _stat_text(stats) -> dict:
    out = {}
    for k, v in stats:
        out[str(k)] = v if isinstance(v, (int, float)) else str(v)
    return out


def events_of(trace_dir: str) -> List[Event]:
    """Every event of the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 _stat_text(ev.stats) if device else {}))
    return out


def is_device(ev: Event) -> bool:
    """An executed op on an accelerator core (not the host, not the chip's
    non-core planes)."""
    return (ev.plane.startswith("/device:") and "NON_CORE" not in ev.plane
            and ev.line == "XLA Ops")


def is_kernel(ev: Event) -> bool:
    """A Pallas (Mosaic) kernel: a TPU custom call."""
    text = " ".join([ev.name] + [str(v) for v in ev.stats.values()])
    return is_device(ev) and "tpu_custom_call" in text


def op_name(ev: Event) -> str:
    """The HLO instruction's name: a TPU trace names each op event by its
    whole HLO line, "%name = type op(operands), attributes"."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _window(events) -> Optional[tuple]:
    spans = [e for e in events if e.name.startswith(HOST_PREFIX)]
    if not spans:
        return None
    return (min(e.start_ns for e in spans),
            max(e.start_ns + e.dur_ns for e in spans))


@dataclasses.dataclass
class Summary:
    """What the readers and the breakdown take from one traced window."""
    window_s: float
    busy_s: float               # union of device-op time, mean per device
    kernel_s: float             # summed device time of Pallas kernels
    kernel_launches: int
    device_ops: list            # [[name, seconds]] most time first
    idle_gaps: list             # [[cause, seconds]] idle time per cause


class _Timeline:
    """The innermost harness span open at each moment: the span boundaries
    cut time into pieces, each named once."""

    def __init__(self, spans):
        self.cuts = sorted({t for sp in spans
                            for t in (sp.start_ns, sp.start_ns + sp.dur_ns)})
        self.names = []
        for a, b in zip(self.cuts, self.cuts[1:]):
            mid = 0.5 * (a + b)
            inside = [sp for sp in spans
                      if sp.start_ns <= mid <= sp.start_ns + sp.dur_ns]
            self.names.append(
                min(inside, key=lambda sp: sp.dur_ns).name[len(HOST_PREFIX):]
                if inside else "outside spans")

    def name_at(self, t: float) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.names[i] if 0 <= i < len(self.names) else "outside spans"

    def split(self, a: float, b: float):
        """[(name, ns)] of the interval [a, b), cut at span boundaries."""
        points = ([a] + self.cuts[bisect.bisect_right(self.cuts, a):
                                  bisect.bisect_left(self.cuts, b)] + [b])
        return [(self.name_at(0.5 * (x + y)), y - x)
                for x, y in zip(points, points[1:]) if y > x]


def summarize(events: List[Event], top: int = 10) -> Optional[Summary]:
    """Reduce one trace's events; None when the trace holds no device op
    or no harness span."""
    win = _window(events)
    dev = [e for e in events if is_device(e)]
    if win is None or not dev:
        return None
    w0, w1 = win
    dev = [e for e in dev if e.start_ns < w1 and e.start_ns + e.dur_ns > w0]
    planes = sorted({e.plane for e in dev})
    if not planes:
        return None
    busy = sum(union_ns((max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
                        for e in dev if e.plane == p) for p in planes)
    kernels = [e for e in dev if is_kernel(e)]

    per_op: dict = {}
    for e in dev:
        per_op[op_name(e)] = per_op.get(op_name(e), 0.0) + e.dur_ns
    device_ops = sorted(([k, v * 1e-9] for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:top]

    gaps = []
    timeline = _Timeline([e for e in events
                          if e.name.startswith(HOST_PREFIX)])
    for p in planes:
        ops = sorted((e for e in dev if e.plane == p),
                     key=lambda e: e.start_ns)
        prev_end, prev_name = w0, "window start"
        for e in ops + [None]:
            start = w1 if e is None else e.start_ns
            if start > prev_end:
                # Each piece of the gap goes to the innermost harness span
                # open over it.
                for host, ns in timeline.split(prev_end, start):
                    gaps.append([f"{host} after {prev_name}", ns * 1e-9])
            if e is not None and e.start_ns + e.dur_ns > prev_end:
                prev_end, prev_name = e.start_ns + e.dur_ns, op_name(e)
    merged: dict = {}
    for name, s in gaps:
        merged[name] = merged.get(name, 0.0) + s
    idle_gaps = sorted(([k, v] for k, v in merged.items()),
                       key=lambda kv: -kv[1])[:top]

    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy * 1e-9 / len(planes),
                   kernel_s=sum(e.dur_ns for e in kernels) * 1e-9,
                   kernel_launches=len(kernels), device_ops=device_ops,
                   idle_gaps=idle_gaps)
