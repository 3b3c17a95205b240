"""The program's spans in one profiler trace: per-layer host self time on
the device trace's clock.

The program opens `jax.profiler.TraceAnnotation` spans named "dxpta.*"
(`src/repro/tracing.py`) at each layer boundary of a query, nested on the
calling thread inside the harness's "bench.query". They land in the same
`.xplane.pb` as the device ops. This module reads them with the device ops
and reduces them:

  span self time  a span's duration less the union of the spans nested in
                  it on the same thread line, inside the window; the self
                  times of a query's spans add up to the query's duration
  idle gaps       each piece of device idle time named by the innermost
                  span open over it, harness or program
  lanes           the summed `lanes` stat of the "dxpta.launch" spans:
                  lanes each launch ran, padding and masked lanes included

Every field `devtrace.summarize` reports is computed here as it computes it,
the window still bounded by the harness spans alone, so a trace without
program spans gives the same numbers. Innermost spans come from one sort
and one heap sweep, not a scan of every span per cut: a 51 s window holds
tens of thousands of program spans.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from typing import List, Optional

import devtrace
from devtrace import Event

PROGRAM_PREFIX = "dxpta."
SPAN_PREFIXES = (devtrace.HOST_PREFIX, PROGRAM_PREFIX)
QUERY = devtrace.HOST_PREFIX + "query"
LAUNCH = PROGRAM_PREFIX + "launch"


def is_span(ev: Event) -> bool:
    """A harness or program span on a host plane."""
    return (not ev.plane.startswith("/device:")
            and ev.name.startswith(SPAN_PREFIXES))


def events_of(trace_dir: str) -> List[Event]:
    """The events `devtrace.events_of` keeps, and every program span, all
    with their stats. A host event's `line` is "<index>:<name>": each
    thread has a line of its own, and lines can share a name."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            if device and line.name != "XLA Ops":
                continue
            name = line.name if device else f"{i}:{line.name}"
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIXES):
                    continue
                out.append(Event(plane.name, name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 devtrace._stat_text(ev.stats)))
    return out


def _innermost(spans) -> tuple:
    """(cuts, owners): the sorted span boundaries, and for each piece
    [cuts[i], cuts[i + 1]) the index in `spans` of the shortest span open
    over it, the first listed among equals (-1: none). Where spans nest,
    the shortest open one is the innermost."""
    cuts = sorted({t for sp in spans
                   for t in (sp.start_ns, sp.start_ns + sp.dur_ns)})
    order = sorted(range(len(spans)), key=lambda i: spans[i].start_ns)
    heap, owners, j = [], [], 0
    for a in cuts[:-1]:
        while j < len(order) and spans[order[j]].start_ns <= a:
            heapq.heappush(heap, (spans[order[j]].dur_ns, order[j]))
            j += 1
        while heap and (spans[heap[0][1]].start_ns
                        + spans[heap[0][1]].dur_ns) <= a:
            heapq.heappop(heap)
        owners.append(heap[0][1] if heap else -1)
    return cuts, owners


OUTSIDE = "outside spans"


def short_name(name: str) -> str:
    """A span's name without its prefix, as the breakdown gives it."""
    for p in SPAN_PREFIXES:
        if name.startswith(p):
            return name[len(p):]
    return name


class Timeline(devtrace._Timeline):
    """The innermost span, harness or program, open at each moment, by its
    full name."""

    def __init__(self, spans):
        self.cuts, owners = _innermost(spans)
        self.names = [spans[i].name if i >= 0 else OUTSIDE for i in owners]


def self_times(spans, w0: float, w1: float) -> dict:
    """{span name: summed self time in seconds} inside [w0, w1)."""
    lines: dict = {}
    for sp in spans:
        lines.setdefault((sp.plane, sp.line), []).append(sp)
    out: dict = {}
    for group in lines.values():
        cuts, owners = _innermost(group)
        for a, b, i in zip(cuts, cuts[1:], owners):
            ns = min(b, w1) - max(a, w0)
            if i >= 0 and ns > 0:
                name = group[i].name
                out[name] = out.get(name, 0.0) + ns * 1e-9
    return out


@dataclasses.dataclass
class SpanSummary(devtrace.Summary):
    """`devtrace.Summary`, with `idle_gaps` named by program spans too, and
    what the span readers take."""
    span_self_s: dict           # {span name: self time in the window}
    query_s: float              # summed "bench.query" durations
    lanes: int                  # summed `lanes` of the launch spans
    idle_by_span: dict          # {innermost span: device idle seconds}


def summarize(events: List[Event], top: int = 10) -> Optional[SpanSummary]:
    """Reduce one trace's events; None when the trace holds no device op
    or no harness span (as `devtrace.summarize`)."""
    win = devtrace._window(events)
    dev = [e for e in events if devtrace.is_device(e)]
    if win is None or not dev:
        return None
    w0, w1 = win
    dev = [e for e in dev if e.start_ns < w1 and e.start_ns + e.dur_ns > w0]
    planes = sorted({e.plane for e in dev})
    if not planes:
        return None
    busy = sum(devtrace.union_ns(
        (max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
        for e in dev if e.plane == p) for p in planes)
    kernels = [e for e in dev if devtrace.is_kernel(e)]

    per_op: dict = {}
    for e in dev:
        name = devtrace.op_name(e)
        per_op[name] = per_op.get(name, 0.0) + e.dur_ns
    device_ops = sorted(([k, v * 1e-9] for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:top]

    spans = [e for e in events if is_span(e)]
    timeline = Timeline(spans)
    gaps: dict = {}
    idle_by_span: dict = {}
    for p in planes:
        ops = sorted((e for e in dev if e.plane == p),
                     key=lambda e: e.start_ns)
        prev_end, prev_name = w0, "window start"
        for e in ops + [None]:
            start = w1 if e is None else e.start_ns
            if start > prev_end:
                for host, ns in timeline.split(prev_end, start):
                    cause = f"{short_name(host)} after {prev_name}"
                    gaps[cause] = gaps.get(cause, 0.0) + ns * 1e-9
                    idle_by_span[host] = idle_by_span.get(host, 0.0) \
                        + ns * 1e-9
            if e is not None and e.start_ns + e.dur_ns > prev_end:
                prev_end, prev_name = e.start_ns + e.dur_ns, \
                    devtrace.op_name(e)
    idle_gaps = sorted(([k, v] for k, v in gaps.items()),
                       key=lambda kv: -kv[1])[:top]

    return SpanSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / len(planes),
        kernel_s=sum(e.dur_ns for e in kernels) * 1e-9,
        kernel_launches=len(kernels), device_ops=device_ops,
        idle_gaps=idle_gaps,
        span_self_s=self_times(spans, w0, w1),
        query_s=sum(e.dur_ns for e in spans if e.name == QUERY) * 1e-9,
        lanes=sum(int(e.stats.get("lanes", 0)) for e in spans
                  if e.name == LAUNCH and w0 <= e.start_ns < w1),
        idle_by_span=idle_by_span)


def self_ms_per_query(run, *names: str) -> Optional[float]:
    """Summed self time of the named spans, in ms per window query; None
    where the trace holds none of them, as on a program without spans or a
    trace reduced without them (`devtrace.Summary`)."""
    self_s = getattr(run.trace, "span_self_s", None)
    got = [self_s[n] for n in names if n in self_s] if self_s else []
    if not got or run.n_queries == 0:
        return None
    return sum(got) * 1e3 / run.n_queries
