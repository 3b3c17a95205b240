"""The span reduction (`spans.py`): self times that partition each query,
the span readers, idle gaps named by the innermost program span, the
harness's own numbers unchanged by program spans, and its speed; then the
program's spans on a real CPU trace, where each one nests where it
belongs and turning the profiler on changes no answer."""
import random
import time

import numpy as np
import pytest

from _tiny import BENCH

import devtrace
import run
import spans
from cell import load_module
from devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MAIN = "0:python3"
WORKER = "1:python3"
READERS = ("service_host_ms_per_query", "driver_ms_per_query",
           "descent_ms_per_query", "bound_pricing_ms_per_query",
           "refine_ms_per_query", "launch_host_ms_per_query",
           "launch_wait_ms_per_query", "lanes_per_query",
           "untraced_query_share")
TIMES = READERS[:7]


def _op(name, start, dur, plane=DEV, **stats):
    return Event(plane, "XLA Ops", name, start, dur, stats)


def _span(name, start, dur, line=MAIN, **stats):
    return Event(HOST, line, name, start, dur, stats)


def _p(name, start, dur, line=MAIN, **stats):
    return _span("dxpta." + name, start, dur, line, **stats)


KERNEL = dict(long_name='custom_call_target="tpu_custom_call"')


def _harness():
    return [_span("bench.window", 0, 1000), _span("bench.query", 0, 400),
            _span("bench.query", 500, 500),
            _op("custom-call.1", 210, 80, **KERNEL), _op("copy.2", 720, 150)]


def _program():
    """A warm query and a cold one, and a worker thread's span between
    them on a line of its own."""
    return [
        _p("service.query", 10, 380), _p("service.reprice", 20, 50),
        _p("search", 80, 300), _p("search.descend", 90, 60),
        _p("search.bounds", 100, 30), _p("launch", 160, 150, lanes=16384),
        _p("launch.wait", 200, 100), _p("search.refine", 320, 40),
        _p("search", 510, 480), _p("search.bounds", 520, 80),
        _p("launch", 620, 300, lanes=2048), _p("launch.wait", 700, 190),
        _p("search.bounds", 420, 40, line=WORKER)]


def _run(summary, n_queries=2):
    r = run.Run()
    r.latencies_s = [1.0] * n_queries
    r.trace = summary
    return r


def _read(name, r):
    return load_module(BENCH / "metrics" / f"{name}.py").read(r)


def test_self_times_partition_each_query():
    main = [e for e in _harness() + _program()
            if spans.is_span(e) and e.line == MAIN]
    for q0, q1 in ((0, 400), (500, 1000)):
        got = spans.self_times(main, q0, q1)
        assert sum(got.values()) == pytest.approx((q1 - q0) * 1e-9)
    s = spans.summarize(_harness() + _program())
    want = {"bench.window": 100, "bench.query": 40,
            "dxpta.service.query": 30, "dxpta.service.reprice": 50,
            "dxpta.search": 150, "dxpta.search.descend": 30,
            "dxpta.search.bounds": 150, "dxpta.launch": 160,
            "dxpta.launch.wait": 290, "dxpta.search.refine": 40}
    assert s.span_self_s == pytest.approx({k: v * 1e-9
                                           for k, v in want.items()})
    assert s.query_s == pytest.approx(900e-9)
    assert s.lanes == 16384 + 2048


def test_the_span_readers():
    r = _run(spans.summarize(_harness() + _program()))
    ms = 1e-9 * 1e3 / 2   # 1 ns of self time, in ms per query
    want = {"service_host_ms_per_query": 80 * ms,
            "driver_ms_per_query": 150 * ms,
            "descent_ms_per_query": 30 * ms,
            "bound_pricing_ms_per_query": 150 * ms,
            "refine_ms_per_query": 40 * ms,
            "launch_host_ms_per_query": 160 * ms,
            "launch_wait_ms_per_query": 290 * ms,
            "lanes_per_query": (16384 + 2048) / 2,
            "untraced_query_share": 40 / 900}
    got = {name: _read(name, r) for name in READERS}
    assert got == pytest.approx(want)


def test_span_times_add_up_to_the_mean_query_time():
    """The seven time metrics and the untraced share of the mean query
    time make the mean query time, where every program span runs inside
    a query on the harness's thread."""
    events = _harness() + [e for e in _program() if e.line == MAIN]
    r = _run(spans.summarize(events))
    mean_ms = r.trace.query_s * 1e3 / r.n_queries
    total = sum(_read(name, r) for name in TIMES)
    assert total + _read("untraced_query_share", r) * mean_ms == \
        pytest.approx(mean_ms)


def test_readers_report_nothing_without_program_spans():
    harness_only = spans.summarize(_harness())
    today = devtrace.summarize(_harness())
    for summary in (harness_only, today, None):
        r = _run(summary)
        assert all(_read(name, r) is None for name in READERS)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    s = spans.summarize(_harness() + _program(), top=100)
    gaps = dict(s.idle_gaps)
    assert gaps["query after window start"] == pytest.approx(10e-9)
    assert gaps["service.reprice after window start"] == \
        pytest.approx(50e-9)
    assert gaps["search.bounds after window start"] == pytest.approx(30e-9)
    assert gaps["launch.wait after window start"] == pytest.approx(10e-9)
    # The worker's span is the innermost between the queries.
    assert gaps["search.bounds after custom-call.1"] == \
        pytest.approx((40 + 80) * 1e-9)
    assert gaps["window after custom-call.1"] == pytest.approx(60e-9)
    assert gaps["launch.wait after copy.2"] == pytest.approx(20e-9)
    assert gaps["query after copy.2"] == pytest.approx(10e-9)
    idle = s.window_s - s.busy_s
    assert sum(gaps.values()) == pytest.approx(idle)
    assert sum(s.idle_by_span.values()) == pytest.approx(idle)
    under = sum(v for k, v in s.idle_by_span.items()
                if k.startswith(spans.PROGRAM_PREFIX))
    # Harness spans innermost: four 10 ns ends of queries, 60 ns of window.
    assert under == pytest.approx(idle - (4 * 10 + 60) * 1e-9)


def test_program_spans_outside_the_window_change_nothing():
    events = _harness() + _program()
    outside = [_p("search", -500, 200), _p("launch", 1100, 50, lanes=99),
               _p("launch.wait", 1110, 30)]
    a = spans.summarize(events)
    b = spans.summarize(events + outside)
    for f in ("window_s", "busy_s", "kernel_s", "kernel_launches",
              "device_ops", "span_self_s", "lanes"):
        assert getattr(a, f) == getattr(b, f), f
    today = devtrace.summarize(events + outside)
    assert (today.window_s, today.busy_s) == (a.window_s, a.busy_s)


def _fields(s):
    return (s.window_s, s.busy_s, s.kernel_s, s.kernel_launches,
            s.device_ops, s.idle_gaps)


def _random_harness_trace(rng):
    t, events = 0.0, []
    w0 = t
    for _ in range(rng.randint(1, 30)):
        t += rng.randint(0, 50)
        q0 = t
        for _ in range(rng.randint(0, 4)):
            t += rng.randint(0, 40)
            d = rng.randint(1, 60)
            name = rng.choice(["fusion", "copy", "custom-call"])
            events.append(_op(f"{name}.{rng.randint(0, 3)}", t, d,
                              **(KERNEL if name == "custom-call" else {})))
            t += rng.randint(0, d + 20)
        t += rng.randint(1, 30)
        events.append(_span("bench.query", q0, t - q0))
    events.append(_span("bench.window", w0, t - w0 + rng.randint(0, 20)))
    rng.shuffle(events)
    return events


def test_a_harness_only_trace_gives_todays_summary():
    small = [
        _span("bench.window", 0, 1000), _span("bench.query", 0, 400),
        _span("bench.query", 500, 500),
        _op("custom-call.1", 100, 100, **KERNEL), _op("fusion.2", 150, 100),
        _op("%k.3 = f32[3,128]{1,0} custom-call(f32[5,24] %a.1), "
            "custom_call_target=\"tpu_custom_call\"", 600, 200),
        _op("copy.3", 950, 100),
        _op("noise", 100, 50, plane="/device:TPU_NON_CORE:0")]
    assert _fields(spans.summarize(small)) == \
        _fields(devtrace.summarize(small))
    rng = random.Random(14)
    for _ in range(200):
        events = _random_harness_trace(rng)
        today = devtrace.summarize(events)
        got = spans.summarize(events)
        assert (got is None) == (today is None)
        if today is not None:
            assert _fields(got) == _fields(today)
    assert spans.summarize([_span("bench.window", 0, 10)]) is None
    assert spans.summarize([_op("fusion", 0, 10)]) is None


def test_one_hundred_thousand_nested_spans_reduce_in_seconds():
    events = [_span("bench.window", 0, 20_000 * 1000)]
    for q in range(20_000):
        t = q * 1000
        events += [_span("bench.query", t, 990),
                   _p("service.query", t + 5, 980),
                   _p("search", t + 10, 960),
                   _p("search.descend", t + 20, 300),
                   _p("search.bounds", t + 30, 100),
                   _p("launch", t + 400, 500, lanes=2048),
                   _op("custom-call", t + 600, 100, **KERNEL)]
    t0 = time.perf_counter()
    s = spans.summarize(events)
    took = time.perf_counter() - t0
    assert took < 30.0, took
    assert s.lanes == 20_000 * 2048
    assert s.span_self_s["dxpta.search.bounds"] == \
        pytest.approx(20_000 * 100e-9)


# ---------------------------------------------------------------------------
# The program's spans on a real trace (CPU, interpret-mode kernels).
# ---------------------------------------------------------------------------

PARENTS = {
    "dxpta.service.query": {"bench.query"},
    "dxpta.service.reprice": {"dxpta.service.query"},
    "dxpta.search": {"bench.query", "dxpta.service.query"},
    "dxpta.search.descend": {"dxpta.search"},
    "dxpta.search.bounds": {"dxpta.search", "dxpta.search.descend"},
    "dxpta.search.refine": {"dxpta.search"},
    "dxpta.launch": {"dxpta.search"},
    "dxpta.launch.wait": {"dxpta.launch"},
}


def _parents(events):
    """{span name: set of the names of its innermost enclosing spans}."""
    out: dict = {}
    host = [e for e in events if spans.is_span(e)]
    for e in host:
        around = [o for o in host if o is not e and o.line == e.line
                  and o.start_ns <= e.start_ns
                  and e.start_ns + e.dur_ns <= o.start_ns + o.dur_ns
                  and o.dur_ns >= e.dur_ns]
        parent = min(around, key=lambda o: o.dur_ns).name if around \
            else None
        out.setdefault(e.name, set()).add(parent)
    return out


def _answers(res):
    if hasattr(res, "front"):
        return (np.asarray(res.front).tolist(),
                {k: np.asarray(v).tolist() for k, v in res.metrics.items()})
    return (None if res.best_cfg is None else res.best_cfg.as_array().tolist(),
            res.edp, res.n_feasible, res.n_workload_evals)


def _queries():
    """Answers of a cold BnB search and of a service's warm EDP and
    Pareto deltas."""
    from repro.core.arch_params import Constraints
    from repro.core.factorized import FactorizedSpace
    from repro.core.paper_workloads import load
    from repro.core.search import search
    from repro.serve import SearchService

    space = FactorizedSpace.full(8)
    wl = load("deit-t")
    base = Constraints(area_mm2=60.0, power_w=6.0)
    tight = Constraints(area_mm2=45.0, power_w=4.5)
    out = [search(wl, base, engine="pallas", factorized=True, space=space,
                  prune="bound")]
    svc = SearchService(space=space, engine="pallas")
    for objective in ("edp", "pareto"):
        svc.query(wl, base, objective=objective)
        out.append(svc.query(wl, tight, objective=objective))
    assert svc.stats["warm"] == 2
    return [_answers(r) for r in out]


def test_the_program_emits_each_span_where_it_belongs(tmp_path):
    import jax

    plain = _queries()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.query"):
            traced = _queries()
    assert traced == plain
    events = spans.events_of(str(tmp_path))
    parents = _parents(events)
    for name, allowed in PARENTS.items():
        assert name in parents, name
        assert parents[name] <= allowed, (name, parents[name])
    launches = [e for e in events if e.name == "dxpta.launch"]
    assert all(e.stats["lanes"] > 0 and e.stats["lanes"] % 2048 == 0
               for e in launches)
