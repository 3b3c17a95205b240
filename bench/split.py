"""Split one cell's query time over the program's spans, on the chip.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Run from the repository root, like `run.py`, whose set-up and window it
reuses. After the set-up come three windows of `--seconds` each, with the
items of seeds n, n + 1 and n + 2 (the same strata, new boxes, so a warm
cell's memo does not answer a later window): one untraced; one under the
profiler as `run.py --trace 1` runs it ("harness"), whose Python tracer
records every Python call; one with the Python tracer off
("spans_only"), where the host events are the program's and the
harness's spans. The last line of standard output is one JSON object:

  untraced           the window's queries, queries/s and mean query ms
  harness,           the same for each traced window (against "untraced",
  spans_only         what that tracing costs), with the span metrics read
                     by `metrics/<name>.py` from the `spans.py` reduction,
                     each span's self time, the idle gaps named by the
                     innermost span, and the share of device idle time
                     under a program span

Exits 2 without a result where `run.py` would, 1 if a trace holds no
device op.
"""
import argparse
import json
import shutil
import sys
import time

import run
import spans
from cell import Cell, load_module, load_spec, resolve

SPAN_METRICS = ("service_host_ms_per_query", "driver_ms_per_query",
                "descent_ms_per_query", "bound_pricing_ms_per_query",
                "refine_ms_per_query", "launch_host_ms_per_query",
                "launch_wait_ms_per_query", "lanes_per_query",
                "untraced_query_share")


def _rate(r: "run.Run") -> dict:
    return {"queries": r.n_queries, "window_s": r.window_s,
            "queries_per_s": r.n_queries / r.window_s,
            "mean_query_ms": 1e3 * sum(r.latencies_s) / r.n_queries,
            "failed": r.failed, "compiles_in_window": r.compiles_in_window}


def _split(traced: "run.Run") -> dict:
    s = traced.trace
    metrics = {}
    for name in SPAN_METRICS:
        value = load_module(run.BENCH / "metrics" / f"{name}.py").read(traced)
        if value is not None:
            metrics[name] = value
    idle = sum(s.idle_by_span.values())
    under = sum(v for k, v in s.idle_by_span.items()
                if k.startswith(spans.PROGRAM_PREFIX))
    return dict(_rate(traced),
                device_idle_share=1.0 - s.busy_s / s.window_s,
                kernel_ms_per_query=s.kernel_s * 1e3 / traced.n_queries,
                kernel_launches_per_query=s.kernel_launches
                / traced.n_queries,
                metrics=metrics, span_self_s=s.span_self_s,
                idle_under_program=under / idle if idle else None,
                idle_gaps=s.idle_gaps, device_ops=s.device_ops)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    run.prepare_process()
    import repro.core  # noqa: F401 -- the system under test must be here

    entry, config, traffic = resolve(load_spec(), args.workload)
    devices = run.chips_or_none(int(entry["chips"]))
    if devices is None:
        return 2
    import jax
    import jax.monitoring

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = run.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    cell = Cell(config, traffic)
    run.warm_up(cell, counter)
    setup_s = time.perf_counter() - run.T0

    plain = run.Run()
    run.window(cell, args.seed, args.seconds, counter, plain)
    line = {"workload": args.workload, "seed": args.seed,
            "setup_s": setup_s, "untraced": _rate(plain),
            "device": {"kind": devices[0].device_kind,
                       "count": len(devices)}}
    profiles = {"harness": jax.profiler.ProfileOptions(),
                "spans_only": jax.profiler.ProfileOptions()}
    profiles["spans_only"].python_tracer_level = 0
    for k, (name, options) in enumerate(profiles.items(), start=1):
        traced = run.Run()
        trace_dir = run.CACHE / "split-trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        run.window(cell, args.seed + k, args.seconds, counter, traced)
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        traced.trace = spans.summarize(spans.events_of(str(trace_dir)))
        reduce_s = time.perf_counter() - t0
        shutil.rmtree(trace_dir, ignore_errors=True)
        if traced.trace is None:
            print("bench: the trace holds no device op", file=sys.stderr)
            return 1
        line[name] = _split(traced)
        line[name]["reduce_s"] = reduce_s
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
