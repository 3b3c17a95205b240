"""One run of one benchmark cell on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the repository root (the script finds `src/` and `bench/`
itself). A run:

  1. exits non-zero without a result when JAX finds no TPU, or fewer
     chips than the cell asks for;
  2. keeps JAX's persistent compilation cache at `bench/_cache/jax`;
  3. builds the cell from its configuration and traffic files and
     answers the same fixed warm-up items in every run, so the cell's own
     launch shapes are compiled or loaded (the set-up, `setup_s`);
  4. sends the seed's items through the traffic's loop for `--seconds`,
     under the profiler with `--trace 1`;
  5. compares a sample of the window's answers, drawn from the seed, with
     the plain float64 reference (`check.py`);
  6. prints the result as the last line of standard output: the cell's
     end-to-end metrics (`--trace 0`) or per-layer metrics (`--trace 1`),
     with the compared numbers and their limits last.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / "_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_SAMPLE = 40   # window queries compared with the reference per run


def prepare_process() -> None:
    """Paths and environment, before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def chips_or_none(chips: int):
    """The TPU devices of this process, or None (with the reason on
    standard error) when there is no TPU or too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chip(s); JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


class CompileCounter:
    """Counts executables compiled or loaded from the persistent cache,
    keeping the name of the function each was for."""

    def __init__(self):
        self.names = []

    @property
    def n(self) -> int:
        return len(self.names)

    def __call__(self, event, duration_secs, **kwargs):
        if event == COMPILE_EVENT:
            self.names.append(str(kwargs.get("fun_name", "?")))


class Run:
    """What a window produced, as the metric readers see it."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_s = 0.0
        self.latencies_s = []
        self.items = []
        self.results = []          # per query [(workload, result)] or None
        self.compiled = []         # functions compiled inside the window
        self.counters = {}         # the entry's counters over the window
        self.trace = None

    @property
    def n_queries(self) -> int:
        return len(self.latencies_s)

    @property
    def failed(self) -> int:
        """Queries that raised, or came back degraded: an answer that did
        not come from the device path."""
        return sum(res is None or any(r.n_fallbacks or r.n_quarantined
                                      for _, r in res)
                   for res in self.results)

    @property
    def compiles_in_window(self) -> int:
        return len(self.compiled)


def warm_up(cell, counter: CompileCounter) -> None:
    """The entry's own preparation, then a fixed list of items answered
    (`traffic.warmup_items`): the same work in every run, whatever the
    seed. Prints how many executables had been compiled or loaded after
    each step, so a list too short for the cell's shapes shows."""
    import traffic as tr

    cell.entry.prepare()
    seen = [counter.n]
    for item in tr.warmup_items(cell.traffic, cell.workloads):
        cell.entry.answer(item)
        seen.append(counter.n)
    print(f"bench: executables after each warm-up step: {seen}",
          file=sys.stderr)


def window(cell, seed: int, seconds: float, counter: CompileCounter,
           run: Run) -> None:
    """The traffic's loop over the seed's items for `seconds`."""
    import traffic as tr

    before = cell.entry.counters()
    compiles0 = counter.n
    cell.loop.window(cell.entry,
                     tr.items(cell.traffic, cell.workloads, seed), seconds,
                     run)
    run.compiled = counter.names[compiles0:]
    run.counters = {k: v - before.get(k, 0)
                    for k, v in cell.entry.counters().items()}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def reference_sweep(config: dict, workload: str, traffic_: dict) -> tuple:
    """The float64 reference's feasible set of one workload under the
    traffic's loosest box, kept at `bench/_cache/ref` keyed by everything
    it depends on, so later runs of the same configuration in this
    checkout reuse it."""
    import numpy as np

    import reference
    import traffic as tr
    from cell import BENCH as bench, lowering

    box = tr.si(tr.loosest(traffic_))
    sizes = config["workloads"][workload]
    h = hashlib.sha256()
    h.update(json.dumps([sizes, config["space"], config["constants"], box],
                        sort_keys=True).encode())
    for src in (bench / "reference.py",
                bench / "lowering" / f"{config['lowering']}.py"):
        h.update(src.read_bytes())
    path = CACHE / "ref" / f"{config['name']}.{workload}-" \
        f"{h.hexdigest()[:20]}.npz"
    if path.exists():
        with np.load(path) as z:
            return z["idx"], z["rows"], {k: z[k] for k in reference.METRICS}
    wl = lowering(config).reference_workload(sizes)
    idx, rows, met = reference.sweep(int(config["space"]["n_z"]), wl,
                                     config["constants"], box)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + f".{os.getpid()}.tmp.npz")
    np.savez(tmp, idx=idx, rows=rows, **met)
    os.replace(tmp, path)
    return idx, rows, met


def sample(run: Run, seed: int, k: int = CHECK_SAMPLE) -> list:
    """Indices of the window answers to compare: `k` drawn from the seed,
    plus the slowest query."""
    import traffic as tr

    done = [i for i, r in enumerate(run.results) if r is not None]
    if not done:
        return []
    rng = tr.stream(seed, tr.SAMPLE)
    pick = set(rng.choice(done, size=min(k, len(done)), replace=False)
               .tolist())
    pick.add(max(done, key=lambda i: run.latencies_s[i]))
    return sorted(pick)


def check(config: dict, traffic_: dict, run: Run, seed: int) -> tuple:
    """(correct, compared numbers with their limits)."""
    import check as ck
    import traffic as tr
    from cell import lowering

    low = lowering(config)
    refs = {}
    items = []
    for i in sample(run, seed):
        box = tr.si(run.items[i]["box"])
        for name, res in run.results[i]:
            if name not in refs:
                refs[name] = (reference_sweep(config, name, traffic_),
                              low.reference_workload(
                                  config["workloads"][name]))
            items.append((box, ck.answer_of(res)) + refs[name])
    numbers = ck.compare(traffic_["objective"], items, config["constants"],
                         traffic_.get("pareto_metrics"))
    numbers["unanswered"] = sum(r is None for r in run.results)
    return ck.verdict(numbers)


def read_metrics(spec: dict, cell_name: str, kind: str, run: Run) -> dict:
    """{name: {value, unit}} of every metric of `kind` ("end_to_end" or
    "per_layer") that the spec gives this cell, each from the reader file
    of its own name; a reader that finds nothing leaves its metric out."""
    from cell import load_module

    out = {}
    for m in spec[kind]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, devices, counter: CompileCounter,
             config=None) -> dict:
    """Set up, time and check one cell; returns the result line's object.
    `counter` must be listening to JAX's compile events; `config`
    replaces the cell's configuration file (tests)."""
    import jax

    import devtrace
    from cell import Cell, resolve

    entry, cfg, traffic_ = resolve(spec, cell_name)
    config = config or cfg
    cell = Cell(config, traffic_)
    warm_up(cell, counter)
    run = Run()
    run.setup_s = time.perf_counter() - T0
    print(f"bench: set-up {run.setup_s:.3f} s, {counter.n} executables "
          f"compiled or loaded", file=sys.stderr)

    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(
            str(trace_dir), profiler_options=jax.profiler.ProfileOptions())
    window(cell, seed, seconds, counter, run)
    if trace:
        jax.profiler.stop_trace()
    print(f"bench: window {run.window_s:.3f} s, {run.n_queries} queries, "
          f"{run.failed} failed, executables compiled or loaded: "
          f"{run.compiled}", file=sys.stderr)
    peak = memory_peak(devices)
    cell = None  # the program's state goes before the reference runs
    gc.collect()
    if trace:
        run.trace = devtrace.summarize(devtrace.events_of(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_check = time.perf_counter()
    correct, checked = check(config, traffic_, run, seed)
    print(f"bench: reference check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": run.n_queries,
            "failed": run.failed,
            "metrics": read_metrics(spec, cell_name,
                                    "per_layer" if trace else "end_to_end",
                                    run),
            "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["checked"] = checked
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    prepare_process()
    import repro.core  # noqa: F401 -- the system under test must be here
    from cell import load_spec, resolve

    spec = load_spec()
    entry, _, _ = resolve(spec, args.workload)
    devices = chips_or_none(int(entry["chips"]))
    if devices is None:
        return 2
    import jax
    import jax.monitoring

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Every executable goes to the cache, however fast it compiled, so a
    # second run of the cell loads all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    line = run_cell(spec, args.workload, args.seed, args.seconds,
                    bool(args.trace), devices, counter)
    for name, v in line["checked"].items():
        print(f"bench: checked {name} = {v['value']!r} "
              f"(limit {v['limit']!r})", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
