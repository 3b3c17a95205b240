"""Shared set-up of the benchmark's CPU tests: the benchmark's modules on
the path, and each cell at a size a test run holds (two encoder layers at
the cell's published widths, tokens and batch, over the 1..6 space, where
the answers still change from box to box), driven through the harness
with the look for a chip skipped."""
import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (str(BENCH), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELLS = ("deit-b.s24.edp-cold", "bert-l.s20.pareto-sweep",
         "deit-b.s24.edp-warm", "bert-l.s20.pareto-warm",
         "deit-b.s24.edp-warm-tight")


def tiny_config(cell_name: str, n_z: int = 6) -> dict:
    """The cell's configuration cut to a CPU test's size."""
    from cell import load_spec, resolve

    _, cfg, _ = resolve(load_spec(), cell_name)
    cfg = copy.deepcopy(cfg)
    cfg["name"] = f"{cfg['name']}.tiny{n_z}"
    cfg["space"]["n_z"] = n_z
    for w in cfg["workloads"].values():
        w["layers"] = 2
    return cfg


def drive(cell_name: str, seed: int = 7, seconds: float = 0.5,
          trace: bool = False) -> dict:
    """One run of the cell at the tiny size on the CPU: everything a run
    does except the look for a chip and the process-level cache set-up."""
    import jax
    import jax.monitoring

    import run

    counter = run.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        from cell import load_spec
        return run.run_cell(load_spec(), cell_name, seed, seconds, trace,
                            jax.devices(), counter,
                            config=tiny_config(cell_name))
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
