"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a roofline summary if a
dry-run results file exists). Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import json
import os
import sys


def main() -> None:
    # exec-safe dots: benchmarks execute on CPU
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.layers import set_exec_safe
    enable_compile_cache()
    set_exec_safe(True)

    from . import (arch_dse, fig2_param_sweep, fig7_significance, fig9_dse,
                   fig10_area_power, fig11_platforms, fig12_search_time,
                   pareto_front)
    mods = [fig2_param_sweep, fig7_significance, fig9_dse, fig10_area_power,
            fig11_platforms, fig12_search_time, arch_dse, pareto_front]
    print("name,us_per_call,derived")
    failures = 0
    for m in mods:
        try:
            for name, us, derived in m.run():
                print(f"{name},{us},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{m.__name__},ERROR,{type(e).__name__}: {e}",
                  file=sys.stderr)

    # roofline summary from the dry-run artifact, if present
    path = os.environ.get("DRYRUN_JSON", "results/dryrun_all.json")
    if os.path.exists(path):
        cells = json.load(open(path))
        ok = [c for c in cells if c.get("status") == "ok"]
        for c in ok:
            r = c["roofline"]
            frac = r.get("roofline_fraction")
            print(f"roofline/{c['arch']}/{c['shape']}/{c['mesh']},"
                  f"{c['compile_s']*1e6:.0f},"
                  f"bottleneck={r['bottleneck']} "
                  f"frac={frac if frac is None else round(frac,4)}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
