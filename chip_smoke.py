"""Smoke run of the DSE search path on one TPU chip (or four with --chips 4).

Drives the architect's two entry points at the paper's full width, with the
Pallas kernels compiled by Mosaic, and holds every answer to the float64
numpy engine byte for byte:

  A. one-shot EDP search: DeiT-B over the 20^5 (3.2M-point) space with
     branch-and-bound (`search(..., engine="pallas", prune="bound")`) —
     the decoded search kernel on coarse slabs, the grid-operand search
     kernel on fine survivors;
  B. Pareto search: BERT-L over the same 20^5 space (decoded frontier
     kernel) and over the paper's materialized 12^5 grid (grid-operand
     frontier kernel);
  C. a `SearchService` session: a cold query, a warm constraint delta and
     a memo hit, each equal to a cold numpy search of the same box.

`--chips 4` instead runs phases A and B with `shard=4` on a 4-device
candidate mesh next to `shard=1`, and asserts the two byte-identical.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the shard= fan-out on four chips

Lines before the last are informational (wall times include compilation
on first calls; they are not benchmark numbers). The last line is one JSON
object naming the device. Any failed phase exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def _canonical(result) -> dict:
    """Every compared field of a Search/ParetoResult as bytes: arrays by
    dtype, shape and contents, floats by their hex form. Wall time is how
    long the answer took, not the answer."""
    def enc(v):
        if isinstance(v, np.ndarray):
            return (str(v.dtype), v.shape, np.ascontiguousarray(v).tobytes())
        if isinstance(v, dict):
            return {k: enc(x) for k, x in sorted(v.items())}
        if isinstance(v, float):
            return v.hex()
        return repr(v)
    return {f.name: enc(getattr(result, f.name))
            for f in dataclasses.fields(result)
            if f.compare and f.name != "wall_time_s"}


def _answer(result) -> dict:
    """The answer fields a service returns identically to a cold search
    (winner and its float64 metrics, or frontier and its metrics); its
    work counters describe the delta it ran, not the question."""
    keys = (("front", "metrics") if hasattr(result, "front") else
            ("best_cfg", "area_mm2", "power_w", "energy_j", "latency_s",
             "edp"))
    full = _canonical(result)
    return {k: full[k] for k in keys}


def _same(label: str, got: dict, want: dict) -> None:
    if got != want:
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        raise AssertionError(f"{label}: differs from the reference in "
                             f"{diff}")
    print(f"[ok] {label}", flush=True)


def _timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()  # every search returns host numpy/python values: synced
    print(f"[info] {label}: {time.perf_counter() - t0:.3f} s wall",
          flush=True)
    return out


def _no_degradation(label: str, result) -> None:
    if result.n_fallbacks or result.n_quarantined:
        raise AssertionError(f"{label}: {result.n_fallbacks} fallback(s), "
                             f"{result.n_quarantined} quarantined unit(s)")


def phase_a(search, load, Constraints, space, shard=None):
    """DeiT-B min-EDP over 20^5 with branch-and-bound."""
    kw = dict(factorized=True, space=space, prune="bound")
    wl = load("deit-b")
    ref = _timed("A numpy", lambda: search(wl, Constraints(),
                                            engine="numpy", **kw))
    for i in range(2):
        got = _timed(f"A pallas shard={shard} call {i + 1}", lambda: search(
            wl, Constraints(), engine="pallas", shard=shard, **kw))
        _no_degradation("A", got)
        _same(f"A deit-b 20^5 edp bnb shard={shard} call {i + 1}",
              _canonical(got), _canonical(ref))
    print(f"[info] A winner {got.best_cfg} n_feasible={got.n_feasible} "
          f"n_pruned={got.n_pruned} n_workload_evals={got.n_workload_evals}",
          flush=True)
    return got


def phase_b(search, load, Constraints, space, shard=None):
    """BERT-L Pareto fronts over 20^5 (decoded) and the 12^5 grid."""
    wl = load("bert-l")
    out = []
    for label, kw in (("20^5 factorized", dict(factorized=True,
                                                space=space)),
                      ("12^5 grid", dict(factorized=False, n_z=12))):
        ref = _timed(f"B numpy {label}", lambda: search(
            wl, Constraints(), engine="numpy", objective="pareto", **kw))
        for i in range(2):
            got = _timed(f"B pallas {label} shard={shard} call {i + 1}",
                         lambda: search(wl, Constraints(), engine="pallas",
                                        objective="pareto", shard=shard,
                                        **kw))
            _no_degradation("B", got)
            _same(f"B bert-l {label} pareto shard={shard} call {i + 1}",
                  _canonical(got), _canonical(ref))
        print(f"[info] B {label}: front {got.size} points, "
              f"n_feasible={got.n_feasible} n_overflow={got.n_overflow}",
              flush=True)
        out.append(got)
    return out


def phase_c(search, load, Constraints, space):
    """SearchService session: cold, warm delta, memo."""
    from repro.serve import SearchService

    svc = SearchService(n_z=20, engine="pallas")
    wl = load("deit-b")
    session = (("cold", Constraints()), ("warm", Constraints(power_w=4.5)),
               ("memo", Constraints()))
    for how, box in session:
        got = _timed(f"C {how} query", lambda: svc.query(wl, box))
        _no_degradation("C", got)
        ref = search(wl, box, engine="numpy", factorized=True, space=space,
                     prune="bound")
        _same(f"C {how} query == cold numpy search", _answer(got),
              _answer(ref))
    want = {"queries": 3, "cold": 1, "warm": 1, "memo_hits": 1}
    seen = {k: svc.stats[k] for k in want}
    if seen != want:
        raise AssertionError(f"C service stats {seen}, expected {want}")
    print(f"[ok] C service stats {seen}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run phases A and B with shard=4 against "
                         "shard=1 on a four-chip host (and nothing else)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[info] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.core.arch_params import Constraints
    from repro.core.factorized import FactorizedSpace
    from repro.core.paper_workloads import load
    from repro.core.search import search
    from repro.kernels.backend import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[info] compile cache: {enable_compile_cache()}", flush=True)
    if resolve_interpret():
        raise AssertionError("Pallas would run in interpret mode on the chip")
    space = FactorizedSpace.full(20)
    common = (search, load, Constraints, space)

    if args.chips == 4:
        from repro.launch.mesh import make_candidate_mesh
        k = make_candidate_mesh(4).devices.size
        if k != 4:
            raise AssertionError(f"candidate mesh has {k} device(s), not 4")
        print("[ok] candidate mesh spans 4 devices", flush=True)
        one = _canonical(phase_a(*common, shard=1))
        four = _canonical(phase_a(*common, shard=4))
        _same("A shard=4 == shard=1", four, one)
        for f1, f4 in zip(phase_b(*common, shard=1),
                          phase_b(*common, shard=4)):
            _same("B shard=4 == shard=1", _canonical(f4), _canonical(f1))
    else:
        phase_a(*common)
        phase_b(*common)
        phase_c(*common)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
