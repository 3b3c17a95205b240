"""Alg. 2 — constraint-aware architecture search, plus the engine layer.

The paper-level entry points:

  * `dxpta_search`      — the paper's Alg. 2: significance-guided candidate
                          sets (fine-grained N_t/N_c, progressive step for
                          N_v/N_h/N_lambda), feasible min-EDP selection.
                          `prune=True` (default) skips the workload
                          evaluation once area/power already violate — the
                          "constraint-aware" part of the exploration.
                          `engine=` dispatches the reduced grid to any of
                          the vectorized backends below.
  * `exhaustive_search` — the paper's comparison baseline: every combination
                          of all five parameters in 1..N_z, fully evaluated.

Beyond-paper, the unified engine layer (`search` / `search_workloads`): four
interchangeable backends over the same cost model, all returning identical
`SearchResult`s. A materialized grid runs through one chunked loop per
objective, with the engine chosen from that objective's chunk table
(EDP_CHUNK_ENGINES / PARETO_CHUNK_ENGINES); a one-shot search is the
single-chunk case —

  * `python` — the paper-faithful Alg. 2 sequential loop (the oracle).
  * `numpy`  — the whole grid as one broadcasted float64 computation.
  * `jax`    — the same math jit-compiled, with constraint masking and the
               EDP argmin fused on-device (jit-cached per workload).
  * `pallas` — the fused `dse_search` kernel: feasibility, EDP and a
               per-block argmin reduction inside the kernel, so the (4, G)
               metrics array is never materialized on the host.

`hierarchical=True` adds the two-phase pass (the vectorized analogue of the
paper's `prune=True`): a cheap area/power-only sweep of the full grid
(`hw_prefilter` — no workload term), compaction of the survivors, then
workload evaluation only on the feasible subset. `search_workloads` batches
all requested workloads against one grid — on the pallas backend in a single
jit-cached kernel launch with dynamic constraint operands, so
constraint-scenario sweeps never recompile.

Whichever backend selects the winner, its reported metrics are recomputed
through the float64 reference model (`eval_full`), so results are
bit-identical across engines whenever they agree on `best_cfg`.

Both entry points also take `objective="pareto"`: instead of the single
min-EDP point they return the whole non-dominated feasible set over
`pareto_metrics` as a `ParetoResult`. Backends propose frontier candidates
their own way (sequential incremental front, exact float64 mask, jit
sort-and-scan, per-block dominance reduction in the fused kernel) and every
proposal is refined through the float64 reference model, so identical
frontiers come back byte-identical; see PARETO_CHUNK_ENGINES below.

Scaling past one device / one resident grid, both entry points take
`shard=` (shard_map fan-out over a 1-D candidate-axis mesh) and
`chunk_size=` (host-side streaming of grid chunks, with a running argmin /
bounded running frontier carried across chunks — and, on pallas, *into* the
kernels, whose launches compose through carry operands). Every
(shard, chunk_size) setting is byte-identical to the single unsharded
chunk on every engine and objective; tests/test_sharded_search.py is the
differential harness that pins that down.

When the grid is a Cartesian product of per-parameter candidate sets (every
paper grid is), `factorized=True` switches the numpy/jax/pallas engines to
the axis-table evaluation of core.factorized: the cost model's separable
factors are tabulated per axis slice and combined by broadcasted outer
products, the (G, 5) grid never exists on the host (the pallas kernels
decode candidate rows on device from the chunk base + per-axis vectors),
and results stay byte-identical to the unfactorized engines because the
combine replays the same float ops per element. Composes with `shard=` /
`chunk_size=`; tests/test_factorized.py pins the equivalence.

Finally, `prune="bound"` (factorized engines, both objectives) stops
evaluating the space point-by-point at all: a significance-ordered
branch-and-bound recursion prices whole mixed-radix slabs with admissible
interval lower bounds (core.factorized.SlabBoundEvaluator) and discards
every slab that cannot contain the winner (or a frontier member) before
any engine sees it — winners and frontiers stay byte-identical to the
unpruned sweep, with the skipped volume reported in `n_pruned`.
tests/test_bnb.py pins the equivalence and the bound soundness.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import Dict, Mapping, Optional, Sequence, Union

import numpy as np

from .arch_params import Constraints, PTAConfig, config_grid
from .calibration import (CalibratedConstants, RobustBand, as_calibration)
from .factorized import FactorizedSpace, factorized_evaluate_grid
from .pareto import DEFAULT_OBJECTIVES, pareto_mask
from .performance_model import (calc_edp, eval_full, eval_wload_arrays,
                                workload_statics)
from .photonic_model import CONSTANTS, DeviceConstants, eval_hw, sram_mb_for_workload
from .runtime import (SearchRuntime, activate as _activate_rt,
                      decode_best_indexed, decode_best_row, decode_front,
                      encode_best_indexed, encode_best_row, encode_front,
                      fingerprint as _fingerprint)
from .significance import SignificanceScore, observe_significance, significant_params
from .workload import Workload
from ..tracing import gemm_lane_tally, span, traced

# Metric arrays reported per frontier point (every evaluate_grid key).
REPORT_METRICS = ("area", "power", "energy", "latency", "util", "edp")


@dataclasses.dataclass
class SearchResult:
    """Feasible min-EDP selection (objective="edp" search mode).

    `best_cfg` is the winning config (None when nothing satisfied the
    constraints) and the metric fields its float64 reference-model
    evaluation — whichever engine proposed the winner, the reported
    numbers come from `eval_full`, so results are bit-identical across
    engines whenever they agree on `best_cfg`. The counter fields record
    how much work the search did (and, under `prune="bound"` / `runtime=`,
    how much it skipped or survived).
    """

    best_cfg: Optional[PTAConfig]
    area_mm2: float = float("nan")
    power_w: float = float("nan")
    energy_j: float = float("nan")
    latency_s: float = float("nan")
    edp: float = float("inf")
    n_evaluated: int = 0
    n_feasible: int = 0
    n_workload_evals: int = 0
    wall_time_s: float = 0.0
    # Bound-guided search (prune="bound") counters: configs skipped by the
    # admissible slab bounds (never evaluated) and slab bound evaluations
    # performed. Zero on every other path.
    n_pruned: int = 0
    n_bounds: int = 0
    # Resilient-runtime counters (search(..., runtime=)): transient launch
    # retries, engine degradations, NaN-quarantined units re-evaluated on
    # the host, committed snapshots, and the unit cursor this run resumed
    # from (0 = cold start). Zero when no runtime is attached.
    n_retries: int = 0
    n_fallbacks: int = 0
    n_quarantined: int = 0
    n_checkpoints: int = 0
    resumed_step: int = 0
    # Optional (collect=True): per-candidate metric arrays for Fig. 9 scatter.
    history: Optional[Dict[str, np.ndarray]] = None

    # Slab ledger (search(..., prune="bound", keep_ledger=True)): the run's
    # pruned/evaluated slab partition with stored bounds, the warm-start
    # substrate of repro.serve. None unless requested. Excluded from
    # equality: two searches that agree on everything above are the same
    # result whether or not one kept its ledger.
    ledger: Optional[object] = dataclasses.field(default=None, repr=False,
                                                 compare=False)

    # Robust search (search(..., calibration=)): the winner's uncertainty
    # band — float64 reference metrics at the calibration's worst, nominal
    # and best corners (a core.calibration.RobustBand). None on
    # uncalibrated searches and infeasible results. Excluded from equality
    # like the ledger: the band is derived reporting, not the answer.
    band: Optional[RobustBand] = dataclasses.field(default=None, repr=False,
                                                   compare=False)

    # Parallel slab scheduler (search(..., workers=N)): the run's
    # lease/requeue/merge telemetry (a repro.parallel.slab_sched.SchedStats).
    # None on single-executor searches. Excluded from equality like the
    # ledger: scheduling is how the answer was computed, not the answer.
    sched: Optional[object] = dataclasses.field(default=None, repr=False,
                                                compare=False)

    # Lanes x GEMM rows the device launches of this search ran, padding
    # and masked lanes included (`repro.tracing.gemm_lane_tally`); 0 on
    # host engines. Excluded from equality like the ledger: lane padding is
    # how an engine ran, not the answer.
    n_gemm_lanes: int = dataclasses.field(default=0, compare=False)

    @property
    def feasible(self) -> bool:
        """True when the search found any constraint-satisfying config."""
        return self.best_cfg is not None

    @property
    def pruned_fraction(self) -> float:
        """Fraction of the candidate space the bound pruning skipped."""
        return self.n_pruned / max(self.n_evaluated, 1)


@dataclasses.dataclass
class ParetoResult:
    """A feasible Pareto frontier (objective="pareto" search mode).

    `front` holds the non-dominated feasible config rows in canonical
    (lexicographic) order; `metrics` the float64 reference-model metric
    arrays aligned row-for-row with it. Whatever backend proposed the
    frontier, both are finalized through the numpy reference model, so
    results are byte-identical across engines whenever they agree on the
    frontier membership.
    """
    front: np.ndarray                      # (F, 5) int64 config rows
    metrics: Dict[str, np.ndarray]         # {REPORT_METRICS: (F,) float64}
    objectives: tuple = DEFAULT_OBJECTIVES
    n_evaluated: int = 0
    n_feasible: int = 0
    n_workload_evals: int = 0
    wall_time_s: float = 0.0
    # Bound-guided search counters, as on SearchResult.
    n_pruned: int = 0
    n_bounds: int = 0
    # Resilient-runtime counters, as on SearchResult.
    n_retries: int = 0
    n_fallbacks: int = 0
    n_quarantined: int = 0
    n_checkpoints: int = 0
    resumed_step: int = 0
    # Pallas kernel blocks whose per-block frontier overflowed MAX_FRONT
    # and were host-refined from the whole block (exact, just slower).
    # Always 0 on the host/jax engines.
    n_overflow: int = 0
    # Slab ledger, as on SearchResult (keep_ledger=True only).
    ledger: Optional[object] = dataclasses.field(default=None, repr=False,
                                                 compare=False)

    # Robust-search uncertainty band, as on SearchResult but with
    # (F,)-arrays aligned row-for-row with `front` — `band.best` is the
    # best-case corner retained for reporting the variation band of each
    # frontier member. None on uncalibrated searches and empty frontiers.
    band: Optional[RobustBand] = dataclasses.field(default=None, repr=False,
                                                   compare=False)

    # Parallel slab scheduler telemetry, as on SearchResult (workers=N).
    sched: Optional[object] = dataclasses.field(default=None, repr=False,
                                                compare=False)

    # Lanes x GEMM rows launched, as on SearchResult.
    n_gemm_lanes: int = dataclasses.field(default=0, compare=False)

    @property
    def size(self) -> int:
        """Number of points on the frontier."""
        return len(self.front)

    @property
    def pruned_fraction(self) -> float:
        """Fraction of the candidate space the bound pruning skipped."""
        return self.n_pruned / max(self.n_evaluated, 1)

    @property
    def feasible(self) -> bool:
        """True when any constraint-satisfying config exists."""
        return self.size > 0

    @property
    def configs(self):
        """The frontier rows as `PTAConfig` objects."""
        return [PTAConfig.from_array(row) for row in self.front]


def progressive_candidates(n_z: int, step: int,
                           align_dims: Optional[Sequence[int]] = None):
    """Candidate set for the non-significant parameters (Alg. 2 lines 3-8).

    Default: progressive values {step, 2*step, ...} <= n_z. With
    `align_dims`, candidates are additionally snapped towards divisors of the
    workload's evenly-sized data dimensions (paper: "exploration step based
    on evenly-sized data dimension") so ceil() utilization losses vanish.
    """
    base = list(range(step, n_z + 1, step))
    if not align_dims:
        return base
    divisors = sorted({d for dim in align_dims for d in range(2, n_z + 1)
                       if dim % d == 0})
    return sorted(set(base) | set(divisors)) if divisors else base


def build_search_space(n_z: int = 12, step: int = 2,
                       significance: Optional[Dict[str, SignificanceScore]] = None,
                       align_dims: Optional[Sequence[int]] = None):
    """Candidate sets per parameter, driven by Alg. 1 significance output.

    The top-2 significant parameters get incremental sets 1..N_z; the rest get
    progressive sets. With the calibrated cost model this reproduces the
    paper's assignment (N_t, N_c fine; N_v, N_h, N_lambda coarse).
    """
    significance = significance or observe_significance()
    fine = set(significant_params(significance, top_k=2))
    inc = list(range(1, n_z + 1))
    prog = progressive_candidates(n_z, step, align_dims)
    return {name: (inc if name in fine else prog)
            for name in ("n_t", "n_c", "n_h", "n_v", "n_lambda")}


def _space_to_grid(space) -> np.ndarray:
    return config_grid(space["n_t"], space["n_c"], space["n_v"],
                       space["n_h"], space["n_lambda"])


def _sequential_search(grid: np.ndarray, wl: Workload, constraints: Constraints,
                       prune: bool, collect: bool, c: DeviceConstants,
                       edp_init: float = 1000.0) -> SearchResult:
    """Shared Alg. 2-style sequential loop (also used for the exhaustive
    baseline, with pruning disabled and the full grid). `edp_init` defaults
    to the paper's EDP_svd cap; the engine layer passes inf so that the
    python backend matches the uncapped vectorized backends."""
    sram_mb = sram_mb_for_workload(wl.max_act_bytes, c)
    gemms = wl.gemm_array
    best = SearchResult(best_cfg=None, edp=edp_init)  # EDP_svd init (Alg. 2)
    hist = {k: [] for k in ("area", "power", "energy", "latency",
                            "feasible")} if collect else None
    n_wl = 0
    n_feasible = 0
    t0 = time.perf_counter()
    for row in grid:
        n_t, n_c, n_h, n_v, n_l = (int(x) for x in row)
        area, power = eval_hw(n_t, n_c, n_h, n_v, n_l, sram_mb, c)
        hw_ok = (area < constraints.area_mm2) and (power < constraints.power_w)
        if prune and not hw_ok:
            if collect:
                for k, v in (("area", area), ("power", power),
                             ("energy", np.nan), ("latency", np.nan),
                             ("feasible", False)):
                    hist[k].append(v)
            continue
        energy, latency, _ = eval_wload_arrays(
            n_t, n_c, n_h, n_v, n_l, gemms, wl.elec_ops, wl.weight_bytes,
            wl.act_io_bytes, sram_mb, c)
        energy, latency = float(energy), float(latency)
        n_wl += 1
        ok = hw_ok and (energy < constraints.energy_j) \
            and (latency < constraints.latency_s)
        if collect:
            for k, v in (("area", area), ("power", power), ("energy", energy),
                         ("latency", latency), ("feasible", ok)):
                hist[k].append(v)
        if not ok:
            continue
        n_feasible += 1
        edp = calc_edp(energy, latency)
        if edp < best.edp:
            best = SearchResult(
                best_cfg=PTAConfig(n_t, n_c, n_h, n_v, n_l),
                area_mm2=float(area), power_w=float(power), energy_j=energy,
                latency_s=latency, edp=edp)
    best.n_evaluated = len(grid)
    best.n_feasible = n_feasible
    best.n_workload_evals = n_wl
    best.wall_time_s = time.perf_counter() - t0
    if collect:
        best.history = {k: np.asarray(v) for k, v in hist.items()}
    return best


def dxpta_search(wl: Workload, constraints: Constraints = Constraints(),
                 n_z: int = 12, step: int = 2,
                 significance: Optional[Dict[str, SignificanceScore]] = None,
                 align_dims: Optional[Sequence[int]] = None,
                 prune: Union[bool, str] = True, collect: bool = False,
                 c: DeviceConstants = CONSTANTS, engine: str = "python",
                 interpret: Optional[bool] = None, factorized: bool = False,
                 calibration=None,
                 robust: Optional[str] = None) -> SearchResult:
    """The paper's constraint-aware search (Alg. 2).

    `engine` dispatches the significance-reduced grid to any backend of the
    engine layer; `prune` maps to the hierarchical two-phase pass there.
    The default `python` engine is the paper-faithful sequential loop
    (including the EDP_svd=1000 initial cap, which the vectorized engines
    deliberately drop); `collect=True` requires it. `factorized=True`
    hands the candidate sets to the factorized product-space evaluation
    (numpy/jax/pallas engines) — Alg. 2's search space is a Cartesian
    product, so it factorizes directly; boolean `prune` is subsumed there
    (the axis-table combine prices area/power for free).
    `prune="bound"` goes one step further: the candidate space is explored
    by the bound-guided branch-and-bound driver (implies factorized=True;
    numpy/jax/pallas engines), which skips whole slabs whose admissible
    lower bounds already violate the constraints or cannot beat the
    running incumbent — the vectorized realization of the paper's claim
    that constraint-aware significance-guided search beats sweeping.
    `calibration=` / `robust="worst_case"` carry calibration uncertainty
    through whichever path dispatches, exactly as in `search` (robust
    mode needs a vectorized engine; the paper-faithful python loop stays
    point-calibrated and accepts `calibration=` only without `robust=`,
    running at its nominal constants).
    """
    if collect and engine != "python":
        raise ValueError("collect=True (per-candidate history) is only "
                         "implemented by the python engine")
    space = build_search_space(n_z, step, significance, align_dims)
    if prune == "bound":
        return search(wl, constraints, engine=engine, factorized=True,
                      space=space, c=c, interpret=interpret, prune="bound",
                      calibration=calibration, robust=robust)
    if factorized:
        return search(wl, constraints, engine=engine, factorized=True,
                      space=space, c=c, interpret=interpret,
                      calibration=calibration, robust=robust)
    grid = _space_to_grid(space)
    if engine == "python":
        c, cal, _ = _resolve_robust(calibration, robust, c, engine)
        res = _sequential_search(grid, wl, constraints, prune, collect, c)
        if cal is not None:
            res.band = _measure_band(res, cal, wl)
        return res
    return search(wl, constraints, engine=engine, grid=grid,
                  hierarchical=prune, c=c, interpret=interpret,
                  calibration=calibration, robust=robust)


def exhaustive_search(wl: Workload, constraints: Constraints = Constraints(),
                      n_z: int = 12, collect: bool = False,
                      c: DeviceConstants = CONSTANTS) -> SearchResult:
    """The paper's exhaustive baseline: full 1..N_z grid on all parameters."""
    inc = list(range(1, n_z + 1))
    grid = config_grid(inc, inc, inc, inc, inc)
    return _sequential_search(grid, wl, constraints, prune=False,
                              collect=collect, c=c)


def evaluate_grid(grid: np.ndarray, wl: Workload,
                  c: DeviceConstants = CONSTANTS, xp=np):
    """Vectorized metrics for a (G, 5) config grid.

    Returns dict of (G,) arrays: area, power, energy, latency, util, edp.
    """
    sram_mb = sram_mb_for_workload(wl.max_act_bytes, c)
    g = xp.asarray(grid)
    cols = [g[:, i] for i in range(5)]
    area, power = eval_hw(*cols, sram_mb, c, xp)
    energy, latency, util = eval_wload_arrays(
        *cols, wl.gemm_array, wl.elec_ops, wl.weight_bytes, wl.act_io_bytes,
        sram_mb, c, xp)
    return {"area": area, "power": power, "energy": energy,
            "latency": latency, "util": util, "edp": energy * latency}


def grid_search_vectorized(wl: Workload,
                           constraints: Constraints = Constraints(),
                           grid: Optional[np.ndarray] = None, n_z: int = 12,
                           c: DeviceConstants = CONSTANTS,
                           xp=np) -> SearchResult:
    """Beyond-paper: whole-grid broadcasted evaluation (numpy or jax)."""
    if grid is None:
        inc = list(range(1, n_z + 1))
        grid = config_grid(inc, inc, inc, inc, inc)
    t0 = time.perf_counter()
    m = evaluate_grid(grid, wl, c, xp)
    ok = constraints.satisfied(m["area"], m["power"], m["energy"],
                               m["latency"])
    edp = np.where(np.asarray(ok), np.asarray(m["edp"]), np.inf)
    n_feasible = int(np.sum(np.asarray(ok)))
    wall = time.perf_counter() - t0
    if n_feasible == 0:
        return SearchResult(best_cfg=None, n_evaluated=len(grid),
                            n_feasible=0, n_workload_evals=len(grid),
                            wall_time_s=wall)
    i = int(np.argmin(edp))
    return SearchResult(
        best_cfg=PTAConfig.from_array(grid[i]),
        area_mm2=float(np.asarray(m["area"])[i]),
        power_w=float(np.asarray(m["power"])[i]),
        energy_j=float(np.asarray(m["energy"])[i]),
        latency_s=float(np.asarray(m["latency"])[i]),
        edp=float(edp[i]), n_evaluated=len(grid), n_feasible=n_feasible,
        n_workload_evals=len(grid), wall_time_s=wall)


# ---------------------------------------------------------------------------
# Unified engine layer (beyond-paper): python | numpy | jax | pallas
# ---------------------------------------------------------------------------

def _full_grid(n_z: int) -> np.ndarray:
    inc = list(range(1, n_z + 1))
    return config_grid(inc, inc, inc, inc, inc)


@functools.lru_cache(maxsize=8)
def _hw_base_fn(c: DeviceConstants):
    """Jit'd workload-independent area/power prefix columns.

    The derived SRAM size is the *only* workload dependence of the hardware
    model, and its term sits second-to-last in `eval_hw`'s component sum —
    so summing every component *before* it once per grid, and replaying
    `(prefix + sram * coef) + chip_fixed` per workload bucket, reproduces
    eval_hw's float32 value bit-for-bit (same additions, same order). One
    grid sweep then serves every workload and constraint scenario without
    perturbing which edge-of-bound configs the prefilter keeps."""
    import jax
    import jax.numpy as jnp

    from .photonic_model import area_breakdown, power_breakdown

    def fn(cols):
        five = tuple(cols[i] for i in range(5))

        def prefix(breakdown):
            total = None
            for key, term in breakdown(*five, 0.0, c, xp=jnp).items():
                if key == "memory":  # chip_misc follows it — stop before
                    return total
                total = term if total is None else total + term

        return prefix(area_breakdown), prefix(power_breakdown)

    return jax.jit(fn)


@functools.lru_cache(maxsize=8)
def _hw_bucket_mask_fn(c: DeviceConstants):
    """Jit'd (S, G) feasibility masks from the shared prefix columns, one
    row per distinct (sram_mb, area bound, power bound) bucket — finishing
    eval_hw's sum in its own order (memory term, then the fixed chip
    term), so the masks match a full per-workload eval_hw exactly."""
    import jax
    import jax.numpy as jnp

    def fn(area0, power0, buckets):
        area = (area0[None, :] + buckets[:, 0:1] * c.a_sram_per_mb) \
            + c.a_chip_fixed
        power = (power0[None, :] + buckets[:, 0:1] * c.p_sram_per_mb) \
            + c.p_chip_fixed
        return (area < buckets[:, 1:2]) & (power < buckets[:, 2:3])

    return jax.jit(fn)


def hw_prefilter_masks(grid: np.ndarray, wls: Sequence[Workload],
                       constraints_seq: Sequence[Constraints],
                       c: DeviceConstants = CONSTANTS):
    """Per-workload area/power feasibility masks over one grid.

    The workload-independent base columns are computed once per grid
    (`_hw_base_fn`), each workload then costs one affine (sram, bounds)
    compare — and workloads landing in the same (sram_mb, area, power)
    bucket (the paper's five workloads share bounds and several share the
    derived SRAM size) are deduped down to a single mask row.

    Returns a list of (G,) boolean masks aligned with `wls`.
    """
    import jax.numpy as jnp
    area0, power0 = _hw_base_fn(c)(
        jnp.asarray(np.asarray(grid).T, jnp.float32))
    keys = [(float(sram_mb_for_workload(wl.max_act_bytes, c)),
             float(cc.area_mm2), float(cc.power_w))
            for wl, cc in zip(wls, constraints_seq)]
    uniq = sorted(set(keys))
    masks = np.asarray(_hw_bucket_mask_fn(c)(
        area0, power0, jnp.asarray(uniq, jnp.float32)))
    by_key = {key: masks[i] for i, key in enumerate(uniq)}
    return [by_key[key] for key in keys]


def hw_prefilter(grid: np.ndarray, wl: Workload, constraints: Constraints,
                 c: DeviceConstants = CONSTANTS) -> np.ndarray:
    """Phase-1 mask of the hierarchical search: area/power feasibility only.

    No workload term (the GEMM loop is the expensive part of the model), so
    this is one cheap fused elementwise sweep of the full grid; the
    survivors are then compacted and handed to the workload evaluation —
    the vectorized analogue of Alg. 2's prune-on-violation. Only the (G,)
    boolean mask leaves the device. Multi-workload callers should use
    `hw_prefilter_masks`, which amortizes the grid sweep across workloads.
    """
    return hw_prefilter_masks(grid, [wl], [constraints], c)[0]


@traced("search.refine")
def _make_result(cfg_row, n_feasible: int, wl: Workload, c: DeviceConstants,
                 n_evaluated: int, n_workload_evals: int,
                 wall: float) -> SearchResult:
    """Finalize an engine's selection through the float64 reference model so
    reported metrics are bit-identical across backends."""
    if cfg_row is None:
        return SearchResult(best_cfg=None, n_evaluated=n_evaluated,
                            n_feasible=0, n_workload_evals=n_workload_evals,
                            wall_time_s=wall)
    cfg = PTAConfig.from_array(cfg_row)
    area, power, energy, latency = eval_full(cfg, wl, c)[:4]
    return SearchResult(
        best_cfg=cfg, area_mm2=area, power_w=power, energy_j=energy,
        latency_s=latency, edp=calc_edp(energy, latency),
        n_evaluated=n_evaluated, n_feasible=n_feasible,
        n_workload_evals=n_workload_evals, wall_time_s=wall)


def _prefiltered(grid, wl, constraints, c, hierarchical):
    """(survivor subset, n_workload_evals) for one workload."""
    if not hierarchical:
        return grid, len(grid)
    sub = grid[hw_prefilter(grid, wl, constraints, c)]
    return sub, len(sub)


@functools.lru_cache(maxsize=128)
def _jax_search_fn(gemms, wl_scalars, c: DeviceConstants):
    """Jit-cached fused (argmin_idx, its EDP, n_feasible) for one workload.
    The constraint vector and the validity mask (padding rows of a sharded
    launch) are dynamic operands, so scenario sweeps reuse the cache entry;
    only three scalars leave the device. The returned EDP is the engine's
    own float32 value — the cross-chunk running argmin compares natively,
    so every chunking picks the winner a single chunk picks."""
    import jax
    import jax.numpy as jnp

    # int array, not float32: GEMM dims past the 24-bit float32 mantissa
    # must reach gemm_cycles' exact int32 ceil-division undamaged.
    gemm_arr = jnp.asarray(np.asarray(gemms, np.int64))

    def fn(cols, valid, cons):
        n_t, n_c, n_h, n_v, n_l = (cols[i] for i in range(5))
        energy, latency, _ = eval_wload_arrays(
            n_t, n_c, n_h, n_v, n_l, gemm_arr, *wl_scalars[:3],
            wl_scalars[3], c, xp=jnp)
        area, power = eval_hw(n_t, n_c, n_h, n_v, n_l, wl_scalars[3], c,
                              xp=jnp)
        ok = (valid & (area < cons[0]) & (power < cons[1])
              & (energy < cons[2]) & (latency < cons[3]))
        edp = jnp.where(ok, energy * latency, jnp.inf)
        i = jnp.argmin(edp)
        return i, edp[i], jnp.sum(ok)

    return jax.jit(fn)


def _constraint_vec(constraints):
    import jax.numpy as jnp
    return jnp.asarray([constraints.area_mm2, constraints.power_w,
                        constraints.energy_j, constraints.latency_s],
                       jnp.float32)


# ---------------------------------------------------------------------------
# Pareto-frontier search mode (objective="pareto"), same four backends
# ---------------------------------------------------------------------------

def _pareto_from_rows(rows, wl: Workload, constraints: Constraints,
                      c: DeviceConstants, objectives: tuple, m=None):
    """Exact float64 frontier over candidate rows.

    Every backend funnels its (possibly float32-proposed) candidate set
    through here: feasibility and dominance are re-decided by the numpy
    float64 reference model, and the frontier comes back in canonical
    lexicographic row order with reference-model metrics — so backends that
    agree on candidates return byte-identical `ParetoResult`s. Pass `m` to
    reuse already-computed `evaluate_grid` metrics for `rows`.

    Returns (front_rows, metrics, n_feasible_in_rows).
    """
    rows = np.asarray(rows, dtype=np.int64).reshape(-1, 5)
    empty = (np.zeros((0, 5), np.int64),
             {k: np.zeros(0, np.float64) for k in REPORT_METRICS}, 0)
    if len(rows) == 0:
        return empty
    if m is None:
        m = evaluate_grid(rows, wl, c, xp=np)
    ok = np.asarray(constraints.satisfied(m["area"], m["power"], m["energy"],
                                          m["latency"]))
    if not ok.any():
        return empty
    pts = np.stack([np.asarray(m[k], np.float64)[ok] for k in objectives],
                   axis=1)
    mask = pareto_mask(pts)
    front = rows[ok][mask]
    order = np.lexsort(front.T[::-1])
    sel = np.where(ok)[0][mask][order]
    met = {k: np.asarray(m[k], np.float64)[sel] for k in REPORT_METRICS}
    return front[order], met, int(ok.sum())


def _sequential_pareto(grid, wl: Workload, constraints: Constraints,
                       prune: bool, c: DeviceConstants, objectives: tuple):
    """Alg. 2-style sequential oracle for the frontier: stream the grid,
    maintain the running non-dominated set incrementally (dominated
    newcomers are rejected, newly-dominated incumbents evicted, exact ties
    kept). Returns (front_rows, n_feasible, n_workload_evals)."""
    sram_mb = sram_mb_for_workload(wl.max_act_bytes, c)
    gemms = wl.gemm_array
    front_rows: list = []
    front_pts: list = []
    n_wl = 0
    n_feasible = 0
    for row in grid:
        n_t, n_c, n_h, n_v, n_l = (int(x) for x in row)
        area, power = eval_hw(n_t, n_c, n_h, n_v, n_l, sram_mb, c)
        hw_ok = (area < constraints.area_mm2) and (power < constraints.power_w)
        if prune and not hw_ok:
            continue
        energy, latency, util = eval_wload_arrays(
            n_t, n_c, n_h, n_v, n_l, gemms, wl.elec_ops, wl.weight_bytes,
            wl.act_io_bytes, sram_mb, c)
        energy, latency = float(energy), float(latency)
        n_wl += 1
        if not (hw_ok and (energy < constraints.energy_j)
                and (latency < constraints.latency_s)):
            continue
        n_feasible += 1
        vals = {"area": float(area), "power": float(power), "energy": energy,
                "latency": latency, "util": float(util),
                "edp": calc_edp(energy, latency)}
        p = np.array([vals[k] for k in objectives], np.float64)
        if front_pts:
            fr = np.asarray(front_pts)
            if bool(np.any(np.all(fr <= p, axis=1) & np.any(fr < p, axis=1))):
                continue
            keep = ~(np.all(p <= fr, axis=1) & np.any(p < fr, axis=1))
            front_rows = [r for r, k in zip(front_rows, keep) if k]
            front_pts = [q for q, k in zip(front_pts, keep) if k]
        front_rows.append(np.asarray(row))
        front_pts.append(p)
    return front_rows, n_feasible, n_wl


# Sorted points per scan step and running-frontier buffer bound of the jax
# sort-and-scan dominance pass. An overflowing buffer only grows the
# candidate superset (never drops a true frontier point) — the host
# refinement restores exactness — so the bound is a perf knob, not a limit.
JAX_PARETO_CHUNK = 2048
JAX_PARETO_MAX_FRONT = 256


def _pareto_scan_mask(objs):
    """Sort-and-scan dominance pass over already-masked objective vectors.

    objs: list of equal-length float32 arrays (length a JAX_PARETO_CHUNK
    multiple) with infeasible/padding rows already +inf — they sort last,
    never dominate (inf <= finite is false), and are excluded by the
    finite() check. Rows are lex-sorted (so any dominator strictly precedes
    what it dominates, and frontier membership is decided the moment a row
    is visited), then scanned in chunks against (a) a bounded
    running-frontier buffer carried across chunks and (b) the earlier rows
    of their own chunk. Returns the (n,) candidate mask in input order.
    Shared by the grid-operand and the factorized jax frontier engines.
    """
    import jax
    import jax.numpy as jnp

    d = len(objs)
    order = jnp.lexsort(tuple(objs[::-1]))
    pts = jnp.stack([o[order] for o in objs], axis=1)
    chunks = pts.reshape(-1, JAX_PARETO_CHUNK, d)
    tri = jnp.tri(JAX_PARETO_CHUNK, k=-1, dtype=bool)  # [i, j]: j < i

    def step(buf, p):
        le = jnp.all(buf[None, :, :] <= p[:, None, :], axis=-1)
        lt = jnp.any(buf[None, :, :] < p[:, None, :], axis=-1)
        dom_buf = jnp.any(le & lt, axis=1)
        le_c = jnp.all(p[None, :, :] <= p[:, None, :], axis=-1)
        lt_c = jnp.any(p[None, :, :] < p[:, None, :], axis=-1)
        dom_chunk = jnp.any(le_c & lt_c & tri, axis=1)
        surv = jnp.isfinite(p[:, 0]) & ~dom_buf & ~dom_chunk
        # Merge survivors into the buffer, preserving lex order (buffer
        # rows come from earlier chunks, hence lex-precede survivors);
        # stable-compact the finite rows, drop overflow beyond the cap.
        pool = jnp.concatenate(
            [buf, jnp.where(surv[:, None], p, jnp.inf)], axis=0)
        live = jnp.isfinite(pool[:, 0])
        key = jnp.where(live, jnp.arange(pool.shape[0]), pool.shape[0])
        buf = pool[jnp.argsort(key)[:JAX_PARETO_MAX_FRONT]]
        return buf, surv

    buf0 = jnp.full((JAX_PARETO_MAX_FRONT, d), jnp.inf, jnp.float32)
    _, surv = jax.lax.scan(step, buf0, chunks)
    return jnp.zeros(pts.shape[0], bool).at[order].set(surv.reshape(-1))


@functools.lru_cache(maxsize=64)
def _jax_pareto_fn(gemms, wl_scalars, c: DeviceConstants, objectives: tuple):
    """Jit-cached fused frontier-candidate mask for one workload.

    Metrics + feasibility as in `_jax_search_fn`, then the shared
    `_pareto_scan_mask` dominance pass. Constraints stay a dynamic operand;
    only the (G,) candidate mask and the feasible count leave the device.
    """
    import jax
    import jax.numpy as jnp

    gemm_arr = jnp.asarray(np.asarray(gemms, np.int64))

    def fn(cols, valid, cons):
        n_t, n_c, n_h, n_v, n_l = (cols[i] for i in range(5))
        energy, latency, util = eval_wload_arrays(
            n_t, n_c, n_h, n_v, n_l, gemm_arr, *wl_scalars[:3],
            wl_scalars[3], c, xp=jnp)
        area, power = eval_hw(n_t, n_c, n_h, n_v, n_l, wl_scalars[3], c,
                              xp=jnp)
        ok = (valid & (area < cons[0]) & (power < cons[1])
              & (energy < cons[2]) & (latency < cons[3]))
        vals = {"area": area, "power": power, "energy": energy,
                "latency": latency, "util": util, "edp": energy * latency}
        objs = [jnp.where(ok, vals[k].astype(jnp.float32), jnp.inf)
                for k in objectives]
        return _pareto_scan_mask(objs), jnp.sum(ok)

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Materialized-grid drivers: one chunked loop per objective
#
# A (G, 5) grid streams through the engines in host-side chunks of
# `chunk_size=` candidates (None: the whole grid as a single chunk, the
# one-shot sweep), carrying a running argmin (EDP mode) or a bounded running
# frontier (pareto mode) across chunks — no full (4, G) metrics array ever
# has to be resident at once. One chunk table per objective
# (EDP_CHUNK_ENGINES, PARETO_CHUNK_ENGINES) selects the engine; every chunk
# evaluator is handed the carry, and only pallas folds it into its launch.
# `shard=` fans each chunk's evaluation out over a 1-D candidate-axis device
# mesh with shard_map (jax/pallas engines; the host engines split the chunk
# the same way so every backend exercises the identical reduction). Both
# knobs are exact: any (shard, chunk_size) setting returns byte-identical
# results to a single unsharded chunk, which tests/test_sharded_search.py
# enforces per engine x objective.
# ---------------------------------------------------------------------------

def _iter_chunks(grid, chunk_size: int):
    for s in range(0, len(grid), chunk_size):
        yield grid[s:s + chunk_size]


def _host_shards(chunk, shard):
    """Contiguous split of a chunk for the host (python/numpy) engines —
    the simulated analogue of the device fan-out, so the cross-shard
    reduction path is identical on every backend."""
    if not shard or int(shard) <= 1 or len(chunk) == 0:
        return [chunk]
    return np.array_split(chunk, min(int(shard), len(chunk)))


def merge_running_best(carry, candidate):
    """Cross-chunk/shard running-argmin reduction over (row, edp) pairs.

    Strict-< replacement: exact EDP ties keep the incumbent, which arrived
    from an earlier chunk/shard and therefore has the lower global grid
    index — composing this merge over any partition of the grid reproduces
    a single chunk's first-hit argmin rule exactly.
    """
    row, edp = candidate
    if row is not None and edp < carry[1]:
        return (row, edp)
    return carry


def _edp_chunk_python(chunk, wl, constraints, c, hierarchical, interpret,
                      shard, carry):
    best = (None, float("inf"))
    nf = n_wl = 0
    for part in _host_shards(chunk, shard):
        r = _sequential_search(part, wl, constraints, prune=hierarchical,
                               collect=False, c=c, edp_init=float("inf"))
        nf += r.n_feasible
        n_wl += r.n_workload_evals
        row = None if r.best_cfg is None else r.best_cfg.as_array()
        best = merge_running_best(best, (row, r.edp))
    return best[0], best[1], nf, n_wl


def _edp_chunk_numpy(chunk, wl, constraints, c, hierarchical, interpret,
                     shard, carry):
    best = (None, float("inf"))
    nf = n_wl = 0
    for part in _host_shards(chunk, shard):
        sub, nw = _prefiltered(part, wl, constraints, c, hierarchical)
        n_wl += nw
        if len(sub) == 0:
            continue
        m = evaluate_grid(sub, wl, c, np)
        ok = np.asarray(constraints.satisfied(m["area"], m["power"],
                                              m["energy"], m["latency"]))
        nf += int(ok.sum())
        if not ok.any():
            continue
        edp = np.where(ok, np.asarray(m["edp"]), np.inf)
        i = int(np.argmin(edp))
        best = merge_running_best(best, (sub[i], float(edp[i])))
    return best[0], best[1], nf, n_wl


def _padded_candidate_cols(sub, multiple: int):
    """((5, n_pad) float32 cols, (n_pad,) bool valid mask) with the
    candidate axis padded to a `multiple` multiple — all-ones padding
    configs (valid model inputs, no div-by-zero), masked invalid. The
    single source of padding semantics for the jax shard/stream paths."""
    n = len(sub)
    pad = (-n) % multiple
    cols = np.ones((5, n + pad), np.float32)
    cols[:, :n] = sub.T
    valid = np.zeros(n + pad, bool)
    valid[:n] = True
    return cols, valid


def _assert_candidate_spec(shape, k: int):
    """The candidate axis is padded to a k-multiple before every shard_map
    launch, so the spec can never degrade; assert rather than carry an
    untestable replicated-fallback path."""
    from repro.parallel.sharding import (CANDIDATE_AXIS, candidate_spec,
                                         sanitize_spec)
    spec = candidate_spec(2, 1)
    assert sanitize_spec(shape, spec, {CANDIDATE_AXIS: k}) == spec


@functools.lru_cache(maxsize=64)
def _jax_sharded_fn(fn, k: int, mode: str):
    """Jit-cached shard_map wrapper of a fused jax sweep over a k-shard
    candidate mesh. mode "argmin": each shard returns its (argmin, EDP,
    feasible count); mode "mask": its (candidate mask, feasible count).
    Keyed on the inner jitted fn (itself lru-cached, so identity is
    stable) + mesh size — streamed chunk launches reuse one executable."""
    import jax

    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_candidate_mesh
    from repro.parallel.sharding import candidate_spec

    mesh = make_candidate_mesh(k)
    spec2, spec1 = candidate_spec(2, 1), candidate_spec(1, 0)

    if mode == "argmin":
        def body(cols_l, valid_l, cons):
            i, e, f = fn(cols_l, valid_l, cons)
            return i[None], e[None], f[None]
        out_specs = (spec1, spec1, spec1)
    else:
        def body(cols_l, valid_l, cons):
            mask, f = fn(cols_l, valid_l, cons)
            return mask, f[None]
        out_specs = (spec1, spec1)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(spec2, spec1, P(None)),
                                 out_specs=out_specs, check_vma=False))


def _jax_sharded_argmin(fn, sub, cons_vec, shard):
    """shard_map fan-out of the fused jax argmin over the candidate mesh.

    Each shard reduces its slice to (local argmin, its EDP, feasible
    count); the host picks the min-EDP shard (earliest shard on exact ties
    — shards are contiguous grid slices, so that is the global first-hit).
    Returns (global_idx or -1, edp, n_feasible).
    """
    from repro.launch.mesh import make_candidate_mesh

    k = make_candidate_mesh(shard).devices.size
    cols, valid = _padded_candidate_cols(sub, k)
    _assert_candidate_spec(cols.shape, k)
    f = _jax_sharded_fn(fn, k, "argmin")
    i_s, e_s, f_s = (np.asarray(x) for x in f(cols, valid, cons_vec))
    nf = int(f_s.sum())
    if nf == 0:
        return -1, float("inf"), 0
    s = int(np.lexsort((np.arange(k), e_s))[0])
    return s * (cols.shape[1] // k) + int(i_s[s]), float(e_s[s]), nf


def _edp_chunk_jax(chunk, wl, constraints, c, hierarchical, interpret,
                   shard, carry):
    import jax.numpy as jnp
    sub, n_wl = _prefiltered(chunk, wl, constraints, c, hierarchical)
    if len(sub) == 0:
        return None, float("inf"), 0, n_wl
    gemms, scalars = workload_statics(wl, c)
    fn = _jax_search_fn(gemms, scalars, c)
    cons_vec = _constraint_vec(constraints)
    if shard is not None and int(shard) > 1:
        i, e, nf = _jax_sharded_argmin(fn, sub, cons_vec, shard)
        return (sub[i] if i >= 0 else None), e, nf, n_wl
    i, e, nf = fn(jnp.asarray(sub.T, jnp.float32), jnp.ones(len(sub), bool),
                  cons_vec)
    nf = int(nf)
    if nf == 0:
        return None, float("inf"), 0, n_wl
    return sub[int(i)], float(e), nf, n_wl


def _edp_chunk_pallas(chunk, wl, constraints, c, hierarchical, interpret,
                      shard, carry):
    """The kernel folds the carried best EDP into its own reduction (the
    carry wins ties), so per-chunk launches compose on-device."""
    from repro.kernels.ops import dse_search_grid
    sub, n_wl = _prefiltered(chunk, wl, constraints, c, hierarchical)
    if len(sub) == 0:
        return None, float("inf"), 0, n_wl
    i, e, nf = dse_search_grid(
        sub, wl, constraints, c, interpret, shard=shard,
        carry_edp=carry[1] if carry[0] is not None else None)
    return (sub[i] if i >= 0 else None), e, nf, n_wl


# Every chunk evaluator: (chunk, wl, constraints, c, hierarchical,
# interpret, shard, carry) -> (best row or None, its EDP, n_feasible, n_wl),
# where `carry` is the running (row, edp) best of the earlier chunks.
EDP_CHUNK_ENGINES = {"python": _edp_chunk_python, "numpy": _edp_chunk_numpy,
                     "jax": _edp_chunk_jax, "pallas": _edp_chunk_pallas}
ENGINES = tuple(sorted(EDP_CHUNK_ENGINES))


def _rt_fp(tag, wl, constraints, engine, c, interpret, shard, chunk_size,
           **extra):
    """Search-signature fingerprint binding a checkpoint directory to one
    exact search. Engine is part of the signature: resume re-runs the tail
    on the same engine the head ran on (degradation within a run is fine —
    engines are byte-identical — but resuming under a different engine=
    is a different campaign)."""
    from repro.kernels.backend import resolve_interpret
    return _fingerprint(tag=tag, wl=wl.name, gemms=wl.gemm_array,
                        act=int(wl.max_act_bytes), cons=repr(constraints),
                        engine=engine, c=repr(c),
                        interpret=resolve_interpret(interpret),
                        shard=shard, chunk=chunk_size, **extra)


def _search_streamed(grid, wl, constraints, engine, hierarchical, c,
                     interpret, shard, chunk_size, rt=None) -> SearchResult:
    """Min-EDP driver over a materialized grid, any engine (one-shot is
    the single-chunk case; `shard=` fans each chunk out)."""
    t0 = time.perf_counter()
    n = len(grid)
    cs = int(chunk_size) if chunk_size else max(n, 1)
    best = (None, float("inf"))
    nf = n_wl = 0
    start = 0
    fp = None
    if rt is not None:
        fp = _rt_fp("edp_stream", wl, constraints, engine, c, interpret,
                    shard, chunk_size, grid=np.ascontiguousarray(grid),
                    hier=bool(hierarchical))
        rec = rt.resume(fp)
        if rec is not None:
            start, st, extra = rec
            best = decode_best_row(st)
            nf, n_wl = int(extra["nf"]), int(extra["n_wl"])
    for u, chunk in enumerate(_iter_chunks(grid, cs)):
        if u < start:
            continue
        # Byte-identical per-engine evaluations of the chunk: the runtime
        # guard falls back along them.
        thunks = {eng: functools.partial(fn, chunk, wl, constraints, c,
                                         hierarchical, interpret, shard, best)
                  for eng, fn in EDP_CHUNK_ENGINES.items()}
        if rt is not None:
            row, e, cf, cw = rt.eval_unit(engine, thunks)
        else:
            row, e, cf, cw = thunks[engine]()
        nf += cf
        n_wl += cw
        best = merge_running_best(best, (row, e))
        if rt is not None:
            rt.unit_done(fp, u, encode_best_row(best),
                         {"nf": nf, "n_wl": n_wl})
    res = _make_result(best[0], nf, wl, c, n, n_wl,
                       time.perf_counter() - t0)
    return rt.annotate(res) if rt is not None else res


def _pareto_chunk_python(chunk, wl, constraints, c, hierarchical, interpret,
                         shard, objectives, carry_rows):
    cands = []
    nf = n_wl = 0
    for part in _host_shards(chunk, shard):
        rows, f, nw = _sequential_pareto(part, wl, constraints, hierarchical,
                                         c, objectives)
        cands += list(rows)
        nf += f
        n_wl += nw
    return np.asarray(cands, np.int64).reshape(-1, 5), nf, n_wl, 0


def _pareto_chunk_numpy(chunk, wl, constraints, c, hierarchical, interpret,
                        shard, objectives, carry_rows):
    cands = []
    nf = n_wl = 0
    for part in _host_shards(chunk, shard):
        sub, nw = _prefiltered(part, wl, constraints, c, hierarchical)
        n_wl += nw
        if len(sub) == 0:
            continue
        m = evaluate_grid(sub, wl, c, np)
        front, _, f = _pareto_from_rows(sub, wl, constraints, c, objectives,
                                        m=m)
        nf += f
        cands.append(front)
    if not cands:
        return np.zeros((0, 5), np.int64), nf, n_wl, 0
    return np.concatenate(cands, axis=0), nf, n_wl, 0


def _jax_sharded_pareto_mask(fn, sub, cons_vec, shard):
    """shard_map fan-out of the jit frontier-candidate mask: each shard
    reduces its slice to a shard-local non-dominated mask (a superset of
    that slice's global-frontier members, so the union stays exact after
    the float64 refinement). Returns (mask over sub, n_feasible)."""
    from repro.launch.mesh import make_candidate_mesh

    k = make_candidate_mesh(shard).devices.size
    cols, valid = _padded_candidate_cols(sub, k * JAX_PARETO_CHUNK)
    _assert_candidate_spec(cols.shape, k)
    f = _jax_sharded_fn(fn, k, "mask")
    mask, f_s = (np.asarray(x) for x in f(cols, valid, cons_vec))
    return mask[:len(sub)], int(f_s.sum())


def _pareto_chunk_jax(chunk, wl, constraints, c, hierarchical, interpret,
                      shard, objectives, carry_rows):
    import jax.numpy as jnp
    sub, n_wl = _prefiltered(chunk, wl, constraints, c, hierarchical)
    if len(sub) == 0:
        return np.zeros((0, 5), np.int64), 0, n_wl, 0
    gemms, scalars = workload_statics(wl, c)
    fn = _jax_pareto_fn(gemms, scalars, c, objectives)
    cons_vec = _constraint_vec(constraints)
    if shard is not None and int(shard) > 1:
        mask, nf = _jax_sharded_pareto_mask(fn, sub, cons_vec, shard)
        return sub[mask], nf, n_wl, 0
    cols, valid = _padded_candidate_cols(sub, JAX_PARETO_CHUNK)
    mask, nf = fn(jnp.asarray(cols), jnp.asarray(valid), cons_vec)
    return sub[np.asarray(mask)[:len(sub)]], int(nf), n_wl, 0


def _pallas_front_points(rows, wl, c, interpret, objectives):
    """Objective points of `rows` in the pallas kernel's own float32 metric
    space (the dse_eval kernel runs the identical `_config_metrics`
    pipeline), so the carried-front prune compares like with like."""
    from repro.kernels.ops import dse_eval_grid
    m = dse_eval_grid(rows, wl, c, interpret).astype(np.float32)
    vals = {"area": m[:, 0], "power": m[:, 1], "energy": m[:, 2],
            "latency": m[:, 3], "edp": m[:, 2] * m[:, 3]}
    return np.stack([vals[k] for k in objectives], axis=1)


def _pareto_chunk_pallas(chunk, wl, constraints, c, hierarchical, interpret,
                         shard, objectives, carry_rows):
    """The kernel drops candidates a carried front point strictly
    dominates, so per-chunk emissions stay frontier-sized."""
    from repro.kernels.ops import dse_pareto_multi
    sub, n_wl = _prefiltered(chunk, wl, constraints, c, hierarchical)
    if len(sub) == 0:
        return np.zeros((0, 5), np.int64), 0, n_wl, 0
    carry_points = None
    if len(carry_rows):
        carry_points = [_pallas_front_points(carry_rows, wl, c, interpret,
                                             objectives)]
    (idx, nf, n_over), = dse_pareto_multi(sub, [wl], [constraints], c,
                                          interpret, objectives=objectives,
                                          shard=shard,
                                          carry_points=carry_points)
    return sub[idx], nf, n_wl, n_over


# Every chunk evaluator: (chunk, wl, constraints, c, hierarchical,
# interpret, shard, objectives, carry_rows) -> (candidate rows, n_feasible,
# n_wl, n_overflow), where `carry_rows` is the running front of the earlier
# chunks; the host engines report no overflow.
PARETO_CHUNK_ENGINES = {"python": _pareto_chunk_python,
                        "numpy": _pareto_chunk_numpy,
                        "jax": _pareto_chunk_jax,
                        "pallas": _pareto_chunk_pallas}
PARETO_ENGINES = tuple(sorted(PARETO_CHUNK_ENGINES))


def _empty_run_state():
    return (np.zeros((0, 5), np.int64),
            {k: np.zeros(0, np.float64) for k in REPORT_METRICS})


@traced("search.refine")
def _merge_running_front(run_rows, run_met, cand_rows, wl, constraints, c,
                         objectives):
    """Fold one chunk/shard's candidate rows into the bounded running
    frontier: refine the candidates through the float64 reference model,
    then keep the non-dominated union (`pareto.merge_fronts` — exact ties
    kept, so duplicate grid rows survive any chunking like they survive a
    single chunk). The carried state stays frontier-sized: a strictly
    dominated point can never re-enter, so dropping it is exact."""
    from .pareto import merge_fronts
    front_c, met_c, _ = _pareto_from_rows(cand_rows, wl, constraints, c,
                                          objectives)
    if len(front_c) == 0:
        return run_rows, run_met
    d = len(objectives)
    pts_a = (np.stack([run_met[k] for k in objectives], axis=1)
             if len(run_rows) else np.zeros((0, d)))
    pts_b = np.stack([met_c[k] for k in objectives], axis=1)
    keep = merge_fronts(pts_a, pts_b)
    rows = np.concatenate([run_rows, front_c], axis=0)[keep]
    met = {k: np.concatenate([run_met[k], met_c[k]])[keep]
           for k in REPORT_METRICS}
    return rows, met


def _pareto_streamed(grid, wl, constraints, engine, hierarchical, c,
                     interpret, objectives, shard, chunk_size, rt=None
                     ) -> ParetoResult:
    """Frontier driver over a materialized grid, any engine (one-shot is
    the single-chunk case; `shard=` fans each chunk out)."""
    t0 = time.perf_counter()
    n = len(grid)
    cs = int(chunk_size) if chunk_size else max(n, 1)
    run_rows, run_met = _empty_run_state()
    nf = n_wl = n_over = 0
    start = 0
    fp = None
    if rt is not None:
        fp = _rt_fp("pareto_stream", wl, constraints, engine, c, interpret,
                    shard, chunk_size, grid=np.ascontiguousarray(grid),
                    hier=bool(hierarchical), objectives=tuple(objectives))
        rec = rt.resume(fp)
        if rec is not None:
            start, st, extra = rec
            run_rows, run_met = decode_front(st, REPORT_METRICS)
            nf, n_wl = int(extra["nf"]), int(extra["n_wl"])
            n_over = int(extra["n_over"])
    for u, chunk in enumerate(_iter_chunks(grid, cs)):
        if u < start:
            continue
        thunks = {eng: functools.partial(fn, chunk, wl, constraints, c,
                                         hierarchical, interpret, shard,
                                         objectives, run_rows)
                  for eng, fn in PARETO_CHUNK_ENGINES.items()}
        if rt is not None:
            cand, cf, cw, co = rt.eval_unit(engine, thunks)
        else:
            cand, cf, cw, co = thunks[engine]()
        nf += cf
        n_wl += cw
        n_over += co
        if len(cand):
            run_rows, run_met = _merge_running_front(
                run_rows, run_met, cand, wl, constraints, c, objectives)
        if rt is not None:
            rt.unit_done(fp, u, encode_front(run_rows, run_met,
                                             REPORT_METRICS),
                         {"nf": nf, "n_wl": n_wl, "n_over": n_over})
    with span("search.refine"):
        front, met, _ = _pareto_from_rows(run_rows, wl, constraints, c,
                                          objectives, m=run_met)
    res = ParetoResult(front=front, metrics=met, objectives=objectives,
                       n_evaluated=n, n_feasible=nf, n_workload_evals=n_wl,
                       wall_time_s=time.perf_counter() - t0,
                       n_overflow=n_over)
    return rt.annotate(res) if rt is not None else res


# ---------------------------------------------------------------------------
# Factorized product-space engines (factorized=True)
#
# When the candidate grid is a Cartesian product of per-parameter candidate
# sets (every paper grid is), `factorized=True` evaluates it from per-GEMM
# axis factor tables (core.factorized) instead of per-point model runs:
# the ceil-division factors of gemm_cycles cost O(|T||H| + |V| + |C||L|)
# work per GEMM, combined over the space by broadcasted outer products —
# and the (G, 5) grid is never materialized on the host at all (the numpy
# engine combines tables, the jax engines bake the axes into the jit, the
# pallas kernels reconstruct candidate rows on device from a chunk base
# offset + the per-axis candidate vectors). Because the combine replays the
# per-config float ops on the same values in the same order, every
# factorized engine is *byte-identical* to its unfactorized counterpart —
# winners, frontiers, n_feasible and all — and `shard=` / `chunk_size=`
# compose exactly as for materialized grids (index spans instead of row
# chunks). `hierarchical=True` is rejected: compacting survivors would
# break the product structure, and the factorized combine already prices
# the area/power terms at axis-table cost.
# ---------------------------------------------------------------------------

FACTORIZED_ENGINES = ("numpy", "jax", "pallas")


def _factorized_space(space, grid, n_z, engine, hierarchical
                      ) -> FactorizedSpace:
    if engine not in FACTORIZED_ENGINES:
        raise ValueError(f"factorized=True supports engines "
                         f"{FACTORIZED_ENGINES}, not {engine!r}")
    if grid is not None:
        raise ValueError("factorized=True evaluates a product space; pass "
                         "the candidate sets via space= (or n_z=), not a "
                         "materialized grid")
    if hierarchical:
        raise ValueError("hierarchical=True is incompatible with "
                         "factorized=True: survivor compaction would break "
                         "the product structure (the factorized combine "
                         "already evaluates area/power at axis-table cost)")
    fspace = (FactorizedSpace.full(n_z) if space is None
              else FactorizedSpace.from_space(space))
    if engine == "pallas" and fspace.size > 1 << 24:
        raise ValueError(
            f"the factorized pallas engine addresses configs by float32 "
            f"global index, exact only below 2**24 points; this space has "
            f"{fspace.size}. Use the jax or numpy factorized engines "
            f"(exact integer indices) for spaces this large.")
    return fspace


def _span_parts(start: int, n: int, shard):
    """Contiguous sub-spans of [start, start + n) for the host engines'
    simulated shard fan-out — same sizes as np.array_split, mirroring
    `_host_shards`."""
    if not shard or int(shard) <= 1 or n == 0:
        return [(start, start + n)]
    k = min(int(shard), n)
    base, rem = divmod(n, k)
    parts, s = [], start
    for i in range(k):
        size = base + (1 if i < rem else 0)
        parts.append((s, s + size))
        s += size
    return parts


def _np_factorized_metrics(fspace, wl, c, start, stop):
    """Float64 factorized metrics for an index span (the whole space goes
    through the index-free broadcast combine)."""
    if (start, stop) == (0, fspace.size):
        return factorized_evaluate_grid(fspace, wl, c)
    return factorized_evaluate_grid(
        fspace, wl, c, idx=np.arange(start, stop, dtype=np.int64))


def _merge_best_indexed(best, cand):
    """Running argmin over (global index, edp) pairs: strictly lower EDP
    wins, exact EDP ties go to the lower flat-space index — the first-hit
    rule stated over indices instead of arrival order, so the bound-guided
    traversal (which may visit slabs out of flat order) composes exactly
    like the ascending span streams. Index -1 means 'no candidate'."""
    gi, ge = cand
    if gi < 0:
        return best
    bi, be = best
    if bi < 0 or ge < be or (ge == be and gi < bi):
        return cand
    return best


def _edp_span_numpy_factorized(fspace, wl, constraints, c, start, n, shard):
    """(best gidx or -1, its engine EDP, n_feasible, n) over an index span."""
    best = (-1, float("inf"))
    nf = 0
    for s0, s1 in _span_parts(start, n, shard):
        m = _np_factorized_metrics(fspace, wl, c, s0, s1)
        ok = np.asarray(constraints.satisfied(m["area"], m["power"],
                                              m["energy"], m["latency"]))
        nf += int(ok.sum())
        if not ok.any():
            continue
        edp = np.where(ok, np.asarray(m["edp"]), np.inf)
        i = int(np.argmin(edp))
        best = _merge_best_indexed(best, (s0 + i, float(edp[i])))
    return best[0], best[1], nf, n


def _pareto_idx_numpy(fspace, wl, constraints, c, idx_arr, shard,
                      objectives):
    """Frontier candidates (gidx array) + feasible count over an explicit
    ascending flat-index vector, float64 metrics, split per host shard —
    the gather-form work unit of the bound-guided numpy engine."""
    cands = []
    nf = 0
    for part in _host_shards(np.asarray(idx_arr, np.int64), shard):
        if len(part) == 0:
            continue
        m = factorized_evaluate_grid(fspace, wl, c, idx=part)
        ok = np.asarray(constraints.satisfied(m["area"], m["power"],
                                              m["energy"], m["latency"]))
        f = int(ok.sum())
        nf += f
        if f == 0:
            continue
        pts = np.stack([np.asarray(m[k], np.float64)[ok]
                        for k in objectives], axis=1)
        cands.append(part[ok][pareto_mask(pts)])
    if not cands:
        return np.zeros(0, np.int64), nf
    return np.concatenate(cands), nf


def _pareto_span_numpy_factorized(fspace, wl, constraints, c, start, n,
                                  shard, objectives):
    """(cand gidx array, n_feasible, n) over a contiguous index span (the
    whole-space span takes the index-free broadcast combine)."""
    cands = []
    nf = 0
    for s0, s1 in _span_parts(start, n, shard):
        m = _np_factorized_metrics(fspace, wl, c, s0, s1)
        ok = np.asarray(constraints.satisfied(m["area"], m["power"],
                                              m["energy"], m["latency"]))
        f = int(ok.sum())
        nf += f
        if f == 0:
            continue
        pts = np.stack([np.asarray(m[k], np.float64)[ok]
                        for k in objectives], axis=1)
        cands.append(s0 + np.where(ok)[0][pareto_mask(pts)])
    if not cands:
        return np.zeros(0, np.int64), nf, n
    return np.concatenate(cands), nf, n


@functools.lru_cache(maxsize=64)
def _jax_factorized_full_fn(axes, gemms, wl_scalars, c: DeviceConstants,
                            objectives):
    """Jit-cached factorized sweep of the *whole* product space (axes baked
    static, so the factor tables constant-fold). objectives=None: fused
    (argmin, EDP, n_feasible); otherwise the frontier-candidate mask."""
    import jax
    import jax.numpy as jnp

    from .factorized import evaluate_space

    gemm_arr = np.asarray(gemms, np.int64)
    size = math.prod(len(a) for a in axes)

    def fn(cons):
        m = evaluate_space(axes, gemm_arr, *wl_scalars[:3], wl_scalars[3],
                           c, xp=jnp, col_dtype=np.float32)
        ok = ((m["area"] < cons[0]) & (m["power"] < cons[1])
              & (m["energy"] < cons[2]) & (m["latency"] < cons[3]))
        if objectives is None:
            edp = jnp.where(ok, m["edp"], jnp.inf)
            i = jnp.argmin(edp)
            return i, edp[i], jnp.sum(ok)
        objs = [jnp.where(ok, m[k].astype(jnp.float32), jnp.inf)
                for k in objectives]
        pad = (-size) % JAX_PARETO_CHUNK
        if pad:
            objs = [jnp.concatenate([o, jnp.full(pad, jnp.inf, o.dtype)])
                    for o in objs]
        return _pareto_scan_mask(objs)[:size], jnp.sum(ok)

    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _jax_factorized_span_fn(axes, gemms, wl_scalars, c: DeviceConstants,
                            objectives):
    """Jit-cached factorized sweep of a dynamic index span: mixed-radix
    decode + table gathers (bit-identical per element to the full-space
    broadcast combine, so chunked/sharded launches compose exactly)."""
    import jax
    import jax.numpy as jnp

    from .factorized import evaluate_space

    gemm_arr = np.asarray(gemms, np.int64)

    def fn(idx, valid, cons):
        m = evaluate_space(axes, gemm_arr, *wl_scalars[:3], wl_scalars[3],
                           c, xp=jnp, col_dtype=np.float32, idx=idx)
        ok = (valid & (m["area"] < cons[0]) & (m["power"] < cons[1])
              & (m["energy"] < cons[2]) & (m["latency"] < cons[3]))
        if objectives is None:
            edp = jnp.where(ok, m["edp"], jnp.inf)
            i = jnp.argmin(edp)
            return i, edp[i], jnp.sum(ok)
        objs = [jnp.where(ok, m[k].astype(jnp.float32), jnp.inf)
                for k in objectives]
        return _pareto_scan_mask(objs), jnp.sum(ok)

    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _jax_factorized_sharded_fn(fn, k: int, mode: str):
    """shard_map wrapper of a factorized span fn over the candidate mesh:
    the (n,) index vector and validity mask shard, constraints replicate
    (the 1-D analogue of `_jax_sharded_fn`)."""
    import jax

    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_candidate_mesh
    from repro.parallel.sharding import candidate_spec

    mesh = make_candidate_mesh(k)
    spec1 = candidate_spec(1, 0)

    if mode == "argmin":
        def body(idx_l, valid_l, cons):
            i, e, f = fn(idx_l, valid_l, cons)
            return i[None], e[None], f[None]
        out_specs = (spec1, spec1, spec1)
    else:
        def body(idx_l, valid_l, cons):
            mask, f = fn(idx_l, valid_l, cons)
            return mask, f[None]
        out_specs = (spec1, spec1)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(spec1, spec1, P(None)),
                                 out_specs=out_specs, check_vma=False))


def _padded_idx_operands(idx_arr, multiple: int):
    """((n_pad,) int32 global indices, (n_pad,) validity) for an arbitrary
    ascending flat-index vector, padded to a `multiple` multiple with the
    unit count bucketed to a power of two (index vectors of the
    bound-guided leaves vary in length; bucketing bounds the jitted span
    fn to O(log n) distinct shapes, mirroring `_bucketed_cols`). Padding
    lanes repeat the last real index — always decodable — and are retired
    by the validity mask."""
    import jax.numpy as jnp
    idx_arr = np.asarray(idx_arr, np.int32)
    n = len(idx_arr)
    units = max(1, -(-n // multiple))
    units = 1 << (units - 1).bit_length()
    n_pad = units * multiple
    out = np.full(n_pad, idx_arr[-1] if n else 0, np.int32)
    out[:n] = idx_arr
    valid = np.zeros(n_pad, bool)
    valid[:n] = True
    return jnp.asarray(out), jnp.asarray(valid)


def _jax_factorized_idx_argmin(fspace, wl, constraints, c, idx_arr, shard):
    """Fused jax argmin over an explicit ascending flat-index vector (the
    gather-form work unit — contiguous spans and bound-guided slab leaves
    alike). Returns (best gidx or -1, its EDP, n_feasible)."""
    import jax.numpy as jnp
    gemms, scalars = workload_statics(wl, c)
    cons_vec = _constraint_vec(constraints)
    fn = _jax_factorized_span_fn(fspace.axes, gemms, scalars, c, None)
    sharded = shard is not None and int(shard) > 1
    if sharded:
        from repro.launch.mesh import make_candidate_mesh
        k = make_candidate_mesh(shard).devices.size
        idx, valid = _padded_idx_operands(idx_arr, k)
        f = _jax_factorized_sharded_fn(fn, k, "argmin")
        i_s, e_s, f_s = (np.asarray(x) for x in f(idx, valid, cons_vec))
        nf = int(f_s.sum())
        if nf == 0:
            return -1, float("inf"), 0
        s = int(np.lexsort((np.arange(k), e_s))[0])
        gi = int(np.asarray(idx)[s * (len(idx) // k) + int(i_s[s])])
        return gi, float(e_s[s]), nf
    idx, valid = _padded_idx_operands(idx_arr, 1)
    i, e, nf = fn(idx, valid, cons_vec)
    nf = int(nf)
    if nf == 0:
        return -1, float("inf"), 0
    return int(np.asarray(idx)[int(i)]), float(e), nf


def _edp_span_jax_factorized(fspace, wl, constraints, c, start, n, shard):
    """(best gidx or -1, its engine EDP, n_feasible, n) over an index span."""
    gemms, scalars = workload_statics(wl, c)
    cons_vec = _constraint_vec(constraints)
    sharded = shard is not None and int(shard) > 1
    if (start, n) == (0, fspace.size) and not sharded:
        fn = _jax_factorized_full_fn(fspace.axes, gemms, scalars, c, None)
        i, e, nf = fn(cons_vec)
        nf = int(nf)
        return (int(i) if nf > 0 else -1), float(e), nf, n
    idx = np.arange(start, start + n, dtype=np.int32)
    gi, e, nf = _jax_factorized_idx_argmin(fspace, wl, constraints, c, idx,
                                           shard)
    return gi, e, nf, n


def _edp_idx_numpy(fspace, wl, constraints, c, idx_arr, shard):
    """(best gidx or -1, EDP, n_feasible) over an explicit ascending
    flat-index vector, float64 metrics — the numpy bound-guided leaf."""
    best = (-1, float("inf"))
    nf = 0
    for part in _host_shards(np.asarray(idx_arr, np.int64), shard):
        if len(part) == 0:
            continue
        m = factorized_evaluate_grid(fspace, wl, c, idx=part)
        ok = np.asarray(constraints.satisfied(m["area"], m["power"],
                                              m["energy"], m["latency"]))
        nf += int(ok.sum())
        if not ok.any():
            continue
        edp = np.where(ok, np.asarray(m["edp"]), np.inf)
        i = int(np.argmin(edp))
        best = _merge_best_indexed(best, (int(part[i]), float(edp[i])))
    return best[0], best[1], nf


def _jax_factorized_idx_mask(fspace, wl, constraints, c, idx_arr, shard,
                             objectives):
    """(cand gidx array, n_feasible) over an explicit ascending flat-index
    vector via the jitted frontier-candidate mask."""
    gemms, scalars = workload_statics(wl, c)
    cons_vec = _constraint_vec(constraints)
    fn = _jax_factorized_span_fn(fspace.axes, gemms, scalars, c, objectives)
    sharded = shard is not None and int(shard) > 1
    if sharded:
        from repro.launch.mesh import make_candidate_mesh
        k = make_candidate_mesh(shard).devices.size
        idx, valid = _padded_idx_operands(idx_arr, k * JAX_PARETO_CHUNK)
        f = _jax_factorized_sharded_fn(fn, k, "mask")
        mask, f_s = (np.asarray(x) for x in f(idx, valid, cons_vec))
        nf = int(f_s.sum())
    else:
        idx, valid = _padded_idx_operands(idx_arr, JAX_PARETO_CHUNK)
        mask, nf = fn(idx, valid, cons_vec)
        mask, nf = np.asarray(mask), int(nf)
    # Padding lanes are invalid, hence infeasible, hence never masked in.
    return np.asarray(idx)[mask].astype(np.int64), nf


def _pareto_span_jax_factorized(fspace, wl, constraints, c, start, n, shard,
                                objectives):
    """(cand gidx array, n_feasible, n) over a contiguous index span."""
    gemms, scalars = workload_statics(wl, c)
    cons_vec = _constraint_vec(constraints)
    sharded = shard is not None and int(shard) > 1
    if (start, n) == (0, fspace.size) and not sharded:
        fn = _jax_factorized_full_fn(fspace.axes, gemms, scalars, c,
                                     objectives)
        mask, nf = fn(cons_vec)
        return np.nonzero(np.asarray(mask))[0], int(nf), n
    idx = np.arange(start, start + n, dtype=np.int32)
    cand, nf = _jax_factorized_idx_mask(fspace, wl, constraints, c, idx,
                                        shard, objectives)
    return cand, nf, n


def _iter_spans(size: int, chunk_size):
    cs = int(chunk_size) if chunk_size else max(size, 1)
    for s in range(0, size, cs):
        yield s, min(cs, size - s)


def _edp_span_thunks(fspace, wl, constraints, c, interpret, shard, s, n,
                     best):
    """Per-engine factorized EDP span evaluations, normalized to
    (gidx or -1/CARRY_IDX, edp, n_feasible) for the runtime guard."""
    def pallas():
        from repro.kernels.ops import dse_search_multi_factorized
        carry = best[1] if best[0] >= 0 else None
        bi, be, bn = dse_search_multi_factorized(
            fspace, s, n, [wl], [constraints], c, interpret, shard=shard,
            carry_edp=None if carry is None else [carry])
        return bi[0], be[0], bn[0]

    def jax_():
        gi, e, cf, _ = _edp_span_jax_factorized(fspace, wl, constraints, c,
                                                s, n, shard)
        return gi, e, cf

    def numpy_():
        gi, e, cf, _ = _edp_span_numpy_factorized(fspace, wl, constraints,
                                                  c, s, n, shard)
        return gi, e, cf

    return {"pallas": pallas, "jax": jax_, "numpy": numpy_}


def _pareto_span_thunks(fspace, wl, constraints, c, interpret, objectives,
                        shard, s, n, run_rows):
    """Per-engine factorized frontier span evaluations, normalized to
    (cand gidx array, n_feasible, n_overflow)."""
    def pallas():
        from repro.kernels.ops import dse_pareto_multi_factorized
        carry_points = None
        if len(run_rows):
            carry_points = [_pallas_front_points(run_rows, wl, c, interpret,
                                                 objectives)]
        (idx, cf, n_over), = dse_pareto_multi_factorized(
            fspace, s, n, [wl], [constraints], c, interpret,
            objectives=objectives, shard=shard, carry_points=carry_points)
        return idx, cf, n_over

    def jax_():
        idx, cf, _ = _pareto_span_jax_factorized(fspace, wl, constraints, c,
                                                 s, n, shard, objectives)
        return idx, cf, 0

    def numpy_():
        idx, cf, _ = _pareto_span_numpy_factorized(
            fspace, wl, constraints, c, s, n, shard, objectives)
        return idx, cf, 0

    return {"pallas": pallas, "jax": jax_, "numpy": numpy_}


def _search_factorized(fspace, wl, constraints, engine, c, interpret,
                       shard, chunk_size, rt=None) -> SearchResult:
    """Factorized min-EDP driver (one-shot is the single-span case)."""
    t0 = time.perf_counter()
    best = (-1, float("inf"))
    nf = n_wl = 0
    start = 0
    fp = None
    if rt is not None:
        fp = _rt_fp("edp_fact", wl, constraints, engine, c, interpret,
                    shard, chunk_size, axes=fspace.axes)
        rec = rt.resume(fp)
        if rec is not None:
            start, st, extra = rec
            best = decode_best_indexed(st)
            nf, n_wl = int(extra["nf"]), int(extra["n_wl"])
    for u, (s, n) in enumerate(_iter_spans(fspace.size, chunk_size)):
        if u < start:
            continue
        thunks = _edp_span_thunks(fspace, wl, constraints, c, interpret,
                                  shard, s, n, best)
        if rt is not None:
            gi, e, cf = rt.eval_unit(engine, thunks)
        else:
            gi, e, cf = thunks[engine]()
        nf += cf
        n_wl += n
        best = _merge_best_indexed(best, (gi, e))
        if rt is not None:
            rt.unit_done(fp, u, encode_best_indexed(best),
                         {"nf": nf, "n_wl": n_wl})
    row = fspace.decode([best[0]])[0] if best[0] >= 0 else None
    res = _make_result(row, nf, wl, c, fspace.size, n_wl,
                       time.perf_counter() - t0)
    return rt.annotate(res) if rt is not None else res


def _pareto_factorized(fspace, wl, constraints, engine, c, interpret,
                       objectives, shard, chunk_size, rt=None
                       ) -> ParetoResult:
    """Factorized frontier driver (one-shot is the single-span case)."""
    t0 = time.perf_counter()
    run_rows, run_met = _empty_run_state()
    nf = n_wl = n_over = 0
    start = 0
    fp = None
    if rt is not None:
        fp = _rt_fp("pareto_fact", wl, constraints, engine, c, interpret,
                    shard, chunk_size, axes=fspace.axes,
                    objectives=tuple(objectives))
        rec = rt.resume(fp)
        if rec is not None:
            start, st, extra = rec
            run_rows, run_met = decode_front(st, REPORT_METRICS)
            nf, n_wl = int(extra["nf"]), int(extra["n_wl"])
            n_over = int(extra["n_over"])
    for u, (s, n) in enumerate(_iter_spans(fspace.size, chunk_size)):
        if u < start:
            continue
        thunks = _pareto_span_thunks(fspace, wl, constraints, c, interpret,
                                     objectives, shard, s, n, run_rows)
        if rt is not None:
            idx, cf, co = rt.eval_unit(engine, thunks)
        else:
            idx, cf, co = thunks[engine]()
        nf += cf
        n_wl += n
        n_over += co
        if len(idx):
            run_rows, run_met = _merge_running_front(
                run_rows, run_met, fspace.decode(idx), wl, constraints, c,
                objectives)
        if rt is not None:
            rt.unit_done(fp, u, encode_front(run_rows, run_met,
                                             REPORT_METRICS),
                         {"nf": nf, "n_wl": n_wl, "n_over": n_over})
    with span("search.refine"):
        front, met, _ = _pareto_from_rows(run_rows, wl, constraints, c,
                                          objectives, m=run_met)
    res = ParetoResult(front=front, metrics=met, objectives=objectives,
                       n_evaluated=fspace.size, n_feasible=nf,
                       n_workload_evals=n_wl,
                       wall_time_s=time.perf_counter() - t0,
                       n_overflow=n_over)
    return rt.annotate(res) if rt is not None else res


# ---------------------------------------------------------------------------
# Bound-guided branch-and-bound over the factorized space (prune="bound")
#
# The paper's core claim is that a constraint-aware, significance-guided
# search beats exhaustive sweeps; the engines above are fast per point but
# still *touch* every point. `prune="bound"` stops touching them: the
# mixed-radix space is recursively split into slabs — the Alg. 1-most-
# significant axes first, so the bounds that matter (area/power explode in
# N_t, N_c) tighten earliest — and each slab is priced by the admissible
# interval lower bounds of core.factorized.SlabBoundEvaluator (float64,
# replaying the reference model's own float ops, so pruning decisions are
# engine-independent). A slab dies when a constraint lower bound already
# violates its limit, when its EDP lower bound exceeds the running
# incumbent (strictly — ties survive, preserving the first-hit rule), or —
# in pareto mode — when its objective lower-bound corner is strictly
# dominated by a running-frontier point (then every slab point is strictly
# dominated too, transitively safe even if that frontier point is later
# evicted). Surviving slabs at or below the fixed BNB_LEAF size are
# evaluated exactly by the selected engine: numpy/jax through the
# gather-form index evaluators, pallas through one decoded slab launch per
# leaf (the kernels' slab meta masks non-member lanes of the bounding
# span; the carry operands compose the in-leaf chunk splits — no new
# kernel semantics). Winners/frontiers are byte-identical to the unpruned
# factorized sweep (the pruned regions cannot contain a winner or frontier
# member, and the (EDP, index) merge reproduces argmin tie-breaking
# exactly); n_feasible / n_workload_evals count only the evaluated
# survivors, with the skipped volume reported via n_pruned / n_bounds.
# The slab tree, its traversal order and the leaf size are fixed and
# engine-independent, so every engine x (shard, chunk_size) setting visits
# identical survivors and returns identical counters.
#
# Caveat (shared with hierarchical=True and the jax/pallas engines): the
# bounds are float64-admissible; a config whose float32 engine metric sits
# within one ulp of a constraint bound or an exact EDP tie can classify
# differently than under float64 — real design points never ride that
# edge, and the differential tests pin the equivalence on the real grids.
# ---------------------------------------------------------------------------

BNB_LEAF = 4096  # slab size at or below which a surviving slab is evaluated
# exactly. Fixed (not a tuning knob surfaced per call) so the pruning
# schedule — and with it every counter — is identical across engines,
# shards and chunk sizes.


@functools.lru_cache(maxsize=8)
def _bnb_axis_order(c: DeviceConstants = CONSTANTS):
    """Meshgrid-axis indices ranked by Alg. 1 significance (descending),
    ties broken toward the slower-varying (outer) meshgrid axis. The
    calibrated model ranks (n_t, n_c, n_lambda, n_h, n_v) with n_h == n_v
    exactly (the component model is symmetric in them); the outer-axis tie
    break keeps leaf slabs as contiguous as the ranking allows."""
    from .factorized import AXIS_NAMES
    scores = observe_significance(c=c)
    return tuple(sorted(
        range(5),
        key=lambda ax: (-(scores[AXIS_NAMES[ax]].s_area
                          + scores[AXIS_NAMES[ax]].s_power), ax)))


def _bnb_split(ranges, order):
    """Halve the most significant axis that still has width > 1; returns
    (left, right) child slabs in ascending digit order."""
    for ax in order:
        lo, hi = ranges[ax]
        if hi - lo > 1:
            mid = (lo + hi) // 2
            left = ranges[:ax] + ((lo, mid),) + ranges[ax + 1:]
            right = ranges[:ax] + ((mid, hi),) + ranges[ax + 1:]
            return left, right
    return None


BNB_BATCH = 16384  # points per leaf-evaluation batch: the incumbent /
# running frontier refreshes between batches, so later batches prune
# against near-final bounds. Fixed for the same determinism reason as
# BNB_LEAF.

BNB_FINE = 16  # slab size floor of the post-incumbent refinement rounds:
# once a probe batch has seeded the incumbent (or running frontier), the
# remaining leaves are re-split down to this size — the interval corners
# of a fine slab nearly touch, so the objective bounds finally bite.


def _bnb_infeasible_mask(lbs, constraints):
    """(B,) mask of slabs whose constraint *lower* bounds already violate
    a limit — every point inside is infeasible. Used at every pruning
    stage: the constraint bounds tighten dramatically as slabs narrow, so
    re-checking them each refinement round is where most of the space
    dies (the min-corner area/power of a near-singleton slab is almost
    the exact value)."""
    return ((np.asarray(lbs["area"]) >= constraints.area_mm2)
            | (np.asarray(lbs["power"]) >= constraints.power_w)
            | (np.asarray(lbs["energy"]) >= constraints.energy_j)
            | (np.asarray(lbs["latency"]) >= constraints.latency_s))


def _slab_sizes(ranges_list) -> np.ndarray:
    if len(ranges_list) == 0:
        return np.zeros(0, np.int64)
    arr = np.asarray(ranges_list, np.int64)
    return np.prod(arr[:, :, 1] - arr[:, :, 0], axis=1)


def _slab_first_indices(radices, ranges_list) -> np.ndarray:
    """(B,) first (lowest) flat index of each slab — the deterministic
    tie-break key of the best-first leaf ordering."""
    strides = np.ones(5, np.int64)
    for i in range(3, -1, -1):
        strides[i] = strides[i + 1] * int(radices[i + 1])
    if len(ranges_list) == 0:
        return np.zeros(0, np.int64)
    arr = np.asarray(ranges_list, np.int64)
    return arr[:, :, 0] @ strides


@traced("search.descend")
def _bnb_descend(fspace, ev, prune_mask_fn, start, start_lbs, leaf_size,
                 stats, c, led=None):
    """Shared slab-tree descent: process the active set — a (B, 5, 2)
    digit-range array — level by level. Each level is one *vectorized*
    `lower_bounds_batch` call plus one vectorized halving of the
    survivors along the significance order; nothing in the loop is
    per-slab python. Returns the surviving
    ((L, 5, 2) leaf array, {metric: (L,) bound arrays}). With a
    `LedgerRecorder` attached every pruned slab is recorded with the
    bounds it was priced at."""
    order = np.asarray(_bnb_axis_order(c))
    active, lbs = np.asarray(start, np.int64).reshape(-1, 5, 2), start_lbs
    leaf_parts = []
    leaf_lbs = []
    while len(active):
        die = prune_mask_fn(lbs)
        widths = active[:, :, 1] - active[:, :, 0]
        sizes = np.prod(widths, axis=1)
        stats["n_pruned"] += int(sizes[die].sum())
        if led is not None:
            led.prune(active[die], {k: v[die] for k, v in lbs.items()})
        keep = ~die
        is_leaf = keep & (sizes <= leaf_size)
        leaf_parts.append(active[is_leaf])
        leaf_lbs.append({k: v[is_leaf] for k, v in lbs.items()})
        sub = active[keep & ~is_leaf]
        if not len(sub):
            break
        # Vectorized significance-ordered halving: each slab splits its
        # most significant axis with width > 1 (size > leaf_size >= 1
        # guarantees one exists) at mid = (lo + hi) // 2.
        wid = (sub[:, :, 1] - sub[:, :, 0])[:, order] > 1
        ax = order[np.argmax(wid, axis=1)]
        rows = np.arange(len(sub))
        lo = sub[rows, ax, 0]
        hi = sub[rows, ax, 1]
        mid = (lo + hi) // 2
        left = sub.copy()
        left[rows, ax, 1] = mid
        right = sub.copy()
        right[rows, ax, 0] = mid
        active = np.concatenate([left, right])
        lbs = ev.lower_bounds_batch(active)
        stats["n_bounds"] += len(active)
    leaves = (np.concatenate(leaf_parts) if leaf_parts
              else np.zeros((0, 5, 2), np.int64))
    out_lbs = {k: (np.concatenate([d[k] for d in leaf_lbs])
                   if leaf_lbs else np.zeros(0))
               for k in REPORT_METRICS}
    return leaves, out_lbs


def _bnb_frontier(fspace, ev, constraints, c, stats, led=None):
    """Constraint-driven descent from the whole space to BNB_LEAF leaves.

    Objective pruning (incumbent EDP / frontier dominance) happens later,
    against the stored leaf bounds — constraints don't move during the
    search, so splitting the phases costs nothing in pruning power and
    keeps every level one vectorized bound pass.
    """
    from .factorized import full_ranges
    root = np.asarray([full_ranges(fspace.radices)], np.int64)
    lbs = ev.lower_bounds_batch(root)
    stats["n_bounds"] += 1
    return _bnb_descend(fspace, ev,
                        lambda b: _bnb_infeasible_mask(b, constraints),
                        root, lbs, BNB_LEAF, stats, c, led)


def _bnb_dominated_vs(pts: np.ndarray, lbs_arrays, objectives) -> np.ndarray:
    """(B,) mask of slabs whose objective lower-bound corner is strictly
    dominated by some point of `pts` ((F, d) float64 objective rows). Every
    point of such a slab is at or above the corner in every objective, so
    it is strictly dominated too — transitively safe even if the
    dominating point is later evicted from a running frontier (its evictor
    dominates the slab as well)."""
    corners = np.stack([np.asarray(lbs_arrays[k], np.float64)
                        for k in objectives], axis=1)
    if not len(pts):
        return np.zeros(len(corners), bool)
    le = np.all(pts[None, :, :] <= corners[:, None, :], axis=-1)
    lt = np.any(pts[None, :, :] < corners[:, None, :], axis=-1)
    return np.any(le & lt, axis=1)


@dataclasses.dataclass
class WarmStart:
    """Seed state for a warm-started bound-guided driver.

    The constraint-delta path of `repro.serve.SearchService` re-prices a
    prior search's `SlabLedger` against a new constraint box and hands the
    slabs it could not kill to the BnB drivers through this object instead
    of the root descent: `start` (with its stored `lbs`) replaces the
    `_bnb_frontier` leaf set, `best` / `nf` seed the EDP driver's running
    argmin and incumbent with the best already-known feasible point, and
    `rows` / `met` seed the pareto driver's running (float64-refined)
    frontier. Because the seeds are true achievable values and the stored
    bounds are admissible, the warm drivers return the same winners and
    frontiers as a cold search of the whole space under the new box.
    """

    start: np.ndarray                      # (B, 5, 2) slabs still to search
    lbs: Optional[Dict[str, np.ndarray]] = None  # their stored lower bounds
    best: tuple = (-1, float("inf"))       # EDP mode: (gidx, float64 edp)
    nf: int = 0                            # feasible count already known
    rows: Optional[np.ndarray] = None      # pareto mode: (F, 5) seed rows
    met: Optional[Dict[str, np.ndarray]] = None  # their metric columns


def _bnb_order(fspace, ranges_list, lbs, objectives=None) -> np.ndarray:
    """Deterministic best-first permutation: ascending EDP lower bound
    (or the objective lower-bound vectors in pareto mode), ties broken by
    each leaf's first flat index — the evaluation order is a pure
    function of the slab tree, never of the engine."""
    first = _slab_first_indices(fspace.radices, ranges_list)
    keys = ([first, lbs["edp"]] if objectives is None
            else [first] + [lbs[k] for k in reversed(objectives)])
    return np.lexsort(tuple(keys))


def _bnb_batch_slices(sizes: np.ndarray, max_points: Optional[int] = None):
    """Consecutive [s, e) leaf slices of at most `max_points` total points
    (default BNB_BATCH; a lone bigger leaf still forms its own slice)."""
    cap = BNB_BATCH if max_points is None else int(max_points)
    cum = np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])
    n = len(cum) - 1
    out = []
    s = 0
    while s < n:
        # Greedy: the longest run from s whose points fit the cap, and at
        # least the one leaf at s.
        e = int(np.searchsorted(cum, cum[s] + cap, side="right")) - 1
        e = max(e, s + 1)
        out.append((s, e))
        s = e
    return out


def _bnb_coarse_batch(ranges_list) -> bool:
    """Whether a leaf batch holds a slab above BNB_FINE points: the
    pallas drivers' launch-form test (decoded span list, not gathered
    survivor rows)."""
    return bool((_slab_sizes(ranges_list) > BNB_FINE).any())


def _bnb_leaf_items(fspace, ranges, chunk_size):
    """A leaf slab as decoded-launch work items [(start, count, slab), ...]
    for the pallas span-list drivers: the slab's bounding index range,
    chunked to at most `chunk_size` lanes per item (the kernel masks
    non-member lanes, so chunk splits never change membership)."""
    from .factorized import slab_bounding_span
    b0, b1 = slab_bounding_span(fspace.radices, ranges)
    cs = int(chunk_size) if chunk_size else b1 - b0
    return [(s, min(cs, b1 - s), ranges) for s in range(b0, b1, cs)]


def _bnb_eval_edp(engine, fspace, wl, constraints, c, interpret,
                  ranges_list, shard, chunk_size):
    """(best gidx or -1, its engine EDP, n_feasible) over one batch of
    leaf slabs.

    numpy/jax evaluate the batch's ascending concatenated index vector
    (chunked by `chunk_size`, fanned out by `shard`). pallas picks its
    launch form per batch: coarse slabs (the probe phase) go through the
    span-list driver — one decoded launch for the whole batch, a meta row
    per block of each leaf's bounding span, the slab ranges masking
    non-members — while batches of fine refined slabs (whose members are
    scattered single indices, hopeless as spans) materialize just the
    survivor rows and reuse the grid-operand kernel, one bucketed launch
    per chunk. Either way only survivor-sized data ever exists on the
    host."""
    from .factorized import slab_indices_batch
    best = (-1, float("inf"))
    nf = 0
    if engine == "pallas" and _bnb_coarse_batch(ranges_list):
        from repro.kernels.ops import dse_search_spans_factorized
        items = [it for ranges in ranges_list
                 for it in _bnb_leaf_items(fspace, ranges, chunk_size)]
        (bi,), (be,), (bn,) = dse_search_spans_factorized(
            fspace, items, [wl], [constraints], c, interpret, shard=shard)
        return bi, be, bn
    idx = slab_indices_batch(fspace.radices, ranges_list)
    cs = int(chunk_size) if chunk_size else len(idx)
    for s in range(0, len(idx), cs):
        part = idx[s:s + cs]
        if engine == "pallas":
            from repro.kernels.ops import dse_search_multi
            rows = fspace.decode(part)
            (bi,), (be,), (bn,) = dse_search_multi(
                rows, [wl], [constraints], c, interpret, shard=shard)
            gi, e, f = (int(part[bi]) if bi >= 0 else -1), float(be), \
                int(bn)
        elif engine == "jax":
            gi, e, f = _jax_factorized_idx_argmin(fspace, wl, constraints,
                                                  c, part, shard)
        else:
            gi, e, f = _edp_idx_numpy(fspace, wl, constraints, c, part,
                                      shard)
        nf += f
        best = _merge_best_indexed(best, (gi, e))
    return best[0], best[1], nf


def _bnb_eval_pareto(engine, fspace, wl, constraints, c, interpret,
                     ranges_list, shard, chunk_size, objectives, run_rows):
    """(cand gidx array, n_feasible, n_overflow) over one batch of leaf
    slabs; launch forms as in `_bnb_eval_edp`."""
    from .factorized import slab_indices_batch
    cands = []
    nf = n_over = 0
    carry_points = None
    if engine == "pallas" and len(run_rows):
        carry_points = [_pallas_front_points(run_rows, wl, c, interpret,
                                             objectives)]
    if engine == "pallas" and _bnb_coarse_batch(ranges_list):
        from repro.kernels.ops import dse_pareto_spans_factorized
        for ranges in ranges_list:
            items = _bnb_leaf_items(fspace, ranges, chunk_size)
            (idx, f, o), = dse_pareto_spans_factorized(
                fspace, items, [wl], [constraints], c, interpret,
                objectives=objectives, shard=shard,
                carry_points=carry_points)
            nf += f
            n_over += o
            if len(idx):
                cands.append(idx)
        return (np.concatenate(cands) if cands
                else np.zeros(0, np.int64)), nf, n_over
    idx = slab_indices_batch(fspace.radices, ranges_list)
    cs = int(chunk_size) if chunk_size else len(idx)
    for s in range(0, len(idx), cs):
        part = idx[s:s + cs]
        if engine == "pallas":
            from repro.kernels.ops import dse_pareto_multi
            rows = fspace.decode(part)
            (local, f, o), = dse_pareto_multi(
                rows, [wl], [constraints], c, interpret,
                objectives=objectives, shard=shard,
                carry_points=carry_points)
            cand = part[local]
            n_over += o
        elif engine == "jax":
            cand, f = _jax_factorized_idx_mask(fspace, wl, constraints, c,
                                               part, shard, objectives)
        else:
            cand, f = _pareto_idx_numpy(fspace, wl, constraints, c, part,
                                        shard, objectives)
        nf += f
        if len(cand):
            cands.append(cand)
    return (np.concatenate(cands) if cands
            else np.zeros(0, np.int64)), nf, n_over


def _search_factorized_bnb(fspace, wl, constraints, engine, c, interpret,
                           shard, chunk_size, rt=None, led=None,
                           warm=None, executor=None) -> SearchResult:
    """Bound-guided min-EDP driver.

    Phase 1 (`_bnb_frontier`): constraint-prune the slab tree down to
    BNB_LEAF-sized leaves with vectorized interval bounds. Phase 2:
    *probe* — evaluate the most promising leaves (ascending EDP lower
    bound) until an incumbent exists; *refine* — re-split everything else
    down to BNB_FINE against the incumbent (`_bnb_descend` again, now
    with the incumbent-EDP test joined to the constraint test), which is
    where the bulk of the space dies; *sweep* — evaluate the refined
    survivors best-first in BNB_BATCH batches, stopping the moment the
    smallest remaining bound clears the incumbent. The evaluated volume
    stops growing with the space once the incumbent region is covered,
    which is what makes the win over streamed sweeps super-linear.

    With a runtime attached the evaluation *unit* is one probe/sweep
    batch. The checkpoint carries the incumbent, the running (gidx, edp)
    argmin, the counters and the phase cursor; the slab frontier and the
    refinement are recomputed on resume (pure deterministic functions of
    the space + the checkpointed incumbent — cheaper to replay than to
    persist, and their bound/prune work is already inside the restored
    counters, so a throwaway stats dict keeps the totals exact).

    A `WarmStart` (`warm=`) replaces the root slab frontier with a prior
    run's re-priced surviving slabs and seeds the running argmin /
    incumbent from its point store — the `repro.serve` constraint-delta
    path. A `LedgerRecorder` (`led=`) captures the pruned/evaluated slab
    partition onto ``result.ledger``. Warm starts exclude both the
    runtime (a delta query is a sub-second re-price; checkpoint the cold
    search instead) and the ledger (warm slabs no longer tile the space,
    so there is no complete partition to capture — chained deltas
    re-price against the original cold ledger, which stays valid for any
    box inside the original one).

    An `executor` (a `repro.parallel.slab_sched.SlabScheduler`) replaces
    the direct `_bnb_eval_edp` call with a leased multi-worker fan-out of
    the same batch. The fan-out is byte-identical to the direct call (per
    the scheduler's merge contract), so every other line of this driver —
    the schedule, the checkpoints, the counters — is untouched.
    """
    from .factorized import cached_bound_evaluator
    if warm is not None and rt is not None:
        raise ValueError("warm= cannot combine with a runtime: checkpoint "
                         "the cold search, re-price deltas warm")
    if warm is not None and led is not None:
        raise ValueError("warm= cannot capture a ledger: warm slabs do not "
                         "tile the space (delta against the cold ledger)")
    t0 = time.perf_counter()
    ev = cached_bound_evaluator(fspace, wl, c)
    stats = {"n_pruned": 0, "n_bounds": 0}
    state = {"inc": float("inf"), "best": (-1, float("inf")),
             "nf": 0, "n_eval": 0}
    fp = None
    rec = None
    if rt is not None:
        fp = _rt_fp("edp_bnb", wl, constraints, engine, c, interpret,
                    shard, chunk_size, axes=fspace.axes, leaf=BNB_LEAF,
                    batch=BNB_BATCH, fine=BNB_FINE)
        rec = rt.resume(fp)
    unit = 0
    phase, probe_end = "probe", 0
    inc_refine = float("inf")
    if rec is not None:
        # A resumed run replays only the tail of the schedule — the head's
        # evaluated leaves never pass through this process, so no complete
        # partition can be captured.
        led = None
        unit, st, extra = rec
        leaves, lbs = _bnb_frontier(fspace, ev, constraints, c,
                                    {"n_pruned": 0, "n_bounds": 0})
        state["best"] = decode_best_indexed(st)
        state["inc"] = float(st["inc"][0])
        inc_refine = float(st["inc_refine"][0])
        state["nf"] = int(extra["nf"])
        state["n_eval"] = int(extra["n_eval"])
        stats["n_pruned"] = int(extra["n_pruned"])
        stats["n_bounds"] = int(extra["n_bounds"])
        phase, probe_end = extra["phase"], int(extra["probe_end"])
    elif warm is not None:
        leaves = np.asarray(warm.start, np.int64).reshape(-1, 5, 2)
        if warm.lbs is not None and len(leaves):
            lbs = {k: np.asarray(warm.lbs[k], np.float64)
                   for k in REPORT_METRICS}
        elif len(leaves):
            lbs = ev.lower_bounds_batch([tuple(tuple(r) for r in rng)
                                         for rng in leaves])
            stats["n_bounds"] += len(leaves)
        else:
            lbs = {k: np.zeros(0) for k in REPORT_METRICS}
        state["best"] = (int(warm.best[0]), float(warm.best[1]))
        if state["best"][0] >= 0:
            state["inc"] = state["best"][1]
        state["nf"] = int(warm.nf)
    else:
        leaves, lbs = _bnb_frontier(fspace, ev, constraints, c, stats, led)
    resumed_sweep = phase == "sweep"

    def evaluate(ranges_list, n_points):
        if led is not None:
            led.evaluate(np.asarray(ranges_list, np.int64).reshape(-1, 5, 2))

        def run(eng):
            if executor is not None:
                return executor.eval_edp(eng, ranges_list)
            return _bnb_eval_edp(eng, fspace, wl, constraints, c,
                                 interpret, ranges_list, shard, chunk_size)

        if rt is None:
            gi, e, f = run(engine)
        else:
            gi, e, f = rt.eval_unit(engine, {
                eng: functools.partial(run, eng)
                for eng in ("numpy", "jax", "pallas")})
        state["nf"] += f
        state["n_eval"] += n_points
        merged = _merge_best_indexed(state["best"], (gi, e))
        if merged is not state["best"]:
            state["best"] = merged
            # The pruning incumbent is the winner's float64 reference EDP,
            # so the slab schedule is identical no matter which engine
            # proposed the winner.
            with span("search.refine"):
                cfg = PTAConfig.from_array(fspace.decode([merged[0]])[0])
                _, _, energy, latency = eval_full(cfg, wl, c)[:4]
                state["inc"] = calc_edp(energy, latency)

    def snapshot():
        st = encode_best_indexed(state["best"])
        st["inc"] = np.asarray([state["inc"]], np.float64)
        st["inc_refine"] = np.asarray([inc_refine], np.float64)
        rt.unit_done(fp, unit, st, {
            "nf": state["nf"], "n_eval": state["n_eval"],
            "n_pruned": stats["n_pruned"], "n_bounds": stats["n_bounds"],
            "phase": phase, "probe_end": probe_end})

    # Probe: evaluate best-first batches until an incumbent exists (one
    # batch, unless the most promising leaves turn out infeasible).
    order = _bnb_order(fspace, leaves, lbs)
    leaves = leaves[order]
    lbs = {k: v[order] for k, v in lbs.items()}
    sizes = _slab_sizes(leaves)
    slices = _bnb_batch_slices(sizes)
    bi = probe_end
    while (not resumed_sweep and bi < len(slices)
           and state["inc"] == float("inf")):
        s, e = slices[bi]
        evaluate(leaves[s:e], int(sizes[s:e].sum()))
        bi += 1
        if rt is not None:
            probe_end = bi
            snapshot()
            unit += 1
    rs = slices[bi][0] if bi < len(slices) else len(leaves)

    # Refine the remainder against the incumbent, then evaluate whatever
    # survives, best-first — the sorted early-exit stops the sweep the
    # moment the smallest remaining bound clears the incumbent. The
    # incumbent frozen at refine start is what the prune compares against
    # (evaluation never runs during the descent, so the live incumbent
    # equals the frozen one — persisting it makes the resumed replay
    # exact even though the live incumbent keeps moving in the sweep).
    if not resumed_sweep:
        inc_refine = state["inc"]
        refine_stats = stats
    else:
        refine_stats = {"n_pruned": 0, "n_bounds": 0}
    ready, rlbs = _bnb_descend(
        fspace, ev,
        lambda b: (_bnb_infeasible_mask(b, constraints)
                   | (np.asarray(b["edp"]) > inc_refine)),
        leaves[rs:], {k: v[rs:] for k, v in lbs.items()}, BNB_FINE,
        refine_stats, c, led)
    phase, probe_end = "sweep", bi
    order = _bnb_order(fspace, ready, rlbs)
    ready = ready[order]
    rlbs = {k: v[order] for k, v in rlbs.items()}
    edp_lo = rlbs["edp"] if len(ready) else np.zeros(0)
    sizes = _slab_sizes(ready)
    sweep_done = unit - bi
    for j, (s, e) in enumerate(_bnb_batch_slices(sizes)):
        if j < sweep_done:
            continue
        if edp_lo[s] > state["inc"]:
            # Sorted leaves: once the smallest remaining bound exceeds
            # the incumbent, everything left is prunable.
            stats["n_pruned"] += int(sizes[s:].sum())
            if led is not None:
                led.prune(ready[s:], {k: v[s:] for k, v in rlbs.items()})
            break
        live = edp_lo[s:e] <= state["inc"]
        stats["n_pruned"] += int(sizes[s:e][~live].sum())
        if led is not None:
            led.prune(ready[s:e][~live],
                      {k: v[s:e][~live] for k, v in rlbs.items()})
        evaluate(ready[s:e][live], int(sizes[s:e][live].sum()))
        if rt is not None:
            snapshot()
            unit += 1
    best = state["best"]
    row = fspace.decode([best[0]])[0] if best[0] >= 0 else None
    r = _make_result(row, state["nf"], wl, c, fspace.size, state["n_eval"],
                     time.perf_counter() - t0)
    r.n_pruned = stats["n_pruned"]
    r.n_bounds = stats["n_bounds"]
    if led is not None:
        r.ledger = led.build(fspace)
    return rt.annotate(r) if rt is not None else r


def _pareto_factorized_bnb(fspace, wl, constraints, engine, c, interpret,
                           objectives, shard, chunk_size, rt=None, led=None,
                           warm=None, executor=None) -> ParetoResult:
    """Bound-guided frontier driver: probe the objective-sorted leaves to
    seed the running (float64-refined) frontier, refine the remainder
    against it, then evaluate the survivors in batches. A slab is pruned
    when its objective lower-bound corner is strictly dominated by a
    running-frontier point — every point of such a slab is strictly
    dominated too, transitively safe even if that frontier point is
    later evicted (its evictor dominates the slab as well). Runtime
    checkpointing follows `_search_factorized_bnb`, with the frozen
    refinement frontier persisted alongside the live one. `warm=` /
    `led=` / `executor=` follow `_search_factorized_bnb` too (warm seeds
    the running frontier from `WarmStart.rows`/`met` instead of an
    argmin; the executor fan-out's candidate union is
    frontier-identical to the direct call)."""
    from .factorized import cached_bound_evaluator
    if warm is not None and rt is not None:
        raise ValueError("warm= cannot combine with a runtime: checkpoint "
                         "the cold search, re-price deltas warm")
    if warm is not None and led is not None:
        raise ValueError("warm= cannot capture a ledger: warm slabs do not "
                         "tile the space (delta against the cold ledger)")
    t0 = time.perf_counter()
    d = len(objectives)
    ev = cached_bound_evaluator(fspace, wl, c)
    stats = {"n_pruned": 0, "n_bounds": 0}
    state = {"rows": _empty_run_state()[0], "met": _empty_run_state()[1],
             "pts": np.zeros((0, d)), "nf": 0, "n_eval": 0, "n_over": 0}
    fp = None
    rec = None
    if rt is not None:
        fp = _rt_fp("pareto_bnb", wl, constraints, engine, c, interpret,
                    shard, chunk_size, axes=fspace.axes,
                    objectives=tuple(objectives), leaf=BNB_LEAF,
                    batch=BNB_BATCH, fine=BNB_FINE)
        rec = rt.resume(fp)
    unit = 0
    phase, probe_end = "probe", 0
    pts_refine = np.zeros((0, d))
    if rec is not None:
        # Resumed runs replay only the schedule's tail — no complete slab
        # partition passes through this process, so no ledger.
        led = None
        unit, st, extra = rec
        leaves, lbs = _bnb_frontier(fspace, ev, constraints, c,
                                    {"n_pruned": 0, "n_bounds": 0})
        state["rows"], state["met"] = decode_front(st, REPORT_METRICS)
        state["pts"] = (np.stack([state["met"][k] for k in objectives],
                                 axis=1) if len(state["rows"])
                        else np.zeros((0, d)))
        pts_refine = np.asarray(st["pts_refine"],
                                np.float64).reshape(-1, d)
        state["nf"] = int(extra["nf"])
        state["n_eval"] = int(extra["n_eval"])
        state["n_over"] = int(extra["n_over"])
        stats["n_pruned"] = int(extra["n_pruned"])
        stats["n_bounds"] = int(extra["n_bounds"])
        phase, probe_end = extra["phase"], int(extra["probe_end"])
    elif warm is not None:
        leaves = np.asarray(warm.start, np.int64).reshape(-1, 5, 2)
        if warm.lbs is not None and len(leaves):
            lbs = {k: np.asarray(warm.lbs[k], np.float64)
                   for k in REPORT_METRICS}
        elif len(leaves):
            lbs = ev.lower_bounds_batch([tuple(tuple(r) for r in rng)
                                         for rng in leaves])
            stats["n_bounds"] += len(leaves)
        else:
            lbs = {k: np.zeros(0) for k in REPORT_METRICS}
        if warm.rows is not None and len(warm.rows):
            state["rows"] = np.asarray(warm.rows, np.int64).reshape(-1, 5)
            state["met"] = {k: np.asarray(warm.met[k], np.float64)
                            for k in REPORT_METRICS}
            state["pts"] = np.stack([state["met"][k] for k in objectives],
                                    axis=1)
        state["nf"] = int(warm.nf)
    else:
        leaves, lbs = _bnb_frontier(fspace, ev, constraints, c, stats, led)
    resumed_sweep = phase == "sweep"

    def dominated_vs(pts, lbs_arrays):
        return _bnb_dominated_vs(pts, lbs_arrays, objectives)

    def evaluate(ranges_list, n_points):
        if led is not None:
            led.evaluate(np.asarray(ranges_list, np.int64).reshape(-1, 5, 2))

        def run(eng):
            if executor is not None:
                return executor.eval_pareto(eng, ranges_list,
                                            state["rows"])
            return _bnb_eval_pareto(eng, fspace, wl, constraints, c,
                                    interpret, ranges_list, shard,
                                    chunk_size, objectives, state["rows"])

        if rt is None:
            idx, f, o = run(engine)
        else:
            idx, f, o = rt.eval_unit(engine, {
                eng: functools.partial(run, eng)
                for eng in ("numpy", "jax", "pallas")})
        state["nf"] += f
        state["n_eval"] += n_points
        state["n_over"] += o
        if len(idx):
            state["rows"], state["met"] = _merge_running_front(
                state["rows"], state["met"], fspace.decode(idx), wl,
                constraints, c, objectives)
            state["pts"] = (np.stack([state["met"][k] for k in objectives],
                                     axis=1) if len(state["rows"])
                            else np.zeros((0, d)))

    def snapshot():
        st = encode_front(state["rows"], state["met"], REPORT_METRICS)
        st["pts_refine"] = np.asarray(pts_refine,
                                      np.float64).reshape(-1, d)
        rt.unit_done(fp, unit, st, {
            "nf": state["nf"], "n_eval": state["n_eval"],
            "n_over": state["n_over"], "n_pruned": stats["n_pruned"],
            "n_bounds": stats["n_bounds"], "phase": phase,
            "probe_end": probe_end})

    order = _bnb_order(fspace, leaves, lbs, objectives)
    leaves = leaves[order]
    lbs = {k: v[order] for k, v in lbs.items()}
    sizes = _slab_sizes(leaves)
    slices = _bnb_batch_slices(sizes)
    bi = probe_end
    while not resumed_sweep and bi < len(slices) and not len(state["pts"]):
        s, e = slices[bi]
        evaluate(leaves[s:e], int(sizes[s:e].sum()))
        bi += 1
        if rt is not None:
            probe_end = bi
            snapshot()
            unit += 1
    rs = slices[bi][0] if bi < len(slices) else len(leaves)
    # The frontier frozen at refine start drives the refinement prune
    # (the descent never evaluates, so freezing it is exact — and
    # persisting it makes the resumed replay identical even after the
    # live frontier moves during the sweep).
    if not resumed_sweep:
        pts_refine = state["pts"]
        refine_stats = stats
    else:
        refine_stats = {"n_pruned": 0, "n_bounds": 0}
    ready, rlbs = _bnb_descend(
        fspace, ev,
        lambda b: (_bnb_infeasible_mask(b, constraints)
                   | dominated_vs(pts_refine, b)),
        leaves[rs:], {k: v[rs:] for k, v in lbs.items()}, BNB_FINE,
        refine_stats, c, led)
    phase, probe_end = "sweep", bi
    order = _bnb_order(fspace, ready, rlbs, objectives)
    ready = ready[order]
    rlbs = {k: v[order] for k, v in rlbs.items()}
    sizes = _slab_sizes(ready)
    sweep_done = unit - bi
    for j, (s, e) in enumerate(_bnb_batch_slices(sizes)):
        if j < sweep_done:
            continue
        die = dominated_vs(state["pts"], {k: v[s:e]
                                          for k, v in rlbs.items()})
        stats["n_pruned"] += int(sizes[s:e][die].sum())
        if led is not None:
            led.prune(ready[s:e][die],
                      {k: v[s:e][die] for k, v in rlbs.items()})
        if not die.all():
            evaluate(ready[s:e][~die], int(sizes[s:e][~die].sum()))
        if rt is not None:
            snapshot()
            unit += 1
    with span("search.refine"):
        front, met, _ = _pareto_from_rows(state["rows"], wl, constraints, c,
                                          objectives, m=state["met"])
    res = ParetoResult(front=front, metrics=met, objectives=objectives,
                       n_evaluated=fspace.size, n_feasible=state["nf"],
                       n_workload_evals=state["n_eval"],
                       wall_time_s=time.perf_counter() - t0,
                       n_pruned=stats["n_pruned"],
                       n_bounds=stats["n_bounds"],
                       n_overflow=state["n_over"])
    if led is not None:
        res.ledger = led.build(fspace)
    return rt.annotate(res) if rt is not None else res


def _workloads_pallas_factorized(wls, names, cons_for, fspace, c, interpret,
                                 objective, metrics, shard, chunk_size):
    """Batched factorized driver: every span is one all-workloads decoded
    launch, with the same per-workload carries as the grid-operand batched
    driver."""
    from repro.kernels.ops import (dse_pareto_multi_factorized,
                                   dse_search_multi_factorized)
    t0 = time.perf_counter()
    wl_list = [wls[nm] for nm in names]
    cons_list = [cons_for(nm) for nm in names]
    n_wl = 0
    if objective == "edp":
        best = {nm: (None, float("inf")) for nm in names}
        nf = {nm: 0 for nm in names}
        for s, n in _iter_spans(fspace.size, chunk_size):
            n_wl += n
            carry = [best[nm][1] for nm in names]
            bi, be, bn = dse_search_multi_factorized(
                fspace, s, n, wl_list, cons_list, c, interpret,
                shard=shard, carry_edp=carry)
            for nm, i, e, f in zip(names, bi, be, bn):
                nf[nm] += f
                if i >= 0:
                    best[nm] = (fspace.decode([i])[0], e)
        wall = time.perf_counter() - t0
        return {nm: _make_result(best[nm][0], nf[nm], wls[nm], c,
                                 fspace.size, n_wl, wall)
                for nm in names}

    run = {nm: _empty_run_state() for nm in names}
    nf = {nm: 0 for nm in names}
    n_over = {nm: 0 for nm in names}
    for s, n in _iter_spans(fspace.size, chunk_size):
        n_wl += n
        carry_points = [
            _pallas_front_points(run[nm][0], wls[nm], c, interpret, metrics)
            if len(run[nm][0]) else None
            for nm in names]
        per_wl = dse_pareto_multi_factorized(
            fspace, s, n, wl_list, cons_list, c, interpret,
            objectives=metrics, shard=shard, carry_points=carry_points)
        for nm, (idx, f, o) in zip(names, per_wl):
            nf[nm] += f
            n_over[nm] += o
            if len(idx):
                run[nm] = _merge_running_front(
                    run[nm][0], run[nm][1], fspace.decode(idx), wls[nm],
                    cons_for(nm), c, metrics)
    wall = time.perf_counter() - t0
    out = {}
    for nm in names:
        with span("search.refine"):
            front, met, _ = _pareto_from_rows(run[nm][0], wls[nm],
                                              cons_for(nm), c, metrics,
                                              m=run[nm][1])
        out[nm] = ParetoResult(front=front, metrics=met, objectives=metrics,
                               n_evaluated=fspace.size, n_feasible=nf[nm],
                               n_workload_evals=n_wl, wall_time_s=wall,
                               n_overflow=n_over[nm])
    return out


def _check_pareto_metrics(engine: str, pareto_metrics) -> tuple:
    metrics = tuple(pareto_metrics)
    unknown = [k for k in metrics if k not in REPORT_METRICS]
    if unknown or not metrics:
        raise ValueError(f"pareto_metrics must be a non-empty subset of "
                         f"{REPORT_METRICS}, got {pareto_metrics!r}")
    if engine == "pallas" and "util" in metrics:
        raise ValueError("the pallas frontier kernel does not model 'util'; "
                         "use the python/numpy/jax engines for it")
    return metrics


def _check_stream_args(shard, chunk_size):
    if shard is not None and int(shard) < 1:
        raise ValueError(f"shard must be >= 1, got {shard!r}")
    if chunk_size is not None and int(chunk_size) < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size!r}")


def _check_prune_arg(prune, factorized):
    if prune is None:
        return
    if prune != "bound":
        raise ValueError(f"unknown prune mode {prune!r}; the engine layer "
                         f"supports prune='bound' (branch-and-bound slab "
                         f"pruning) or None")
    if not factorized:
        raise ValueError("prune='bound' prices slabs of a product space "
                         "via the factorized axis tables; it requires "
                         "factorized=True (numpy/jax/pallas engines)")


def _check_grid(grid) -> np.ndarray:
    """Reject malformed candidate grids up front: a wrong-shaped or
    non-positive grid would surface as a silent zero-feasible result (or a
    model-layer division blowup), indistinguishable from a genuinely
    infeasible search."""
    g = np.asarray(grid)
    if g.ndim != 2 or (len(g) and g.shape[1] != 5):
        raise ValueError(f"grid must be a (G, 5) array of config rows "
                         f"(n_t, n_c, n_h, n_v, n_lambda); got shape "
                         f"{g.shape}")
    if len(g) == 0:
        raise ValueError("grid is empty: no candidate configs to search")
    if g.dtype.kind not in "iuf":
        raise ValueError(f"grid must be numeric, got dtype {g.dtype}")
    if g.dtype.kind == "f" and not np.isfinite(g).all():
        raise ValueError("grid contains non-finite (NaN/Inf) entries")
    if (g < 1).any():
        raise ValueError("grid entries are parallelism degrees and must "
                         "all be >= 1")
    return g


# ---------------------------------------------------------------------------
# Robust search: calibration uncertainty through the cost model
# ---------------------------------------------------------------------------
#
# `core.calibration`'s certified-monotone lemma reduces worst-case-robust
# search to an ordinary search at the calibration's worst corner — so the
# resolution below simply swaps the `DeviceConstants` the engines run on
# and attaches the winner's (or frontier's) uncertainty band afterwards.
# Only calibrations with *unresolved* fields (explicitly `uncertified=`,
# or a direction conflict in a future cost model) leave that fast path,
# via the conservative host-side vertex sweep `_robust_vertex_search`.

#: Engines robust="worst_case" supports — the vectorized backends the
#: worst-corner reduction prices in one sweep. The python engine is the
#: paper-faithful sequential oracle (EDP_svd cap and all) and stays
#: point-calibrated.
ROBUST_ENGINES = ("numpy", "jax", "pallas")


def _resolve_robust(calibration, robust, c, engine):
    """Validate and resolve `calibration=` / `robust=` into the constants
    the engines should run at.

    Returns `(c_run, cal, fallback)`: `cal` is None on uncalibrated
    searches; `fallback=True` routes through `_robust_vertex_search`
    (unresolved fields), in which case `c_run` is None.
    """
    if calibration is None:
        if robust is not None:
            raise ValueError("robust= prices a calibration's uncertainty; "
                             "pass calibration= (a CalibratedConstants, a "
                             "{field: interval} mapping, or a preset name)")
        return c, None, False
    cal = as_calibration(calibration)
    if c != CONSTANTS:
        raise ValueError("pass either c= or calibration=, not both: the "
                         "calibration's nominal values are the point "
                         "constants")
    if robust is None:
        return cal.nominal(), cal, False
    if robust != "worst_case":
        raise ValueError(f"unknown robust mode {robust!r}; the engine "
                         f"layer supports robust='worst_case' or None")
    if engine not in ROBUST_ENGINES:
        raise ValueError(f"robust='worst_case' supports engines "
                         f"{ROBUST_ENGINES}, not {engine!r}")
    if cal.unresolved():
        return None, cal, True
    return cal.worst_case(), cal, False


def _corner_reduced_metrics(rows, wl, cal, sign, fspace=None, idx=None):
    """Per-metric elementwise extreme over the calibration's `sign`-side
    vertex corners (float64 host reference). One corner — hence one plain
    `evaluate_grid` sweep — for fully certified calibrations."""
    op = np.maximum if sign > 0 else np.minimum
    out = None
    for corner in cal.vertex_corners(sign=sign):
        m = (factorized_evaluate_grid(fspace, wl, corner, idx=idx)
             if fspace is not None else evaluate_grid(rows, wl, corner))
        out = m if out is None else {k: op(out[k], m[k])
                                     for k in REPORT_METRICS}
    return out


def _measure_band(res, cal, wl) -> Optional[RobustBand]:
    """The result's uncertainty band: float64 reference metrics of the
    winner (or each frontier row) at the calibration's worst / nominal /
    best corners. None for infeasible results."""
    if isinstance(res, ParetoResult):
        if res.size == 0:
            return None
        rows = np.asarray(res.front, np.int64)

        def to(m):
            return {k: np.asarray(m[k], np.float64) for k in REPORT_METRICS}
    else:
        if res.best_cfg is None:
            return None
        rows = np.asarray([res.best_cfg.as_array()], np.int64)

        def to(m):
            return {k: float(np.asarray(m[k])[0]) for k in REPORT_METRICS}
    worst = _corner_reduced_metrics(rows, wl, cal, +1)
    best = _corner_reduced_metrics(rows, wl, cal, -1)
    nom = evaluate_grid(rows, wl, cal.nominal())
    return RobustBand(calibration=cal, worst=to(worst), nominal=to(nom),
                      best=to(best))


def _robust_vertex_search(wl, constraints, cal, engine, grid, n_z,
                          objective, pareto_metrics, factorized, space,
                          hierarchical):
    """Conservative fallback for calibrations with unresolved fields: a
    host-side float64 sweep over the 2^k vertex corners of the uncertified
    fields (certified fields pinned at their worst end), each metric priced
    at its elementwise corner max. Sound — per-field monotone metrics
    attain their box extrema at vertices — but conservative: per-metric
    maxes may come from different corners. `shard`/`chunk_size` are
    accepted and ignored (the host sweep returns the same bytes);
    `prune`/`runtime`/`keep_ledger` are rejected by `search` before this
    runs."""
    t0 = time.perf_counter()
    fspace = None
    if factorized:
        fspace = _factorized_space(space, grid, n_z, engine, hierarchical)
        rows = fspace.to_grid()
    else:
        if space is not None:
            raise ValueError("space= requires factorized=True (pass grid= "
                             "for materialized candidate sets)")
        rows = _full_grid(n_z) if grid is None else _check_grid(grid)
        rows = np.asarray(rows, np.int64)
    n_corners = len(cal.vertex_corners())
    worst = _corner_reduced_metrics(rows, wl, cal, +1, fspace=fspace)
    ok = np.asarray(constraints.satisfied(worst["area"], worst["power"],
                                          worst["energy"],
                                          worst["latency"]))
    n_eval = len(rows) * n_corners
    n_feasible = int(ok.sum())

    if objective == "edp":
        if not ok.any():
            return SearchResult(best_cfg=None, n_evaluated=n_eval,
                                n_feasible=0, n_workload_evals=n_eval,
                                wall_time_s=time.perf_counter() - t0)
        idx = np.where(ok)[0]
        best = int(idx[np.lexsort((idx, worst["edp"][idx]))[0]])
        res = SearchResult(
            best_cfg=PTAConfig.from_array(rows[best]),
            area_mm2=float(worst["area"][best]),
            power_w=float(worst["power"][best]),
            energy_j=float(worst["energy"][best]),
            latency_s=float(worst["latency"][best]),
            edp=float(worst["edp"][best]),
            n_evaluated=n_eval, n_feasible=n_feasible,
            n_workload_evals=n_eval,
            wall_time_s=time.perf_counter() - t0)
    else:
        metrics = _check_pareto_metrics(engine, pareto_metrics)
        if not ok.any():
            front = np.zeros((0, 5), np.int64)
            met = {k: np.zeros(0, np.float64) for k in REPORT_METRICS}
            return ParetoResult(front=front, metrics=met,
                                objectives=metrics, n_evaluated=n_eval,
                                n_feasible=0, n_workload_evals=n_eval,
                                wall_time_s=time.perf_counter() - t0)
        pts = np.stack([np.asarray(worst[k], np.float64)[ok]
                        for k in metrics], axis=1)
        mask = pareto_mask(pts)
        front = rows[ok][mask]
        order = np.lexsort(front.T[::-1])
        sel = np.where(ok)[0][mask][order]
        met = {k: np.asarray(worst[k], np.float64)[sel]
               for k in REPORT_METRICS}
        res = ParetoResult(front=front[order], metrics=met,
                           objectives=metrics, n_evaluated=n_eval,
                           n_feasible=n_feasible, n_workload_evals=n_eval,
                           wall_time_s=time.perf_counter() - t0)
    res.band = _measure_band(res, cal, wl)
    return res


def _counts_gemm_lanes(fn):
    """Set `n_gemm_lanes` on what a search returns (a result, or a dict of
    them, each reporting the whole batch's count as it does its wall
    time): the lanes x GEMM rows launched while it ran."""
    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with gemm_lane_tally() as tally:
            out = fn(*args, **kwargs)
        for r in (out.values() if isinstance(out, dict) else (out,)):
            r.n_gemm_lanes = tally.n
        return out
    return inner


@traced("search")
@_counts_gemm_lanes
def search(wl: Workload, constraints: Constraints = Constraints(), *,
           engine: str = "numpy", grid: Optional[np.ndarray] = None,
           n_z: int = 12, hierarchical: bool = False,
           c: DeviceConstants = CONSTANTS, interpret: Optional[bool] = None,
           objective: str = "edp",
           pareto_metrics: tuple = DEFAULT_OBJECTIVES,
           shard: Optional[int] = None, chunk_size: Optional[int] = None,
           factorized: bool = False, space=None,
           prune: Optional[str] = None, runtime=None,
           keep_ledger: bool = False,
           workers: Optional[int] = None, deterministic: bool = True,
           calibration=None, robust: Optional[str] = None
           ) -> Union[SearchResult, ParetoResult]:
    """Unified search over a config grid.

    Args:
      engine: one of ENGINES. All backends return identical results; they
        differ only in where the evaluation runs (host loop, broadcasted
        numpy, jit'd jax, fused Pallas kernel). Caveat: the jax/pallas
        backends (and the hierarchical prefilter) test feasibility in
        float32, so a config whose metric sits within one float32 ulp of a
        constraint bound can classify differently than under the float64
        python/numpy engines — real design points never ride that edge.
      grid: (G, 5) candidate configs; defaults to the full 1..n_z grid.
      hierarchical: two-phase search — area/power-only prefilter over the
        grid, then workload evaluation on the survivors only. Safe in both
        modes: prefilter losers are area/power-infeasible, so they can't be
        the min-EDP pick or on the feasible frontier.
      interpret: Pallas interpret mode; None (the default) follows the
        backend (see `repro.kernels.backend.resolve_interpret`).
      objective: "edp" — feasible min-EDP point (a SearchResult) — or
        "pareto" — the whole non-dominated feasible set over
        `pareto_metrics` (a ParetoResult). Frontier backends propose
        candidates their own way (python: incremental oracle; numpy: exact
        float64 mask; jax: jit sort-and-scan; pallas: per-block dominance
        reduction in the fused kernel), then every proposal is refined
        through the float64 reference model, so identical frontiers come
        back byte-identical.
      pareto_metrics: objectives to minimize in "pareto" mode, a subset of
        REPORT_METRICS (the pallas kernel models all but "util").
      shard: fan each evaluation out over up to `shard` devices with
        shard_map on the 1-D candidate mesh (jax/pallas; the host engines
        split the grid the same way). Clamped to the devices the process
        has, so `shard=4` works — and returns the same bytes — on a
        1-device box and a 4-device slice alike.
      chunk_size: stream the grid through the engine in chunks of this
        many candidates, carrying a running argmin / bounded frontier
        across chunks — peak memory follows the chunk, not the grid.
        None (the default) is a single chunk, the one-shot sweep; any
        (shard, chunk_size) combination is byte-identical to it
        (tests/test_sharded_search.py).
      factorized: evaluate the grid as a *product space* from per-GEMM
        axis factor tables (core.factorized) instead of per-point model
        runs — byte-identical results at a fraction of the work whenever
        the grid is a Cartesian product (numpy/jax/pallas engines, both
        objectives, shard/chunk compose; hierarchical and an explicit
        `grid` are rejected). See the module section above for the math.
      space: the candidate sets of the factorized product space — a
        mapping with `build_search_space`'s keys or a FactorizedSpace;
        defaults to the full 1..n_z space. Requires factorized=True.
      prune: "bound" switches the factorized engines to the bound-guided
        branch-and-bound driver: the space is recursively split into
        slabs (most Alg. 1-significant axes first), each slab priced by
        the admissible interval lower bounds of
        `core.factorized.SlabBoundEvaluator`, and only the slabs that
        survive the constraint / incumbent-EDP / frontier-dominance
        pruning are ever evaluated. Winners and frontiers stay
        byte-identical to the unpruned sweep; `n_feasible` and
        `n_workload_evals` count the evaluated survivors only, with the
        skipped volume in `n_pruned` (see `SearchResult.pruned_fraction`).
        Composes with `shard=` / `chunk_size=` without changing the slab
        tree, so counters match across every setting. Requires
        factorized=True.
      runtime: a `core.runtime.RuntimePolicy` (or `SearchRuntime`)
        attaching the resilient control plane: checkpoint/resume through
        the step-atomic snapshot layer, bounded-backoff launch retries
        with pallas -> jax -> numpy degradation, a per-launch watchdog,
        and NaN quarantine with host float64 re-evaluation. Results are
        byte-identical with or without a runtime; the campaign's
        retry/fallback/quarantine/checkpoint counters come back on the
        result. See README "Long searches".
      keep_ledger: retain the bound-guided run's slab partition — every
        pruned slab with the admissible lower bounds it was priced at,
        plus every evaluated leaf — as a `core.factorized.SlabLedger` on
        ``result.ledger``. Requires `prune="bound"`. This is what makes a
        later *tightened-box* query incremental: re-price the stored
        bounds instead of re-descending the space
        (`repro.serve.SearchService` is the consumer). A checkpointed run
        that actually *resumed* returns ``ledger=None`` — the resumed
        process replays only the schedule's tail, so no complete
        partition passes through it.
      workers: fan the bound-guided slab queue out across this many
        leased worker executors (`repro.parallel.slab_sched`): every
        slab batch is taken under a heartbeat lease, a worker that dies
        or hangs has its batch requeued (never silently dropped — the
        run ends with an explicit tiling assertion), and the
        incumbent/frontier is shared through versioned monotone merges.
        Requires `prune="bound"`. Composes with `runtime=` (the queue +
        lease table checkpoint/resume through the same step-atomic
        layer) and `keep_ledger=True`. Scheduler telemetry comes back on
        ``result.sched``.
      deterministic: with `workers=`, True (default) replays merges on
        the sequential drivers' fixed schedule — byte-identical to
        `workers=1` (winners, frontiers, and the canonical counter set;
        see `repro.parallel.slab_sched.canonical_counters`). False runs
        the async work-stealing sweep: faster under skew, pinned to
        "same winner/frontier after float64 exact verification,
        coverage-complete" instead (prune counters become
        schedule-dependent).
      calibration: a `core.calibration.CalibratedConstants` (or a
        `{field: interval}` mapping, or a shipped preset name like
        "conservative") carrying per-field (lo, nominal, hi) uncertainty
        intervals over the device constants. Mutually exclusive with a
        non-default `c=`. Without `robust=`, the search runs at
        `calibration.nominal()` — existing behavior — and the result
        additionally carries the winner's uncertainty band on
        ``result.band``.
      robust: "worst_case" prices the search at the calibration's
        certified worst corner: feasibility is decided on each metric's
        worst-case value, the EDP incumbent (or frontier dominance) on
        worst-case metrics, and the reported numbers are worst-case —
        "best config whose worst-case metrics still meet the
        constraints". The degenerate calibration (lo == nominal == hi)
        returns byte-identical results to an uncalibrated search. Sound
        by the `core.calibration.MONOTONE` direction lemma, which also
        keeps `prune="bound"` admissible (the slab bounds are simply
        built at the worst-corner constants); calibrations with
        uncertified varying fields fall back to a conservative host-side
        vertex sweep (which rejects prune/runtime/keep_ledger).
        Vectorized engines only (numpy/jax/pallas).
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; pick from "
                         f"{sorted(ENGINES)}")
    _check_stream_args(shard, chunk_size)
    _check_prune_arg(prune, factorized)
    if keep_ledger and prune != "bound":
        raise ValueError("keep_ledger=True records the bound-guided slab "
                         "partition; it requires prune='bound'")
    if workers is not None:
        workers = int(workers)
        if workers < 1:
            raise ValueError("workers= must be a positive integer")
        if prune != "bound":
            raise ValueError("workers= fans out the bound-guided slab "
                             "queue; it requires prune='bound' "
                             "(factorized=True)")
    c, cal, fallback = _resolve_robust(calibration, robust, c, engine)
    if fallback:
        if prune is not None or runtime is not None or keep_ledger:
            raise ValueError(
                "this calibration has uncertified varying fields "
                f"({cal.unresolved()}): robust search runs the "
                "conservative vertex sweep, which supports neither "
                "prune='bound' nor runtime= nor keep_ledger=True — "
                "certify the field directions (core.calibration.MONOTONE) "
                "to use the worst-corner fast path")
        if objective not in ("edp", "pareto"):
            raise ValueError(f"unknown objective {objective!r}; "
                             f"pick 'edp' or 'pareto'")
        return _robust_vertex_search(wl, constraints, cal, engine, grid,
                                     n_z, objective, pareto_metrics,
                                     factorized, space, hierarchical)
    rt = SearchRuntime.of(runtime) if runtime is not None else None
    if rt is None:
        res = _search_impl(wl, constraints, engine, grid, n_z,
                           hierarchical, c, interpret, objective,
                           pareto_metrics, shard, chunk_size, factorized,
                           space, prune, None, keep_ledger, workers,
                           deterministic)
    else:
        with _activate_rt(rt):
            res = _search_impl(wl, constraints, engine, grid, n_z,
                               hierarchical, c, interpret, objective,
                               pareto_metrics, shard, chunk_size,
                               factorized, space, prune, rt, keep_ledger,
                               workers, deterministic)
    if cal is not None:
        res.band = _measure_band(res, cal, wl)
    return res


def _search_impl(wl, constraints, engine, grid, n_z, hierarchical, c,
                 interpret, objective, pareto_metrics, shard, chunk_size,
                 factorized, space, prune, rt, keep_ledger=False,
                 workers=None, deterministic=True):
    if factorized:
        from .factorized import LedgerRecorder
        fspace = _factorized_space(space, grid, n_z, engine, hierarchical)
        led = LedgerRecorder() if keep_ledger else None
        if objective == "edp":
            if prune == "bound":
                if workers is not None:
                    from repro.parallel.slab_sched import parallel_bnb
                    return parallel_bnb(fspace, wl, constraints, engine,
                                        c, interpret, shard, chunk_size,
                                        objective="edp", metrics=None,
                                        workers=workers,
                                        deterministic=deterministic,
                                        rt=rt, led=led)
                return _search_factorized_bnb(fspace, wl, constraints,
                                              engine, c, interpret, shard,
                                              chunk_size, rt, led)
            return _search_factorized(fspace, wl, constraints, engine, c,
                                      interpret, shard, chunk_size, rt)
        if objective != "pareto":
            raise ValueError(f"unknown objective {objective!r}; "
                             f"pick 'edp' or 'pareto'")
        metrics = _check_pareto_metrics(engine, pareto_metrics)
        if prune == "bound":
            if workers is not None:
                from repro.parallel.slab_sched import parallel_bnb
                return parallel_bnb(fspace, wl, constraints, engine, c,
                                    interpret, shard, chunk_size,
                                    objective="pareto", metrics=metrics,
                                    workers=workers,
                                    deterministic=deterministic,
                                    rt=rt, led=led)
            return _pareto_factorized_bnb(fspace, wl, constraints, engine,
                                          c, interpret, metrics, shard,
                                          chunk_size, rt, led)
        return _pareto_factorized(fspace, wl, constraints, engine, c,
                                  interpret, metrics, shard, chunk_size, rt)
    if space is not None:
        raise ValueError("space= requires factorized=True (pass grid= for "
                         "materialized candidate sets)")
    grid = _full_grid(n_z) if grid is None else _check_grid(grid)
    if objective == "edp":
        return _search_streamed(grid, wl, constraints, engine, hierarchical,
                                c, interpret, shard, chunk_size, rt)
    if objective != "pareto":
        raise ValueError(f"unknown objective {objective!r}; "
                         f"pick 'edp' or 'pareto'")
    metrics = _check_pareto_metrics(engine, pareto_metrics)
    return _pareto_streamed(grid, wl, constraints, engine, hierarchical, c,
                            interpret, metrics, shard, chunk_size, rt)


def _union_prefiltered(chunk, wls, names, cons_for, c, hierarchical):
    """The batched analogue of `_prefiltered`: union of the per-workload
    area/power survivor sets (the kernel still applies each workload's
    exact constraints). One base-column sweep of the chunk covers all
    workloads; identical (sram, bounds) buckets are deduped
    (`hw_prefilter_masks`)."""
    if not hierarchical:
        return chunk
    masks = hw_prefilter_masks(chunk, [wls[name] for name in names],
                               [cons_for(name) for name in names], c)
    union = np.zeros(len(chunk), dtype=bool)
    for mask in masks:
        union |= mask
    return chunk[union]


def _workloads_pallas_streamed(wls, names, cons_for, grid, hierarchical, c,
                               interpret, objective, metrics, shard,
                               chunk_size):
    """Batched pallas driver over a materialized grid: each chunk (the
    whole grid when `chunk_size` is None) is one fused launch covering all
    W workloads; per-workload carries (best EDP / running front) ride
    between launches."""
    from repro.kernels.ops import dse_pareto_multi, dse_search_multi
    t0 = time.perf_counter()
    n = len(grid)
    cs = int(chunk_size) if chunk_size else max(n, 1)
    wl_list = [wls[nm] for nm in names]
    cons_list = [cons_for(nm) for nm in names]
    n_wl = 0
    if objective == "edp":
        best = {nm: (None, float("inf")) for nm in names}
        nf = {nm: 0 for nm in names}
        for chunk in _iter_chunks(grid, cs):
            sub = _union_prefiltered(chunk, wls, names, cons_for, c,
                                     hierarchical)
            n_wl += len(sub)
            if len(sub) == 0:
                continue
            carry = [best[nm][1] for nm in names]
            bi, be, bn = dse_search_multi(sub, wl_list, cons_list, c,
                                          interpret, shard=shard,
                                          carry_edp=carry)
            for nm, i, e, f in zip(names, bi, be, bn):
                nf[nm] += f
                if i >= 0:
                    best[nm] = (sub[i], e)
        wall = time.perf_counter() - t0
        return {nm: _make_result(best[nm][0], nf[nm], wls[nm], c, n, n_wl,
                                 wall)
                for nm in names}

    run = {nm: _empty_run_state() for nm in names}
    nf = {nm: 0 for nm in names}
    n_over = {nm: 0 for nm in names}
    for chunk in _iter_chunks(grid, cs):
        sub = _union_prefiltered(chunk, wls, names, cons_for, c,
                                 hierarchical)
        n_wl += len(sub)
        if len(sub) == 0:
            continue
        carry_points = [
            _pallas_front_points(run[nm][0], wls[nm], c, interpret, metrics)
            if len(run[nm][0]) else None
            for nm in names]
        per_wl = dse_pareto_multi(sub, wl_list, cons_list, c, interpret,
                                  objectives=metrics, shard=shard,
                                  carry_points=carry_points)
        for nm, (cand_idx, f, o) in zip(names, per_wl):
            nf[nm] += f
            n_over[nm] += o
            if len(cand_idx):
                run[nm] = _merge_running_front(
                    run[nm][0], run[nm][1], sub[cand_idx], wls[nm],
                    cons_for(nm), c, metrics)
    wall = time.perf_counter() - t0
    out = {}
    for nm in names:
        with span("search.refine"):
            front, met, _ = _pareto_from_rows(run[nm][0], wls[nm],
                                              cons_for(nm), c, metrics,
                                              m=run[nm][1])
        out[nm] = ParetoResult(front=front, metrics=met, objectives=metrics,
                               n_evaluated=n, n_feasible=nf[nm],
                               n_workload_evals=n_wl, wall_time_s=wall,
                               n_overflow=n_over[nm])
    return out


@traced("search")
@_counts_gemm_lanes
def search_workloads(wls: Union[Mapping[str, Workload], Sequence[Workload]],
                     constraints: Union[Constraints,
                                        Mapping[str, Constraints]]
                     = Constraints(), *,
                     engine: str = "pallas",
                     grid: Optional[np.ndarray] = None, n_z: int = 12,
                     hierarchical: bool = False,
                     c: DeviceConstants = CONSTANTS,
                     interpret: Optional[bool] = None, objective: str = "edp",
                     pareto_metrics: tuple = DEFAULT_OBJECTIVES,
                     shard: Optional[int] = None,
                     chunk_size: Optional[int] = None,
                     factorized: bool = False, space=None,
                     prune: Optional[str] = None, runtime=None,
                     keep_ledger: bool = False,
                     workers: Optional[int] = None,
                     deterministic: bool = True,
                     calibration=None, robust: Optional[str] = None
                     ) -> Dict[str, Union[SearchResult, ParetoResult]]:
    """Batched search: many workloads against one grid.

    On the `pallas` engine all workloads are evaluated in a *single* fused
    kernel launch (their GEMM lists unrolled back-to-back, constraints as a
    dynamic (W, 4) operand) — constraint-scenario sweeps hit one jit cache
    entry. Other engines fall back to a per-workload loop. With
    `hierarchical=True` the compacted grid is the union of the per-workload
    area/power survivor sets (the kernel still applies each workload's exact
    constraints). `objective="pareto"` returns each workload's frontier
    (ParetoResult) instead of its min-EDP point; on pallas the per-block
    dominance reduction for all workloads still shares the one launch. Each
    returned result reports the whole batch's wall time (the launch is
    shared). `shard=` / `chunk_size=` stream and fan out exactly as in
    `search` — on pallas each chunk remains one all-workloads launch, with
    per-workload carries (best EDP / running front) composing the chunks.
    `factorized=True` evaluates a product `space` from axis factor tables
    exactly as in `search` — on pallas the batched launches decode their
    candidates on device. `prune="bound"` runs the bound-guided
    branch-and-bound driver per workload (the slab tree is specialized by
    each workload's bounds and incumbent, so there is no shared batched
    launch to fuse — wall time reports the whole batch as usual).
    `runtime=` attaches the resilient control plane as in `search`; the
    batch runs as a per-workload loop (full checkpoint/resume per
    workload, each under `<checkpoint_dir>/<workload name>`); every
    sub-search shares the batch campaign's fault injector, and each
    result carries its own workload's counters. `keep_ledger=True`
    retains each workload's slab partition on its result exactly as in
    `search` (requires `prune="bound"`). `workers=` / `deterministic=`
    fan each workload's slab queue out across the leased scheduler
    exactly as in `search` (a fresh worker pool per workload — the slab
    tree is per-workload, so there is nothing to share).
    `calibration=` / `robust=` carry
    calibration uncertainty exactly as in `search`, resolved once for the
    whole batch: the fused all-workloads launches simply run at the
    calibration's worst corner (the worst-corner reduction is
    engine-agnostic), and every result carries its own workload's
    uncertainty band on ``result.band``.
    """
    if not isinstance(wls, Mapping):
        wls = {wl.name: wl for wl in wls}
    if objective not in ("edp", "pareto"):
        raise ValueError(f"unknown objective {objective!r}; "
                         f"pick 'edp' or 'pareto'")
    c, cal, fallback = _resolve_robust(calibration, robust, c, engine)
    if fallback:
        if prune is not None or runtime is not None or keep_ledger:
            raise ValueError(
                "this calibration has uncertified varying fields "
                f"({cal.unresolved()}): robust search runs the "
                "conservative vertex sweep, which supports neither "
                "prune='bound' nor runtime= nor keep_ledger=True — "
                "certify the field directions (core.calibration.MONOTONE) "
                "to use the worst-corner fast path")
        _check_stream_args(shard, chunk_size)
        out = {name: _robust_vertex_search(
                   wl, (constraints[name] if isinstance(constraints,
                                                        Mapping)
                        else constraints), cal, engine, grid, n_z,
                   objective, pareto_metrics, factorized, space,
                   hierarchical)
               for name, wl in wls.items()}
        total = sum(r.wall_time_s for r in out.values())
        for r in out.values():
            r.wall_time_s = total
        return out
    out = _search_workloads_impl(wls, constraints, engine, grid, n_z,
                                 hierarchical, c, interpret, objective,
                                 pareto_metrics, shard, chunk_size,
                                 factorized, space, prune, runtime,
                                 keep_ledger, workers, deterministic)
    if cal is not None:
        for name, r in out.items():
            r.band = _measure_band(r, cal, wls[name])
    return out


def _search_workloads_impl(wls, constraints, engine, grid, n_z,
                           hierarchical, c, interpret, objective,
                           pareto_metrics, shard, chunk_size, factorized,
                           space, prune, runtime, keep_ledger,
                           workers=None, deterministic=True
                           ) -> Dict[str, Union[SearchResult,
                                                ParetoResult]]:
    """The batched dispatch behind `search_workloads`, post calibration
    resolution (`c` is already the corner the batch should run at)."""
    _check_stream_args(shard, chunk_size)
    _check_prune_arg(prune, factorized)
    if keep_ledger and prune != "bound":
        raise ValueError("keep_ledger=True records the bound-guided slab "
                         "partition; it requires prune='bound'")
    if workers is not None and prune != "bound":
        raise ValueError("workers= fans out the bound-guided slab queue; "
                         "it requires prune='bound' (factorized=True)")
    rt0 = SearchRuntime.of(runtime) if runtime is not None else None
    if grid is not None:
        grid = _check_grid(grid)

    def cons_for(name):
        return constraints[name] if isinstance(constraints, Mapping) \
            else constraints

    def rt_for(name):
        """Per-workload campaign (own counters + checkpoint subdirectory)
        sharing the batch runtime's fault injector."""
        if rt0 is None:
            return None
        pol = rt0.policy
        if pol.checkpoint_dir:
            pol = dataclasses.replace(
                pol, checkpoint_dir=os.path.join(pol.checkpoint_dir, name))
        sub = SearchRuntime(pol)
        sub.fault_injector = rt0.fault_injector
        return sub

    if prune == "bound":
        # Same argument contract as search(): a materialized grid or the
        # hierarchical prefilter cannot combine with the factorized slab
        # pruning — validate here rather than silently searching the
        # default product space.
        _factorized_space(space, grid, n_z, engine, hierarchical)
        out = {name: search(wl, cons_for(name), engine=engine, n_z=n_z,
                            c=c, interpret=interpret, objective=objective,
                            pareto_metrics=pareto_metrics, shard=shard,
                            chunk_size=chunk_size, factorized=True,
                            space=space, prune="bound",
                            runtime=rt_for(name), keep_ledger=keep_ledger,
                            workers=workers, deterministic=deterministic)
               for name, wl in wls.items()}
        total = sum(r.wall_time_s for r in out.values())
        for r in out.values():
            r.wall_time_s = total
        return out

    if factorized and engine == "pallas" and rt0 is None:
        fspace = _factorized_space(space, grid, n_z, engine, hierarchical)
        names = list(wls)
        metrics = (_check_pareto_metrics(engine, pareto_metrics)
                   if objective == "pareto" else None)
        return _workloads_pallas_factorized(wls, names, cons_for, fspace,
                                            c, interpret, objective,
                                            metrics, shard, chunk_size)
    if engine != "pallas" or rt0 is not None:
        # The resilient runtime always takes the per-workload loop: the
        # fused batched launches return byte-identical results, so the
        # only cost is launch count — and per-workload campaigns are what
        # make the checkpoint cursors and counters well-defined.
        if grid is None and not factorized:
            grid = _full_grid(n_z)  # materialize once, share across workloads
        out = {name: search(wl, cons_for(name), engine=engine, grid=grid,
                            n_z=n_z, hierarchical=hierarchical, c=c,
                            interpret=interpret, objective=objective,
                            pareto_metrics=pareto_metrics, shard=shard,
                            chunk_size=chunk_size, factorized=factorized,
                            space=space, runtime=rt_for(name))
               for name, wl in wls.items()}
        total = sum(r.wall_time_s for r in out.values())
        for r in out.values():
            r.wall_time_s = total
        return out
    if space is not None:
        raise ValueError("space= requires factorized=True (pass grid= for "
                         "materialized candidate sets)")
    if grid is None:
        grid = _full_grid(n_z)
    metrics = (_check_pareto_metrics(engine, pareto_metrics)
               if objective == "pareto" else None)
    return _workloads_pallas_streamed(wls, list(wls), cons_for, grid,
                                      hierarchical, c, interpret, objective,
                                      metrics, shard, chunk_size)
