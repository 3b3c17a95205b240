"""Frontier-equivalence tests: `search(..., objective="pareto")` must return
byte-identical frontiers (config rows and reference-model metrics) from all
four backends — python oracle, numpy, jax sort-and-scan, pallas per-block
dominance kernel — flat and hierarchical, on sampled grids, the full 12^5
grid, and the edge cases (ties, single point, zero feasible, overflowing
block-local fronts). Mirrors tests/test_search_engines.py for the EDP mode.
"""
import numpy as np
import pytest

from repro.core import (Constraints, PARETO_ENGINES, REPORT_METRICS,
                        config_grid, pareto_front, pareto_mask,
                        pareto_search_refined, search, search_workloads)
from repro.core.paper_workloads import PAPER_WORKLOADS, load

ALL_ENGINES = sorted(PARETO_ENGINES)


def _sample_grid(seed, size=3000):
    rng = np.random.default_rng(seed)
    return np.unique(rng.integers(1, 13, size=(size, 5)), axis=0)


def _assert_same_front(ref, got, label):
    assert np.array_equal(got.front, ref.front), label
    assert got.n_feasible == ref.n_feasible, label
    assert got.n_evaluated == ref.n_evaluated, label
    assert got.objectives == ref.objectives, label
    for k in REPORT_METRICS:
        assert np.array_equal(got.metrics[k], ref.metrics[k]), (label, k)


# ---------------------------------------------------------------------------
# pareto_mask edge cases
# ---------------------------------------------------------------------------

def test_pareto_mask_exact_ties_kept():
    pts = np.array([[1.0, 2.0], [1.0, 2.0], [2.0, 1.0], [2.0, 2.0]])
    assert pareto_mask(pts).tolist() == [True, True, True, False]


def test_pareto_mask_tie_on_first_metric_regression():
    # Regression: sorting by metric 0 alone let [1, 3] survive its
    # dominator [1, 2] when they tie on the first metric; the full
    # lexicographic order must eliminate it regardless of input order.
    assert pareto_mask(np.array([[1.0, 3.0], [1.0, 2.0]])).tolist() \
        == [False, True]
    assert pareto_mask(np.array([[1.0, 2.0], [1.0, 3.0]])).tolist() \
        == [True, False]


def test_pareto_mask_single_point_and_empty():
    assert pareto_mask(np.array([[3.0, 7.0, 1.0]])).tolist() == [True]
    assert pareto_mask(np.zeros((0, 3))).tolist() == []


def test_pareto_mask_all_dominated_column():
    # One point dominates every other on all metrics: front is that single
    # point, whatever the column being swept looks like.
    pts = np.stack([np.arange(1.0, 9.0), np.arange(1.0, 9.0)], axis=1)
    assert pareto_mask(pts).tolist() == [True] + [False] * 7


def test_pareto_mask_constant_column_ignored():
    # A metric on which every point ties contributes nothing: the mask must
    # equal the mask over the remaining metrics.
    rng = np.random.default_rng(0)
    pts = rng.random((64, 2))
    padded = np.column_stack([pts[:, 0], np.full(64, 5.0), pts[:, 1]])
    assert pareto_mask(padded).tolist() == pareto_mask(pts).tolist()


# ---------------------------------------------------------------------------
# Cross-backend frontier equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wname", sorted(PAPER_WORKLOADS))
def test_all_engines_identical_per_workload(wname):
    wl = load(wname)
    cons = Constraints()
    grid = _sample_grid(sorted(PAPER_WORKLOADS).index(wname))
    ref = search(wl, cons, engine="python", grid=grid, objective="pareto")
    assert ref.feasible  # the sampled grid always contains feasible configs
    assert len(ref.front) == len(ref.metrics["edp"])
    for eng in ALL_ENGINES:
        _assert_same_front(ref, search(wl, cons, engine=eng, grid=grid,
                                       objective="pareto"),
                           f"{eng}/{wname}")
        _assert_same_front(ref, search(wl, cons, engine=eng, grid=grid,
                                       objective="pareto",
                                       hierarchical=True),
                           f"{eng}/{wname}/hierarchical")


def test_engines_on_full_grid_match():
    # The acceptance bar: identical frontiers on the full 12^5 grid in
    # interpret mode. numpy flat is the float64 reference; the other
    # backends run hierarchical (the prefilter only drops area/power-
    # infeasible configs, which can never reach the feasible frontier).
    wl = load("deit-b")
    cons = Constraints()
    ref = search(wl, cons, engine="numpy", objective="pareto")
    assert ref.feasible
    for eng in ("python", "jax", "pallas"):
        _assert_same_front(ref, search(wl, cons, engine=eng,
                                       objective="pareto",
                                       hierarchical=True),
                           f"{eng}/full")


def test_frontier_contains_min_edp_and_duplicates_kept():
    wl = load("deit-t")
    cons = Constraints()
    grid = _sample_grid(29, size=1500)
    # Duplicate every row: exact metric ties must be kept, so each frontier
    # config shows up exactly twice, on every backend.
    doubled = np.concatenate([grid, grid], axis=0)
    ref = search(wl, cons, engine="numpy", grid=doubled, objective="pareto")
    uniq, counts = np.unique(ref.front, axis=0, return_counts=True)
    assert (counts == 2).all()
    for eng in ("python", "jax", "pallas"):
        _assert_same_front(ref, search(wl, cons, engine=eng, grid=doubled,
                                       objective="pareto"), eng)
    # The min-EDP config is never dominated on any objective set that
    # includes edp, so it is on the frontier.
    best = search(wl, cons, engine="numpy", grid=grid).best_cfg
    assert any((row == best.as_array()).all() for row in uniq)


@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("hierarchical", [False, True])
def test_zero_feasible_empty_front(engine, hierarchical):
    wl = load("deit-t")
    impossible = Constraints(area_mm2=1.0, power_w=0.01, energy_mj=1e-9,
                             latency_ms=1e-9)
    grid = _sample_grid(7, size=500)
    r = search(wl, impossible, engine=engine, grid=grid, objective="pareto",
               hierarchical=hierarchical)
    assert not r.feasible
    assert r.size == 0
    assert r.front.shape == (0, 5)
    assert r.n_feasible == 0
    assert r.n_evaluated == len(grid)
    assert all(len(r.metrics[k]) == 0 for k in REPORT_METRICS)


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_single_point_grid(engine):
    wl = load("deit-t")
    cons = Constraints()
    grid = np.array([[1, 1, 8, 8, 8]])
    r = search(wl, cons, engine=engine, grid=grid, objective="pareto")
    assert r.n_evaluated == 1
    if r.feasible:
        assert np.array_equal(r.front, grid)


def test_pallas_block_overflow_at_real_bound_host_refine_taken():
    # Force a genuine per-block frontier overflow at the *real* MAX_FRONT:
    # a full 2048-config block of exact duplicates of a feasible config is
    # 2048 mutually non-dominated ties — far past the 128-index emission
    # bound — so the kernel must report the true count and the host must
    # refine the whole block. A second duplicate run rides in the *partial*
    # last block, so the fallback's arange is also clipped to the grid.
    from repro.kernels import dse_eval, dse_pareto_multi
    wl = load("deit-t")
    cons = Constraints()
    best = search(wl, cons, engine="numpy", grid=_sample_grid(2)).best_cfg
    dup = np.tile(best.as_array(), (dse_eval.BLOCK, 1))
    filler = _sample_grid(43, size=1100)
    tail_dup = np.tile(best.as_array(), (dse_eval.MAX_FRONT + 33, 1))
    grid = np.concatenate([dup, filler, tail_dup], axis=0)
    assert len(grid) % dse_eval.BLOCK != 0  # last block really is partial

    # The fallback is observably taken: every row of the overflowing block
    # joins the candidate list, which the <=MAX_FRONT emission path alone
    # could never produce — and nothing past len(grid) leaks in.
    (cand, nf, n_over), = dse_pareto_multi(grid, [wl], [cons])
    assert set(range(dse_eval.BLOCK)) <= set(cand.tolist())
    assert cand.max() < len(grid)
    # Both duplicate runs overflowed their blocks, and the kernel says so.
    assert n_over >= 2

    # End-to-end exactness: every duplicate is an exact tie, so all
    # BLOCK + MAX_FRONT + 33 copies are on the frontier, byte-identically
    # to the float64 reference.
    ref = search(wl, cons, engine="numpy", grid=grid, objective="pareto")
    got = search(wl, cons, engine="pallas", grid=grid, objective="pareto")
    _assert_same_front(ref, got, "real-bound overflow")
    n_copies = int((got.front == best.as_array()).all(axis=1).sum())
    assert n_copies == dse_eval.BLOCK + dse_eval.MAX_FRONT + 33


@pytest.mark.parametrize("objectives", [("area", "power", "edp"),
                                        ("energy", "latency")])
def test_pallas_block_front_is_exact_local_front(objectives):
    # The kernel's dominance pass is full pairwise (no presort), so each
    # block emits exactly its local non-dominated feasible set in the
    # kernel's float32 metric space — no superset slack.
    from repro.kernels import dse_eval, dse_eval_grid, dse_pareto_multi
    wl = load("deit-t")
    cons = Constraints()
    grid = _sample_grid(7, size=6000)
    assert len(grid) > 2 * dse_eval.BLOCK
    (cand, _, n_over), = dse_pareto_multi(grid, [wl], [cons],
                                          objectives=objectives)
    assert n_over == 0
    m = dse_eval_grid(grid, wl).astype(np.float32)
    bounds = np.float32([cons.area_mm2, cons.power_w, cons.energy_j,
                         cons.latency_s])
    ok = (m < bounds).all(axis=1)
    vals = {"area": m[:, 0], "power": m[:, 1], "energy": m[:, 2],
            "latency": m[:, 3], "edp": m[:, 2] * m[:, 3]}
    pts = np.stack([vals[k] for k in objectives], axis=1)
    want = []
    for lo in range(0, len(grid), dse_eval.BLOCK):
        idx = np.arange(lo, min(lo + dse_eval.BLOCK, len(grid)))
        idx = idx[ok[idx]]
        want.append(idx[pareto_mask(pts[idx])])
    assert np.array_equal(cand, np.concatenate(want))


def test_pallas_block_overflow_falls_back_exact():
    # A grid whose feasible points are mutually non-dominated by
    # construction (distinct configs -> distinct metric trade-offs can't be
    # guaranteed, so force it through MAX_FRONT instead): shrink the bound
    # so block-local fronts overflow and the host must refine whole blocks.
    from repro.kernels import dse_eval
    wl = load("deit-t")
    cons = Constraints()
    grid = _sample_grid(13, size=2500)
    ref = search(wl, cons, engine="numpy", grid=grid, objective="pareto")
    old = dse_eval.MAX_FRONT
    try:
        dse_eval.MAX_FRONT = 2
        dse_eval.PARETO_ROWS = dse_eval.PARETO_HEADER + 2
        dse_eval.dse_pareto_padded.clear_cache()
        _assert_same_front(ref, search(wl, cons, engine="pallas", grid=grid,
                                       objective="pareto"), "overflow")
    finally:
        dse_eval.MAX_FRONT = old
        dse_eval.PARETO_ROWS = dse_eval.PARETO_HEADER + old
        dse_eval.dse_pareto_padded.clear_cache()


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_search_workloads_pareto_matches_individual(engine):
    wls = {name: load(name) for name in sorted(PAPER_WORKLOADS)}
    cons = Constraints()
    grid = _sample_grid(3, size=1500)
    batch = search_workloads(wls, cons, engine=engine, grid=grid,
                             objective="pareto")
    for name, wl in wls.items():
        _assert_same_front(search(wl, cons, engine="numpy", grid=grid,
                                  objective="pareto"),
                           batch[name], f"batch/{engine}/{name}")


def test_search_workloads_pareto_per_workload_constraints():
    wls = {name: load(name) for name in ("deit-t", "bert-l")}
    cons = {"deit-t": Constraints(),
            "bert-l": Constraints(area_mm2=1.0, power_w=0.01)}
    grid = _sample_grid(5, size=1500)
    batch = search_workloads(wls, cons, engine="pallas", grid=grid,
                             objective="pareto", hierarchical=True)
    ref = search(wls["deit-t"], cons["deit-t"], engine="numpy", grid=grid,
                 objective="pareto")
    assert np.array_equal(batch["deit-t"].front, ref.front)
    assert not batch["bert-l"].feasible


def test_objective_and_metric_validation():
    wl = load("deit-t")
    with pytest.raises(ValueError, match="objective"):
        search(wl, objective="latency")
    with pytest.raises(ValueError, match="pareto_metrics"):
        search(wl, objective="pareto", pareto_metrics=("area", "speed"))
    with pytest.raises(ValueError, match="util"):
        search(wl, engine="pallas", objective="pareto",
               pareto_metrics=("area", "util"))


def test_custom_objectives_cross_backend():
    wl = load("deit-s")
    cons = Constraints()
    grid = _sample_grid(17, size=1200)
    metrics = ("energy", "latency")
    ref = search(wl, cons, engine="numpy", grid=grid, objective="pareto",
                 pareto_metrics=metrics)
    assert ref.objectives == metrics
    for eng in ("python", "jax", "pallas"):
        _assert_same_front(ref, search(wl, cons, engine=eng, grid=grid,
                                       objective="pareto",
                                       pareto_metrics=metrics), eng)


# ---------------------------------------------------------------------------
# pareto_front routing + significance-guided refinement
# ---------------------------------------------------------------------------

def test_pareto_front_reuses_prefilter_survivors():
    wl = load("deit-t")
    cons = Constraints()
    grid = _sample_grid(11)
    flat = pareto_front(grid, wl, constraints=cons)
    hier = pareto_front(grid, wl, constraints=cons, hierarchical=True)
    assert np.array_equal(flat[0], hier[0])
    for k in flat[1]:
        assert np.array_equal(flat[1][k], hier[1][k])
    # the engine-layer route really pruned: survivors < grid
    r = search(wl, cons, grid=grid, objective="pareto", hierarchical=True)
    assert r.n_workload_evals < len(grid)


def test_pareto_front_unconstrained_keeps_legacy_behaviour():
    wl = load("deit-t")
    grid = _sample_grid(19, size=800)
    front, met = pareto_front(grid, wl, metrics=("area", "edp"))
    from repro.core import evaluate_grid
    m = evaluate_grid(grid, wl)
    pts = np.stack([m["area"], m["edp"]], axis=1)
    expect = grid[pareto_mask(pts)]
    assert np.array_equal(front, expect[np.lexsort(expect.T[::-1])])
    assert sorted(met) == ["area", "edp"]


def test_pareto_search_refined_improves_or_matches_coarse():
    from repro.core import build_search_space, observe_significance
    from repro.core.search import _space_to_grid
    wl = load("deit-t")
    cons = Constraints()
    sig = observe_significance()
    coarse = search(wl, cons, engine="numpy",
                    grid=_space_to_grid(build_search_space(12, 2, sig)),
                    objective="pareto")
    refined = pareto_search_refined(wl, cons, engine="numpy",
                                    significance=sig)
    assert refined.feasible
    assert refined.n_evaluated > coarse.n_evaluated
    # No refined frontier point is dominated by any coarse frontier point.
    cpts = np.stack([coarse.metrics[k] for k in coarse.objectives], axis=1)
    rpts = np.stack([refined.metrics[k] for k in refined.objectives], axis=1)
    for p in rpts:
        assert not np.any(np.all(cpts <= p, axis=1)
                          & np.any(cpts < p, axis=1))


def test_refinement_sets_shapes():
    from repro.core import observe_significance, refinement_sets, significant_params
    sig = observe_significance()
    front = np.array([[2, 2, 4, 6, 8], [4, 2, 4, 6, 8]])
    sets = refinement_sets(sig, front, n_z=12, top_k=2, radius=1)
    fine = set(significant_params(sig, top_k=2))
    for name, vals in sets.items():
        assert vals == sorted(set(vals))
        assert min(vals) >= 1 and max(vals) <= 12
        if name not in fine:
            j = ["n_t", "n_c", "n_h", "n_v", "n_lambda"].index(name)
            assert vals == sorted(set(front[:, j].tolist()))
