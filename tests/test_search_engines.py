"""Engine-equivalence tests: the four SearchEngine backends (python, numpy,
jax, pallas) must return identical results — best_cfg, n_feasible, and the
finalized metrics — on every paper workload, flat and hierarchical, plus the
zero-feasible edge case and the batched multi-workload path."""
import importlib

import numpy as np
import pytest

from repro.core import (ENGINES, Constraints, config_grid, dxpta_search,
                        hw_prefilter, search, search_workloads)
from repro.core.paper_workloads import PAPER_WORKLOADS, load

ALL_ENGINES = sorted(ENGINES)


def _sample_grid(seed, size=3000):
    rng = np.random.default_rng(seed)
    return np.unique(rng.integers(1, 13, size=(size, 5)), axis=0)


def _assert_same(ref, got, label):
    assert got.best_cfg == ref.best_cfg, label
    assert got.n_feasible == ref.n_feasible, label
    assert got.n_evaluated == ref.n_evaluated, label
    for f in ("area_mm2", "power_w", "energy_j", "latency_s", "edp"):
        a, b = getattr(ref, f), getattr(got, f)
        assert (a == b) or (np.isnan(a) and np.isnan(b)), (label, f)


@pytest.mark.parametrize("wname", sorted(PAPER_WORKLOADS))
def test_all_engines_identical_per_workload(wname):
    wl = load(wname)
    cons = Constraints()
    grid = _sample_grid(sorted(PAPER_WORKLOADS).index(wname))
    ref = search(wl, cons, engine="python", grid=grid)
    assert ref.feasible  # the sampled grid always contains feasible configs
    for eng in ALL_ENGINES:
        _assert_same(ref, search(wl, cons, engine=eng, grid=grid),
                     f"{eng}/{wname}")
        _assert_same(ref, search(wl, cons, engine=eng, grid=grid,
                                 hierarchical=True),
                     f"{eng}/{wname}/hierarchical")


def test_engines_on_full_grid_match():
    wl = load("deit-b")
    cons = Constraints()
    ref = search(wl, cons, engine="numpy")
    for eng in ("jax", "pallas"):
        _assert_same(ref, search(wl, cons, engine=eng), f"{eng}/full")
        _assert_same(ref, search(wl, cons, engine=eng, hierarchical=True),
                     f"{eng}/full/hierarchical")


@pytest.mark.parametrize("engine", ALL_ENGINES)
@pytest.mark.parametrize("hierarchical", [False, True])
def test_zero_feasible_configs(engine, hierarchical):
    wl = load("deit-t")
    impossible = Constraints(area_mm2=1.0, power_w=0.01, energy_mj=1e-9,
                             latency_ms=1e-9)
    grid = _sample_grid(7, size=500)
    r = search(wl, impossible, engine=engine, grid=grid,
               hierarchical=hierarchical)
    assert not r.feasible
    assert r.best_cfg is None
    assert r.n_feasible == 0
    assert r.n_evaluated == len(grid)
    assert np.isnan(r.area_mm2) and r.edp == float("inf")


def test_hierarchical_prunes_but_preserves_result():
    wl = load("bert-l")
    cons = Constraints()
    grid = _sample_grid(11)
    flat = search(wl, cons, engine="pallas", grid=grid)
    hier = search(wl, cons, engine="pallas", grid=grid, hierarchical=True)
    _assert_same(flat, hier, "hierarchical")
    n_survivors = int(hw_prefilter(grid, wl, cons).sum())
    assert hier.n_workload_evals == n_survivors < flat.n_workload_evals


@pytest.mark.parametrize("engine", ALL_ENGINES)
def test_search_workloads_matches_individual(engine):
    wls = {name: load(name) for name in sorted(PAPER_WORKLOADS)}
    cons = Constraints()
    grid = _sample_grid(3, size=1500)
    batch = search_workloads(wls, cons, engine=engine, grid=grid)
    for name, wl in wls.items():
        _assert_same(search(wl, cons, engine="numpy", grid=grid),
                     batch[name], f"batch/{engine}/{name}")


def test_search_workloads_per_workload_constraints_and_hierarchy():
    wls = {name: load(name) for name in ("deit-t", "bert-l")}
    cons = {"deit-t": Constraints(),
            "bert-l": Constraints(area_mm2=1.0, power_w=0.01)}
    grid = _sample_grid(5, size=1500)
    batch = search_workloads(wls, cons, engine="pallas", grid=grid,
                             hierarchical=True)
    ref = search(wls["deit-t"], cons["deit-t"], engine="numpy", grid=grid)
    assert batch["deit-t"].best_cfg == ref.best_cfg
    assert batch["deit-t"].n_feasible == ref.n_feasible
    assert not batch["bert-l"].feasible


def test_search_rejects_unknown_engine():
    with pytest.raises(ValueError, match="unknown engine"):
        search(load("deit-t"), engine="cuda")


@pytest.mark.parametrize("name, engines", [
    ("ENGINES", {"python", "numpy", "jax", "pallas"}),
    ("PARETO_ENGINES", {"python", "numpy", "jax", "pallas"}),
    ("FACTORIZED_ENGINES", {"numpy", "jax", "pallas"}),
    ("ROBUST_ENGINES", {"numpy", "jax", "pallas"}),
])
def test_public_engine_name_sets(name, engines):
    # Parametrised tests across the suite take their engine lists from
    # these names: a wrong set would drop cases instead of failing one.
    names = getattr(importlib.import_module("repro.core.search"), name)
    assert set(names) == engines and len(names) == len(engines)


@pytest.mark.parametrize("engine, kwargs, match", [
    ("python", {"factorized": True}, "factorized"),
    ("cuda", {}, "unknown engine"),
    ("cuda", {"objective": "pareto"}, "unknown engine"),
])
def test_engines_outside_the_sets_are_refused(engine, kwargs, match):
    with pytest.raises(ValueError, match=match):
        search(load("deit-t"), engine=engine, n_z=2, **kwargs)


def test_dxpta_search_engine_dispatch():
    wl = load("deit-s")
    cons = Constraints()
    seq = dxpta_search(wl, cons)  # paper-faithful python loop
    for eng in ("numpy", "jax", "pallas"):
        r = dxpta_search(wl, cons, engine=eng)
        assert r.best_cfg == seq.best_cfg
        assert r.n_feasible == seq.n_feasible


def test_arbitrary_grid_sizes_no_padding_required():
    # Exercises the pad+mask wrapper: sizes around the BLOCK boundary,
    # including pruned-candidate-set-like tiny grids.
    from repro.kernels.dse_eval import BLOCK
    wl = load("deit-t")
    cons = Constraints()
    for g in (1, 3, BLOCK - 1, BLOCK, BLOCK + 1):
        rng = np.random.default_rng(g)
        grid = rng.integers(1, 13, size=(g, 5))
        r = search(wl, cons, engine="pallas", grid=grid)
        _assert_same(search(wl, cons, engine="numpy", grid=grid), r,
                     f"G={g}")
