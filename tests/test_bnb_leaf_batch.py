"""A branch-and-bound leaf batch on its way to a launch.

Three helpers turn a (B, 5, 2) leaf batch into work: `slab_indices_batch`
(the ascending flat indices of every member), `_bnb_batch_slices` (the
greedy [s, e) slices of at most BNB_BATCH points) and `_bnb_coarse_batch`
(the pallas launch-form test). Each is pinned here against its per-slab
reference form, kept in this file: the same values, dtype and order. Then
the drivers are pinned end to end on a small space — a cold search and
warm `SearchService` deltas under numpy and pallas — to winners and
counters frozen from the per-slab implementation.
"""
import numpy as np
import pytest

from repro.core import Constraints, FactorizedSpace, search
from repro.core.factorized import slab_indices, slab_indices_batch, slab_size
from repro.core.paper_workloads import load
from repro.core.search import (BNB_BATCH, BNB_FINE, _bnb_batch_slices,
                               _bnb_coarse_batch)
from repro.serve import SearchService

RADICES = {"uneven": (5, 4, 3, 4, 3), "s24": (24,) * 5}


# ---------------------------------------------------------------------------
# Per-slab reference forms
# ---------------------------------------------------------------------------

def _ref_slab_indices_batch(radices, ranges_list):
    parts = [slab_indices(radices, tuple((int(lo), int(hi))
                                         for lo, hi in ranges))
             for ranges in ranges_list]
    return (np.sort(np.concatenate(parts)) if parts
            else np.zeros(0, np.int64))


def _ref_batch_slices(sizes, max_points=None):
    cap = BNB_BATCH if max_points is None else int(max_points)
    out = []
    s = 0
    pts = 0
    for j, n in enumerate(sizes):
        if j > s and pts + int(n) > cap:
            out.append((s, j))
            s, pts = j, 0
        pts += int(n)
    if s < len(sizes):
        out.append((s, len(sizes)))
    return out


def _ref_coarse_batch(ranges_list):
    return any(slab_size(r) > BNB_FINE for r in ranges_list)


# ---------------------------------------------------------------------------
# Slab sets
# ---------------------------------------------------------------------------

def _random_slab(rng, radices, max_width):
    out = []
    for r in radices:
        w = int(rng.integers(1, min(r, max_width) + 1))
        lo = int(rng.integers(0, r - w + 1))
        out.append((lo, lo + w))
    return out


def _slab_set(case, radices, seed):
    rng = np.random.default_rng(seed)
    if case == "empty":
        return np.zeros((0, 5, 2), np.int64)
    if case == "mixed":
        slabs = [_random_slab(rng, radices, 4) for _ in range(200)]
    elif case == "fine":
        # Refined BnB leaves: at most BNB_FINE points each.
        slabs = []
        while len(slabs) < 300:
            s = _random_slab(rng, radices, 3)
            if slab_size(s) <= BNB_FINE:
                slabs.append(s)
    elif case == "one_shape":
        w = [int(rng.integers(1, min(r, 3) + 1)) for r in radices]
        slabs = []
        for _ in range(150):
            lo = [int(rng.integers(0, r - wi + 1))
                  for r, wi in zip(radices, w)]
            slabs.append([(a, a + wi) for a, wi in zip(lo, w)])
    elif case == "overlap":
        # Repeated and overlapping slabs: members listed once per slab.
        base = [_random_slab(rng, radices, 3) for _ in range(40)]
        slabs = base + base[:10] + [
            [(lo, min(hi + 1, r)) for (lo, hi), r in zip(s, radices)]
            for s in base[10:30]]
    elif case == "single":
        slabs = [_random_slab(rng, radices, 4)]
    else:
        raise ValueError(case)
    return np.asarray(slabs, np.int64)


def _as_form(arr, form):
    if form == "array":
        return arr
    return [tuple((int(lo), int(hi)) for lo, hi in r) for r in arr]


CASES = ("mixed", "fine", "one_shape", "overlap", "single", "empty")


@pytest.mark.parametrize("form", ["array", "tuples"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("space", sorted(RADICES))
def test_slab_indices_batch_equals_per_slab_form(space, case, form):
    radices = RADICES[space]
    for seed in range(3):
        ranges = _as_form(_slab_set(case, radices, seed), form)
        got = slab_indices_batch(radices, ranges)
        ref = _ref_slab_indices_batch(radices, ranges)
        assert got.dtype == np.int64 and got.ndim == 1
        assert np.array_equal(got, ref), (space, case, form, seed)
        assert len(got) == sum(slab_size(r) for r in ranges)


@pytest.mark.parametrize("max_points", [None, BNB_FINE, 1, 0, 5000])
@pytest.mark.parametrize("sizes_kind", ["leaf", "fine", "oversized",
                                        "zeros", "empty"])
def test_batch_slices_equal_greedy_loop(sizes_kind, max_points):
    for seed in range(4):
        rng = np.random.default_rng(seed)
        if sizes_kind == "leaf":
            sizes = rng.integers(1, 4097, size=60)
        elif sizes_kind == "fine":
            sizes = rng.integers(1, BNB_FINE + 1, size=3000)
        elif sizes_kind == "oversized":
            # Leaves above the cap, each alone, between runs that fit.
            sizes = rng.integers(1, 2 * BNB_BATCH, size=40)
            sizes[::7] = 3 * BNB_BATCH
        elif sizes_kind == "zeros":
            sizes = rng.integers(0, 3, size=200)
        else:
            sizes = np.zeros(0, np.int64)
        sizes = np.asarray(sizes, np.int64)
        got = _bnb_batch_slices(sizes, max_points)
        assert got == _ref_batch_slices(sizes, max_points), \
            (sizes_kind, max_points, seed)
        assert all(type(s) is int and type(e) is int for s, e in got)


@pytest.mark.parametrize("form", ["array", "tuples"])
@pytest.mark.parametrize("case", CASES + ("at_fine", "last_coarse"))
def test_coarse_batch_equals_any_slab_above_fine(case, form):
    radices = RADICES["s24"]
    for seed in range(3):
        if case == "at_fine":
            arr = np.asarray([[(0, 2), (0, 2), (0, 2), (0, 2), (0, 1)],
                              [(3, 4)] * 5], np.int64)
        elif case == "last_coarse":
            arr = _slab_set("fine", radices, seed)
            arr = np.concatenate([arr, np.asarray(
                [[(0, 17), (0, 1), (0, 1), (0, 1), (0, 1)]], np.int64)])
        else:
            arr = _slab_set(case, radices, seed)
        ranges = _as_form(arr, form)
        assert _bnb_coarse_batch(ranges) is _ref_coarse_batch(ranges), \
            (case, form, seed)


# ---------------------------------------------------------------------------
# Drivers end to end: winners and counters of the per-slab implementation
# ---------------------------------------------------------------------------

SPACE = FactorizedSpace.full(8)
WL = load("deit-t")
BASE_BOX = Constraints(power_w=5 * 1.3, area_mm2=50 * 1.3)
WARM_BOXES = (Constraints(power_w=4.5),
              Constraints(power_w=4.0, area_mm2=45.0),
              Constraints(power_w=3.8))

# (n_feasible, n_pruned, n_bounds, n_workload_evals), frozen from the
# per-slab helpers; the same under every engine.
COLD_EDP = ([1, 4, 8, 8, 8], 1.07857285176606e-05, (4193, 15664, 203, 17104))
COLD_PARETO = (29, (5585, 16384, 89, 16384))
WARM_EDP = (
    ([1, 4, 7, 8, 8], 1.2795822584189324e-05, (2941, 2352, 70, 416)),
    ([1, 3, 8, 8, 8], 1.455584865900159e-05, (2200, 2704, 152, 1040)),
    ([1, 3, 8, 8, 7], 1.8903818568240414e-05, (1859, 2432, 160, 1312)),
)


def _counters(r):
    return (r.n_feasible, r.n_pruned, r.n_bounds, r.n_workload_evals)


def _assert_edp(r, want, label):
    best, edp, counters = want
    assert [int(x) for x in r.best_cfg.as_array()] == best, label
    assert float(r.edp) == edp, label
    assert _counters(r) == counters, label


@pytest.mark.parametrize("engine", ["numpy", "pallas"])
def test_cold_bnb_pinned(engine):
    r = search(WL, Constraints(), engine=engine, factorized=True,
               space=SPACE, prune="bound")
    _assert_edp(r, COLD_EDP, engine)
    p = search(WL, Constraints(), engine=engine, factorized=True,
               space=SPACE, prune="bound", objective="pareto")
    assert (len(p.front), _counters(p)) == COLD_PARETO, engine


@pytest.mark.parametrize("engine", ["numpy", "pallas"])
def test_warm_bnb_delta_pinned(engine):
    svc = SearchService(space=SPACE, engine=engine)
    svc.query(WL, BASE_BOX)
    for i, (cons, want) in enumerate(zip(WARM_BOXES, WARM_EDP)):
        before = svc.stats["warm"]
        r = svc.query(WL, cons)
        assert svc.stats["warm"] == before + 1, (engine, i)
        _assert_edp(r, want, (engine, i))
