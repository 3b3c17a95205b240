"""Serve-layer harness: canonical memo keys, warm constraint-delta
byte-identity, batching equivalence, the slab ledger substrate, and
service-owned checkpoints.

The load-bearing pin is the middle one: for every engine x objective, a
query answered by re-pricing a prior search's `SlabLedger` and
warm-starting branch-and-bound must return byte-identical winners /
frontiers / reference metrics to a cold `search()` of the same box —
including the adversarial cases (the tighten kills the old winner; the
tighten kills *everything*), and on the full 12^5 golden spaces.
"""
import numpy as np
import pytest

from repro.core import (Constraints, FactorizedSpace,
                        factorized_evaluate_grid, search, search_workloads)
from repro.core.factorized import LedgerRecorder, SlabLedger
from repro.core.paper_workloads import load
from repro.core.photonic_model import CONSTANTS
from repro.core.runtime import query_checkpoint_dir, query_policy
from repro.core.search import (WarmStart, _bnb_dominated_vs,
                               _bnb_infeasible_mask, _pareto_from_rows,
                               _search_factorized_bnb)
from repro.serve import (QueryBatcher, SearchService, ServeQuery,
                         box_constraints, box_contains, canonical_box,
                         launch_key, query_key, workload_key)

# Small uneven product space (720 configs): big enough to prune, small
# enough that the engine x objective matrix runs in seconds.
SPACE = FactorizedSpace(((1, 2, 3, 4, 5), (1, 2, 3, 4), (2, 4, 6),
                        (1, 3, 5, 7), (4, 8, 12)))
WL = load("deit-t")

ENGINES = ("numpy", "jax", "pallas")


def _same_edp(a, b, label=""):
    assert a.best_cfg == b.best_cfg, label
    for f in ("area_mm2", "power_w", "energy_j", "latency_s", "edp"):
        av, bv = getattr(a, f), getattr(b, f)
        assert av == bv or (np.isnan(av) and np.isnan(bv)), (label, f)


def _same_pareto(a, b, label=""):
    assert np.array_equal(np.asarray(a.front), np.asarray(b.front)), label
    assert set(a.metrics) == set(b.metrics), label
    for k in a.metrics:
        assert np.array_equal(a.metrics[k], b.metrics[k]), (label, k)


# ---------------------------------------------------------------------------
# Canonicalization: same question -> same key, however it is spelled.
# ---------------------------------------------------------------------------

def test_canonical_box_spelling_invariance():
    a = canonical_box({"power_w": 4, "area_mm2": 45.0})
    b = canonical_box({"area_mm2": 45, "power_w": 4.0})
    c = canonical_box(Constraints(power_w=4.0, area_mm2=45.0))
    assert a == b == c
    assert canonical_box({}) == canonical_box(Constraints())


def test_canonical_box_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown constraint field"):
        canonical_box({"watts": 5.0})


def test_canonical_box_round_trip():
    box = canonical_box({"power_w": 4.5})
    cons = box_constraints(box)
    assert cons == Constraints(power_w=4.5)
    assert canonical_box(cons) == box


def test_box_contains_is_elementwise_tightening():
    base = canonical_box({})
    assert box_contains(base, canonical_box({"power_w": 4.0}))
    assert box_contains(base, base)
    assert not box_contains(base, canonical_box({"power_w": 6.0}))
    # Incomparable: one bound tighter, one looser.
    assert not box_contains(
        canonical_box({"power_w": 4.0}),
        canonical_box({"power_w": 3.0, "area_mm2": 60.0}))


def test_query_key_spelling_invariance():
    wk = workload_key(WL)
    k1 = query_key(wk, canonical_box({"power_w": 4, "latency_ms": 10}),
                   SPACE.axes, "edp", None)
    k2 = query_key(wk, canonical_box(Constraints(power_w=4.0)),
                   SPACE.axes, "edp", None)
    assert k1 == k2
    # A different box, objective, or space is a different question.
    assert k1 != query_key(wk, canonical_box({}), SPACE.axes, "edp", None)
    assert k1 != query_key(wk, canonical_box({"power_w": 4}),
                           SPACE.axes, "pareto", ("area", "edp"))
    assert k1 != query_key(wk, canonical_box({"power_w": 4}),
                           FactorizedSpace.full(3).axes, "edp", None)


def test_workload_key_is_content_based():
    import dataclasses
    assert workload_key(WL) == workload_key(load("deit-t"))
    assert workload_key(WL) != workload_key(load("deit-s"))
    # Same GEMMs under a different alias stays distinguishable (the name
    # keys batched-result dicts and service logs).
    assert workload_key(WL) != workload_key(
        dataclasses.replace(WL, name="alias"))


def test_launch_key_pow2_bucketing():
    from repro.kernels import dse_eval as _dse
    from repro.kernels.ops import _bucket_blocks
    assert launch_key("pallas", 100) == launch_key("pallas", 1900)
    assert launch_key("pallas", 100) != launch_key("pallas", 200000)
    assert launch_key("jax", 300) == \
        ("jax", _bucket_blocks(300) * _dse.BLOCK)
    assert launch_key("numpy", 300) == ("numpy", 0)  # compiles nothing


# ---------------------------------------------------------------------------
# Memo: identical questions return the identical object.
# ---------------------------------------------------------------------------

def test_memo_hit_returns_identical_object():
    svc = SearchService(space=SPACE, engine="numpy")
    r1 = svc.query(WL, Constraints())
    r2 = svc.query(WL, Constraints())
    assert r2 is r1
    # Respelled box: dict, int bounds, permuted order -> still the memo.
    r3 = svc.query(WL, {"latency_ms": 10, "power_w": 5, "area_mm2": 50,
                        "energy_mj": 50})
    assert r3 is r1
    assert svc.stats["cold"] == 1 and svc.stats["memo_hits"] == 2


def test_pareto_metrics_excluded_from_edp_key():
    svc = SearchService(space=SPACE, engine="numpy")
    r1 = svc.query(WL, Constraints(), objective="edp")
    r2 = svc.query(WL, Constraints(), objective="edp",
                   pareto_metrics=("area", "edp"))  # ignored in edp mode
    assert r2 is r1


# ---------------------------------------------------------------------------
# Warm constraint-delta byte-identity, engine x objective.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("objective", ("edp", "pareto"))
def test_warm_delta_matches_cold_twin(engine, objective):
    svc = SearchService(space=SPACE, engine=engine)
    base = svc.query(WL, Constraints(), objective=objective)
    if objective == "edp":
        # A tighten that keeps the winner, one that kills it (strict-<
        # feasibility: the bound lands exactly on the winner's power),
        # and one nothing survives.
        boxes = [Constraints(power_w=4.5),
                 Constraints(power_w=float(base.power_w)),
                 Constraints(latency_ms=1e-6)]
    else:
        boxes = [Constraints(power_w=4.5),
                 Constraints(power_w=4.0, area_mm2=45.0),
                 Constraints(latency_ms=1e-6)]
    for cons in boxes:
        before = dict(svc.stats)
        got = svc.query(WL, cons, objective=objective)
        assert svc.stats["warm"] == before["warm"] + 1, cons
        ref = search(WL, cons, engine=engine, factorized=True, space=SPACE,
                     prune="bound", objective=objective)
        label = f"{engine}/{objective}/{cons}"
        if objective == "edp":
            _same_edp(got, ref, label)
        else:
            _same_pareto(got, ref, label)
    # Zero-feasible sanity: the warm path reported it as such.
    last = svc.query(WL, boxes[-1], objective=objective)
    if objective == "edp":
        assert last.best_cfg is None
    else:
        assert last.size == 0


def test_warm_chain_prices_against_widest_base():
    # base(defaults) -> warm(4.5) -> warm(4.0): the second delta re-prices
    # the ORIGINAL cold ledger (valid for any box inside it), not the
    # first delta's partial traversal.
    svc = SearchService(space=SPACE, engine="numpy")
    svc.query(WL, Constraints())
    svc.query(WL, Constraints(power_w=4.5))
    got = svc.query(WL, Constraints(power_w=4.0))
    assert svc.stats == {**svc.stats, "cold": 1, "warm": 2}
    _same_edp(got, search(WL, Constraints(power_w=4.0), engine="numpy",
                          factorized=True, space=SPACE, prune="bound"))


def test_loosened_box_goes_cold_and_replaces_base():
    svc = SearchService(space=SPACE, engine="numpy")
    svc.query(WL, Constraints(power_w=4.0))          # cold, base @ 4.0
    svc.query(WL, Constraints(power_w=4.5))          # loosened -> cold,
    assert svc.stats["cold"] == 2                    # base replaced @ 4.5
    svc.query(WL, Constraints(power_w=4.2))          # inside 4.5 -> warm
    assert svc.stats["warm"] == 1


def test_incomparable_box_keeps_standing_base():
    svc = SearchService(space=SPACE, engine="numpy")
    svc.query(WL, Constraints(power_w=4.5))          # cold, base @ 4.5
    # Tighter power, looser area: incomparable with the base -> cold, and
    # the standing base must survive (it covers boxes this one would not).
    svc.query(WL, Constraints(power_w=4.0, area_mm2=60.0))
    assert svc.stats["cold"] == 2
    svc.query(WL, Constraints(power_w=4.2))          # still warm @ 4.5 base
    assert svc.stats["warm"] == 1


# ---------------------------------------------------------------------------
# Warm Pareto re-price: corner dominance on the box's live slabs only.
# ---------------------------------------------------------------------------

# DeiT-B over 1..8 under a 1.3x paper box: the cold ledger keeps 172
# pruned slabs, enough for boxes inside it to revive some and kill others.
REPRICE_SPACE = FactorizedSpace.full(8)
REPRICE_WL = load("deit-b")
REPRICE_BASE = {"area_mm2": 65.0, "power_w": 6.5, "energy_mj": 65.0,
                "latency_ms": 13.0}
_BOUNDS = tuple(REPRICE_BASE)
_rng = np.random.default_rng(15)
REPRICE_CASES = {
    "base": (1.0, 1.0, 1.0, 1.0),
    "revives": (0.888, 0.948, 0.764, 0.838),  # some dominated, some live
    "empty-front": (0.518, 0.885, 0.693, 0.993),  # live slabs, no point
    "kills-all": (1.0, 1.0, 1.0, 1e-7),      # every slab by constraint
    **{f"random{i}": tuple(_rng.uniform(0.4, 1.0, 4)) for i in range(14)},
}


def _reprice_query(factors):
    box = {k: REPRICE_BASE[k] * f for k, f in zip(_BOUNDS, factors)}
    return ServeQuery(wl=REPRICE_WL, constraints=box_constraints(
        canonical_box(box)), objective="pareto")


@pytest.fixture(scope="module")
def reprice_service():
    svc = SearchService(space=REPRICE_SPACE, engine="numpy")
    svc.query(REPRICE_WL, REPRICE_BASE, objective="pareto")
    (base,) = svc._base.values()
    return svc, base


def _full_ledger_reprice(svc, base, q):
    """The re-price as a dominance test over every stored slab."""
    cons = q.constraints
    metrics = svc._metrics(q)
    front, met, nf = _pareto_from_rows(base.rows, q.wl, cons, svc.c,
                                       metrics, m=base.met)
    pts = (np.stack([met[k] for k in metrics], axis=1)
           if len(front) else np.zeros((0, len(metrics))))
    dead = (_bnb_infeasible_mask(base.ledger.bounds, cons)
            | _bnb_dominated_vs(pts, base.ledger.bounds, metrics))
    return WarmStart(
        start=base.ledger.pruned[~dead],
        lbs={k: v[~dead] for k, v in base.ledger.bounds.items()},
        rows=front, met=met, nf=nf)


@pytest.mark.parametrize("case", list(REPRICE_CASES))
def test_warm_pareto_reprice_matches_full_ledger(reprice_service, case):
    svc, base = reprice_service
    q = _reprice_query(REPRICE_CASES[case])
    got, tested = svc._reprice(base, q)
    ref = _full_ledger_reprice(svc, base, q)
    live = int((~_bnb_infeasible_mask(base.ledger.bounds,
                                      q.constraints)).sum())
    assert tested == (live if len(ref.rows) else 0)
    assert got.start.dtype == ref.start.dtype
    assert np.array_equal(got.start, ref.start)
    assert got.lbs.keys() == ref.lbs.keys()
    for k in ref.lbs:
        assert got.lbs[k].dtype == ref.lbs[k].dtype, k
        assert np.array_equal(got.lbs[k], ref.lbs[k]), k
    assert np.array_equal(got.rows, ref.rows)
    assert got.met.keys() == ref.met.keys()
    for k in ref.met:
        assert np.array_equal(got.met[k], ref.met[k]), k
    assert got.nf == ref.nf and got.best == ref.best
    # Each named case still is what its name says.
    if case == "revives":
        assert len(got.rows) and 0 < len(got.start) < tested
    elif case == "empty-front":
        assert got.nf == 0 and tested == 0 and len(got.start) == live > 0
    elif case == "kills-all":
        assert live == 0 and len(got.start) == 0


def test_dominance_counter_counts_warm_pareto_deltas_only(monkeypatch):
    import contextlib
    from repro.serve import dse_service
    spans = []

    class _Span(contextlib.nullcontext):
        def __enter__(self):
            return self

        def set_metadata(self, **stats):
            spans.append((self.enter_result, stats))

    monkeypatch.setattr(dse_service, "span", lambda name: _Span(name))
    svc = SearchService(space=REPRICE_SPACE, engine="numpy")
    svc.query(REPRICE_WL, REPRICE_BASE)
    svc.query(REPRICE_WL, _reprice_query(REPRICE_CASES["revives"])
              .constraints)
    assert svc.stats["warm"] == 1 and svc.stats["slabs_repriced"] > 0
    assert svc.stats["slabs_dominance_tested"] == 0    # EDP traffic only
    svc.query(REPRICE_WL, REPRICE_BASE, objective="pareto")
    assert svc.stats["slabs_dominance_tested"] == 0    # a cold query
    q = _reprice_query(REPRICE_CASES["revives"])
    svc.query(REPRICE_WL, q.constraints, objective="pareto")
    base = svc._base[svc._keys(q)[2]]
    tested = int((~_bnb_infeasible_mask(base.ledger.bounds,
                                        q.constraints)).sum())
    assert tested > 0
    assert svc.stats["slabs_dominance_tested"] == tested
    assert svc.stats["slabs_dominance_tested"] <= svc.stats["slabs_repriced"]
    svc.query(REPRICE_WL, q.constraints, objective="pareto")  # memo hit
    for case in ("kills-all", "empty-front"):    # no test runs
        svc.query(REPRICE_WL, _reprice_query(REPRICE_CASES[case])
                  .constraints, objective="pareto")
    assert svc.stats["warm"] == 4
    assert svc.stats["slabs_dominance_tested"] == tested
    # The re-price span carries each warm query's count.
    assert spans == [("service.reprice", {"slabs_tested": n})
                     for n in (0, tested, 0, 0)]


# ---------------------------------------------------------------------------
# Full 12^5 golden spaces: service cold answers land on the frozen
# numbers, and every workload's delta matches its cold twin.
# ---------------------------------------------------------------------------

def test_golden_12x5_cold_and_delta():
    import json
    import pathlib
    committed = json.loads(
        (pathlib.Path(__file__).parent / "golden" /
         "dse_12x5.json").read_text())["workloads"]
    svc = SearchService(n_z=12, engine="jax")
    names = sorted(committed)
    for name in names:
        svc.submit(load(name), Constraints())
    for name, res in zip(names, svc.drain()):      # one batched cold wave
        assert [int(x) for x in res.best_cfg.as_array()] == \
            committed[name]["best"], name
        assert float(res.edp) == committed[name]["edp"], name
    assert svc.stats["batched_calls"] == 1
    tight = Constraints(power_w=4.5)
    for name in names:
        got = svc.query(load(name), tight)
        ref = search(load(name), tight, engine="jax", factorized=True,
                     n_z=12, prune="bound")
        _same_edp(got, ref, name)
    assert svc.stats["warm"] == len(names)


# ---------------------------------------------------------------------------
# Batching: drain() == sequential query(), with deduped cold work.
# ---------------------------------------------------------------------------

def test_drain_matches_sequential_queries():
    asks = [(load("deit-t"), Constraints()),
            (load("deit-s"), Constraints(power_w=4.5)),
            (load("deit-t"), Constraints()),            # duplicate
            (load("deit-s"), Constraints(power_w=4.0))]
    seq = SearchService(space=SPACE, engine="numpy")
    want = [seq.query(wl, cons) for wl, cons in asks]
    bat = SearchService(space=SPACE, engine="numpy")
    for wl, cons in asks:
        bat.submit(wl, cons)
    got = bat.drain()
    assert len(got) == len(want)
    for g, w, (wl, cons) in zip(got, want, asks):
        _same_edp(g, w, f"{wl.name}/{cons}")
    # The duplicate was not searched twice. Classification happens before
    # any cold runs, so the second deit-s box cannot ride the first's
    # ledger warm — it colds too, but in a second wave (name clash).
    assert bat.stats["cold"] == 3
    assert bat.stats["memo_hits"] == 1
    assert bat.stats["batched_calls"] == 2
    assert got[0] is got[2]


def test_batcher_groups_by_signature_and_name():
    qs = [ServeQuery(wl=load("deit-t"), constraints=Constraints()),
          ServeQuery(wl=load("deit-s"), constraints=Constraints()),
          ServeQuery(wl=load("deit-t"),
                     constraints=Constraints(power_w=4.0)),  # name clash
          ServeQuery(wl=load("deit-b"), constraints=Constraints(),
                     objective="pareto", pareto_metrics=("area", "edp"))]
    waves = QueryBatcher.group(qs)
    assert [len(w) for _, w in waves] == [2, 1, 1]
    (sig0, w0), (sig1, w1), (sig2, w2) = waves
    assert sig0 == ("edp", None) and sig1 == ("edp", None)
    assert {q.wl.name for q in w0} == {load("deit-t").name,
                                       load("deit-s").name}
    assert w1[0].constraints == Constraints(power_w=4.0)
    assert sig2 == ("pareto", ("area", "edp"))


# ---------------------------------------------------------------------------
# The slab ledger substrate.
# ---------------------------------------------------------------------------

def test_keep_ledger_partitions_the_space(tmp_path):
    r = search(WL, Constraints(), engine="numpy", factorized=True,
               space=SPACE, prune="bound", keep_ledger=True)
    led = r.ledger
    assert isinstance(led, SlabLedger)
    assert led.axes == SPACE.axes
    assert led.accounted() == SPACE.size
    idx = led.evaluated_indices()
    assert len(np.unique(idx)) == len(idx)
    assert len(idx) + int(led.pruned_sizes().sum()) == SPACE.size
    assert set(led.bounds) == set(LedgerRecorder.METRIC_KEYS)
    # Exact npz round-trip.
    path = tmp_path / "led.npz"
    led.save(str(path))
    back = SlabLedger.load(str(path))
    assert back.axes == led.axes
    assert np.array_equal(back.pruned, led.pruned)
    assert np.array_equal(back.evaluated, led.evaluated)
    for k in led.bounds:
        assert np.array_equal(back.bounds[k], led.bounds[k])


def test_ledger_bounds_are_admissible():
    r = search(WL, Constraints(), engine="numpy", factorized=True,
               space=SPACE, prune="bound", keep_ledger=True)
    led = r.ledger
    full = factorized_evaluate_grid(SPACE, WL, CONSTANTS)
    radices = SPACE.radices
    for i, rng in enumerate(led.pruned[:50]):
        digits = np.stack(np.meshgrid(
            *[np.arange(lo, hi) for lo, hi in rng],
            indexing="ij")).reshape(5, -1)
        flat = np.ravel_multi_index(digits, radices)
        for k, v in led.bounds.items():
            assert v[i] <= full[k][flat].min() + 1e-12, (i, k)


def test_keep_ledger_requires_bound_prune():
    with pytest.raises(ValueError, match="keep_ledger"):
        search(WL, Constraints(), engine="numpy", factorized=True,
               space=SPACE, keep_ledger=True)
    with pytest.raises(ValueError, match="keep_ledger"):
        search_workloads({"deit-t": WL}, Constraints(), engine="numpy",
                         factorized=True, space=SPACE, keep_ledger=True)


def test_ledger_recorder_rejects_partial_accounting():
    rec = LedgerRecorder()
    rec.prune(np.asarray([[(0, 1)] * 5], np.int64),
              {k: np.zeros(1) for k in LedgerRecorder.METRIC_KEYS})
    with pytest.raises(AssertionError, match="slab ledger accounts"):
        rec.build(SPACE)


def test_warm_excludes_runtime_and_ledger():
    warm = WarmStart(start=np.zeros((0, 5, 2), np.int64))
    with pytest.raises(ValueError, match="warm.*runtime"):
        _search_factorized_bnb(SPACE, WL, Constraints(), "numpy", CONSTANTS,
                               True, None, None, rt=object(), warm=warm)
    with pytest.raises(ValueError, match="warm.*ledger"):
        _search_factorized_bnb(SPACE, WL, Constraints(), "numpy", CONSTANTS,
                               True, None, None, led=object(), warm=warm)


# ---------------------------------------------------------------------------
# Service-owned checkpoints.
# ---------------------------------------------------------------------------

def test_query_checkpoint_dir_layout(tmp_path):
    root = str(tmp_path / "ckpt")
    d1 = query_checkpoint_dir(root, "a" * 64)
    assert d1.startswith(root) and ("a" * 24) in d1
    import os
    assert os.path.isdir(d1)
    d2 = query_checkpoint_dir(root, "b" * 64, create=False)
    assert not os.path.exists(d2)
    pol = query_policy(root, "a" * 64, checkpoint_every=2)
    assert pol.checkpoint_dir == d1 and pol.checkpoint_every == 2


def test_service_checkpoint_root_resume(tmp_path):
    root = str(tmp_path / "svc-ckpt")
    ref = search(WL, Constraints(), engine="numpy", factorized=True,
                 space=SPACE, prune="bound")
    svc = SearchService(space=SPACE, engine="numpy", checkpoint_root=root)
    r1 = svc.query(WL, Constraints())
    _same_edp(r1, ref)
    assert r1.n_checkpoints > 0
    import os
    assert len(os.listdir(root)) == 1  # one per-query-fingerprint dir

    # A restarted service (fresh memo) re-runs the query against the same
    # root: it resumes from the committed snapshots and still lands on the
    # same answer. A resumed run carries no complete slab partition, so it
    # seeds no warm-start base — the follow-up tighten goes cold but stays
    # byte-identical to its own cold twin.
    svc2 = SearchService(space=SPACE, engine="numpy", checkpoint_root=root)
    r2 = svc2.query(WL, Constraints())
    _same_edp(r2, ref)
    assert r2.resumed_step > 0 and r2.ledger is None
    tight = Constraints(power_w=4.5)
    r3 = svc2.query(WL, tight)
    assert svc2.stats["warm"] == 0 and svc2.stats["cold"] == 2
    _same_edp(r3, search(WL, tight, engine="numpy", factorized=True,
                         space=SPACE, prune="bound"))


# ---------------------------------------------------------------------------
# Hardened long-lived service: base eviction, deadlines, checkpoint GC
# ---------------------------------------------------------------------------

def test_lru_eviction_then_requery_is_byte_identical():
    # max_bases=1: the second workload's base evicts the first; a delta
    # query against the evicted base goes cold again and still matches
    # its cold twin exactly.
    wl2 = load("deit-s")
    svc = SearchService(space=SPACE, engine="numpy", max_bases=1)
    svc.query(WL, Constraints())
    svc.query(wl2, Constraints())
    assert svc.stats["evicted_bases"] == 1
    tight = Constraints(power_w=4.0)
    got = svc.query(WL, tight)
    assert svc.stats["evicted_bases"] == 2
    assert svc.stats["warm"] == 0 and svc.stats["cold"] == 3
    _same_edp(got, search(WL, tight, engine="numpy", factorized=True,
                          space=SPACE, prune="bound"), "evicted requery")
    # The surviving base (the power_w=4.0 re-search) still serves warm
    # deltas for boxes that tighten it.
    got2 = svc.query(WL, Constraints(power_w=3.5))
    assert svc.stats["warm"] == 1
    _same_edp(got2, search(WL, Constraints(power_w=3.5), engine="numpy",
                           factorized=True, space=SPACE, prune="bound"))


def test_ledger_byte_budget_eviction():
    # The budget accounts each base at its exact save() npz size; a
    # 1-byte budget can hold no base at all.
    led = search(WL, Constraints(), engine="numpy", factorized=True,
                 space=SPACE, prune="bound", keep_ledger=True).ledger
    assert led.nbytes() > 0
    svc = SearchService(space=SPACE, engine="numpy", max_ledger_bytes=1)
    svc.query(WL, Constraints())
    assert svc.stats["evicted_bases"] == 1
    with pytest.raises(ValueError, match="max_ledger_bytes"):
        SearchService(space=SPACE, max_ledger_bytes=-1)


def test_mru_base_survives_eviction():
    # Touching a base via a warm delta refreshes its LRU position.
    wl2, wl3 = load("deit-s"), load("deit-b")
    svc = SearchService(space=SPACE, engine="numpy", max_bases=2)
    svc.query(WL, Constraints())
    svc.query(wl2, Constraints())
    svc.query(WL, Constraints(power_w=4.5))      # warm: WL becomes MRU
    svc.query(wl3, Constraints())                # evicts wl2, not WL
    svc.query(WL, Constraints(power_w=4.0))
    assert svc.stats["warm"] == 2                # WL's base survived


def test_deadline_timeout_surfaces_in_drain():
    from repro.core.runtime import QueryTimeout
    wl2 = load("deit-s")
    svc = SearchService(space=SPACE, engine="numpy")
    svc.submit(WL, Constraints(), deadline_s=0.0)
    svc.submit(wl2, Constraints())
    out = svc.drain()
    assert isinstance(out[0], QueryTimeout)
    assert out[0].query_name == WL.name
    assert SearchService.timed_out(out) == [WL.name]
    assert svc.stats["timeouts"] == 1
    _same_edp(out[1], search(wl2, Constraints(), engine="numpy",
                             factorized=True, space=SPACE, prune="bound"))
    # The timed-out query left no memo or base poison: resubmitting
    # without a deadline completes and matches the cold twin.
    got = svc.query(WL, Constraints())
    _same_edp(got, search(WL, Constraints(), engine="numpy",
                          factorized=True, space=SPACE, prune="bound"))
    with pytest.raises(ValueError, match="deadline_s"):
        svc.submit(WL, Constraints(), deadline_s=-1.0)


def test_gc_checkpoints_prunes_and_skips_foreign(tmp_path):
    import os
    from repro.core.runtime import gc_checkpoints
    root = str(tmp_path / "root")
    svc = SearchService(space=SPACE, engine="numpy", checkpoint_root=root)
    svc.query(WL, Constraints())
    svc.query(WL, Constraints(power_w=4.0), objective="pareto")
    dirs = sorted(os.listdir(root))
    assert len(dirs) == 2
    # Foreign content is never deleted: wrong name shape, and a
    # fingerprint-shaped name without our manifest layout.
    os.makedirs(os.path.join(root, "not-ours"))
    open(os.path.join(root, "not-ours", "data.bin"), "w").close()
    os.makedirs(os.path.join(root, "a" * 24))
    open(os.path.join(root, "a" * 24, "user.txt"), "w").close()
    kept = gc_checkpoints(root, keep=1)
    assert len(kept) == 1 and kept[0].startswith(root)
    left = sorted(os.listdir(root))
    assert "not-ours" in left and "a" * 24 in left
    assert len([d for d in left if d in dirs]) == 1
    # known= protects in-flight queries regardless of age.
    removed = gc_checkpoints(root, keep=0,
                             known=[d for d in left if d in dirs])
    assert removed == []
    with pytest.raises(ValueError):
        gc_checkpoints(root, keep=-1)
    assert gc_checkpoints(str(tmp_path / "missing"), keep=0) == []


def test_service_workers_byte_identical():
    # A worker-pool service answers cold and warm queries byte-identically
    # to the sequential service.
    tight = Constraints(power_w=4.5)
    ref, refw = SearchService(space=SPACE, engine="numpy"), \
        SearchService(space=SPACE, engine="numpy", workers=2)
    for svc in (ref, refw):
        svc.query(WL, Constraints())
    a, b = ref.query(WL, tight), refw.query(WL, tight)
    assert refw.stats["warm"] == 1
    _same_edp(a, b, "workers warm delta")
