"""A run driven end to end on the CPU (the look for a chip skipped, the
cell at a test's size), with the timed path sound and then broken
underneath: `correct` has to come out true, and false for every fault the
cell can have and for the float32 control."""
import importlib

import numpy as np
import pytest

from _tiny import CELLS, drive, tiny_config

import control
import run
from cell import load_spec, resolve


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    """Reference sweeps and traces go to the test's directory."""
    monkeypatch.setattr(run, "CACHE", tmp_path)


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_sound_run_is_correct(cell_name):
    line = drive(cell_name)
    assert line["correct"], line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checked"
    assert set(line["metrics"]) == {
        m["name"] for m in load_spec()["end_to_end"]
        if cell_name in m.get("workloads", [cell_name])}
    assert {"queries_per_s", "setup_s"} <= set(line["metrics"])


def _alter_the_answer(monkeypatch):
    """Each answer altered where it is produced: the min-EDP winner moved
    one step along N_lambda, the first frontier row dropped."""
    search = importlib.import_module("repro.core.search")
    make, front = search._make_result, search._pareto_from_rows

    def moved(cfg_row, *a, **kw):
        if cfg_row is not None:
            cfg_row = np.array(cfg_row)
            cfg_row[4] += 1 if cfg_row[4] == 1 else -1
        return make(cfg_row, *a, **kw)

    def dropped(*a, **kw):
        rows, met, nf = front(*a, **kw)
        return rows[1:], {k: v[1:] for k, v in met.items()}, nf

    monkeypatch.setattr(search, "_make_result", moved)
    monkeypatch.setattr(search, "_pareto_from_rows", dropped)


def _leave_out_half(monkeypatch):
    """Half of each batch left out: every decoded kernel launch skips the
    first half of its span, every grid-operand launch the second half of
    its rows, and a warm delta sees the second half of its base's stored
    points only."""
    import dataclasses

    from repro.serve.dse_service import SearchService

    ops = importlib.import_module("repro.kernels.ops")
    launch, grid_search = ops._decoded_launch, ops.dse_search_multi
    grid_front = ops.dse_pareto_multi
    delta = SearchService._delta

    def half_span(space, start, count, *a, **kw):
        return launch(space, start + count // 2, count - count // 2,
                      *a, **kw)

    def half_rows(fn):
        return lambda grid, *a, **kw: fn(grid[:max(len(grid) // 2, 1)],
                                         *a, **kw)

    def half_store(self, base, q):
        h = len(base.idx) // 2
        return delta(self, dataclasses.replace(
            base, idx=base.idx[h:], rows=base.rows[h:],
            met={k: v[h:] for k, v in base.met.items()}), q)

    monkeypatch.setattr(ops, "_decoded_launch", half_span)
    monkeypatch.setattr(ops, "dse_search_multi", half_rows(grid_search))
    monkeypatch.setattr(ops, "dse_pareto_multi", half_rows(grid_front))
    monkeypatch.setattr(SearchService, "_delta", half_store)


def _keep_the_state(monkeypatch):
    """The service answers every warm delta with its base answer."""
    from repro.serve.dse_service import SearchService

    monkeypatch.setattr(SearchService, "_delta",
                        lambda self, base, q: next(iter(self._memo.values())))


def test_a_run_whose_queries_raise_is_not_correct(monkeypatch):
    """Every seventh window query raises: those answers never come."""
    warm_up = run.warm_up

    def then_flaky(cell, counter):
        warm_up(cell, counter)
        real = cell.entry.answer
        calls = {"n": 0}

        def flaky(item):
            calls["n"] += 1
            if calls["n"] % 7 == 0:
                raise RuntimeError("no answer")
            return real(item)

        cell.entry.answer = flaky

    monkeypatch.setattr(run, "warm_up", then_flaky)
    line = drive(CELLS[0])
    assert line["failed"] > 0
    assert line["checked"]["unanswered"]["value"] == line["failed"]
    assert not line["correct"]


FAULTS = [(c, f) for c in CELLS for f in (_alter_the_answer, _leave_out_half)]
FAULTS += [(c, _keep_the_state) for c in CELLS if "warm" in c]


@pytest.mark.parametrize("cell_name,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(cell_name, fault, monkeypatch):
    fault(monkeypatch)
    line = drive(cell_name)
    assert not line["correct"], line["checked"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_float32_control_is_not_correct(cell_name):
    _, _, traffic = resolve(load_spec(), cell_name)
    got = control.readings(tiny_config(cell_name), traffic, seed=11)
    assert not got["correct"]
    gap = got["checked"]["metric_rel_gap"]["value"]
    assert gap > 1e-9, got
