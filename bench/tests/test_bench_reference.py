"""The benchmark's plain reference and the comparison that decides
`correct`, at sizes a CPU test holds (Pallas in interpret mode)."""
import itertools
import json

import numpy as np
import pytest

from _tiny import BENCH, CELLS, tiny_config

import check
import reference
import traffic as tr
from cell import Cell, load_module, load_spec, resolve

ENCODER = load_module(BENCH / "lowering" / "encoder.py")


def _sizes(cfg):
    """(name, sizes) of the configuration's one workload."""
    (name, sizes), = cfg["workloads"].items()
    return name, sizes


def _ref_wl(cfg):
    return ENCODER.reference_workload(_sizes(cfg)[1])


@pytest.mark.parametrize("config", ["deit-b.s24", "bert-l.s20"])
def test_reference_lowering_matches_the_program_at_full_size(config):
    with open(BENCH / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    prog = ENCODER.program_workload(*_sizes(cfg))
    ref = _ref_wl(cfg)
    assert [tuple(g) for g in prog.gemm_array.tolist()] == ref["gemms"]
    for k in ("elec_ops", "weight_bytes", "act_io_bytes", "max_act_bytes"):
        assert getattr(prog, k) == ref[k], k


def test_space_rows_are_the_program_grid_order():
    from repro.core.factorized import FactorizedSpace

    idx = np.arange(5 ** 5)
    assert np.array_equal(reference.space_rows(5, idx),
                          FactorizedSpace.full(5).decode(idx))


def test_reference_metrics_agree_with_the_program_float64_model():
    from repro.core.factorized import factorized_evaluate_grid
    from repro.core.photonic_model import DeviceConstants

    cfg = tiny_config(CELLS[0], n_z=5)
    cell = Cell(cfg, {"entry": "search", "objective": "edp"})
    prog = factorized_evaluate_grid(cell.space,
                                    cell.workloads[_sizes(cfg)[0]],
                                    DeviceConstants(**cfg["constants"]))
    rows = reference.space_rows(5, np.arange(5 ** 5))
    ref = reference.evaluate(rows, _ref_wl(cfg), cfg["constants"])
    for k in reference.METRICS:
        np.testing.assert_allclose(ref[k], prog[k], rtol=1e-13, atol=0)


def test_pareto_mask_is_the_brute_force_front_with_ties_kept():
    rng = np.random.default_rng(3)
    pts = rng.integers(0, 6, size=(700, 3)).astype(float)  # many ties
    brute = np.array([not any(np.all(q <= p) and np.any(q < p) for q in pts)
                      for p in pts])
    assert np.array_equal(reference.pareto_mask(pts), brute)


def _answers(cell_name, n=6):
    spec = load_spec()
    _, _, traffic = resolve(spec, cell_name)
    cfg = tiny_config(cell_name)
    cell = Cell(cfg, traffic)
    cell.entry.prepare()
    items = list(itertools.islice(tr.items(traffic, cfg["workloads"], 5), n))
    got = [check.answer_of(cell.entry.answer(it)[0][1]) for it in items]
    sub = reference.sweep(cfg["space"]["n_z"], _ref_wl(cfg),
                          cfg["constants"], tr.si(tr.loosest(traffic)))
    return cfg, traffic, [tr.si(it["box"]) for it in items], got, sub


def _compare(cfg, traffic, boxes, got, sub):
    wl = _ref_wl(cfg)
    return check.compare(traffic["objective"],
                         [(b, a, sub, wl) for b, a in zip(boxes, got)],
                         cfg["constants"], traffic.get("pareto_metrics"))


@pytest.mark.parametrize("cell_name", CELLS)
def test_comparison_accepts_the_program_answers(cell_name):
    cfg, traffic, boxes, got, sub = _answers(cell_name)
    numbers = _compare(cfg, traffic, boxes, got, sub)
    assert numbers["compared"] == len(boxes)
    assert check.verdict(numbers)[0], numbers
    assert numbers["wrong_answers"] == 0
    assert numbers["metric_rel_gap"] < 1e-14


@pytest.mark.parametrize("cell_name", [CELLS[0], CELLS[2]])
def test_comparison_rejects_a_perturbed_winner(cell_name):
    cfg, traffic, boxes, got, sub = _answers(cell_name)
    i = next(j for j, a in enumerate(got) if a["row"] is not None)
    moved = dict(got[i], row=got[i]["row"].copy())
    moved["row"][4] += 1 if moved["row"][4] < cfg["space"]["n_z"] else -1
    numbers = _compare(cfg, traffic, [boxes[i]], [moved], sub)
    assert numbers["wrong_answers"] == 1
    assert not check.verdict(numbers)[0]
    # The same point with one metric a float32 rounding off is wrong too.
    skewed = dict(got[i], metrics=dict(got[i]["metrics"]))
    skewed["metrics"]["energy"] *= 1 + 2 ** -23
    numbers = _compare(cfg, traffic, [boxes[i]], [skewed], sub)
    assert numbers["wrong_answers"] == 0
    assert numbers["metric_rel_gap"] > check.LIMITS["metric_rel_gap"]
    assert not check.verdict(numbers)[0]


@pytest.mark.parametrize("cell_name", [CELLS[1], CELLS[3]])
def test_comparison_rejects_a_dropped_frontier_row(cell_name):
    cfg, traffic, boxes, got, sub = _answers(cell_name)
    i = max(range(len(got)), key=lambda j: len(got[j]["rows"]))
    assert len(got[i]["rows"]) > 1
    dropped = {"rows": got[i]["rows"][1:],
               "metrics": {k: v[1:] for k, v in got[i]["metrics"].items()}}
    numbers = _compare(cfg, traffic, [boxes[i]], [dropped], sub)
    assert numbers["wrong_answers"] == 1
    assert not check.verdict(numbers)[0]
