"""The comparison that decides `correct`.

Each compared answer is one window query's box with what the timed path
returned for one of its workloads, normalized by `answer_of`:

  edp     {"row": (5,) ints or None, "metrics": {metric: float}}
  pareto  {"rows": (F, 5) ints, "metrics": {metric: (F,) floats}}

against the plain float64 reference (`reference.py`) over the whole
space. Three numbers come out of a run, each with its own limit:

  unanswered      window queries that raised instead of answering: an
                  answer that never came. Limit 0.
  wrong_answers   answers whose design points differ from the reference:
                  min-EDP -- feasible where the reference finds nothing or
                  the reverse, a winner the reference finds infeasible, or
                  one whose reference EDP exceeds the reference minimum;
                  Pareto -- a front whose set of rows differs. An exact
                  comparison: limit 0.
  metric_rel_gap  the largest |reported - reference| / |reference| over
                  every metric (area, power, energy, latency, edp) of
                  every reported point, the reference evaluated at the
                  point the program reported. The program reports float64
                  values; a float32 evaluation (the control) reads about
                  1e-7. The limit and the readings it was set from are in
                  PERF.md.
"""
from __future__ import annotations

import math

import numpy as np

import reference

LIMITS = {"unanswered": 0, "wrong_answers": 0, "metric_rel_gap": 1e-10}

_EDP_FIELDS = {"area": "area_mm2", "power": "power_w", "energy": "energy_j",
               "latency": "latency_s", "edp": "edp"}


def answer_of(result) -> dict:
    """The answer a `SearchResult` or `ParetoResult` of the program gives,
    as plain arrays (its counters describe work, not the answer)."""
    if hasattr(result, "front"):
        return {"rows": np.asarray(result.front, np.int64).reshape(-1, 5),
                "metrics": {k: np.asarray(result.metrics[k], np.float64)
                            for k in reference.METRICS}}
    if result.best_cfg is None:
        return {"row": None, "metrics": None}
    return {"row": np.asarray(result.best_cfg.as_array(), np.int64),
            "metrics": {k: float(getattr(result, f))
                        for k, f in _EDP_FIELDS.items()}}


def _rel_gap(got: dict, rows: np.ndarray, wl: dict, c: dict) -> float:
    if len(rows) == 0:
        return 0.0
    want = reference.evaluate(rows, wl, c)
    gap = 0.0
    for k in reference.METRICS:
        g = np.asarray(got[k], np.float64).reshape(-1)
        w = np.asarray(want[k], np.float64)
        r = np.abs(g - w) / np.abs(w)
        if not np.all(np.isfinite(r)):
            return math.inf
        gap = max(gap, float(r.max()))
    return gap


def compare_one(objective: str, box: dict, got: dict, sub: tuple,
                wl: dict, c: dict, objectives=None) -> tuple:
    """(wrong: bool, metric_rel_gap) of one answer; `box` in SI units and
    `sub` the reference sweep of a box that contains it."""
    if objective == "edp":
        want_row, want = reference.answer_edp(sub, box)
        if got["row"] is None:
            return want_row is not None, 0.0
        rows = got["row"][None, :]
        gap = _rel_gap(got["metrics"], rows, wl, c)
        if want_row is None:
            return True, gap
        at = reference.evaluate(rows, wl, c)
        wrong = (not bool(reference.feasible(at, box)[0])
                 or float(at["edp"][0]) > want["edp"])
        return wrong, gap
    want_rows, _ = reference.answer_pareto(sub, box, objectives)
    rows = got["rows"]
    gap = _rel_gap(got["metrics"], rows, wl, c)
    same = (len(rows) == len(want_rows)
            and set(map(tuple, rows.tolist()))
            == set(map(tuple, want_rows.tolist())))
    return not same, gap


def compare(objective: str, items, c: dict, objectives=None) -> dict:
    """{number: value} over (box, answer, sub, wl) items -- `sub` the
    reference sweep and `wl` the reference workload of the answer's
    workload -- plus how many were compared; every answer came
    (`unanswered` 0) unless the caller says otherwise."""
    wrong, gap = 0, 0.0
    for box, got, sub, wl in items:
        w, g = compare_one(objective, box, got, sub, wl, c, objectives)
        wrong += int(w)
        gap = max(gap, g)
    return {"unanswered": 0, "wrong_answers": wrong, "metric_rel_gap": gap,
            "compared": len(items)}


def verdict(numbers: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    ok = numbers["compared"] > 0 and all(
        numbers[k] <= lim for k, lim in LIMITS.items())
    shown = {k: {"value": numbers[k] if math.isfinite(numbers[k])
                 else str(numbers[k]), "limit": lim}
             for k, lim in LIMITS.items()}
    return ok, shown
