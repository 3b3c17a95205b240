"""The one item generator every traffic mix runs through.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:

  entry             how one item is answered: `bench/entries/<entry>.py`
  loop              how items are sent in the window: `bench/loops/<loop>.py`
                    (default "closed": one client, the next item sent when
                    the answer is back)
  objective         "edp" or "pareto"; `pareto_metrics` for the latter
  prune             "bound" for branch-and-bound, or null
  box               the centre box: area_mm2, power_w, energy_mj, latency_ms
  factors           {bound: [lo, hi]}: each item scales each bound by a
                    factor of its own in that range
  base_factor       (service) set-up answers `box` times this, so every
                    window item is a warm delta inside it
  workload_weights  {workload: weight} over the configuration's workloads
                    (default: every workload, equal weights)
  repeat_share      share of items that re-send an earlier item of the
                    stream (default 0: no item repeats)
  warmup_boxes      factor boxes {bound: factor} set-up answers besides
                    the corners and the warm-up stream (default none)

An item is {"workload": name, "box": {bound: value}}. Items come in blocks
of STRATA. Within a block each factor takes each of the STRATA equal
slices of its range exactly once (a Latin hypercube). Which slices go
together, the order of the block, each item's workload and which items
repeat are fixed by the stream alone; where each factor lies inside its
slice is drawn from the seed. So every seed sends other boxes of the same
make-up in the same order, and a window does nearly the same work whatever
the seed.
"""
from __future__ import annotations

import numpy as np

BOUNDS = ("area_mm2", "power_w", "energy_mj", "latency_ms")
STRATA = 8                                # items per Latin-hypercube block
WINDOW, WARMUP, SAMPLE = 0, 1, 2          # independent streams
WARMUP_ITEMS = 4                          # warm-up stream items in set-up


def stream(seed: int, which: int) -> np.random.Generator:
    """The generator of one stream of a seed (any non-negative integer)."""
    return np.random.default_rng([int(seed), which])


def weights(traffic: dict, names) -> tuple:
    """(workload names, probabilities) of the traffic's workload mix."""
    w = traffic.get("workload_weights") or {n: 1.0 for n in names}
    unknown = set(w) - set(names)
    if unknown:
        raise ValueError(f"workload_weights names {sorted(unknown)}; the "
                         f"configuration has {sorted(names)}")
    keys = sorted(w)
    p = np.array([float(w[k]) for k in keys])
    return keys, p / p.sum()


def items(traffic: dict, names, seed: int, which: int = WINDOW):
    """Endless iterator of the items of one stream: each block's make-up
    from the stream alone, the boxes' place inside it from the seed."""
    keys, p = weights(traffic, names)
    ranges = [traffic["factors"][k] for k in BOUNDS]
    centre = traffic["box"]
    repeat = float(traffic.get("repeat_share") or 0.0)
    design = np.random.default_rng([which])
    rng = stream(seed, which)
    sent = []
    while True:
        slices = np.stack([design.permutation(STRATA) for _ in BOUNDS],
                          axis=1)
        wl = design.choice(len(keys), size=STRATA, p=p)
        again = design.random(STRATA) < repeat
        pick = design.random(STRATA)
        u = rng.random((STRATA, len(BOUNDS)))
        for j in range(STRATA):
            if again[j] and sent:
                item = sent[int(pick[j] * len(sent))]
            else:
                f = [lo + (hi - lo) * (s + x) / STRATA for (lo, hi), s, x
                     in zip(ranges, slices[j], u[j])]
                item = {"workload": keys[wl[j]],
                        "box": {k: float(centre[k] * x)
                                for k, x in zip(BOUNDS, f)}}
            sent.append(item)
            yield item


def scaled(traffic: dict, factors) -> dict:
    """The centre box with each bound times its factor ({bound: factor},
    or one number for every bound)."""
    if not isinstance(factors, dict):
        factors = {k: factors for k in BOUNDS}
    return {k: float(traffic["box"][k] * factors[k]) for k in BOUNDS}


def corners(traffic: dict) -> list:
    """The tightest and the loosest box the traffic can send."""
    return [scaled(traffic, {k: traffic["factors"][k][i] for k in BOUNDS})
            for i in (0, 1)]


def loosest(traffic: dict) -> dict:
    """A box that contains every box the traffic sends."""
    base = traffic.get("base_factor") or 0.0
    return scaled(traffic, {k: max(traffic["factors"][k][1], base)
                            for k in BOUNDS})


def warmup_items(traffic: dict, names) -> list:
    """What set-up answers after the entry's own preparation, the same in
    every run: for each workload the two corners and the traffic's
    `warmup_boxes`, then WARMUP_ITEMS items of the warm-up stream."""
    import itertools

    keys, _ = weights(traffic, names)
    extra = [scaled(traffic, f) for f in traffic.get("warmup_boxes", ())]
    out = [{"workload": n, "box": b} for n in keys
           for b in corners(traffic) + extra]
    return out + list(itertools.islice(items(traffic, names, 0, WARMUP),
                                       WARMUP_ITEMS))


def si(box: dict) -> dict:
    """The box in the units the reference compares in (J, s)."""
    return {"area_mm2": box["area_mm2"], "power_w": box["power_w"],
            "energy_j": box["energy_mj"] * 1e-3,
            "latency_s": box["latency_ms"] * 1e-3}
