"""The plain reference: the photonic cost model evaluated at every point of
the design space, in one stated precision, importing nothing of the
program.

The model is the DxPTA paper's (Sections III-IV): a photonic tensor
accelerator with N_t tiles of N_c cores, each core an N_h x N_v array of
dot-product units fed N_lambda wavelengths. The equations are written out
here from that description, term by term:

  area, power  component counts (modulators and DACs per core channel,
               dot units, tile-shared ADC/TIA chains, comb lasers, tile
               logic, the inter-tile network growing as N_t^2, SRAM)
               times per-component constants
  cycles       per GEMM (M, K, N) x count:
               ceil(M / (N_t N_h)) * ceil(N / N_v) * ceil(K / (N_c N_l))
  latency      max(cycles / f_clk, off-chip bytes / DRAM bandwidth)
               + element-wise ops / electronic throughput
  energy       power * latency + DRAM bytes * e_dram
               + SRAM operand bytes * e_sram
  edp          energy * latency

A point is feasible under a box when each of area, power, energy and
latency lies strictly below its bound. The min-EDP answer is the feasible
point of least EDP (ties to the first point in grid order); the Pareto
answer is every feasible point that no other feasible point dominates
(<= on every objective, < on one; exact ties all kept).

Grid order: flat index ((((t * C + c) * V + v) * H + h) * L + l) over the
candidate values 1..n_z of (N_t, N_c, N_v, N_h, N_lambda) -- N_t slowest,
N_lambda fastest. Rows are reported as (N_t, N_c, N_h, N_v, N_lambda).

Every function takes `xp` (numpy, or jax.numpy for the float32 control
on the chip) and a float dtype. The reference proper is numpy float64.
"""
from __future__ import annotations

import numpy as np

METRICS = ("area", "power", "energy", "latency", "edp")
CHUNK = 1 << 20   # points per evaluation block: bounds the host memory


def sram_mb(max_act_bytes: float, c: dict) -> float:
    """Global SRAM: the largest activation double-buffered plus 2 MB of
    staging, clipped to the constants' range (not a searched size)."""
    mb = 2.0 * max_act_bytes / 2 ** 20 + 2.0
    return float(min(max(mb, c["sram_min_mb"]), c["sram_max_mb"]))


def space_rows(n_z: int, idx: np.ndarray) -> np.ndarray:
    """(len(idx), 5) int64 rows (N_t, N_c, N_h, N_v, N_lambda) at the flat
    grid indices `idx` of the full 1..n_z space."""
    i = np.asarray(idx, np.int64)
    digits = []
    for _ in range(5):           # l, h, v, c, t: fastest first
        digits.append(i % n_z)
        i = i // n_z
    d_l, d_h, d_v, d_c, d_t = digits
    return np.stack([d_t, d_c, d_h, d_v, d_l], axis=1) + 1


def evaluate(rows, wl: dict, c: dict, xp=np, dtype=np.float64) -> dict:
    """{metric: (n,) array} for config rows (n, 5) of
    (N_t, N_c, N_h, N_v, N_lambda), every float operation in `dtype`."""
    def f(v):
        return xp.asarray(v, dtype)

    rows = xp.asarray(rows)
    it = rows.dtype
    n_t, n_c, n_h, n_v, n_l = (rows[:, j] for j in range(5))
    t, cc, h, v, lam = (xp.asarray(a, dtype) for a in
                        (n_t, n_c, n_h, n_v, n_l))
    s_mb = f(sram_mb(wl["max_act_bytes"], c))

    cores = t * cc
    channels = cores * (h + v) * lam       # modulator + DAC channels
    dots = cores * h * v                   # dot-product units
    adcs = t * h * v                       # ADC/TIA chains, tile-shared
    area = (channels * f(c["a_mzm"]) + channels * f(c["a_dac"])
            + dots * f(c["a_ddot"]) + dots * f(c["a_acc"])
            + cores * f(c["a_core_fixed"])
            + adcs * (f(c["a_adc"]) + f(c["a_tia"]))
            + t * (f(c["a_comb_base"]) + f(c["a_comb_per_lambda"]) * lam)
            + t * f(c["a_tile_fixed"])
            + f(c["a_inter_tile_net"]) * t * t
            + s_mb * f(c["a_sram_per_mb"]) + f(c["a_chip_fixed"]))
    power = (channels * f(c["p_mzm"]) + channels * f(c["p_dac"])
             + dots * f(2.0) * f(c["p_pd"])
             + adcs * (f(c["p_adc"]) + f(c["p_tia"]))
             + dots * f(c["p_acc"]) + cores * f(c["p_core_fixed"])
             + t * (f(c["p_comb_base"]) + f(c["p_comb_per_lambda"]) * lam)
             + t * f(c["p_laser_split"]) * lam * h * v
             + t * f(c["p_tile_fixed"])
             + f(c["p_inter_tile_net"]) * t * t
             + s_mb * f(c["p_sram_per_mb"]) + f(c["p_chip_fixed"]))

    # Integer ceil-divisions are exact in the integer dtype; only their
    # product enters the float dtype.
    rows_split = n_t * n_h
    k_split = n_c * n_l
    lanes = (t * h + v) * cc * lam          # SRAM operand lanes per cycle
    cycles = xp.zeros_like(t)
    sram_lane_cycles = xp.zeros_like(t)
    for m, k, n, count in wl["gemms"]:
        g = (xp.asarray(-(-xp.asarray(m, it) // rows_split), dtype)
             * xp.asarray(-(-xp.asarray(n, it) // n_v), dtype)
             * xp.asarray(-(-xp.asarray(k, it) // k_split), dtype)
             * f(count))
        cycles = cycles + g
        sram_lane_cycles = sram_lane_cycles + g * lanes
    offchip = f(wl["weight_bytes"]) + f(wl["act_io_bytes"])
    latency = (xp.maximum(cycles / f(c["f_clk_hz"]),
                          offchip / f(c["dram_bw_bytes"]))
               + f(wl["elec_ops"]) / f(c["elec_ops_per_s"]))
    energy = (power * latency + f(c["e_dram_per_byte"]) * offchip
              + f(c["e_sram_per_byte"]) * sram_lane_cycles
              * f(c["act_bits"]) / f(8.0))
    return {"area": area, "power": power, "energy": energy,
            "latency": latency, "edp": energy * latency}


def feasible(m: dict, box: dict, xp=np):
    """Strict feasibility under a box {area_mm2, power_w, energy_j,
    latency_s}."""
    return ((m["area"] < box["area_mm2"]) & (m["power"] < box["power_w"])
            & (m["energy"] < box["energy_j"])
            & (m["latency"] < box["latency_s"]))


def sweep(n_z: int, wl: dict, c: dict, box: dict, xp=np,
          dtype=np.float64) -> tuple:
    """Exhaustive sweep of the 1..n_z space: (idx, rows, metrics) of every
    point feasible under `box`, in grid order, metrics as float64 arrays
    of values computed in `dtype`.

    Each window query's box lies inside the traffic's loosest box, so its
    feasible set is a subset of this one: answering from it is exact."""
    size = n_z ** 5
    keep_idx, keep = [], {k: [] for k in METRICS}
    for start in range(0, size, CHUNK):
        idx = np.arange(start, min(start + CHUNK, size), dtype=np.int64)
        rows = space_rows(n_z, idx)
        dev_rows = xp.asarray(rows.astype(np.int32)) if xp is not np \
            else rows
        m = evaluate(dev_rows, wl, c, xp, dtype)
        ok = np.asarray(feasible(m, box, xp))
        keep_idx.append(idx[ok])
        for k in METRICS:
            keep[k].append(np.asarray(m[k])[ok].astype(np.float64))
    idx = np.concatenate(keep_idx)
    return idx, space_rows(n_z, idx), {k: np.concatenate(v)
                                       for k, v in keep.items()}


def _dominated(cand: np.ndarray, by: np.ndarray) -> np.ndarray:
    """Rows of `cand` that some row of `by` dominates: <= on every column
    and < on one."""
    if len(by) == 0:
        return np.zeros(len(cand), bool)
    le = np.all(by[None, :, :] <= cand[:, None, :], axis=2)
    lt = np.any(by[None, :, :] < cand[:, None, :], axis=2)
    return np.any(le & lt, axis=1)


def pareto_mask(points: np.ndarray) -> np.ndarray:
    """Non-dominated rows of (n, d) points, all minimized; exact ties kept.

    A dominator comes before the point it dominates in lexicographic
    order, and whatever dominates a point is itself dominated by, or is, a
    non-dominated point that also comes earlier. So the points are taken in
    lexicographic order, a block at a time, and each block is tested
    against the front found so far and against itself."""
    n = len(points)
    order = np.lexsort(points.T[::-1])
    keep = np.zeros(n, bool)
    front = points[:0]
    for s in range(0, n, 256):
        blk = order[s:s + 256]
        p = points[blk]
        ok = ~_dominated(p, front)
        ok[ok] = ~_dominated(p[ok], p[ok])
        keep[blk[ok]] = True
        front = np.concatenate([front, p[ok]])
    return keep


def answer_edp(sub: tuple, box: dict):
    """(row, metrics) of the min-EDP feasible point under `box`, or
    (None, None) when no point is feasible."""
    idx, rows, m = sub
    ok = feasible(m, box)
    if not ok.any():
        return None, None
    cand = np.nonzero(ok)[0]
    best = cand[np.lexsort((idx[cand], m["edp"][cand]))[0]]
    return rows[best], {k: float(m[k][best]) for k in METRICS}


def answer_pareto(sub: tuple, box: dict, objectives) -> tuple:
    """(rows, metrics) of the feasible Pareto front under `box`, rows in
    lexicographic order."""
    idx, rows, m = sub
    ok = np.nonzero(feasible(m, box))[0]
    pts = np.stack([m[k][ok] for k in objectives], axis=1)
    sel = ok[pareto_mask(pts)]
    sel = sel[np.lexsort(rows[sel].T[::-1])]
    return rows[sel], {k: m[k][sel] for k in METRICS}
