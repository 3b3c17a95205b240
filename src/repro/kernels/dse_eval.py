"""Pallas TPU kernels: DxPTA config-grid evaluation + fused DSE search.

Two kernels over the same per-config cost model (mirroring
photonic_model.eval_hw + performance_model.eval_wload_arrays):

  * `dse_eval_padded`   — metrics mode: every candidate config in the grid
    maps to its (area, power, energy, latency) tuple. Used for Fig. 9-style
    scatter data where the full metric field is the product.
  * `dse_search_padded` — fused search mode (the DSE hot path): constraint
    masking, EDP computation and a per-block (best_edp, best_idx, n_feasible)
    argmin reduction all happen inside the kernel, so only a (3*W, n_blocks)
    reduction array ever leaves the device — the (4, G) metrics array is
    never materialized on the host. W workloads are evaluated against the
    same grid in a single launch (their static GEMM lists are unrolled in
    sequence); constraints stream in as a dynamic (W, 4) operand so
    constraint-scenario sweeps reuse one jit cache entry.

Both search-mode kernels take a *carry* operand so per-chunk launches
compose — the streaming layer (`core.search` with `chunk_size=`) feeds each
chunk's launch the reduction state of the chunks before it:

  * search mode carries the (W, 1) best EDP seen so far. A block whose local
    best cannot beat the carry emits the carried EDP with the CARRY_IDX
    sentinel instead of a config index (the carry is from an earlier chunk,
    so it also wins exact ties — preserving the global first-hit rule).
  * frontier mode carries up to CARRY_FRONT already-known frontier points
    per workload (the running front's objective values in the kernel's own
    float32 metric space): block-local candidates strictly dominated by a
    carried point are pruned before emission, which keeps per-chunk
    candidate lists (and MAX_FRONT overflows) from accumulating across a
    streamed sweep. Carrying any *subset* of the running front is sound —
    the prune only ever drops points some real carried point dominates.

Each TPU lane owns one candidate architecture; the config grid streams
through VMEM in (5, BLOCK) tiles. Both wrappers pad + mask internally, so
arbitrary grid sizes (e.g. DxPTA's pruned candidate sets) work without
caller-side padding.

Both search-mode kernels also come in a *decoded* (factorized-space)
variant (`dse_search_decoded` / `dse_pareto_decoded`): when the grid is a
Cartesian product of per-axis candidate sets, the kernel takes only the
(5, max_radix) candidate-value matrix plus [start, end) index spans, and
every lane reconstructs its own config row on device via iota -> mixed-radix
decode (`_decode_block`) — the (5, G) grid is never materialized on the
host, and the only per-launch traffic is the per-block reduction output.
These compose with the same carry operands, so chunked/sharded factorized
sweeps stream exactly like the grid-operand ones.

`repro.core.search.evaluate_grid` (pure jnp/numpy) is the oracle these are
tested against (see kernels/ref.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.photonic_model import DeviceConstants

from .backend import resolve_interpret

BLOCK = 2048  # configs per grid step (16 sublane rows x 128 lanes)

# Lane count per grid step of the *decoded search* kernel. Decoded lanes
# are generated from an iota — no (5, BLOCK) operand tile to stream — so
# the block can be much wider than the grid-operand kernels': under
# interpret mode the per-block dispatch overhead dominates the whole
# launch, and 8x wider blocks cut it 8x (the decoded frontier kernel keeps
# BLOCK — its pairwise dominance pass is O(block^2)). The decoded search
# tile is a dense (DECODE_BLOCK // LANES, LANES) array, 64 KiB per float32
# temporary, well inside the chip's scoped VMEM.
DECODE_BLOCK = 16384

# TPU vector lane width: per-block reduction outputs are stored as
# lane-dense (rows, LANES) tiles (a 1-lane block is not a legal Mosaic
# block) and the jitted wrapper keeps lane 0 of each.
LANES = 128

# Per-workload rows in the fused-search reduction output.
SEARCH_ROWS = 3  # (best_edp, best_idx, n_feasible)

# Index sentinel emitted when the carried-in best (from an earlier chunk of a
# streamed sweep) beats — or exactly ties — everything in the block.
CARRY_IDX = -2.0

# Frontier mode: per-block local non-dominated candidate bound. Measured
# local fronts on the paper workloads' 12^5 grid top out around ~100 per
# 2048-config block; a block whose local front overflows the bound reports
# its true count and the host falls back to refining that whole block.
MAX_FRONT = 128
PARETO_HEADER = 2  # (local front count, block feasible count)
PARETO_ROWS = PARETO_HEADER + MAX_FRONT

# Row chunk of the in-kernel pairwise dominance pass ((DOM_CHUNK, BLOCK)
# comparison tiles instead of one (BLOCK, BLOCK) matrix).
DOM_CHUNK = 256

# Frontier mode: carried-in running-front points per workload. +inf padding
# rows never dominate anything, so any shorter carry is just padded out.
CARRY_FRONT = 128

# Decoded-kernel meta row: [start, end) of the launch's flat-index span
# followed by five [lo, hi) digit ranges (meshgrid axis order t, c, v, h,
# lambda) — the slab the lanes must fall inside to count. Full ranges
# reduce the slab test to the plain span test.
META_COLS = 12


def _to_i32(x):
    """int32 conversion that keeps static python scalars exact (no float32
    round-trip — 2**24 + 1 would silently become 2**24). Traced operands
    here are config-parameter products (< 2**24), so their cast is exact."""
    if isinstance(x, (int, float)):
        return jnp.asarray(int(x), jnp.int32)
    return jnp.asarray(x).astype(jnp.int32)


def _ceil_div(a, b):
    """Exact int32 ceil(a / b) for integer-valued inputs.

    The previous float formulation `floor((a + b - 1.0) / b)` drifts once
    a + b - 1 exceeds the 24-bit float32 mantissa (large M/K/N dims at
    serving batch sizes). Integer arithmetic matches
    `performance_model._ceil_div` bit-for-bit for dims up to 2**31 - b
    (the int32 headroom the `+ b - 1` needs; b is a config-parameter
    product <= 4096 in practice). Callers convert to float32 only when
    entering the (rounding-tolerant) cycle products.
    """
    ai, bi = _to_i32(a), _to_i32(b)
    return (ai + bi - 1) // bi


def _config_metrics_hw(wl_scalars, c: DeviceConstants,
                       n_t, n_c, n_h, n_v, n_l):
    """(area, power) for a config tile — the cheap hardware half of the
    cost model (mirrors photonic_model.py)."""
    sram_mb = wl_scalars[3]
    cores = n_t * n_c
    mod_channels = cores * (n_h + n_v) * n_l
    ddots = cores * n_h * n_v
    adc_chains = n_t * n_h * n_v
    area = (mod_channels * (c.a_mzm + c.a_dac)
            + ddots * (c.a_ddot + c.a_acc) + cores * c.a_core_fixed
            + adc_chains * (c.a_adc + c.a_tia)
            + n_t * (c.a_comb_base + c.a_comb_per_lambda * n_l)
            + n_t * c.a_tile_fixed
            + c.a_inter_tile_net * n_t * n_t
            + sram_mb * c.a_sram_per_mb + c.a_chip_fixed)
    power = (mod_channels * (c.p_mzm + c.p_dac)
             + ddots * 2 * c.p_pd
             + adc_chains * (c.p_adc + c.p_tia)
             + ddots * c.p_acc + cores * c.p_core_fixed
             + n_t * (c.p_comb_base + c.p_comb_per_lambda * n_l)
             + n_t * c.p_laser_split * n_l * n_h * n_v
             + n_t * c.p_tile_fixed
             + c.p_inter_tile_net * n_t * n_t
             + sram_mb * c.p_sram_per_mb + c.p_chip_fixed)
    return area, power


def _config_metrics_wl(gemms, wl_scalars, c: DeviceConstants, power,
                       n_t, n_c, n_h, n_v, n_l):
    """(energy, latency) for a config tile — the per-GEMM dataflow half of
    the cost model (mirrors performance_model.py); `power` from
    `_config_metrics_hw`."""
    elec_ops, weight_bytes, act_io_bytes, _ = wl_scalars
    total_cycles = jnp.zeros_like(n_t)
    sram_lane_cycles = jnp.zeros_like(n_t)
    lanes = (n_t * n_h + n_v) * n_c * n_l
    for (m, k, n, count) in gemms:  # static unroll — W is small
        cyc = (_ceil_div(m, n_t * n_h).astype(jnp.float32)
               * _ceil_div(n, n_v).astype(jnp.float32)
               * _ceil_div(k, n_c * n_l).astype(jnp.float32)) * count
        total_cycles += cyc
        sram_lane_cycles += cyc * lanes
    t_photonic = total_cycles / c.f_clk_hz
    t_mem = (weight_bytes + act_io_bytes) / c.dram_bw_bytes
    t_elec = elec_ops / c.elec_ops_per_s
    latency = jnp.maximum(t_photonic, t_mem) + t_elec
    sram_bytes = sram_lane_cycles * (c.act_bits / 8.0)
    energy = (power * latency
              + c.e_dram_per_byte * (weight_bytes + act_io_bytes)
              + c.e_sram_per_byte * sram_bytes)
    return energy, latency


def _config_metrics(gemms, wl_scalars, c: DeviceConstants,
                    n_t, n_c, n_h, n_v, n_l):
    """(area, power, energy, latency) for a (BLOCK,) vector of configs.

    gemms: static python tuple of (m, k, n, count); wl_scalars: static
    (elec_ops, weight_bytes, act_io_bytes, sram_mb). Shared by the metrics
    kernel and the fused search kernels (which call the two halves
    separately, so an all-hw-infeasible block can skip the GEMM loop).
    """
    area, power = _config_metrics_hw(wl_scalars, c, n_t, n_c, n_h, n_v,
                                     n_l)
    energy, latency = _config_metrics_wl(gemms, wl_scalars, c, power,
                                         n_t, n_c, n_h, n_v, n_l)
    return area, power, energy, latency


def _cfg_cols(cfg_ref):
    """The five (1, BLOCK) config rows of a grid-operand tile."""
    return tuple(cfg_ref[r:r + 1, :] for r in range(5))


def _lane_iota(shape, dtype=jnp.int32):
    """Row-major position of every element of a 2-D tile (a 1-D iota does
    not lower on the TPU)."""
    rows = jax.lax.broadcasted_iota(dtype, shape, 0)
    lanes = jax.lax.broadcasted_iota(dtype, shape, 1)
    return rows * shape[1] + lanes


def _store_scalars(out_ref, r0, vals):
    """Write scalars to rows r0, r0 + 1, ... of a (rows, LANES) output
    tile, each broadcast across the lanes (the wrapper keeps lane 0)."""
    for k, v in enumerate(vals):
        out_ref[r0 + k:r0 + k + 1, :] = jnp.full((1, LANES), v, jnp.float32)


def _dse_kernel(gemms, wl_scalars, c: DeviceConstants, cfg_ref, out_ref):
    area, power, energy, latency = _config_metrics(
        gemms, wl_scalars, c, *_cfg_cols(cfg_ref))
    out_ref[0:1, :] = area
    out_ref[1:2, :] = power
    out_ref[2:3, :] = energy
    out_ref[3:4, :] = latency


def _decode_block(radices, axes_ref, base, meta, shape=(1, BLOCK)):
    """On-device candidate generation: one block's configs from its index.

    The factorized kernels never see a (5, G) config operand — each lane
    reconstructs its own candidate row from the block's base index plus
    the per-axis candidate vectors:

      global index = base + row-major position in the `shape` tile,

    mixed-radix decoded with the static `radices` (meshgrid axis order
    t, c, v, h, lambda — N_lambda fastest) via the same
    core.factorized.decode_digits the host engines use — host and device
    decodes cannot diverge — then mapped to candidate values with a
    clamped select chain per axis over the axes_ref row (`radix - 1`
    vector selects: the TPU lowers no 1-D gather).

    Validity is a *slab* test, not just a span test: `meta(k)` reads
    column k of the block's meta row [base or start, end, lo_t, hi_t,
    lo_c, hi_c, lo_v, hi_v, lo_h, hi_h, lo_l, hi_l] (META_COLS int32
    entries), and a lane is valid when its global index sits below `end`
    *and* every decoded digit sits inside its axis's [lo, hi) range. A
    contiguous span is the special case of full ranges; the bound-guided
    (branch-and-bound) search uses the general form to launch over a
    pruned slab's bounding index range with the non-member lanes masked
    out. Invalid lanes (the padded tail of the last block, indices past
    the space, slab non-members) gather a clamped — still valid, never
    div-by-zero — candidate value and are masked out of every reduction.

    Returns ((n_t, n_c, n_h, n_v, n_lambda) float32 `shape` tiles, float32
    global indices, validity mask). Emitted indices are exact for spaces
    below 2**24 points (float32 mantissa), like every kernel index here.
    """
    from repro.core.factorized import decode_digits

    gidx = base + _lane_iota(shape)
    digits = decode_digits(gidx, radices, jnp)
    d_t, d_c, d_v, d_h, d_l = digits

    valid = gidx < meta(1)
    for ax, d in enumerate(digits):
        valid &= (d >= meta(2 + 2 * ax)) & (d < meta(3 + 2 * ax))

    def pick(row, digit):
        # axes[row, clip(digit, 0, radix - 1)] as an ascending select chain.
        val = jnp.full(shape, axes_ref[row, 0], jnp.float32)
        for k in range(1, int(radices[row])):
            val = jnp.where(digit >= k, axes_ref[row, k], val)
        return val

    cols = (pick(0, d_t), pick(1, d_c), pick(3, d_h),
            pick(2, d_v), pick(4, d_l))
    return cols, gidx.astype(jnp.float32), valid


def _span_block(radices, axes_ref, meta_ref, shape=(1, BLOCK)):
    """`_decode_block` for the span kernels: one (1, META_COLS) meta row
    per launch, block `program_id` starting at its base + program_id x
    the block size."""
    def meta(k):
        return meta_ref[0, k]

    base = meta(0) + pl.program_id(0) * (shape[0] * shape[1])
    return _decode_block(radices, axes_ref, base, meta, shape)


def _search_reduce(workloads, c: DeviceConstants, cols, valid, idx,
                   cons_ref, carry_ref, out_ref):
    """Shared fused feasibility + EDP argmin reduction over one config tile
    (used by both the grid-operand and the decode kernels — identical math,
    so the factorized launches are bit-identical per config).

    Early exits mirror the frontier kernel's all-infeasible chunk skip: a
    block with no valid lane (the padded tail of a bucketed launch, or a
    bound-pruned slab's dead bounding-range block) skips the cost model
    entirely; a block whose valid lanes all violate the cheap area/power
    half skips the per-GEMM dataflow loop (the in-kernel analogue of the
    hierarchical prefilter — exact, because feasibility requires the
    area/power pass anyway); and a block whose lanes are all infeasible
    skips the argmin/select. Every branch emits exactly what the
    straight-line code emitted for those blocks — (carried EDP, CARRY_IDX,
    feasible count) — so the reduction output is byte-identical either
    way.
    """
    any_valid = jnp.any(valid)
    for w, (gemms, wl_scalars) in enumerate(workloads):

        def live(w=w, gemms=gemms, wl_scalars=wl_scalars):
            area, power = _config_metrics_hw(wl_scalars, c, *cols)
            hw_ok = (valid
                     & (area < cons_ref[w, 0]) & (power < cons_ref[w, 1]))

            def hw_feasible(w=w, gemms=gemms, wl_scalars=wl_scalars):
                energy, latency = _config_metrics_wl(
                    gemms, wl_scalars, c, power, *cols)
                ok = (hw_ok & (energy < cons_ref[w, 2])
                      & (latency < cons_ref[w, 3]))
                edp = jnp.where(ok, energy * latency, jnp.inf)
                nf = jnp.sum(ok.astype(jnp.float32))

                def feasible():
                    # First-hit argmin without a dynamic index: the block
                    # minimum, then the smallest index attaining it.
                    best = jnp.min(edp)
                    at = jnp.min(jnp.where(edp == best, idx, jnp.inf))
                    carried = carry_ref[w, 0] <= best
                    return (jnp.where(carried, carry_ref[w, 0], best),
                            jnp.where(carried, CARRY_IDX, at), nf)

                def infeasible():
                    return carry_ref[w, 0], jnp.float32(CARRY_IDX), nf

                return jax.lax.cond(jnp.any(ok), feasible, infeasible)

            def hw_dead(w=w):
                return (carry_ref[w, 0], jnp.float32(CARRY_IDX),
                        jnp.float32(0.0))

            return jax.lax.cond(jnp.any(hw_ok), hw_feasible, hw_dead)

        def dead(w=w):
            return carry_ref[w, 0], jnp.float32(CARRY_IDX), jnp.float32(0.0)

        _store_scalars(out_ref, SEARCH_ROWS * w,
                       jax.lax.cond(any_valid, live, dead))


def _dse_search_kernel(workloads, c: DeviceConstants,
                       cfg_ref, mask_ref, cons_ref, carry_ref, out_ref):
    """Fused feasibility + EDP argmin over one (5, BLOCK) config tile.

    workloads: static tuple of (gemms, wl_scalars) pairs; cons_ref holds the
    dynamic (W, 4) [area, power, energy, latency] bounds; carry_ref the
    (W, 1) best EDP carried in from earlier chunks of a streamed sweep
    (+inf when there is none). Emits SEARCH_ROWS rows per workload:
    block-best EDP, its launch-local config index — or CARRY_IDX when the
    carried best wins or exactly ties (the carry precedes every config of
    this launch, so ties go to it, preserving the first-hit rule) — and the
    block feasible count.
    """
    cols = _cfg_cols(cfg_ref)
    valid = mask_ref[...] > 0.0
    idx = (pl.program_id(0) * BLOCK + _lane_iota((1, BLOCK))).astype(
        jnp.float32)
    _search_reduce(workloads, c, cols, valid, idx, cons_ref, carry_ref,
                   out_ref)


def _dse_search_decode_kernel(workloads, radices, c: DeviceConstants,
                              axes_ref, table_ref, cons_ref, carry_ref,
                              out_ref):
    """Factorized-space variant of `_dse_search_kernel`: configs decoded on
    device (see `_decode_block`, DECODE_BLOCK lanes per step) instead of
    streamed in, and the emitted index is the *global* flat-space index
    (the decode already knows it), so the host wrapper needs no base
    bookkeeping.

    Grid step i reads row i of the flattened meta table in SMEM: its own
    block base, span end and slab digit ranges, so one launch covers any
    list of blocks — the leaves of a whole branch-and-bound batch, or the
    blocks of one span."""
    row = pl.program_id(0) * META_COLS

    def meta(k):
        return table_ref[row + k]

    cols, idx, valid = _decode_block(radices, axes_ref, meta(0), meta,
                                     (DECODE_BLOCK // LANES, LANES))
    _search_reduce(workloads, c, cols, valid, idx, cons_ref, carry_ref,
                   out_ref)


def _block_front(objs, ok):
    """(1, BLOCK) mask of block-locally non-dominated feasible configs.

    objs: tuple of (1, BLOCK) objective rows (minimized); ok: feasibility.
    Infeasible rows get +inf objectives, so they never dominate (inf <= x is
    false) and are excluded from the front by the `ok &`. Exact ties are
    kept (dominance needs a strict < somewhere).

    Full pairwise dominance in (DOM_CHUNK, BLOCK) tiles: each chunk of
    potential dominators, turned into a column, is compared with every
    config of the block. No presort (the TPU lowers no sort), so the mask
    is the exact block-local front. Chunks with no feasible row cannot
    dominate anything and skip their tile via lax.cond, so
    sparse-feasibility blocks pay for their feasible chunks only.
    """
    o = [jnp.where(ok, x, jnp.inf) for x in objs]
    n = o[0].shape[1]
    dominated = jnp.zeros((1, n), jnp.float32)
    for s in range(0, n, DOM_CHUNK):
        hi = min(s + DOM_CHUNK, n)

        def tile(s=s, hi=hi):
            le = None
            lt = None
            for x in o:
                r = x[:, s:hi].reshape(hi - s, 1)  # dominators as a column
                l_ = r <= x
                t_ = r < x
                le = l_ if le is None else (le & l_)
                lt = t_ if lt is None else (lt | t_)
            return jnp.any(le & lt, axis=0, keepdims=True).astype(
                jnp.float32)

        dominated = jnp.maximum(dominated, jax.lax.cond(
            jnp.any(ok[:, s:hi]), tile,
            lambda: jnp.zeros((1, n), jnp.float32)))
    return ok & (dominated == 0.0)


def _carry_dominated(carry_ref, r0, objs):
    """(1, BLOCK) mask of configs strictly dominated by a carried point.

    carry_ref rows [r0, r0 + CARRY_FRONT) hold the (CARRY_FRONT, d)
    objective points carried in from earlier chunks (+inf padding —
    inf <= x is false, so padding never dominates); objs: tuple of d
    (1, BLOCK) objective rows. Exact ties survive (dominance needs a strict
    < somewhere), matching `_block_front`.
    """
    le = None
    lt = None
    for j, x in enumerate(objs):
        cj = carry_ref[r0:r0 + CARRY_FRONT, j:j + 1]
        l_ = cj <= x
        t_ = cj < x
        le = l_ if le is None else (le & l_)
        lt = t_ if lt is None else (lt | t_)
    return jnp.any(le & lt, axis=0, keepdims=True)


def _pareto_reduce(workloads, objectives, has_carry: bool,
                   c: DeviceConstants, cols, valid,
                   cons_ref, carry_ref, out_ref):
    """Shared per-block dominance body (grid-operand and decode kernels).

    Emits two (1, BLOCK) rows per workload — the feasibility mask and the
    block-local front mask, as 0/1 floats. The jitted wrapper reduces them
    to the per-block header and index rows (`_front_rows`) before anything
    leaves the device."""
    for w, (gemms, wl_scalars) in enumerate(workloads):
        area, power, energy, latency = _config_metrics(
            gemms, wl_scalars, c, *cols)
        ok = (valid
              & (area < cons_ref[w, 0]) & (power < cons_ref[w, 1])
              & (energy < cons_ref[w, 2]) & (latency < cons_ref[w, 3]))
        vals = {"area": area, "power": power, "energy": energy,
                "latency": latency, "edp": energy * latency}
        objs = tuple(vals[k] for k in objectives)
        front = _block_front(objs, ok)
        if has_carry:
            front = front & ~_carry_dominated(
                carry_ref, w * CARRY_FRONT,
                tuple(jnp.where(ok, x, jnp.inf) for x in objs))
        out_ref[2 * w:2 * w + 1, :] = ok.astype(jnp.float32)
        out_ref[2 * w + 1:2 * w + 2, :] = front.astype(jnp.float32)


def _front_rows(masks, first, w: int, n_blocks: int):
    """(2W, n_blocks * BLOCK) kernel masks -> (PARETO_ROWS * W, n_blocks).

    Per workload and block: the local-front size, the feasible count, then
    the first MAX_FRONT front members' indices (`first` + launch offset,
    float32, ascending) padded with -1. Runs in XLA inside the wrapper's
    jit, so only these per-block rows reach the host."""
    m = masks.reshape(w, 2, n_blocks, BLOCK)
    ok, front = m[:, 0] > 0.0, m[:, 1] > 0.0
    local = jnp.arange(BLOCK, dtype=jnp.float32)
    # Non-members key to BLOCK, sorting after every member.
    key = jnp.sort(jnp.where(front, local, float(BLOCK)),
                   axis=-1)[..., :MAX_FRONT]
    base = (first + jnp.arange(n_blocks, dtype=jnp.int32) * BLOCK).astype(
        jnp.float32)
    gidx = jnp.where(key < BLOCK, base[None, :, None] + key, -1.0)
    rows = jnp.concatenate(
        [jnp.sum(front, axis=-1, dtype=jnp.float32)[:, None],
         jnp.sum(ok, axis=-1, dtype=jnp.float32)[:, None],
         jnp.swapaxes(gidx, 1, 2)], axis=1)
    return rows.reshape(w * PARETO_ROWS, n_blocks)


def _dse_pareto_kernel(workloads, objectives, has_carry: bool,
                       c: DeviceConstants,
                       cfg_ref, mask_ref, cons_ref, carry_ref, out_ref):
    """Per-block dominance reduction over one (5, BLOCK) config tile.

    Local fronts are a superset filter — any point dominated inside its
    block is dominated globally — so the host only merges the per-block
    candidate lists; the (4, G) metrics array never leaves the device.
    carry_ref holds (W * CARRY_FRONT, d) running-front objective points
    from earlier chunks of a streamed sweep (+inf rows when there is no
    carry): block candidates strictly dominated by a carried point are
    pruned before emission, so streamed candidate lists stay bounded by the
    frontier, not the grid. `has_carry` is static: one-shot launches (no
    carry possible) specialize the whole (CARRY_FRONT, BLOCK) prune away
    instead of comparing against +inf.
    """
    _pareto_reduce(workloads, objectives, has_carry, c, _cfg_cols(cfg_ref),
                   mask_ref[...] > 0.0, cons_ref, carry_ref, out_ref)


def _dse_pareto_decode_kernel(workloads, objectives, has_carry: bool,
                              radices, c: DeviceConstants,
                              axes_ref, meta_ref, cons_ref, carry_ref,
                              out_ref):
    """Factorized-space variant of `_dse_pareto_kernel`: configs decoded on
    device from the chunk base + per-axis candidate vectors."""
    cols, _, valid = _span_block(radices, axes_ref, meta_ref)
    _pareto_reduce(workloads, objectives, has_carry, c, cols, valid,
                   cons_ref, carry_ref, out_ref)


def _decode_rows_kernel(radices, axes_ref, meta_ref, out_ref):
    """Decode-proof kernel: emits the decoded (5, BLOCK) config columns plus
    a validity row, so tests can pin the on-device mixed-radix decode
    against `config_grid` rows directly."""
    cols, _, valid = _span_block(radices, axes_ref, meta_ref)
    for r, col in enumerate(cols):
        out_ref[r:r + 1, :] = col
    out_ref[5:6, :] = valid.astype(jnp.float32)


def _pad_cols(cfg_cols, mask=None):
    """(5, G) -> ((5, G_pad), (1, G_pad) validity mask) with G_pad % BLOCK == 0.

    Padding configs are all-ones (valid model inputs, so no div-by-zero) and
    masked out of any reduction; metrics-mode callers simply trim the tail.
    """
    g = cfg_cols.shape[1]
    pad = (-g) % BLOCK
    if mask is None:
        mask = jnp.ones((1, g), jnp.float32)
    if pad:
        cfg_cols = jnp.pad(cfg_cols, ((0, 0), (0, pad)), constant_values=1.0)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return cfg_cols, mask


def _search_out_spec(w: int, n_blocks: int):
    """Lane-dense (SEARCH_ROWS * W, LANES) output tile per grid step, and
    the full (SEARCH_ROWS * W, n_blocks * LANES) output shape."""
    rows = SEARCH_ROWS * w
    return (pl.BlockSpec((rows, LANES), lambda i: (0, i)),
            jax.ShapeDtypeStruct((rows, n_blocks * LANES), jnp.float32))


def _pareto_out_spec(w: int, n_blocks: int):
    """(2W, BLOCK) mask tile per grid step (see `_pareto_reduce`)."""
    return (pl.BlockSpec((2 * w, BLOCK), lambda i: (0, i)),
            jax.ShapeDtypeStruct((2 * w, n_blocks * BLOCK), jnp.float32))


@functools.partial(jax.jit, static_argnames=("gemms", "wl_scalars",
                                             "constants", "interpret"))
def dse_eval_padded(cfg_cols, *, gemms: tuple, wl_scalars: tuple,
                    constants: DeviceConstants,
                    interpret: Optional[bool] = None):
    """cfg_cols: (5, G) float32, any G -> (4, G) [area, power, energy,
    latency]. Pads to a BLOCK multiple internally and trims the result."""
    _, g = cfg_cols.shape
    cfg_cols, _ = _pad_cols(cfg_cols)
    kernel = functools.partial(_dse_kernel, gemms, wl_scalars, constants)
    out = pl.pallas_call(
        kernel,
        grid=(cfg_cols.shape[1] // BLOCK,),
        in_specs=[pl.BlockSpec((5, BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((4, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, cfg_cols.shape[1]), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="dse_eval_padded",
    )(cfg_cols)
    return out[:, :g]


@functools.partial(jax.jit, static_argnames=("workloads", "constants",
                                             "interpret"))
def dse_search_padded(cfg_cols, mask, cons, carry, *, workloads: tuple,
                      constants: DeviceConstants,
                      interpret: Optional[bool] = None):
    """Fused single-pass DSE search over a (5, G) config grid, any G.

    Args:
      cfg_cols: (5, G) float32 config columns (n_t, n_c, n_h, n_v, n_lambda).
      mask: (1, G) float32 validity mask (0 entries never win and never
        count as feasible). Callers that bucket-pad the grid to a shape the
        jit cache has seen (ops.dse_search_multi) mark their padding here;
        any remaining non-BLOCK-multiple tail is padded + masked internally.
      cons: (W, 4) float32 [area_mm2, power_w, energy_j, latency_s] bounds —
        a *dynamic* operand, so sweeping constraint scenarios hits one jit
        cache entry.
      carry: (W, 1) float32 best EDP carried in from earlier chunks of a
        streamed sweep; +inf rows mean "no carry". The carry wins exact
        ties (it precedes every config of this launch).
      workloads: static tuple of (gemms, wl_scalars) pairs (see
        performance_model.workload_statics).

    Returns (SEARCH_ROWS * W, n_blocks) float32: per workload w, rows
    [3w + 0] block-best EDP (inf when neither the block nor the carry has a
    feasible config), [3w + 1] its launch-local config index — CARRY_IDX
    when the carried-in best won the block — [3w + 2] block feasible count.
    Config indices are exact for G < 2**24 (float32 mantissa).
    """
    cfg_cols, mask = _pad_cols(cfg_cols, mask)
    n_blocks = cfg_cols.shape[1] // BLOCK
    w = len(workloads)
    kernel = functools.partial(_dse_search_kernel, workloads, constants)
    out_spec, out_shape = _search_out_spec(w, n_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((5, BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((w, 4), lambda i: (0, 0)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
        name="dse_search_padded",
    )(cfg_cols, mask, cons, carry)
    return out[:, ::LANES]


@functools.partial(jax.jit, static_argnames=("workloads", "objectives",
                                             "has_carry", "constants",
                                             "interpret"))
def dse_pareto_padded(cfg_cols, mask, cons, carry, *, workloads: tuple,
                      objectives: tuple, has_carry: bool = True,
                      constants: DeviceConstants,
                      interpret: Optional[bool] = None):
    """Fused frontier-candidate search over a (5, G) config grid, any G.

    Same operand contract as `dse_search_padded` (dynamic (W, 4) constraint
    rows, (1, G) validity mask, static workload tuple), plus a static
    `objectives` tuple naming the minimized metrics (any subset of area /
    power / energy / latency / edp) and a (W * CARRY_FRONT, d) `carry` of
    running-front objective points from earlier chunks (+inf rows = no
    carry; candidates strictly dominated by a carried point are pruned
    in-kernel — pass the static `has_carry=False` on one-shot launches to
    specialize the prune away entirely). Each block reduces to its local
    non-dominated feasible candidate set.

    Returns (PARETO_ROWS * W, n_blocks) float32: per workload w, row
    [r0 + 0] the block's true local-front size (> MAX_FRONT signals the
    emitted index list was truncated), [r0 + 1] the block feasible count,
    rows [r0 + 2 .. r0 + 2 + MAX_FRONT) launch-local config indices of
    local non-dominated configs, -1-padded, with r0 = PARETO_ROWS * w.
    Config indices are exact for G < 2**24 (float32 mantissa).
    """
    cfg_cols, mask = _pad_cols(cfg_cols, mask)
    n_blocks = cfg_cols.shape[1] // BLOCK
    w = len(workloads)
    d = len(objectives)
    kernel = functools.partial(_dse_pareto_kernel, workloads, objectives,
                               has_carry, constants)
    out_spec, out_shape = _pareto_out_spec(w, n_blocks)
    masks = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((5, BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((w, 4), lambda i: (0, 0)),
                  pl.BlockSpec((w * CARRY_FRONT, d), lambda i: (0, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
        name="dse_pareto_padded",
    )(cfg_cols, mask, cons, carry)
    return _front_rows(masks, 0, w, n_blocks)


# ---------------------------------------------------------------------------
# Factorized-space launches: on-device candidate generation (no (5, G) grid)
# ---------------------------------------------------------------------------
#
# The decode wrappers take the tiny (5, max_radix) candidate-value matrix
# plus int32 meta rows — an index span and the slab digit ranges (full
# ranges = a plain span) — instead of config columns: the kernels
# reconstruct every candidate row on device (`_decode_block`), so nothing
# grid-sized ever crosses the host/device boundary in either direction
# except the per-block reduction rows. The search kernel reads a table of
# meta rows, one per grid step, the grid as long as the table's live rows
# (callers pad the table to one fixed size); the frontier and decode-proof
# kernels read one (1, META_COLS) row and a static `n_blocks`, which
# callers bucket to a power of two exactly like `_bucketed_cols` buckets
# grid shapes, so streamed sweeps of varying chunk sizes reuse O(log G)
# jit entries.

def _axes_meta_specs(axes, w: int, extra):
    return [pl.BlockSpec(axes.shape, lambda i: (0, 0)),
            pl.BlockSpec((1, META_COLS), lambda i: (0, 0)),
            pl.BlockSpec((w, 4), lambda i: (0, 0)),
            extra]


@functools.partial(jax.jit, static_argnames=("radices", "workloads",
                                             "constants", "interpret"))
def dse_search_decoded(axes, table, cons, carry, *, radices: tuple,
                       workloads: tuple, constants: DeviceConstants,
                       interpret: Optional[bool] = None):
    """Fused search over the blocks a (R, META_COLS) int32 meta table names,
    one row per grid step — [block base, span end, five slab digit
    ranges] (see `_dse_search_decode_kernel`) — over a product space with
    static `radices`. Same operand contract and output layout as
    `dse_search_padded`, one output column per table row, except configs
    are decoded on device and emitted indices are global flat-space
    indices (no launch-local rebasing). The table sits in SMEM, flattened
    (a scalar read per column per step).

    Live rows (span end > block base) come first, padding rows after
    them: the grid's length is the live row count, read from the table on
    device, so one executable serves every row count up to R and padding
    costs no grid step. The columns of padding rows are never written;
    read only the live rows' columns."""
    w = len(workloads)
    kernel = functools.partial(_dse_search_decode_kernel, workloads,
                               tuple(radices), constants)
    out_spec, out_shape = _search_out_spec(w, table.shape[0])
    out = pl.pallas_call(
        kernel,
        grid=(jnp.sum(table[:, 1] > table[:, 0], dtype=jnp.int32),),
        in_specs=[pl.BlockSpec(axes.shape, lambda i: (0, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((w, 4), lambda i: (0, 0)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
        name="dse_search_decoded",
    )(axes, table.reshape(-1), cons, carry)
    return out[:, ::LANES]


@functools.partial(jax.jit, static_argnames=("radices", "n_blocks",
                                             "workloads", "objectives",
                                             "has_carry", "constants",
                                             "interpret"))
def dse_pareto_decoded(axes, meta, cons, carry, *, radices: tuple,
                       n_blocks: int, workloads: tuple, objectives: tuple,
                       has_carry: bool = True,
                       constants: DeviceConstants,
                       interpret: Optional[bool] = None):
    """Frontier-candidate search over an index span of a product space;
    same output layout as `dse_pareto_padded` with global candidate
    indices."""
    w = len(workloads)
    d = len(objectives)
    kernel = functools.partial(_dse_pareto_decode_kernel, workloads,
                               objectives, has_carry, tuple(radices),
                               constants)
    out_spec, out_shape = _pareto_out_spec(w, n_blocks)
    masks = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=_axes_meta_specs(
            axes, w, pl.BlockSpec((w * CARRY_FRONT, d), lambda i: (0, 0))),
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
        name="dse_pareto_decoded",
    )(axes, meta, cons, carry)
    return _front_rows(masks, meta[0, 0], w, n_blocks)


@functools.partial(jax.jit, static_argnames=("radices", "n_blocks",
                                             "interpret"))
def dse_decode_rows(axes, meta, *, radices: tuple, n_blocks: int,
                    interpret: Optional[bool] = None):
    """(6, n_blocks * BLOCK) [five decoded config rows; validity] for the
    index span + slab ranges named by the (1, META_COLS) meta row — the
    decode-proof kernel the mixed-radix property tests drive."""
    return pl.pallas_call(
        functools.partial(_decode_rows_kernel, tuple(radices)),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(axes.shape, lambda i: (0, 0)),
                  pl.BlockSpec((1, META_COLS), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((6, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((6, n_blocks * BLOCK), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="dse_decode_rows",
    )(axes, meta)
