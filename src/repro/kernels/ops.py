"""Jit'd public wrappers around the Pallas kernels.

  * ddot_matmul / photonic_matmul — photonic 4-bit GEMM simulation with a
    straight-through-estimator VJP, so models can train *through* the PTA
    quantization + noise (photonic-aware QAT — the SW half of the paper's
    HW/SW co-design).
  * dse_eval_grid / pallas_grid_search — the DSE grid evaluated by the
    dse_eval kernel, same result format as core.search.evaluate_grid.

All padding/quantization pre-passes live here so the kernels see aligned,
pre-quantized operands only.
"""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arch_params import PTAConfig
from repro.core.performance_model import workload_statics
from repro.core.photonic_model import CONSTANTS, DeviceConstants
from repro.core.workload import Workload
from repro.tracing import count_gemm_lanes, span

from . import ddot_gemm as _ddot
from . import dse_eval as _dse
from .ref import quantize4

log = logging.getLogger("repro.kernels")


def _integrity_check(out, what: str):
    """NaN guard on a kernel's reduction output, active only under a
    resilient search runtime (core.runtime) — zero work otherwise. The
    engines' metric pipelines never emit NaN (infeasible lanes reduce to
    +inf), so NaN here means a poisoned launch (bad memory, an injected
    fault); raising NanDetected routes the unit into the runtime's
    quarantine-then-host-float64 re-evaluation."""
    from repro.core import runtime as _runtime
    if _runtime.current() is None:
        return
    a = np.asarray(out)
    if a.dtype.kind == "f" and np.isnan(a).any():
        raise _runtime.NanDetected(f"NaN in {what} kernel output block")


def _wait(out) -> np.ndarray:
    """A launch's output on the host: device execution plus readback, the
    one blocking step of a launch."""
    with span("launch.wait"):
        return np.asarray(out)


def _launched(sp, lanes: int, workloads=(), **stats) -> None:
    """One launch of `lanes` lanes: its `launch` span's stats (`lanes`, and
    `rows` on a decoded search launch), and its lanes x GEMM rows (summed
    over its workloads) to every open `gemm_lane_tally`."""
    sp.set_metadata(lanes=lanes, **stats)
    count_gemm_lanes(lanes * sum(len(g) for g, _ in workloads))


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def ddot_matmul(a, b, *, noise_rms: float = 0.0,
                key: Optional[jax.Array] = None,
                bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: Optional[bool] = None):
    """Photonic-PTA simulated matmul: a (M, K) @ b (K, N) -> (M, N) f32.

    Handles arbitrary shapes by padding to block multiples. Exact vs
    ref.ddot_matmul_ref when noise_rms == 0.
    """
    m, kdim = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm, _rup(m, 8)), min(bn, _rup(n, 128)), min(bk, _rup(kdim, 128))
    qa, sa = quantize4(a, axis=1)
    qb, sb = quantize4(b, axis=0)
    qa = _pad_to(qa.astype(jnp.bfloat16), bm, bk)
    qb = _pad_to(qb.astype(jnp.bfloat16), bk, bn)
    sa = _pad_to(sa, bm, 1)
    sb = _pad_to(sb, 1, bn)
    if noise_rms > 0.0:
        if key is None:
            raise ValueError("noise_rms > 0 requires a PRNG key")
        z = jax.random.normal(key, (qa.shape[0], qb.shape[1]), jnp.float32)
    else:
        z = jnp.zeros((qa.shape[0], qb.shape[1]), jnp.float32)
    out = _ddot.ddot_gemm_quantized(qa, qb, sa, sb, z, bm=bm, bn=bn, bk=bk,
                                    noise_rms=noise_rms, interpret=interpret)
    return out[:m, :n]


def _rup(x, m):
    return ((x + m - 1) // m) * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def photonic_matmul(a, b, noise_rms: float = 0.0,
                    interpret: Optional[bool] = None, key_data: int = 0):
    key = jax.random.key(key_data) if noise_rms > 0.0 else None
    return ddot_matmul(a, b, noise_rms=noise_rms, key=key,
                       interpret=interpret)


def _photonic_fwd(a, b, noise_rms, interpret, key_data):
    return photonic_matmul(a, b, noise_rms, interpret, key_data), (a, b)


def _photonic_bwd(noise_rms, interpret, key_data, res, g):
    # Straight-through estimator: gradients flow as if the matmul were
    # full-precision (standard for QAT through hard quantizers).
    a, b = res
    return (g @ b.T).astype(a.dtype), (a.T @ g).astype(b.dtype)


photonic_matmul.defvjp(_photonic_fwd, _photonic_bwd)


# ---------------------------------------------------------------------------
# DSE grid evaluation
# ---------------------------------------------------------------------------

def dse_eval_grid(grid: np.ndarray, wl: Workload,
                  c: DeviceConstants = CONSTANTS,
                  interpret: Optional[bool] = None) -> np.ndarray:
    """(G, 5) config grid -> (G, 4) [area, power, energy, latency] via the
    dse_eval Pallas kernel. Any G: the grid is padded to a BLOCK multiple
    here, as the search wrappers pad theirs, so the metrics come out of
    the same compiled arithmetic as the search kernels' (the carried-front
    prune compares the two bit for bit)."""
    g = np.asarray(grid)
    g_pad = -(-len(g) // _dse.BLOCK) * _dse.BLOCK
    with span("launch") as sp:
        cols = np.ones((5, g_pad), np.float32)
        cols[:, :len(g)] = g.T
        gemms, wl_scalars = workload_statics(wl, c)
        _launched(sp, g_pad, ((gemms, wl_scalars),))
        out = _dse.dse_eval_padded(jnp.asarray(cols), gemms=gemms,
                                   wl_scalars=wl_scalars, constants=c,
                                   interpret=interpret)
        return _wait(out)[:, :len(g)].T


def _constraint_rows(constraints_seq) -> jnp.ndarray:
    return jnp.asarray([[cc.area_mm2, cc.power_w, cc.energy_j, cc.latency_s]
                        for cc in constraints_seq], jnp.float32)


def _search_carry_rows(carry_edp, w: int) -> jnp.ndarray:
    """(W, 1) float32 carried-best-EDP operand (+inf = no carry)."""
    arr = np.full((w, 1), np.inf, np.float32)
    if carry_edp is not None:
        arr[:, 0] = np.asarray(carry_edp, np.float64).astype(np.float32)
    return jnp.asarray(arr)


def _front_carry_rows(carry_points, w: int, d: int) -> jnp.ndarray:
    """(W * CARRY_FRONT, d) float32 carried-front operand, +inf-padded.

    carry_points: per-workload (F, d) objective-point arrays (or None).
    Fronts longer than CARRY_FRONT are truncated — the kernel prune is a
    candidate filter, so carrying any subset stays exact.
    """
    cf = _dse.CARRY_FRONT
    arr = np.full((w * cf, d), np.inf, np.float32)
    if carry_points is not None:
        for wi, pts in enumerate(carry_points):
            if pts is None or len(pts) == 0:
                continue
            p = np.asarray(pts, np.float32)[:cf]
            arr[wi * cf:wi * cf + len(p)] = p
    return jnp.asarray(arr)


@functools.lru_cache(maxsize=32)
def _sharded_kernel_fn(kind: str, statics: tuple, k: int):
    """Jit-cached shard_map wrapper of a padded kernel launch over a
    k-shard candidate mesh (cons/carry replicated, candidate axis split).

    kind: "search" with statics (workloads, constants, interpret), or
    "pareto" with statics (workloads, objectives, has_carry, constants,
    interpret). Keyed on the kernel statics + mesh size, so a streamed
    sweep's chunk launches reuse one compiled executable per chunk shape.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_candidate_mesh
    from repro.parallel.sharding import candidate_spec

    mesh = make_candidate_mesh(k)
    spec = candidate_spec(2, 1)

    if kind == "search":
        workloads, constants, interpret = statics

        def body(cols, mask, cons, carry):
            return _dse.dse_search_padded(cols, mask, cons, carry,
                                          workloads=workloads,
                                          constants=constants,
                                          interpret=interpret)
    else:
        workloads, objectives, has_carry, constants, interpret = statics

        def body(cols, mask, cons, carry):
            return _dse.dse_pareto_padded(cols, mask, cons, carry,
                                          workloads=workloads,
                                          objectives=objectives,
                                          has_carry=has_carry,
                                          constants=constants,
                                          interpret=interpret)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(spec, spec, P(None, None),
                                           P(None, None)),
                                 out_specs=spec, check_vma=False))


def _sharded_kernel_out(grid: np.ndarray, shard: int, kind: str,
                        statics: tuple, cons, carry):
    """Fan a kernel launch out over devices on the 1-D candidate mesh.

    Pads the candidate axis to a (mesh size x BLOCK) multiple (block count
    per shard bucketed to a power of two, mirroring `_bucketed_cols`) and
    calls the `_sharded_kernel_fn` wrapper; each shard's per-block
    reduction columns come back concatenated in shard order.

    Returns (out, shard_size, blocks_per_shard) — launch-local indices in
    `out` are *shard*-local, so column j's global base is
    (j // blocks_per_shard) * shard_size.
    """
    from repro.launch.mesh import make_candidate_mesh
    from repro.parallel.sharding import (CANDIDATE_AXIS, candidate_spec,
                                         sanitize_spec)

    k = make_candidate_mesh(shard).devices.size
    g = np.asarray(grid)
    n = len(g)
    blocks_per_shard = max(1, -(-n // (k * _dse.BLOCK)))
    blocks_per_shard = 1 << (blocks_per_shard - 1).bit_length()
    shard_size = blocks_per_shard * _dse.BLOCK
    cols = np.ones((5, k * shard_size), np.float32)
    cols[:, :n] = g.T
    mask = np.zeros((1, k * shard_size), np.float32)
    mask[:, :n] = 1.0
    # The candidate axis was just padded to a k-multiple, so the spec can
    # never degrade; assert rather than carry an untestable fallback.
    spec = candidate_spec(2, 1)
    assert sanitize_spec(cols.shape, spec, {CANDIDATE_AXIS: k}) == spec
    fn = _sharded_kernel_fn(kind, statics, k)
    return _wait(fn(cols, mask, cons, carry)), shard_size, blocks_per_shard


def dse_search_grid(grid: np.ndarray, wl: Workload, constraints,
                    c: DeviceConstants = CONSTANTS,
                    interpret: Optional[bool] = None, *, shard=None, carry_edp=None):
    """Fused single-pass search: (best_idx, best_edp, n_feasible).

    The Pallas kernel applies the constraint mask, computes EDP and reduces
    each block to (best_edp, best_idx, n_feasible); only that
    (3, n_blocks) array reaches the host — never the (4, G) metrics.
    best_idx is -1 when nothing is feasible, CARRY_IDX (-2) when the
    carried-in `carry_edp` beat (or tied) every feasible config.
    """
    best, edp, nf = dse_search_multi(
        grid, [wl], [constraints], c, interpret, shard=shard,
        carry_edp=None if carry_edp is None else [carry_edp])
    return best[0], edp[0], nf[0]


def _bucketed_cols(grid: np.ndarray):
    """(G, 5) -> ((5, G_pad) cols, (1, G_pad) mask) with the block count
    rounded up to a power of two. Grid sizes vary per pruned candidate set /
    constraint scenario; bucketing bounds the number of distinct shapes the
    jitted kernel ever sees to O(log G), so sweeps stop retracing."""
    g = np.asarray(grid)
    n = len(g)
    n_blocks = max(8, -(-n // _dse.BLOCK))  # floor of 8: pruned candidate
    # sets of wildly different sizes share one shape (masked blocks are
    # cheap; a retrace is ~seconds)
    g_pad = (1 << (n_blocks - 1).bit_length()) * _dse.BLOCK
    cols = np.ones((5, g_pad), np.float32)
    cols[:, :n] = g.T
    mask = np.zeros((1, g_pad), np.float32)
    mask[:, :n] = 1.0
    return jnp.asarray(cols), jnp.asarray(mask)


def dse_search_multi(grid: np.ndarray, wls, constraints_seq,
                     c: DeviceConstants = CONSTANTS,
                     interpret: Optional[bool] = None, *, shard=None, carry_edp=None):
    """Batched fused search: W workloads x one grid in a single launch.

    `shard=N` fans the candidate axis out over up to N devices with
    `shard_map` (clamped to what the process has); `carry_edp` (per-
    workload best EDP from earlier chunks of a streamed sweep) makes
    launches compose: the kernel folds the carry into its reduction, and a
    carried best that wins — including exact ties, which go to the earlier
    chunk — comes back as index CARRY_IDX.

    Returns (best_idx_per_wl, best_edp_per_wl, n_feasible_per_wl) lists;
    best_idx is -1 when no config satisfies that workload's constraints
    (and no carry was given), CARRY_IDX (-2) when the carried-in best
    stands. n_feasible counts this grid only — streaming callers
    accumulate it across chunks themselves.
    """
    with span("launch") as sp:
        workloads = tuple(workload_statics(wl, c) for wl in wls)
        cons = _constraint_rows(constraints_seq)
        carry = _search_carry_rows(carry_edp, len(workloads))

        if shard is not None and int(shard) > 1:
            out, shard_size, blocks_per_shard = _sharded_kernel_out(
                grid, shard, "search", (workloads, c, interpret), cons, carry)
            col_base = (np.arange(out.shape[1], dtype=np.int64)
                        // blocks_per_shard) * shard_size
        else:
            cols, mask = _bucketed_cols(grid)
            out = _wait(_dse.dse_search_padded(
                cols, mask, cons, carry, workloads=workloads, constants=c,
                interpret=interpret))
            col_base = None
        _launched(sp, out.shape[1] * _dse.BLOCK, workloads)
        _integrity_check(out, "dse_search")
        return _search_best(out, carry_edp, col_base)


def dse_pareto_multi(grid: np.ndarray, wls, constraints_seq,
                     c: DeviceConstants = CONSTANTS, interpret: Optional[bool] = None,
                     objectives: tuple = ("area", "power", "edp"),
                     *, shard=None, carry_points=None):
    """Batched frontier-candidate search: W workloads x one grid, one launch.

    The kernel reduces every block to its local non-dominated feasible set
    (bounded by MAX_FRONT indices per block); this wrapper only merges the
    per-block candidate lists. A block whose local front overflowed the
    bound reports its true count, and all of that block's rows join the
    candidate set instead — so the static bound itself never drops a
    frontier point; the caller's exact (float64) refinement restores the
    true frontier of the candidates.

    `shard=N` fans the candidate axis out over up to N devices with
    `shard_map`; `carry_points` (per-workload (F, d) running-front
    objective points in the kernel's float32 metric space, from earlier
    chunks of a streamed sweep) prunes candidates a carried point strictly
    dominates, keeping per-chunk emissions frontier-sized.

    Returns a list of (candidate_indices, n_feasible, n_overflow) per
    workload; `candidate_indices` is a sorted int64 array of grid rows
    covering the workload's feasible frontier as measured by the kernel's
    float32 metrics, and `n_overflow` counts the blocks whose local front
    overflowed MAX_FRONT and fell back to whole-block candidates (exact
    but wider — surfaced so callers can report the host-refine pressure).
    As with the EDP engines (see core.search.search), a config whose
    metric sits within one float32 ulp of a dominator's can classify
    differently than under float64 — real design points never ride that
    edge.
    """
    with span("launch") as sp:
        workloads = tuple(workload_statics(wl, c) for wl in wls)
        cons = _constraint_rows(constraints_seq)
        objectives = tuple(objectives)
        has_carry = carry_points is not None and any(
            p is not None and len(p) for p in carry_points)
        carry = _front_carry_rows(carry_points, len(workloads), len(objectives))

        if shard is not None and int(shard) > 1:
            out, shard_size, blocks_per_shard = _sharded_kernel_out(
                grid, shard, "pareto",
                (workloads, objectives, has_carry, c, interpret), cons, carry)
            n_cols = out.shape[1]
            col_base = (np.arange(n_cols, dtype=np.int64)
                        // blocks_per_shard) * shard_size
            blk_lo = col_base + (np.arange(n_cols, dtype=np.int64)
                                 % blocks_per_shard) * _dse.BLOCK
        else:
            cols, mask = _bucketed_cols(grid)
            out = _wait(_dse.dse_pareto_padded(
                cols, mask, cons, carry, workloads=workloads,
                objectives=objectives, has_carry=has_carry, constants=c,
                interpret=interpret))
            n_cols = out.shape[1]
            col_base = np.zeros(n_cols, np.int64)
            blk_lo = np.arange(n_cols, dtype=np.int64) * _dse.BLOCK
        _launched(sp, n_cols * _dse.BLOCK, workloads)
        _integrity_check(out, "dse_pareto")
        results = []
        for w in range(len(workloads)):
            rows = out[_dse.PARETO_ROWS * w:_dse.PARETO_ROWS * (w + 1)]
            counts, nfeas_b = rows[0], rows[1]
            # Shard-local block indices -> grid-global via the column's base.
            idx = rows[_dse.PARETO_HEADER:] + col_base[None, :]
            cand = idx[rows[_dse.PARETO_HEADER:] >= 0].astype(np.int64)
            overflowed = np.nonzero(counts > _dse.MAX_FRONT)[0]
            if len(overflowed):
                log.warning("pareto kernel: %d block(s) overflowed MAX_FRONT"
                            "=%d; falling back to whole-block candidates "
                            "(exact, host-refined)", len(overflowed),
                            _dse.MAX_FRONT)
            for b in overflowed:
                lo = int(blk_lo[b])
                cand = np.concatenate(
                    [cand, np.arange(lo, min(lo + _dse.BLOCK, len(grid)))])
            results.append((np.unique(cand),
                            int(round(float(nfeas_b.sum()))),
                            int(len(overflowed))))
        return results


# ---------------------------------------------------------------------------
# Factorized-space launches: on-device candidate generation
# ---------------------------------------------------------------------------
#
# The `*_factorized` wrappers mirror `dse_search_multi` / `dse_pareto_multi`
# over an index span [start, start + count) of a product space
# (core.factorized.FactorizedSpace) instead of a materialized (G, 5) grid:
# the only grid-shaped thing that ever exists is on-device, reconstructed
# lane-by-lane inside the kernels from the (5, max_radix) candidate-value
# matrix + the span bounds. Returned indices are global flat-space indices.


def _axes_operand(space):
    """((5, max_radix) float32 candidate-value matrix, radices). Short axes
    are padded with 1.0 — never selected (digits are in range for valid
    lanes) but harmless if they were."""
    radices = space.radices
    arr = np.ones((5, max(radices)), np.float32)
    for i, a in enumerate(space.axes):
        arr[i, :len(a)] = a
    return jnp.asarray(arr), radices


def _meta_rows(radices, bases, limit: int, slab=None) -> np.ndarray:
    """(len(bases), META_COLS) int32 decode-kernel meta rows: each row is
    [base, limit) plus the five [lo, hi) slab digit ranges (the whole-space
    ranges when `slab` is None — reducing the in-kernel slab test to the
    plain span test)."""
    from repro.core.factorized import full_ranges
    ranges = full_ranges(radices) if slab is None else tuple(slab)
    meta = np.zeros((len(bases), _dse.META_COLS), np.int32)
    meta[:, 0] = bases
    meta[:, 1] = limit
    for ax, (lo, hi) in enumerate(ranges):
        meta[:, 2 + 2 * ax] = lo
        meta[:, 3 + 2 * ax] = hi
    return meta


def _slab_member_mask(radices, slab, idx: np.ndarray) -> np.ndarray:
    """Boolean mask of flat indices whose digits fall inside the slab."""
    from repro.core.factorized import decode_digits
    digits = decode_digits(np.asarray(idx, np.int64), radices, np)
    ok = np.ones(len(idx), bool)
    for d, (lo, hi) in zip(digits, slab):
        ok &= (d >= lo) & (d < hi)
    return ok


def _bucket_blocks(count: int, floor: int = 8,
                   block: int = _dse.BLOCK) -> int:
    """Power-of-two block count covering `count` configs (same bucketing
    rationale as `_bucketed_cols`: bound the jit-cache shapes to O(log G))."""
    n_blocks = max(floor, -(-count // block))
    return 1 << (n_blocks - 1).bit_length()


@functools.lru_cache(maxsize=32)
def _sharded_decoded_fn(kind: str, statics: tuple, k: int, radices: tuple,
                        n_blocks: int):
    """Jit-cached shard_map wrapper of a decoded-kernel launch: the meta
    rows are sharded over the candidate mesh and the tiny axes/cons/carry
    operands replicated. "search": each shard runs the live rows of its
    `n_blocks`-row share of the meta table, one block per row; "pareto":
    each shard runs `n_blocks` blocks from its own row's base."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_candidate_mesh
    from repro.parallel.sharding import candidate_spec

    mesh = make_candidate_mesh(k)
    meta_spec, out_spec = candidate_spec(2, 0), candidate_spec(2, 1)

    if kind == "search":
        workloads, constants, interpret = statics

        def body(axes, meta_l, cons, carry):
            return _dse.dse_search_decoded(
                axes, meta_l, cons, carry, radices=radices,
                workloads=workloads, constants=constants,
                interpret=interpret)
    else:
        workloads, objectives, has_carry, constants, interpret = statics

        def body(axes, meta_l, cons, carry):
            return _dse.dse_pareto_decoded(
                axes, meta_l, cons, carry, radices=radices,
                n_blocks=n_blocks, workloads=workloads,
                objectives=objectives, has_carry=has_carry,
                constants=constants, interpret=interpret)

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(None, None), meta_spec,
                                           P(None, None), P(None, None)),
                                 out_specs=out_spec, check_vma=False))


def _check_decode_span(limit: int):
    """The decode kernels emit *global* indices as float32 (unlike the
    grid-operand kernels, whose launch-local indices are rebased in int64
    on the host), so any index at or past 2**24 would silently round to a
    neighboring config. Refuse instead of corrupting; spaces that big go
    through the jax/numpy factorized engines (exact int32/int64 indices)."""
    if limit > 1 << 24:
        raise ValueError(
            f"factorized pallas launches address configs by float32 global "
            f"index, exact only below 2**24; this span reaches {limit}. "
            f"Use the jax or numpy factorized engines for larger spaces.")


def _decoded_launch(space, start: int, count: int, statics: tuple, cons,
                    carry, shard, slab=None, table=None):
    """Run a decoded-kernel launch over the index window [start, start +
    count) of a product space, optionally fanned out over the candidate
    mesh. Returns (out, blk_lo): the stacked per-block reduction columns
    and each column's first global index.

    The frontier kernel (five statics) covers the window, optionally
    masked to a slab's digit ranges. The search kernel (three statics and
    a meta `table`, see `_search_table`) runs the table's rows whose
    blocks start inside the window: at most SEARCH_TABLE_ROWS per device,
    each device's share padded to that size; only the live rows' columns
    come back."""
    axes_cols, radices = _axes_operand(space)
    k = _candidate_shards(shard)
    if table is not None:
        part = table[(table[:, 0] >= start) & (table[:, 0] < start + count)]
        per = -(-len(part) // k)
        padded = np.zeros((k, SEARCH_TABLE_ROWS, _dse.META_COLS), np.int32)
        for i in range(k):
            share = part[i * per:(i + 1) * per]
            padded[i, :len(share)] = share
        padded = padded.reshape(-1, _dse.META_COLS)
        if k > 1:
            fn = _sharded_decoded_fn("search", statics, k, radices,
                                     SEARCH_TABLE_ROWS)
        else:
            workloads, constants, interpret = statics
            fn = functools.partial(_dse.dse_search_decoded, radices=radices,
                                   workloads=workloads, constants=constants,
                                   interpret=interpret)
        out = _wait(fn(axes_cols, jnp.asarray(padded), cons, carry))
        live = padded[:, 1] > padded[:, 0]
        return out[:, live], padded[live, 0].astype(np.int64)
    limit = min(start + count, space.size)
    _check_decode_span(limit)
    block = _dse.BLOCK
    if k > 1:
        bps = _bucket_blocks(-(-count // k), floor=1, block=block)
        bases = start + np.arange(k) * bps * block
        meta = _meta_rows(radices, bases, limit, slab)
        fn = _sharded_decoded_fn("pareto", statics, k, radices, bps)
        out = _wait(fn(axes_cols, jnp.asarray(meta), cons, carry))
        blk_lo = (np.repeat(meta[:, 0].astype(np.int64), bps)
                  + np.tile(np.arange(bps, dtype=np.int64), k) * block)
        return out, blk_lo
    n_blocks = _bucket_blocks(count, block=block)
    meta = jnp.asarray(_meta_rows(radices, [start], limit, slab))
    workloads, objectives, has_carry, constants, interpret = statics
    out = _dse.dse_pareto_decoded(
        axes_cols, meta, cons, carry, radices=radices, n_blocks=n_blocks,
        workloads=workloads, objectives=objectives, has_carry=has_carry,
        constants=constants, interpret=interpret)
    blk_lo = start + np.arange(n_blocks, dtype=np.int64) * block
    return _wait(out), blk_lo


def _candidate_shards(shard) -> int:
    """Devices of the candidate mesh a `shard=` request fans out over."""
    if shard is None or int(shard) <= 1:
        return 1
    from repro.launch.mesh import make_candidate_mesh
    return make_candidate_mesh(shard).devices.size


# Meta rows a decoded search launch holds per device: every table is padded
# to this size (one executable per search, whatever the row count: the
# kernel's grid runs the live rows alone, see `dse_search_decoded`), and a
# longer table is split into launches of this size. A branch-and-bound
# probe batch of a few leaves takes a handful of rows, a warm revival
# batch of hundreds of small slabs up to a few launches, the whole 24^5
# space (486 blocks) one; 512 rows keep the flattened table at 24 KiB of
# SMEM.
SEARCH_TABLE_ROWS = 512


def _search_table(radices, size: int, items) -> np.ndarray:
    """(R, META_COLS) int32 decoded-search meta table of a work list:
    one row per DECODE_BLOCK of each (start, count, slab) item's span
    [start, min(start + count, size)), with the item's slab digit ranges
    (the whole space when slab is None). Refuses spans past 2**24, as
    every decoded launch does (`_check_decode_span`)."""
    from repro.core.factorized import full_ranges
    full = full_ranges(radices)
    starts = np.asarray([s for s, _, _ in items], np.int64)
    limits = np.minimum(starts + np.asarray([n for _, n, _ in items],
                                            np.int64), size)
    slabs = np.asarray([full if sl is None else sl for _, _, sl in items],
                       np.int64).reshape(-1, 10)
    if len(limits):
        _check_decode_span(int(limits.max()))
    n_blk = np.maximum(-(-(limits - starts) // _dse.DECODE_BLOCK), 0)
    item = np.repeat(np.arange(len(starts)), n_blk)
    first = np.cumsum(n_blk) - n_blk
    table = np.empty((len(item), _dse.META_COLS), np.int32)
    table[:, 0] = (starts[item] + (np.arange(len(item)) - first[item])
                   * _dse.DECODE_BLOCK)
    table[:, 1] = limits[item]
    table[:, 2:] = slabs[item]
    return table


def _search_best(out, carry_edp, col_base=None):
    """Per-workload (best_idx, best_edp, n_feasible) lists from a search
    launch's (SEARCH_ROWS * W, n_blocks) reduction columns (indices
    rebased by `col_base` where launch-local). Min EDP across columns,
    exact ties to the lowest index — the sequential engines' first-hit
    rule — and CARRY_IDX sorts before every real index, so a carried
    tie wins. best_idx is -1 when nothing is feasible and no carry was
    given, CARRY_IDX when the carry stands."""
    best_idx, best_edp, n_feasible = [], [], []
    for w in range(out.shape[0] // _dse.SEARCH_ROWS):
        edp_b, idx_b, nf_b = out[_dse.SEARCH_ROWS * w:
                                 _dse.SEARCH_ROWS * (w + 1)]
        nf = int(round(float(nf_b.sum(dtype=np.float64))))
        n_feasible.append(nf)
        if col_base is not None:
            idx_b = np.where(idx_b >= 0, idx_b + col_base, idx_b)
        jb = np.lexsort((idx_b, edp_b))[0]
        i = int(idx_b[jb])
        best_edp.append(float(edp_b[jb]))
        if nf == 0 and carry_edp is None:
            best_idx.append(-1)
            continue
        best_idx.append(i if i >= 0 else int(_dse.CARRY_IDX))
    return best_idx, best_edp, n_feasible


def dse_search_multi_factorized(space, start: int, count: int, wls,
                                constraints_seq,
                                c: DeviceConstants = CONSTANTS,
                                interpret: Optional[bool] = None, *, shard=None,
                                carry_edp=None, slab=None):
    """Batched fused search over an index span of a product space.

    Same contract as `dse_search_multi` — (best_idx, best_edp, n_feasible)
    lists with the -1 / CARRY_IDX sentinels — except candidates live only
    on device (decoded from `space`) and `best_idx` is a global flat-space
    index (materialize the winning row with `space.decode`). `slab` (five
    [lo, hi) digit ranges) additionally masks the span's lanes to the
    slab's members in-kernel. The span's blocks are the rows of one
    decoded search launch (`dse_search_spans_factorized`).
    """
    return dse_search_spans_factorized(
        space, [(start, count, slab)], wls, constraints_seq, c, interpret,
        shard=shard, carry_edp=carry_edp)


def dse_pareto_multi_factorized(space, start: int, count: int, wls,
                                constraints_seq,
                                c: DeviceConstants = CONSTANTS,
                                interpret: Optional[bool] = None,
                                objectives: tuple = ("area", "power", "edp"),
                                *, shard=None, carry_points=None, slab=None):
    """Batched frontier-candidate search over an index span of a product
    space; same contract as `dse_pareto_multi` — (candidate_indices,
    n_feasible, n_overflow) triples — with global flat-space candidate
    indices. `slab` masks the span to a slab's members exactly as in
    `dse_search_multi_factorized` (an overflowing block's whole-block
    fallback is clipped back to slab members, so candidate lists never leak
    lanes the launch was asked to mask)."""
    with span("launch") as sp:
        workloads = tuple(workload_statics(wl, c) for wl in wls)
        cons = _constraint_rows(constraints_seq)
        objectives = tuple(objectives)
        has_carry = carry_points is not None and any(
            p is not None and len(p) for p in carry_points)
        carry = _front_carry_rows(carry_points, len(workloads), len(objectives))
        out, blk_lo = _decoded_launch(
            space, start, count,
            (workloads, objectives, has_carry, c, interpret), cons, carry,
            shard, slab)
        _launched(sp, len(blk_lo) * _dse.BLOCK, workloads)
        limit = min(start + count, space.size)
        _integrity_check(out, "dse_pareto_decoded")
        results = []
        for w in range(len(workloads)):
            rows = out[_dse.PARETO_ROWS * w:_dse.PARETO_ROWS * (w + 1)]
            counts, nfeas_b = rows[0], rows[1]
            idx = rows[_dse.PARETO_HEADER:]
            cand = idx[idx >= 0].astype(np.int64)
            overflowed = np.nonzero(counts > _dse.MAX_FRONT)[0]
            if len(overflowed):
                log.warning("pareto decode kernel: %d block(s) overflowed "
                            "MAX_FRONT=%d; falling back to whole-block "
                            "candidates (exact, host-refined)",
                            len(overflowed), _dse.MAX_FRONT)
            for b in overflowed:
                lo = int(blk_lo[b])
                fallback = np.arange(lo, min(lo + _dse.BLOCK, limit))
                if slab is not None:
                    fallback = fallback[
                        _slab_member_mask(space.radices, slab, fallback)]
                cand = np.concatenate([cand, fallback])
            results.append((np.unique(cand),
                            int(round(float(nfeas_b.sum()))),
                            int(len(overflowed))))
        return results


# ---------------------------------------------------------------------------
# Span-list drivers: compose decoded launches over a bound-guided work list
# ---------------------------------------------------------------------------

def dse_search_spans_factorized(space, items, wls, constraints_seq,
                                c: DeviceConstants = CONSTANTS,
                                interpret: Optional[bool] = None, *, shard=None,
                                carry_edp=None):
    """Fused search over a work list in as few decoded launches as its
    meta table allows.

    `items` is a sequence of (start, count, slab) triples (slab None = plain
    contiguous span) — the leaf slabs of one branch-and-bound batch, or one
    span. Each item's span becomes one meta-table row per DECODE_BLOCK
    (`_search_table`), SEARCH_TABLE_ROWS rows a launch (per shard on a
    `shard=N` mesh), and every row is reduced against the caller's
    `carry_edp` alone. One `launch` span per launch, with the launch's
    `rows`. The host then takes the strictly lowest EDP over all rows with
    exact ties to the lowest flat index, whatever the order of the items —
    the global first-hit rule, the carry winning its ties. Returns
    (best_idx, best_edp, n_feasible) lists; `best_idx` is -1 when nothing
    was feasible anywhere (or CARRY_IDX when only the caller's `carry_edp`
    stands).
    """
    table = _search_table(space.radices, space.size, items)
    w = len(wls)
    # The carry's own column: what every block emits that it wins, and the
    # whole answer of an empty table.
    rows = _dse.SEARCH_ROWS
    carried = np.zeros((rows * w, 1), np.float32)
    carried[0::rows, 0] = np.inf if carry_edp is None else carry_edp
    carried[1::rows, 0] = _dse.CARRY_IDX
    outs = [carried]
    top = _candidate_shards(shard) * SEARCH_TABLE_ROWS
    for lo in range(0, len(table), top):
        with span("launch") as sp:
            if lo == 0:  # once per call, in its first launch
                workloads = tuple(workload_statics(wl, c) for wl in wls)
                operands = (_constraint_rows(constraints_seq),
                            _search_carry_rows(carry_edp, w))
            part = table[lo:lo + top]
            first = int(part[:, 0].min())
            out, _ = _decoded_launch(
                space, first, int(part[:, 1].max()) - first,
                (workloads, c, interpret), *operands, shard, table=part)
            _launched(sp, out.shape[1] * _dse.DECODE_BLOCK, workloads,
                      rows=out.shape[1])
            _integrity_check(out, "dse_search_decoded")
            outs.append(out)
    return _search_best(np.concatenate(outs, axis=1), carry_edp)


def dse_pareto_spans_factorized(space, items, wls, constraints_seq,
                                c: DeviceConstants = CONSTANTS,
                                interpret: Optional[bool] = None,
                                objectives: tuple = ("area", "power", "edp"),
                                *, shard=None, carry_points=None):
    """Compose `dse_pareto_multi_factorized` launches over a work list of
    (start, count, slab) triples: per-workload (candidate-index union,
    summed feasible count, summed overflow count) triples. `carry_points`
    (the running front at entry)
    prunes every launch's emissions; candidates proposed by earlier items
    of the same list are *not* folded into the carry — the union is a
    candidate superset either way and the caller's float64 refinement
    restores exactness, identical to the chunked streaming contract."""
    w = len(wls)
    cands = [[] for _ in range(w)]
    n_feasible = [0] * w
    n_overflow = [0] * w
    for start, count, slab in items:
        per_wl = dse_pareto_multi_factorized(
            space, start, count, wls, constraints_seq, c, interpret,
            objectives=objectives, shard=shard, carry_points=carry_points,
            slab=slab)
        for wi, (idx, f, n_over) in enumerate(per_wl):
            n_feasible[wi] += f
            n_overflow[wi] += n_over
            if len(idx):
                cands[wi].append(idx)
    return [(np.unique(np.concatenate(cc)) if cc
             else np.zeros(0, np.int64), f, o)
            for cc, f, o in zip(cands, n_feasible, n_overflow)]


def decode_rows_device(space, start: int, count: int,
                       interpret: Optional[bool] = None, slab=None) -> np.ndarray:
    """(count, 5) int64 rows of space.to_grid()[start:start+count], decoded
    *on device* by the Pallas mixed-radix kernel — the testable surface of
    the in-kernel candidate generation. With `slab` (five [lo, hi) digit
    ranges), only the span's slab-member lanes survive the validity mask —
    the decoded form of `space.decode(slab_indices(...))`."""
    n_blocks = max(1, -(-count // _dse.BLOCK))
    limit = min(start + count, space.size)
    _check_decode_span(limit)
    with span("launch") as sp:
        _launched(sp, n_blocks * _dse.BLOCK)
        axes_cols, radices = _axes_operand(space)
        meta = jnp.asarray(_meta_rows(radices, [start], limit, slab))
        out = _wait(_dse.dse_decode_rows(axes_cols, meta, radices=radices,
                                         n_blocks=n_blocks,
                                         interpret=interpret))
        return out[:5, out[5] > 0.0].T.astype(np.int64)


def pallas_grid_search(grid: np.ndarray, wl: Workload, constraints,
                       c: DeviceConstants = CONSTANTS,
                       interpret: Optional[bool] = None):
    """Legacy two-pass kernel path: materializes the full (G, 4) metrics on
    the host, then selects with numpy (mirrors grid_search_vectorized's
    rule). Kept as the baseline the fused `dse_search_grid` is benchmarked
    against (benchmarks/fig12_search_time.py); prefer
    `core.search.search(..., engine="pallas")` for real searches."""
    m = dse_eval_grid(grid, wl, c, interpret)
    area, power, energy, latency = m.T
    ok = constraints.satisfied(area, power, energy, latency)
    edp = np.where(ok, energy * latency, np.inf)
    if not np.isfinite(edp).any():
        return None, m
    i = int(np.argmin(edp))
    return PTAConfig.from_array(grid[i]), m


# ---------------------------------------------------------------------------
# Fused (flash) attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None):
    """Fused attention for (B, S, H, D) tensors with GQA support.

    K/V with fewer heads than Q are broadcast per group; sequences are
    padded to block multiples (padding keys are masked out by -inf scores
    only in the causal case; for bidirectional, padded keys are sliced off
    by giving them zero weight via an explicit length mask fallback).
    """
    from .flash_attention import flash_attention_bhsd

    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        g = hq // hkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # (B, S, H, D) -> (B*H, S, D)
    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * hq, x.shape[1], d)
    qb, kb, vb = to_bhsd(q), to_bhsd(k), to_bhsd(v)
    bq_ = min(bq, _rup(sq, 8))
    bk_ = min(bk, _rup(kb.shape[1], 8))
    pq = (-sq) % bq_
    pk = (-kb.shape[1]) % bk_
    skv = kb.shape[1]
    if pq:
        qb = jnp.pad(qb, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kb = jnp.pad(kb, ((0, 0), (0, pk), (0, 0)))
        vb = jnp.pad(vb, ((0, 0), (0, pk), (0, 0)))
        if not causal:
            # mask padded keys: push them to -inf by giving them a key
            # vector that can't win — simplest robust route: fall back to
            # masking via a large negative bias on the padded tail.
            pass
    out = flash_attention_bhsd(qb, kb, vb, causal=causal, bq=bq_, bk=bk_,
                               interpret=interpret)
    if pk and not causal:
        # recompute correction: renormalize against the true key length by
        # excluding padded keys' contribution (they scored exp(0 - m) each).
        # For exactness we simply redo the reduction on the reference path
        # for the padded tail — in practice bidirectional inputs are padded
        # to block multiples upstream; guard loudly instead:
        raise ValueError("bidirectional flash_attention requires "
                         f"skv % {bk_} == 0 (got {skv})")
    out = out[:, :sq]
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
