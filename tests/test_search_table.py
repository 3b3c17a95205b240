"""One decoded search launch per work list (`dse_search_spans_factorized`).

A branch-and-bound batch's leaf slabs become the rows of one meta table
(a row per DECODE_BLOCK of each leaf's bounding span), padded to
`SEARCH_TABLE_ROWS`. These pins hold the batched launch to the
leaf-by-leaf composition it replaced — one launch per leaf, merged by
`_merge_best_indexed` — in best index, EDP and n_feasible, on 12^5 and
20^5 spaces, and to the grid-operand kernel over the same members:
bounding spans that cross a DECODE_BLOCK boundary, an exact EDP tie whose
lower index sits in a later leaf, an all-infeasible batch, a caller carry
that ties, tables of a few rows to one past SEARCH_TABLE_ROWS (padding
rows included), each launch with its own `launch` span and `rows` stat,
all through one executable.
"""
import numpy as np
import pytest

from repro.core import Constraints, FactorizedSpace
from repro.core.factorized import slab_indices
from repro.core.photonic_model import CONSTANTS
from repro.core.search import (_bnb_eval_edp, _bnb_leaf_items,
                               _merge_best_indexed)
from repro.core.workload import Gemm, Workload
from repro.kernels import dse_eval as K
from repro.kernels import ops

# Square GEMMs (m == n): at N_t = 1 every metric is symmetric in N_h and
# N_v, so (1, c, h, v, l) and (1, c, v, h, l) tie exactly in float32 too.
WL = Workload(name="square", gemms=(Gemm(64, 96, 64, 4), Gemm(32, 48, 32, 2)),
              elec_ops=1e6, weight_bytes=1e5, act_io_bytes=1e4,
              max_act_bytes=1e4)
S12, S20 = FactorizedSpace.full(12), FactorizedSpace.full(20)
BOX = Constraints()
OPEN = Constraints(area_mm2=1e4, power_w=1e4)
NONE_FIT = Constraints(area_mm2=1e-6)
FULL = ((0, 12),) * 5


def _flat(space, digits):
    i = 0
    for d, r in zip(digits, space.radices):
        i = i * r + d
    return i


def _point(digits):
    return tuple((d, d + 1) for d in digits)


def _leaf_items(space, slabs, chunk_size=None):
    return [it for r in slabs for it in _bnb_leaf_items(space, r, chunk_size)]


def _batched(space, items, cons, carry=None):
    (bi,), (be,), (bn,) = ops.dse_search_spans_factorized(
        space, items, [WL], [cons], carry_edp=carry)
    return bi, be, bn


def _per_leaf(space, items, cons, carry=None):
    """The composition the batched launch replaced: a launch per item,
    each against the caller's carry alone, merged lowest EDP first and
    exact ties to the lower index."""
    best, nf = (-1, float("inf")), 0
    for item in items:
        bi, be, bn = _batched(space, [item], cons, carry)
        nf += bn
        best = _merge_best_indexed(best, (bi, be))
    if best[0] < 0 and carry is not None:
        return int(K.CARRY_IDX), float(np.float32(carry[0])), nf
    return best[0], best[1], nf


def _grid(space, slabs, cons):
    """The same float32 arithmetic through the grid-operand kernel, over
    the slabs' members as materialized rows in ascending index order."""
    idx = np.unique(np.concatenate([slab_indices(space.radices, r)
                                    for r in slabs]))
    (bi,), (be,), (bn,) = ops.dse_search_multi(space.decode(idx), [WL],
                                               [cons])
    return (int(idx[bi]) if bi >= 0 else -1), be, bn


class _Launches:
    """Stands in for `ops.span`: keeps each `launch` span's stats."""

    def __init__(self):
        self.stats = []

    def __call__(self, name, **stats):
        assert name in ("launch", "launch.wait")
        rec = dict(stats)
        if name == "launch":
            self.stats.append(rec)
        return _Entered(rec)


class _Entered:
    def __init__(self, rec):
        self.rec = rec

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.rec.update(stats)


# Leaves of 12^5 (strides 20736, 1728, 144, 12, 1; DECODE_BLOCK 16384):
# c in [8, 12) spans [13824, 20736), across the first block boundary; an
# inner-axis slab (h in [3, 5)) spans most of its t slice, many blocks;
# a single point; a leaf wholly inside one block. Disjoint, as leaves are.
CROSSING = (((0, 1), (8, 12), (0, 12), (0, 12), (0, 12)),
            ((1, 3), (0, 12), (0, 12), (3, 5), (0, 12)),
            _point((0, 1, 4, 7, 2)),
            ((3, 4), (5, 6), (0, 6), (0, 12), (0, 12)))


@pytest.mark.parametrize("cons", [BOX, OPEN], ids=["paper-box", "open"])
@pytest.mark.parametrize("chunk_size", [None, 5000])
def test_a_batch_equals_its_leaves_across_block_boundaries(cons, chunk_size):
    items = _leaf_items(S12, CROSSING, chunk_size)
    table = ops._search_table(S12.radices, S12.size, items)
    assert len(table) > len(CROSSING)       # some leaf spans several blocks
    got = _batched(S12, items, cons)
    assert got == _per_leaf(S12, items, cons)
    assert got[0] >= 0 and got[2] > 0
    assert got == _grid(S12, CROSSING, cons)


def test_an_exact_tie_goes_to_the_lower_index_in_a_later_leaf():
    lo_d, hi_d = (0, 1, 2, 5, 2), (0, 1, 5, 2, 2)  # (v, h) swapped at N_t 1
    lo, hi = _flat(S12, lo_d), _flat(S12, hi_d)
    assert lo < hi
    items = _leaf_items(S12, [_point(hi_d), _point(lo_d)])  # lower one last
    (_, e_hi, _), (_, e_lo, _) = (_batched(S12, [it], OPEN) for it in items)
    assert e_hi == e_lo < float("inf")                      # an exact tie
    got = _batched(S12, items, OPEN)
    assert got == (lo, e_lo, 2) == _per_leaf(S12, items, OPEN)
    # A carry equal to the tie keeps it; one just above loses to it.
    assert _batched(S12, items, OPEN, [e_lo]) == (int(K.CARRY_IDX), e_lo, 2)
    assert _per_leaf(S12, items, OPEN, [e_lo]) == (int(K.CARRY_IDX), e_lo, 2)
    above = float(np.nextafter(np.float32(e_lo), np.float32(np.inf)))
    assert _batched(S12, items, OPEN, [above]) == (lo, e_lo, 2)


def test_a_carry_that_ties_a_leaf_best_stands():
    items = _leaf_items(S12, CROSSING)
    bi, be, bn = _batched(S12, items, BOX)
    assert _batched(S12, items, BOX, [be]) == (int(K.CARRY_IDX), be, bn) \
        == _per_leaf(S12, items, BOX, [be])
    below = float(np.nextafter(np.float32(be), np.float32(0)))
    assert _batched(S12, items, BOX, [below])[0] == int(K.CARRY_IDX)
    assert _batched(S12, items, BOX, [be * 2]) == (bi, be, bn)


def test_an_all_infeasible_batch():
    items = _leaf_items(S12, CROSSING)
    assert _batched(S12, items, NONE_FIT) == (-1, float("inf"), 0) \
        == _per_leaf(S12, items, NONE_FIT)
    assert _batched(S12, items, NONE_FIT, [1.0]) == (int(K.CARRY_IDX), 1.0, 0)
    assert _batched(S12, [], BOX) == (-1, float("inf"), 0)


def _rung_slabs(n_rows):
    """20^5 leaves (strides 160000, 8000, 400, 20, 1) whose table has
    `n_rows` rows: single points in distinct blocks, or lambda-restricted
    slabs whose bounding spans run over the whole space (196 blocks)."""
    if n_rows <= 64:
        return [_point((t, 10, 3, 7, 4)) for t in range(n_rows)]
    return [((0, 20),) * 4 + ((j, j + 1),) for j in range(n_rows // 196)]


@pytest.mark.parametrize("n_rows,launches", [(1, [1]), (3, [3]), (20, [20]),
                                             (196, [196]), (588, [512, 76])])
def test_a_table_pads_and_launches_its_live_rows(n_rows, launches,
                                                 monkeypatch):
    slabs = _rung_slabs(n_rows)
    items = _leaf_items(S20, slabs)
    assert len(ops._search_table(S20.radices, S20.size, items)) == n_rows
    assert ops.SEARCH_TABLE_ROWS == 512
    expect = _per_leaf(S20, items, BOX)
    assert expect == _grid(S20, slabs, BOX)
    rec = _Launches()
    monkeypatch.setattr(ops, "span", rec)
    assert _batched(S20, items, BOX) == expect
    assert [s["rows"] for s in rec.stats] == launches
    assert all(s["lanes"] == s["rows"] * K.DECODE_BLOCK for s in rec.stats)


def test_one_executable_serves_every_row_count():
    # The grid runs the table's live rows, so row counts of 1 to 512 share
    # one compiled launch per (radices, workloads): nothing compiles for a
    # row count first met inside a timed window.
    K.dse_search_decoded.clear_cache()
    for n_rows in (1, 3, 20, 196):
        _batched(S20, _leaf_items(S20, _rung_slabs(n_rows)), BOX)
    assert K.dse_search_decoded._cache_size() == 1


@pytest.mark.parametrize("cons", [BOX, OPEN, NONE_FIT],
                         ids=["paper-box", "open", "none-fit"])
@pytest.mark.parametrize("chunk_size", [None, 3000])
def test_a_bnb_batch_is_byte_identical_to_leaf_by_leaf(cons, chunk_size):
    # `_bnb_eval_edp` now sends the whole batch as one table; the
    # leaf-by-leaf driver merged one span-list launch per leaf.
    slabs = list(CROSSING) + [_point((0, 1, 5, 2, 2)), _point((0, 1, 2, 5, 2))]
    best, nf = (-1, float("inf")), 0
    for ranges in slabs:
        bi, be, bn = _batched(S12, _bnb_leaf_items(S12, ranges, chunk_size),
                              cons)
        nf += bn
        best = _merge_best_indexed(best, (bi, be))
    assert _bnb_eval_edp("pallas", S12, WL, cons, CONSTANTS, None, slabs,
                         None, chunk_size) == (best[0], best[1], nf)


def test_the_table_lists_every_block_of_every_item():
    # A row per DECODE_BLOCK of [start, min(start + count, size)): a span
    # of 2.4 blocks gives three rows, one past the space's end none.
    items = [(13000, 8000, CROSSING[0]), (0, 40000, None),
             (S12.size - 10, 50, None), (S12.size, 5, None)]
    table = ops._search_table(S12.radices, S12.size, items)
    assert table.dtype == np.int32 and table.shape[1] == K.META_COLS
    assert table[:, :2].tolist() == [
        [13000, 21000], [0, 40000], [K.DECODE_BLOCK, 40000],
        [2 * K.DECODE_BLOCK, 40000], [S12.size - 10, S12.size]]
    assert table[0, 2:].tolist() == [v for r in CROSSING[0] for v in r]
    assert (table[1:, 2:] == [v for r in FULL for v in r]).all()
