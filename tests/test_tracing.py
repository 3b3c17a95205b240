"""Host spans (`repro.tracing`): no JAX import for the host-only API, a
no-op span until JAX is loaded, a profiler span named "dxpta.<name>"
after, and decorated entry points that keep their names and answers; the
`extract` span, and the `n_gemm_lanes` tally a result carries. Where the
spans land in a real trace is pinned by the benchmark's
`bench/tests/test_bench_spans.py`."""
import subprocess
import sys

import pytest

from repro import tracing


def test_host_only_imports_leave_jax_unloaded_and_spans_inert():
    code = ("import sys\n"
            "import repro.core, repro.serve, repro.tracing as t\n"
            "assert 'jax' not in sys.modules\n"
            "with t.span('launch', lanes=8) as s:\n"
            "    s.set_metadata(lanes=16)\n"
            "assert t.span('search') is t.span('service.query')\n"
            "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_a_span_is_a_profiler_annotation_once_jax_is_loaded():
    import jax

    sp = tracing.span("launch", lanes=2048)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as entered:
        entered.set_metadata(lanes=4096)


def test_traced_functions_keep_their_name_doc_and_value():
    from repro.core.search import search
    from repro.serve import SearchService

    assert search.__name__ == "search"
    assert search.__doc__.startswith("Unified search")
    assert SearchService.query.__name__ == "query"

    @tracing.traced("search")
    def f(x, *, y=1):
        """Doc."""
        return x + y

    assert f(1, y=2) == 3 and f.__doc__ == "Doc."


class _Recorder:
    """Stands in for `tracing.span`: keeps each span's name and stats."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **stats):
        rec = _Recorded(name, stats)
        self.spans.append(rec)
        return rec

    def named(self, name):
        return [s.stats for s in self.spans if s.name == name]


class _Recorded:
    def __init__(self, name, stats):
        self.name, self.stats = name, dict(stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.stats.update(stats)


def test_workload_for_runs_inside_the_extract_span(monkeypatch):
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import extract

    rec = _Recorder()
    monkeypatch.setattr(extract, "span", rec)
    for kind in ("decode", "prefill", "train"):
        extract.workload_for(get_config("qwen2.5-3b"),
                             ShapeConfig("p", 64, 2, kind))
    assert rec.named("extract") == [{}, {}, {}]


def _tiny_workload(name="t", gemms=None):
    from repro.core.workload import Gemm, Workload

    return Workload(name=name,
                    gemms=gemms or (Gemm(8, 16, 16, 2), Gemm(8, 16, 4, 1),
                                    Gemm(1, 4, 8, 3)),
                    elec_ops=1e3, weight_bytes=1e3, act_io_bytes=1e2,
                    max_act_bytes=1e2)


def test_results_count_gemm_lanes_of_their_launches(monkeypatch):
    import dataclasses

    from repro.core.search import ParetoResult, SearchResult, search
    from repro.kernels import dse_eval, ops

    wl = _tiny_workload()
    rec = _Recorder()
    monkeypatch.setattr(ops, "span", rec)
    res = search(wl, engine="pallas", factorized=True, n_z=4)
    launches = rec.named("launch")
    # decoded search launches: their meta-table rows beside their lanes
    assert launches and all(set(s) == {"lanes", "rows"} for s in launches)
    assert all(s["lanes"] == s["rows"] * dse_eval.DECODE_BLOCK
               for s in launches)
    assert res.n_gemm_lanes == sum(s["lanes"] for s in launches) * 3 > 0
    # lane padding is how an engine ran, not the answer
    assert dataclasses.replace(res, n_gemm_lanes=0) == res
    for cls in (SearchResult, ParetoResult):
        field, = [f for f in dataclasses.fields(cls)
                  if f.name == "n_gemm_lanes"]
        assert field.compare is False
    assert search(wl, engine="numpy", factorized=True,
                  n_z=4).n_gemm_lanes == 0


@pytest.mark.parametrize("objective", ["edp", "pareto"])
def test_a_batched_search_gives_each_result_the_batch_count(objective):
    # Two workloads of 3 and 2 GEMM rows share each launch: every result
    # reports the batch's lanes x 5 rows once, as it reports the batch's
    # wall time, and nothing more is counted than was launched.
    from repro.core.search import search_workloads
    from repro.core.workload import Gemm

    wls = {"a": _tiny_workload("a"),
           "b": _tiny_workload("b", (Gemm(4, 8, 8, 1), Gemm(2, 8, 4, 2)))}
    with tracing.gemm_lane_tally() as tally:
        out = search_workloads(wls, engine="pallas", n_z=4,
                               objective=objective)
    assert tally.n > 0 and tally.n % 5 == 0
    assert [r.n_gemm_lanes for r in out.values()] == [tally.n, tally.n]


@pytest.mark.parametrize("mode", ["bnb_workers", "service_warm_delta"])
def test_n_gemm_lanes_counts_every_launch_of_the_query(mode, monkeypatch):
    # Worker threads and a service's warm delta launch outside `search()`'s
    # own frame: their launches still count in the query's tally.
    from repro.core import Constraints
    from repro.core.paper_workloads import load
    from repro.core.search import search
    from repro.kernels import ops
    from repro.serve import SearchService

    wl = load("deit-t")
    rows = len(wl.gemms)
    rec = _Recorder()
    monkeypatch.setattr(ops, "span", rec)
    if mode == "bnb_workers":
        res = search(wl, engine="pallas", factorized=True, n_z=8,
                     prune="bound", workers=2)
    else:
        svc = SearchService(n_z=8, engine="pallas")
        svc.query(wl, Constraints())
        rec.spans.clear()
        res = svc.query(wl, Constraints(power_w=4.5))   # revives slabs
    lanes = sum(s["lanes"] for s in rec.named("launch"))
    assert lanes > 0
    assert res.n_gemm_lanes == lanes * rows
