"""Host spans (`repro.tracing`): no JAX import for the host-only API, a
no-op span until JAX is loaded, a profiler span named "dxpta.<name>"
after, and decorated entry points that keep their names and answers.
Where the spans land in a real trace is pinned by the benchmark's
`bench/tests/test_bench_spans.py`."""
import subprocess
import sys

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
