"""Loop "closed": one client sends the next item when the answer to the
last is back, until the window's seconds have passed. Each item's latency
is caller side, from its send to its answer."""
import sys
import time


def window(entry, items, seconds: float, run) -> None:
    """Fill `run.latencies_s`, `run.items`, `run.results` and
    `run.window_s` (first send to last answer)."""
    import jax

    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            item = next(items)
            with jax.profiler.TraceAnnotation("bench.query"):
                t0 = time.perf_counter()
                try:
                    res = entry.answer(item)
                except Exception as e:  # a failed query, counted
                    print(f"bench: query failed: {e!r}", file=sys.stderr)
                    res = None
                end = time.perf_counter()
            run.latencies_s.append(end - t0)
            run.items.append(item)
            run.results.append(res)
    run.window_s = end - start
