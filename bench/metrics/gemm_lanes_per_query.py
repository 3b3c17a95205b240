"""Lanes x GEMM rows the kernel launches ran per query, padding and
masked lanes included: the program's `n_gemm_lanes` counter, averaged over
the window's answered queries. A query's results come from one search, and
every result of one batched search carries the whole batch's count, so a
query counts the largest of its results' counts once. None where the
program has no such counter."""


def read(run):
    counts = [max(getattr(r, "n_gemm_lanes", None) or 0 for _, r in res)
              for res in run.results if res]
    if not counts or not any(counts):
        return None
    return sum(counts) / len(counts)
