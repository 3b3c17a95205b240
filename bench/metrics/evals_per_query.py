"""Design points the search driver handed to an engine per query: the
program's exact `n_workload_evals` counter, summed over a query's
workloads and averaged over the window's answered queries. Under
branch-and-bound it counts the evaluated survivors only."""


def read(run):
    counts = [sum(r.n_workload_evals for _, r in res)
              for res in run.results if res is not None]
    return sum(counts) / len(counts) if counts else None
