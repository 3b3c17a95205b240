"""Entry "search": each item is one one-shot `search()` call on the
pallas engine over the configuration's factorized space, with the
traffic's objective and `prune`."""


class Entry:
    def __init__(self, cell):
        self.cell = cell

    def prepare(self) -> None:
        """Nothing is resident between one-shot searches."""

    def answer(self, item: dict) -> list:
        """[(workload, the program's result)] for one item."""
        from repro.core.arch_params import Constraints
        from repro.core.search import search

        cell = self.cell
        kw = {"pareto_metrics": cell.metrics} \
            if cell.objective == "pareto" else {}
        res = search(cell.workloads[item["workload"]],
                     Constraints(**item["box"]), engine="pallas",
                     factorized=True, space=cell.space, c=cell.c,
                     objective=cell.objective,
                     prune=cell.traffic.get("prune"), **kw)
        return [(item["workload"], res)]

    def counters(self) -> dict:
        return {}
