"""Entry "service": a resident `SearchService` on the pallas engine over
the configuration's factorized space. Set-up answers the traffic's base
box (`box` times `base_factor`) for each workload of the mix, so every
window item inside it is a warm constraint delta, or a memo hit where the
item repeats an earlier one."""
import traffic as tr


class Entry:
    def __init__(self, cell):
        from repro.serve import SearchService

        self.cell = cell
        self.service = SearchService(space=cell.space, engine="pallas",
                                     c=cell.c)

    def prepare(self) -> None:
        t = self.cell.traffic
        names, _ = tr.weights(t, self.cell.workloads)
        for n in names:
            self.answer({"workload": n,
                         "box": tr.scaled(t, t["base_factor"])})

    def answer(self, item: dict) -> list:
        """[(workload, the program's result)] for one item."""
        from repro.core.arch_params import Constraints

        cell = self.cell
        kw = {"pareto_metrics": cell.metrics} \
            if cell.objective == "pareto" else {}
        res = self.service.query(cell.workloads[item["workload"]],
                                 Constraints(**item["box"]),
                                 objective=cell.objective, **kw)
        return [(item["workload"], res)]

    def counters(self) -> dict:
        return dict(self.service.stats)
