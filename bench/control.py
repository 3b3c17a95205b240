"""The control of `check.py`: the reference itself, computed in float32
(the nearest precision below the float64 the configurations state), put in
the program's place. Its answers must come out not correct; the readings
it gives set the upper end of each limit (PERF.md lists them).

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--device]

For each seed, the first items of the window stream (as many as a run
compares) are answered from a float32 sweep of the whole space, on the
accelerator with `--device` (jax.numpy) and on the host otherwise, and
compared with the float64 reference exactly as a run's answers are. One
JSON line per seed. The benchmark's own runs never run this.
"""
import argparse
import json
import sys

import numpy as np

import run as harness


def control_answers(config: dict, traffic_: dict, items, xp=np) -> list:
    """float32 answers to `items`, normalized like `check.answer_of`."""
    import reference
    import traffic as tr
    from cell import lowering

    subs = {}
    out = []
    for item in items:
        name = item["workload"]
        if name not in subs:
            wl = lowering(config).reference_workload(
                config["workloads"][name])
            subs[name] = reference.sweep(
                int(config["space"]["n_z"]), wl, config["constants"],
                tr.si(tr.loosest(traffic_)), xp=xp, dtype=np.float32)
        box = tr.si(item["box"])
        if traffic_["objective"] == "edp":
            row, met = reference.answer_edp(subs[name], box)
            out.append({"row": row, "metrics": met})
        else:
            rows, met = reference.answer_pareto(
                subs[name], box, traffic_["pareto_metrics"])
            out.append({"rows": rows, "metrics": met})
    return out


def readings(config: dict, traffic_: dict, seed: int, xp=np) -> dict:
    """The compared numbers of the control on one seed's window items."""
    import itertools

    import check as ck
    import traffic as tr
    from cell import lowering

    items = list(itertools.islice(
        tr.items(traffic_, config["workloads"], seed),
        harness.CHECK_SAMPLE + 1))
    got = control_answers(config, traffic_, items, xp)
    low = lowering(config)
    numbers = ck.compare(
        traffic_["objective"],
        [(tr.si(it["box"]), a,
          harness.reference_sweep(config, it["workload"], traffic_),
          low.reference_workload(config["workloads"][it["workload"]]))
         for it, a in zip(items, got)],
        config["constants"], traffic_.get("pareto_metrics"))
    correct, checked = ck.verdict(numbers)
    return {"seed": seed, "correct": correct, "compared":
            numbers["compared"], "checked": checked}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--device", action="store_true",
                    help="compute the float32 sweep on the accelerator")
    args = ap.parse_args(argv)
    harness.prepare_process()
    from cell import load_spec, resolve

    _, config, traffic_ = resolve(load_spec(), args.workload)
    xp = np
    if args.device:
        import jax.numpy as jnp
        xp = jnp
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload,
                          **readings(config, traffic_, seed, xp)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
