"""Serving launchers: token generation and the resident DSE service.

Two subcommands share this entrypoint:

  * ``tokens`` — batched greedy generation through the photonic-aware
    model stack, plus the DxPTA co-design report (the original behavior
    of this module; it remains the default when no subcommand is given)::

        PYTHONPATH=src python -m repro.launch.serve tokens \\
            --arch qwen2.5-3b --reduced

  * ``dse`` — stand up a `repro.serve.SearchService` and replay a
    constraint-scenario session against it: one cold bound-guided search
    per workload, then each ``--scenario`` as a constraint-delta query
    (tightened boxes are answered warm by re-pricing the slab ledger;
    repeated boxes hit the memo). Prints per-query latency and how each
    query was served::

        PYTHONPATH=src python -m repro.launch.serve dse \\
            --workload deit-t --n-z 12 --engine jax \\
            --scenario power_w=4.5 --scenario power_w=4.0,area_mm2=45

  * ``scenarios`` — model-zoo scenario sweep: expand a model x
    shape-kind x batch x seq-len x decode-length grid
    (`repro.scenarios.ScenarioGrid`), lower every cell through the
    config->workload extractor, and co-search all of them through one
    resident `SearchService` (cold queries coalesce into batched
    multi-workload waves; ``--repeat`` sweeps again to show the repeated
    scenarios served from the memo). Prints per-scenario winners and the
    cross-class parameter-shift summary::

        PYTHONPATH=src python -m repro.launch.serve scenarios \\
            --model qwen2.5-3b --model rwkv6-7b --model olmoe-1b-7b \\
            --reduced --engine numpy --n-z 6
"""
from __future__ import annotations

import argparse
import sys
import time


def _tokens_main(args) -> None:
    """Batched greedy generation + co-design report (legacy behavior)."""
    import jax
    import numpy as np

    import repro.models as M
    from repro.configs import get_config, list_archs, reduced
    from repro.models.layers import set_exec_safe
    from repro.train.serve import Request, Server, photonic_report

    if args.arch not in list_archs():
        raise SystemExit(f"unknown arch {args.arch!r}; pick from "
                         f"{list_archs()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
        set_exec_safe(True)
    params = M.init_params(jax.random.key(0), cfg)
    srv = Server(cfg, params, batch_size=args.batch, max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=8).astype(np.int32),
                    max_new=args.max_new) for _ in range(args.batch)]
    stats = srv.generate(reqs)
    print(f"{stats['tokens']} tokens: ttft={stats['ttft_s']*1e3:.1f}ms "
          f"decode={stats['decode_s_per_tok']*1e3:.2f}ms/tok")
    print(photonic_report(get_config(args.arch), seq_len=args.max_len,
                          batch=args.batch, new_tokens=args.max_new))


def _parse_scenario(spec: str) -> dict:
    """``power_w=4.0,area_mm2=45`` -> {"power_w": 4.0, "area_mm2": 45.0}."""
    out = {}
    for part in spec.split(","):
        if "=" not in part:
            raise SystemExit(f"bad --scenario entry {part!r}; expected "
                             f"field=value pairs like power_w=4.0")
        k, v = part.split("=", 1)
        out[k.strip()] = float(v)
    return out


def _dse_main(args) -> None:
    """Resident-service session: cold searches, then scenario deltas."""
    from repro.core import paper_workloads
    from repro.core.arch_params import Constraints
    from repro.serve import SearchService

    names = (list(paper_workloads.PAPER_WORKLOADS) if args.workload == "all"
             else [args.workload])
    svc = SearchService(n_z=args.n_z, engine=args.engine,
                        shard=args.shard, chunk_size=args.chunk_size,
                        checkpoint_root=args.checkpoint_root,
                        workers=args.workers)
    boxes = [("paper defaults", Constraints())]
    boxes += [(spec, Constraints(**_parse_scenario(spec)))
              for spec in args.scenario]
    print(f"service: {args.engine} engine, {args.n_z}^5 space, "
          f"{len(names)} workload(s), {len(boxes)} box(es)")
    for nm in names:
        wl = paper_workloads.load(nm)
        for label, cons in boxes:
            before = dict(svc.stats)
            t0 = time.perf_counter()
            res = svc.query(wl, cons, objective=args.objective)
            ms = (time.perf_counter() - t0) * 1e3
            how = ("memo" if svc.stats["memo_hits"] > before["memo_hits"]
                   else "warm" if svc.stats["warm"] > before["warm"]
                   else "cold")
            if args.objective == "pareto":
                answer = f"frontier of {res.size}"
            else:
                answer = str(res.best_cfg) if res.feasible else "infeasible"
            print(f"  {nm:10s} {label:40s} {how:4s} {ms:9.2f}ms  {answer}")
    s = svc.stats
    print(f"served {s['queries']} queries: {s['cold']} cold, {s['warm']} "
          f"warm, {s['memo_hits']} memoized "
          f"({s['slabs_revived']}/{s['slabs_repriced']} re-priced slabs "
          f"revived)")
    if args.gc is not None:
        if args.checkpoint_root is None:
            raise SystemExit("--gc requires --checkpoint-root")
        from repro.core.runtime import gc_checkpoints
        removed = gc_checkpoints(args.checkpoint_root, keep=args.gc)
        print(f"gc: removed {len(removed)} stale checkpoint dir(s), "
              f"kept newest {args.gc}")


def _scenarios_main(args) -> None:
    """Model-zoo scenario sweep through one resident service."""
    from repro.configs import list_archs
    from repro.core.arch_params import Constraints
    from repro.scenarios import ScenarioGrid, sweep
    from repro.serve import SearchService

    models = tuple(args.model) or ("qwen2.5-3b", "rwkv6-7b", "olmoe-1b-7b")
    unknown = sorted(set(models) - set(list_archs()))
    if unknown:
        raise SystemExit(f"unknown arch(es) {unknown}; pick from "
                         f"{list_archs()}")
    grid = ScenarioGrid(models=models, kinds=tuple(args.kind),
                        seq_lens=tuple(args.seq_len),
                        batches=tuple(args.batch),
                        new_tokens=tuple(args.new_tokens),
                        reduce=args.reduced)
    cons = {spec.split(":", 1)[0]: _parse_scenario(spec.split(":", 1)[1])
            for spec in args.box} if args.box else {}
    svc = SearchService(n_z=args.n_z, engine=args.engine,
                        shard=args.shard, chunk_size=args.chunk_size)
    print(f"service: {args.engine} engine, {args.n_z}^5 space; grid: "
          f"{len(models)} model(s) x {len(args.kind)} kind(s) -> "
          f"{grid.size} scenarios")
    for i in range(max(1, args.repeat)):
        t0 = time.perf_counter()
        rep = sweep(grid, cons if cons else Constraints(), service=svc,
                    objective=args.objective)
        ms = (time.perf_counter() - t0) * 1e3
        print(f"sweep {i + 1} ({ms:.1f}ms):")
        print(rep.format())


def main(argv=None) -> None:
    """Dispatch to a subcommand (``tokens`` when none is given)."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("tokens", "dse", "scenarios"):
        argv.insert(0, "tokens")  # original flag-only invocation

    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    sub = ap.add_subparsers(dest="cmd", required=True)

    tk = sub.add_parser("tokens", help="batched greedy generation")
    tk.add_argument("--arch", required=True)
    tk.add_argument("--reduced", action="store_true")
    tk.add_argument("--batch", type=int, default=4)
    tk.add_argument("--max-new", type=int, default=8)
    tk.add_argument("--max-len", type=int, default=64)

    ds = sub.add_parser("dse", help="resident DSE co-search service")
    ds.add_argument("--workload", default="deit-t",
                    help="paper workload name, or 'all'")
    ds.add_argument("--n-z", type=int, default=12)
    ds.add_argument("--engine", default="jax",
                    choices=("numpy", "jax", "pallas"))
    ds.add_argument("--objective", default="edp",
                    choices=("edp", "pareto"))
    ds.add_argument("--scenario", action="append", default=[],
                    metavar="FIELD=VAL[,FIELD=VAL...]",
                    help="constraint box for one delta query (repeatable)")
    ds.add_argument("--shard", type=int, default=None)
    ds.add_argument("--chunk-size", type=int, default=None)
    ds.add_argument("--checkpoint-root", default=None,
                    help="service-owned checkpoint root (resume per query)")
    ds.add_argument("--workers", type=int, default=None,
                    help="fan cold searches and warm deltas out over N "
                         "leased slab workers (byte-identical answers)")
    ds.add_argument("--gc", type=int, default=None, metavar="KEEP",
                    help="after serving, prune completed-query checkpoint "
                         "dirs under --checkpoint-root down to the newest "
                         "KEEP (manifest-validated; foreign dirs skipped)")

    sc = sub.add_parser("scenarios", help="model-zoo scenario co-search")
    sc.add_argument("--model", action="append", default=[],
                    help="arch name (repeatable; default: a 3-model zoo)")
    sc.add_argument("--kind", action="append", default=None,
                    choices=("train", "prefill", "decode"),
                    help="scenario class (repeatable; default: all three)")
    sc.add_argument("--seq-len", type=int, action="append", default=None,
                    help="context length axis (repeatable; default 2048)")
    sc.add_argument("--batch", type=int, action="append", default=None,
                    help="batch axis (repeatable; default 8)")
    sc.add_argument("--new-tokens", type=int, action="append", default=None,
                    help="decode-length axis (repeatable; default 16, 64)")
    sc.add_argument("--box", action="append", default=[],
                    metavar="KIND:FIELD=VAL[,FIELD=VAL...]",
                    help="per-class constraint box, e.g. "
                         "decode:latency_ms=2 (repeatable)")
    sc.add_argument("--reduced", action="store_true",
                    help="sweep the reduced (CPU-smoke) configs")
    sc.add_argument("--repeat", type=int, default=2,
                    help="sweep the grid this many times (repeats after "
                         "the first are served from the memo)")
    sc.add_argument("--n-z", type=int, default=6)
    sc.add_argument("--engine", default="numpy",
                    choices=("numpy", "jax", "pallas"))
    sc.add_argument("--objective", default="edp",
                    choices=("edp", "pareto"))
    sc.add_argument("--shard", type=int, default=None)
    sc.add_argument("--chunk-size", type=int, default=None)

    args = ap.parse_args(argv)
    if args.cmd == "scenarios":
        args.kind = args.kind or ["train", "prefill", "decode"]
        args.seq_len = args.seq_len or [2048]
        args.batch = args.batch or [8]
        args.new_tokens = args.new_tokens or [16, 64]
        _scenarios_main(args)
    elif args.cmd == "dse":
        _dse_main(args)
    else:
        _tokens_main(args)


if __name__ == "__main__":
    main()
