"""CPU rehearsal of the harness: the box generator, the statistics, the
files BENCHMARK.json names, and the refusal to run without a TPU."""
import itertools
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _tiny import BENCH, CELLS

import run
import traffic as tr
from cell import load_module, load_spec, resolve

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _take(traffic, seed, n, which=tr.WINDOW, names=("w",)):
    return list(itertools.islice(tr.items(traffic, names, seed, which), n))


def _boxes(traffic, seed, n, which=tr.WINDOW):
    return [it["box"] for it in _take(_one(traffic), seed, n, which)]


def _one(traffic):
    """The traffic for a configuration of one workload named "w"."""
    return dict(traffic, workload_weights=None)


def _slices(traffic, boxes):
    """(n, bounds) index of the stratum each box's factor lies in."""
    out = []
    for k in tr.BOUNDS:
        lo, hi = traffic["factors"][k]
        f = np.array([box[k] / traffic["box"][k] for box in boxes])
        assert np.all((f >= lo) & (f <= hi))
        out.append(np.floor((f - lo) / (hi - lo) * tr.STRATA).astype(int))
    return np.stack(out, axis=1)


@pytest.mark.parametrize("cell_name", CELLS)
def test_boxes_are_a_function_of_the_seed(cell_name):
    _, _, traffic = resolve(load_spec(), cell_name)
    big = 2 ** 31 + 12345
    assert _boxes(traffic, big, 40) == _boxes(traffic, big, 40)
    other = _boxes(traffic, big + 1, 40)
    assert all(a != b for a, b in zip(_boxes(traffic, big, 40), other))
    assert sorted(map(str, _boxes(traffic, big, 40))) != sorted(
        map(str, _boxes(traffic, big, 40, tr.WARMUP)))


@pytest.mark.parametrize("cell_name", CELLS)
def test_each_block_covers_every_slice_of_every_factor(cell_name):
    _, _, traffic = resolve(load_spec(), cell_name)
    slices = _slices(traffic, _boxes(traffic, 99, 8 * tr.STRATA))
    for blk in slices.reshape(8, tr.STRATA, len(tr.BOUNDS)):
        for col in blk.T:
            assert sorted(col) == list(range(tr.STRATA))


def test_every_seed_sends_boxes_of_the_same_make_up():
    """Seeds differ in every box but not in which stratum each box's
    factors lie in, nor in the order: a window's work hardly depends on
    the seed, its answers do."""
    _, _, traffic = resolve(load_spec(), CELLS[0])
    a = _boxes(traffic, 1, 5 * tr.STRATA)
    b = _boxes(traffic, 2 ** 32 + 7, 5 * tr.STRATA)
    assert np.array_equal(_slices(traffic, a), _slices(traffic, b))
    assert all(x != y for x, y in zip(a, b))
    assert len({tuple(x.values()) for x in a}) == len(a)


def test_workload_weights_and_repeats_follow_the_traffic():
    _, _, traffic = resolve(load_spec(), CELLS[0])
    mix = dict(traffic, workload_weights={"a": 3.0, "b": 1.0},
               repeat_share=0.25)
    got = _take(mix, 5, 4000, names=("a", "b", "c"))
    names = [it["workload"] for it in got]
    assert set(names) == {"a", "b"}
    assert 0.7 < names.count("a") / len(names) < 0.8
    repeats = sum(any(it is prev for prev in got[:i])
                  for i, it in enumerate(got[:400]))
    assert 0.15 < repeats / 400 < 0.35
    assert got == _take(mix, 5, 4000, names=("a", "b", "c"))
    with pytest.raises(ValueError, match="workload_weights"):
        _take(mix, 5, 1, names=("a",))


@pytest.mark.parametrize("cell_name", [c for c in CELLS if "warm" in c])
def test_every_window_box_of_a_warm_cell_lies_inside_its_base(cell_name):
    from repro.core.arch_params import Constraints
    from repro.serve.cache import box_contains, canonical_box

    _, _, traffic = resolve(load_spec(), cell_name)
    base = tr.scaled(traffic, traffic["base_factor"])
    warm = [it["box"] for it in tr.warmup_items(_one(traffic), ("w",))]
    for box in _boxes(traffic, 2024, 2000) + warm:
        assert all(box[k] <= base[k] for k in tr.BOUNDS)
        assert box_contains(canonical_box(Constraints(**base)),
                            canonical_box(Constraints(**box)))
    assert box_contains(canonical_box(Constraints(**tr.loosest(traffic))),
                        canonical_box(Constraints(**base)))


def test_set_up_answers_the_same_items_in_every_run():
    _, _, traffic = resolve(load_spec(), "bert-l.s20.pareto-warm")
    first = tr.warmup_items(_one(traffic), ("w",))
    assert first == tr.warmup_items(_one(traffic), ("w",))
    assert len(first) == 2 + len(traffic["warmup_boxes"]) + tr.WARMUP_ITEMS


def test_latency_statistics_are_over_every_window_query():
    r = run.Run()
    # Medians of 10-query chunks would read 1 ms; the whole window's
    # 95th percentile sits in the slow tail.
    r.latencies_s = [0.001] * 90 + [0.5] * 10
    r.window_s = 10.0
    p50 = load_module(BENCH / "metrics" / "query_p50_ms.py").read(r)
    p95 = load_module(BENCH / "metrics" / "query_p95_ms.py").read(r)
    qps = load_module(BENCH / "metrics" / "queries_per_s.py").read(r)
    assert p50 == pytest.approx(np.percentile(r.latencies_s, 50) * 1e3)
    assert p95 == pytest.approx(np.percentile(r.latencies_s, 95) * 1e3)
    assert p95 > 100.0
    assert qps == pytest.approx(10.0)


def test_benchmark_json_names_files_that_exist():
    spec = load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] == []
        assert (BENCH / "lowering" / f"{cfg['lowering']}.py").is_file()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
            traffic = json.load(f)
        assert (BENCH / "entries" / f"{traffic['entry']}.py").is_file()
        loop = traffic.get("loop", "closed")
        assert (BENCH / "loops" / f"{loop}.py").is_file()
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert m["moves"] in e2e


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _bench_cmd(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("_cache", "__pycache__"))
    proc = _bench_cmd(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "No module named 'repro'" in proc.stderr
