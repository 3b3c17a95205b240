"""The DeepSeek-V3 decode cell: its plain lowering against the program's
`workload_for` at full size, a CPU run at 4 layers (3 dense + 1 MoE) over
the 1..6 space judged `correct`, a perturbed front rejected, and the two
readers of the program's `n_gemm_lanes` counter."""
import copy
import itertools
import json
import types

import pytest

from _tiny import BENCH

import check
import control
import reference
import run
import traffic as tr
from cell import Cell, load_module, load_spec, resolve

CELL = "deepseek-v3.s24.pareto-decode"
MLA_MOE = load_module(BENCH / "lowering" / "mla_moe.py")
with open(BENCH / "configs" / "deepseek-v3.s24.json") as _f:
    CONFIG = json.load(_f)
(NAME, SIZES), = CONFIG["workloads"].items()

# The workload block's sizes against the published config.json keys the
# configuration file carries at its top level.
PUBLISHED = {"layers": "num_hidden_layers", "d_model": "hidden_size",
             "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
             "d_ff": "intermediate_size", "vocab": "vocab_size",
             "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
             "rope_head_dim": "qk_rope_head_dim",
             "nope_head_dim": "qk_nope_head_dim", "v_head_dim": "v_head_dim",
             "n_experts": "n_routed_experts", "top_k": "num_experts_per_tok",
             "d_expert": "moe_intermediate_size",
             "d_shared": "moe_intermediate_size",
             "n_shared": "n_shared_experts",
             "first_dense_layers": "first_k_dense_replace"}


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    """Reference sweeps and traces go to the test's directory."""
    monkeypatch.setattr(run, "CACHE", tmp_path)


def _small_config(layers: int = 4, n_z: int = 6) -> dict:
    """The cell's configuration at `layers` layers (the 3 dense ones and
    the rest MoE) over the 1..n_z space; every width as published."""
    cfg = copy.deepcopy(CONFIG)
    cfg["name"] = f"{cfg['name']}.l{layers}.s{n_z}"
    cfg["space"]["n_z"] = n_z
    cfg["workloads"][NAME]["layers"] = layers
    return cfg


def test_the_workload_block_is_the_published_model():
    assert CONFIG["reduced"] == []
    for key, published in PUBLISHED.items():
        assert SIZES[key] == CONFIG[published], key
    assert (SIZES["kind"], SIZES["seq_len"], SIZES["batch"],
            SIZES["new_tokens"]) == ("decode", 32768, 32, 32)


def test_reference_lowering_matches_the_program_at_full_size():
    prog = MLA_MOE.program_workload(NAME, SIZES)
    ref = MLA_MOE.reference_workload(SIZES)
    assert sorted(tuple(g) for g in prog.gemm_array.tolist()) == \
        sorted(ref["gemms"])
    assert len(ref["gemms"]) == 18
    for k in ("elec_ops", "weight_bytes", "act_io_bytes", "max_act_bytes"):
        assert getattr(prog, k) == ref[k], k
    # the program's own DeepSeek-V3 config lowers to the same workload
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.extract import workload_for

    own = workload_for(get_config("deepseek-v3-671b"),
                       ShapeConfig("d", 32768, 32, "decode", new_tokens=32))
    assert MLA_MOE.lowering_differences(own, ref) == []


def test_a_program_pricing_top_k_experts_is_refused_at_set_up(monkeypatch):
    from repro.core import extract

    monkeypatch.setattr(extract, "experts_touched",
                        lambda moe, tokens: moe.top_k)
    with pytest.raises(SystemExit, match="weight_bytes"):
        MLA_MOE.program_workload(NAME, SIZES)


def _drive(cfg, seconds=0.5):
    import jax
    import jax.monitoring

    counter = run.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        return run.run_cell(load_spec(), CELL, 7, seconds, False,
                            jax.devices(), counter, config=cfg)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)


def test_a_sound_run_at_four_layers_is_correct():
    line = _drive(_small_config())
    assert line["correct"], line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["checked"]["metric_rel_gap"]["value"] < 1e-14
    assert {"queries_per_s", "query_p50_ms", "query_p95_ms",
            "setup_s"} == set(line["metrics"])


def _answers(cfg, n=4):
    _, _, traffic = resolve(load_spec(), CELL)
    cell = Cell(cfg, traffic)
    items = list(itertools.islice(tr.items(traffic, cfg["workloads"], 5), n))
    results = [cell.entry.answer(it)[0][1] for it in items]
    wl = MLA_MOE.reference_workload(cfg["workloads"][NAME])
    sub = reference.sweep(cfg["space"]["n_z"], wl, cfg["constants"],
                          tr.si(tr.loosest(traffic)))
    return traffic, items, results, wl, sub


def test_a_perturbed_front_is_rejected():
    cfg = _small_config()
    traffic, items, results, wl, sub = _answers(cfg)
    got = [check.answer_of(r) for r in results]

    def compare(i, answer):
        return check.compare("pareto", [(tr.si(items[i]["box"]), answer,
                                          sub, wl)], cfg["constants"],
                             traffic["pareto_metrics"])

    for i, a in enumerate(got):
        assert check.verdict(compare(i, a))[0]
    i = max(range(len(got)), key=lambda j: len(got[j]["rows"]))
    assert len(got[i]["rows"]) > 1
    dropped = {"rows": got[i]["rows"][1:],
               "metrics": {k: v[1:] for k, v in got[i]["metrics"].items()}}
    numbers = compare(i, dropped)
    assert numbers["wrong_answers"] == 1 and not check.verdict(numbers)[0]
    skewed = {"rows": got[i]["rows"],
              "metrics": dict(got[i]["metrics"],
                              latency=got[i]["metrics"]["latency"]
                              * (1 + 2 ** -23))}
    numbers = compare(i, skewed)
    assert numbers["wrong_answers"] == 0
    assert numbers["metric_rel_gap"] > check.LIMITS["metric_rel_gap"]
    assert not check.verdict(numbers)[0]


def test_the_float32_control_is_not_correct():
    _, _, traffic = resolve(load_spec(), CELL)
    got = control.readings(_small_config(), traffic, seed=11)
    assert not got["correct"]
    assert got["checked"]["metric_rel_gap"]["value"] > 1e-9, got


def test_gemm_lane_readers_count_lanes_times_the_18_rows():
    cfg = _small_config()
    _, _, results, _, _ = _answers(cfg, n=2)
    lanes = [r.n_gemm_lanes for r in results]
    assert all(n > 0 and n % 18 == 0 for n in lanes)

    per_query = load_module(BENCH / "metrics" / "gemm_lanes_per_query.py")
    per_us = load_module(BENCH / "metrics" / "kernel_gemm_lanes_per_us.py")
    r = run.Run()
    r.results = [[(NAME, res)] for res in results] + [None]
    assert per_query.read(r) == sum(lanes) / 2
    assert per_us.read(r) is None                     # no trace
    r.trace = types.SimpleNamespace(kernel_s=0.5)
    assert per_us.read(r) == sum(lanes) / 0.5e6
    # a program without the counter gives nothing to read
    bare = run.Run()
    bare.results = [[(NAME, types.SimpleNamespace())]]
    bare.trace = r.trace
    assert per_query.read(bare) is None and per_us.read(bare) is None


def test_gemm_lane_readers_count_a_batched_query_once():
    # Every result of one batched search carries the batch's count: a
    # query answered by two of them counts it once.
    per_query = load_module(BENCH / "metrics" / "gemm_lanes_per_query.py")
    per_us = load_module(BENCH / "metrics" / "kernel_gemm_lanes_per_us.py")
    batch = types.SimpleNamespace(n_gemm_lanes=36)
    r = run.Run()
    r.results = [[("a", batch), ("b", batch)],
                 [("a", types.SimpleNamespace(n_gemm_lanes=18))]]
    r.trace = types.SimpleNamespace(kernel_s=1e-6)
    assert per_query.read(r) == (36 + 18) / 2
    assert per_us.read(r) == 36 + 18
