"""The Kimi-Linear decode cell: its plain lowering against the program's
`workload_for` at full size and on seeded random small hybrids, a program
that prices the KDA layers as MLA refused at set-up, a CPU run of one
whole layer period (three KDA layers, one MLA, 8 experts) over the 1..6
space judged `correct`, a perturbed front rejected, and the float32
control not `correct`."""
import copy
import itertools
import json

import numpy as np
import pytest

from _tiny import BENCH

import check
import control
import reference
import run
import traffic as tr
from cell import Cell, load_module, load_spec, resolve

CELL = "kimi-linear.s24.pareto-decode-500k"
KDA = load_module(BENCH / "lowering" / "kda_mla_moe.py")
with open(BENCH / "configs" / "kimi-linear.s24.json") as _f:
    CONFIG = json.load(_f)
(NAME, SIZES), = CONFIG["workloads"].items()

# The workload block's sizes against the published config.json keys the
# configuration file carries at its top level (or in its
# linear_attn_config group).
PUBLISHED = {"layers": "num_hidden_layers", "d_model": "hidden_size",
             "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
             "d_ff": "intermediate_size", "vocab": "vocab_size",
             "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
             "rope_head_dim": "qk_rope_head_dim",
             "nope_head_dim": "qk_nope_head_dim", "v_head_dim": "v_head_dim",
             "n_experts": "num_experts", "top_k": "num_experts_per_token",
             "d_expert": "moe_intermediate_size",
             "d_shared": "moe_intermediate_size",
             "n_shared": "num_shared_experts",
             "first_dense_layers": "first_k_dense_replace"}
PUBLISHED_KDA = {"kda_layers": "kda_layers",
                 "full_attn_layers": "full_attn_layers",
                 "kda_heads": "num_heads", "kda_head_dim": "head_dim",
                 "conv_kernel": "short_conv_kernel_size"}


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    """Reference sweeps and traces go to the test's directory."""
    monkeypatch.setattr(run, "CACHE", tmp_path)


def _small_config(n_z: int = 6) -> dict:
    """One whole period of the layer pattern: layers 1-4 (the dense KDA
    layer, two more KDA layers and one MLA layer), 8 routed experts, over
    the 1..n_z space; every width and the context as published."""
    cfg = copy.deepcopy(CONFIG)
    cfg["name"] = f"{cfg['name']}.l4.s{n_z}"
    cfg["space"]["n_z"] = n_z
    w = cfg["workloads"][NAME]
    w.update(layers=4, kda_layers=[1, 2, 3], full_attn_layers=[4],
             n_experts=8)
    return cfg


def test_the_workload_block_is_the_published_model():
    assert CONFIG["reduced"] == []
    for key, published in PUBLISHED.items():
        assert SIZES[key] == CONFIG[published], key
    for key, published in PUBLISHED_KDA.items():
        assert SIZES[key] == CONFIG["linear_attn_config"][published], key
    assert (SIZES["kind"], SIZES["seq_len"], SIZES["batch"],
            SIZES["new_tokens"]) == ("decode", 524288, 1, 32)
    assert CONFIG["num_nextn_predict_layers"] == 0


def test_reference_lowering_matches_the_program_at_full_size():
    prog = KDA.program_workload(NAME, SIZES)
    ref = KDA.reference_workload(SIZES)
    assert sorted(tuple(g) for g in prog.gemm_array.tolist()) == \
        sorted(ref["gemms"])
    assert len(ref["gemms"]) == 20
    for k in ("elec_ops", "weight_bytes", "act_io_bytes", "max_act_bytes"):
        assert getattr(prog, k) == ref[k], k
    # the program's own Kimi-Linear config lowers to the same workload
    from repro.configs import SHAPES_BY_NAME
    from repro.configs.archs import KIMI_LINEAR_48B
    from repro.core.extract import workload_for

    own = workload_for(KIMI_LINEAR_48B, SHAPES_BY_NAME["long_500k"])
    assert KDA.lowering_differences(own, ref) == []


def _random_sizes(seed: int) -> dict:
    """A small hybrid: random widths, a random split of the layers into
    KDA and MLA lists (either may be empty), batch and context."""
    rng = np.random.default_rng([seed, 20])

    def pick(lo, hi):
        return int(rng.integers(lo, hi + 1))

    layers = pick(2, 7)
    kda = sorted(rng.choice(np.arange(1, layers + 1), pick(0, layers),
                            replace=False).tolist())
    n_exp = pick(2, 12)
    return {
        "family": "kda_mla_moe", "kind": "decode", "layers": layers,
        "d_model": pick(4, 48), "heads": pick(1, 4), "kv_heads": 1,
        "d_ff": pick(4, 64), "vocab": pick(8, 300),
        "q_lora_rank": pick(1, 16) if rng.random() < 0.5 else None,
        "kv_lora_rank": pick(1, 16), "rope_head_dim": pick(1, 8),
        "nope_head_dim": pick(1, 16), "v_head_dim": pick(1, 16),
        "kda_layers": kda,
        "full_attn_layers": [i for i in range(1, layers + 1)
                             if i not in kda],
        "kda_heads": pick(1, 4), "kda_head_dim": pick(1, 16),
        "conv_kernel": pick(1, 4),
        "n_experts": n_exp, "top_k": pick(1, n_exp),
        "d_expert": pick(1, 32), "n_shared": pick(0, 2),
        "d_shared": pick(1, 32), "first_dense_layers": pick(0, layers - 1),
        "seq_len": pick(1, 5000), "batch": pick(1, 9),
        "new_tokens": pick(1, 4), "act_bits": 4, "weight_bits": 4}


@pytest.mark.parametrize("seed", range(20))
def test_reference_lowering_matches_the_program_on_small_hybrids(seed):
    sizes = _random_sizes(seed)
    prog = KDA.program_workload(f"h{seed}", sizes)   # refuses on a diff
    assert KDA.lowering_differences(prog,
                                    KDA.reference_workload(sizes)) == []


def test_a_program_pricing_kda_layers_as_mla_is_refused_at_set_up(
        monkeypatch):
    from repro.configs.base import ModelConfig

    monkeypatch.setattr(ModelConfig, "n_kda_layers", property(lambda _: 0))
    with pytest.raises(SystemExit, match="gemms"):
        KDA.program_workload(NAME, SIZES)


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


def test_a_sound_run_of_one_layer_period_is_correct():
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
    wl = KDA.reference_workload(cfg["workloads"][NAME])
    sub = reference.sweep(cfg["space"]["n_z"], wl, cfg["constants"],
                          tr.si(tr.loosest(traffic)))
    return traffic, items, results, wl, sub


def test_a_perturbed_front_is_rejected():
    cfg = _small_config()
    traffic, items, results, wl, sub = _answers(cfg)
    # the period has every row kind of the full model: the same 20 GEMM
    # rows, which each launch's `n_gemm_lanes` counts
    assert len(wl["gemms"]) == 20
    assert all(r.n_gemm_lanes > 0 and r.n_gemm_lanes % 20 == 0
               for r in results)
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
