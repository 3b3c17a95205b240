"""Kimi-Linear-style decoder (KDA linear-attention layers beside global
latent-attention layers, routed experts) lowered to GEMMs, for one decode
step repeated over the generated tokens.

Two sides of one configuration file's `workload` block:

  * `program_workload` builds the program's `ModelConfig` and
    `ShapeConfig` from the block and lowers them through the program's own
    path, `repro.core.extract.workload_for`, so the extraction is part of
    what each cell measures and checks. A program whose lowering differs
    from the reference's is refused there, before any search runs.
  * `reference_workload` is the benchmark's own plain lowering of the same
    decode step, written from the papers and importing nothing of the
    program. The comparison that decides `correct` prices the design space
    from it.

Each layer is of the kind the published index lists give it, and is
priced by that kind's equations. For a batch of B sequences that each add
one token against a context of C cached positions:

  KDA layers (Kimi Delta Attention, arXiv:2510.26692 §3; the delta rule of
      arXiv:2412.06464), H_k heads with d_k = d_v: the q, k and v
      projections (d -> 3 H_k d_k); the decay gate's and the output
      gate's down-projections and beta, which share their input
      (d -> 2 d_k + H_k); the two gates' up-projections
      (d_k -> H_k d_k); per head per sequence the state products, one
      (2, d_k, d_v) GEMM of the rows [k; q] against the decayed state
      S' = Diag(alpha) S, which is exact because
      o = S'^T q + beta (k^T q)(v - S'^T k) and
      S = S' + beta k (v - S'^T k)^T; the output projection
      (H_k d_v -> d)
  MLA layers (arXiv:2405.04434 §2.1, up-projections absorbed), H heads:
      the query projection (d -> H (nope + rope), full rank where the
      configuration has no q-LoRA, else down and up through it); the
      joint KV down-projection (d -> kv_lora + rope), the token's cache
      entry; per head the nope query times W_UK (nope -> kv_lora); per
      sequence all H heads' scores against the one shared cache of C
      latents (H x (kv_lora + rope) by C) and the context over it
      (H x C by kv_lora); per head W_UV (kv_lora -> v); the output
      projection (H v -> d)
  feed-forward: the leading dense layers' gated FFN (d -> d_ff -> d);
      every later layer's router (d -> E), routed experts' gated FFNs
      (d -> d_expert -> d) and shared experts' (d -> d_shared -> d)
  output head: d -> vocabulary, once per step

Routing is uniform: the B k routing slots of a step touch
D = round(E (1 - (1 - k/E)^B)) distinct experts, held within
[1, min(E, B k)], and fill them in whole rows, r = (B k) // D each and
B k - r D of them one more. Off chip a step streams every weight once,
except that a MoE layer streams only its D touched experts; reads the
latent cache of the MLA layers (kv_lora + rope values a position a
layer); reads and writes each KDA layer's state, H_k d_k d_v values, and
its short convolutions' last (kernel - 1) inputs of q, k and v; and moves
the step's activations in and out (B x d, twice). Element-wise work on
the electronic unit, a token a layer: ten d-wide passes (norms,
residuals) in every layer; in an MLA layer three operations a score
(softmax); in a KDA layer the short convolution (kernel x 3 H_k d_k),
the decay and the rank-1 state update (2 H_k d_k d_v) and the gates and
the gated output norm (3 H_k d_v); the activation at each layer's real
width (d_ff on the dense layers; B k d_expert routed plus B n_shared
d_shared shared on MoE layers). Activations, cache, state and weights
are `act_bits` / `weight_bits` wide off chip.

Departures from the published model, each a pricing choice:
  * the state is priced at `act_bits`; real kernels keep it in float32;
  * NoPE MLA (`mla_use_nope`) only drops the rotation of the rope part,
    which no lowering prices: the cache stays kv_lora + rope values wide;
  * KDA weights counted: the projections and the short convolutions'
    filters; the per-channel decay parameters, norms, biases and the
    router's bias are not counted among the streamed weights;
  * routing is uniform, with no skew across experts;
  * the context stays at C for every generated token;
  * the largest activation (on-chip SRAM sizing) is B x max(d_ff, 3d);
  * decode only: prefill and training need the chunked KDA form.
"""
from __future__ import annotations

from cell import BENCH, load_module

_MLA_MOE = load_module(BENCH / "lowering" / "mla_moe.py")
lowering_differences = _MLA_MOE.lowering_differences
experts_touched = _MLA_MOE.experts_touched


def program_workload(name: str, w: dict):
    """The program's `Workload` for one configuration's `workload` block."""
    from repro.configs.base import (LinearAttnConfig, MLAConfig,
                                    ModelConfig, MoEConfig, ShapeConfig)
    from repro.core.extract import workload_for

    if (w["act_bits"], w["weight_bits"]) != (4, 4):
        raise ValueError("the program's extraction prices 4-bit operands")
    cfg = ModelConfig(
        name=name, family=w["family"], n_layers=w["layers"],
        d_model=w["d_model"], n_heads=w["heads"], n_kv_heads=w["kv_heads"],
        d_ff=w["d_ff"], vocab=w["vocab"],
        mla=MLAConfig(q_lora_rank=w["q_lora_rank"] or 0,
                      kv_lora_rank=w["kv_lora_rank"],
                      rope_head_dim=w["rope_head_dim"],
                      nope_head_dim=w["nope_head_dim"],
                      v_head_dim=w["v_head_dim"]),
        moe=MoEConfig(n_experts=w["n_experts"], top_k=w["top_k"],
                      d_expert=w["d_expert"], n_shared=w["n_shared"],
                      d_shared=w["d_shared"],
                      first_dense_layers=w["first_dense_layers"]),
        linear_attn=LinearAttnConfig(
            kda_layers=tuple(w["kda_layers"]), n_heads=w["kda_heads"],
            head_dim=w["kda_head_dim"], conv_kernel=w["conv_kernel"]))
    wl = workload_for(cfg, ShapeConfig(name, w["seq_len"], w["batch"],
                                       w["kind"], new_tokens=w["new_tokens"]))
    differs = lowering_differences(wl, reference_workload(w))
    if differs:
        # A program that prices this step otherwise answers every box
        # wrongly: refuse at set-up instead of after a whole window.
        raise SystemExit(f"bench: the program's lowering of {name} differs "
                         f"from the reference in {differs}")
    return wl


def reference_workload(w: dict) -> dict:
    """{gemms: [(m, k, n, count)], elec_ops, weight_bytes, act_io_bytes,
    max_act_bytes} of the whole decode (`new_tokens` steps), in plain
    Python integers and floats."""
    if (w["family"], w["kind"]) != ("kda_mla_moe", "decode"):
        raise ValueError("this lowering writes the KDA + MLA + MoE decode "
                         "step")
    layers, d, heads = w["layers"], w["d_model"], w["heads"]
    kda, mla = len(w["kda_layers"]), len(w["full_attn_layers"])
    if sorted(w["kda_layers"] + w["full_attn_layers"]) != \
            list(range(1, layers + 1)):
        raise ValueError("every layer is either a KDA or an MLA layer")
    batch, ctx, steps = w["batch"], w["seq_len"], w["new_tokens"]
    q_lora, kv_lora = w["q_lora_rank"] or 0, w["kv_lora_rank"]
    rope, nope, v = w["rope_head_dim"], w["nope_head_dim"], w["v_head_dim"]
    h_k, d_k, conv = w["kda_heads"], w["kda_head_dim"], w["conv_kernel"]
    d_ff, vocab = w["d_ff"], w["vocab"]
    n_exp, top_k, d_exp = w["n_experts"], w["top_k"], w["d_expert"]
    n_sh, d_sh = w["n_shared"], w["d_shared"]
    dense = w["first_dense_layers"]
    moe = layers - dense
    latent = kv_lora + rope                    # one cached position
    qk = heads * (nope + rope)                 # query width of MLA
    hk = h_k * d_k                             # q, k or v width of KDA
    touched = experts_touched(n_exp, top_k, batch)
    slots = batch * top_k
    r = slots // touched
    fuller = slots - r * touched               # experts given r + 1 rows

    step = []                                  # one decode step's GEMMs
    if q_lora:
        step += [(batch, d, q_lora, mla),                # query down
                 (batch, q_lora, qk, mla)]               # query up
    else:
        step += [(batch, d, qk, mla)]                    # query
    step += [
        (batch, d, latent, mla),                         # KV down
        (batch, nope, kv_lora, mla * heads),             # W_UK absorbed
        (heads, latent, ctx, mla * batch),               # scores
        (heads, ctx, kv_lora, mla * batch),              # context
        (batch, kv_lora, v, mla * heads),                # W_UV
        (batch, heads * v, d, mla),                      # MLA output
        (batch, d, 3 * hk, kda),                         # q, k, v
        (batch, d, 2 * d_k + h_k, kda),                  # gate downs, beta
        (batch, d_k, hk, 2 * kda),                       # gate ups
        (2, d_k, d_k, kda * batch * h_k),                # [k; q] S'
        (batch, hk, d, kda),                             # KDA output
        (batch, d, d_ff, 2 * dense),                     # dense gate, up
        (batch, d_ff, d, dense),                         # dense down
        (batch, d, n_exp, moe),                          # router
    ]
    for rows, n in ((r + 1, fuller), (r, touched - fuller)):
        if n:
            step.append((rows, d, d_exp, 2 * moe * n))   # expert gate, up
    for rows, n in ((r + 1, fuller), (r, touched - fuller)):
        if n:
            step.append((rows, d_exp, d, moe * n))       # expert down
    if n_sh:
        step += [(batch, d, d_sh * n_sh, 2 * moe),       # shared gate, up
                 (batch, d_sh * n_sh, d, moe)]           # shared down
    step.append((batch, d, vocab, 1))                    # output head

    mla_params = ((d * q_lora + q_lora * qk if q_lora else d * qk)
                  + d * latent + kv_lora * heads * (nope + v)
                  + heads * v * d)
    kda_params = (d * 3 * hk + conv * 3 * hk + d * (2 * d_k + h_k)
                  + 2 * d_k * hk + hk * d)
    params = (2 * vocab * d                              # embedding, head
              + mla * mla_params + kda * kda_params
              + dense * 3 * d * d_ff
              + moe * (touched * 3 * d * d_exp + n_sh * 3 * d * d_sh
                       + d * n_exp))
    elec = (batch * d * 10 * layers                      # norms, residuals
            + batch * heads * ctx * 3 * mla              # softmax
            + batch * (conv * 3 * hk + 2 * hk * d_k      # conv, state,
                       + 3 * hk) * kda                   # gates and norm
            + batch * d_ff * dense                       # dense activation
            + (slots * d_exp + batch * d_sh * n_sh) * moe)
    act = w["act_bits"] / 8.0
    state = kda * (hk * d_k + (conv - 1) * 3 * hk)       # values a sequence
    act_io = (batch * d * 2 * act                        # activations
              + batch * ctx * mla * latent * act         # latent cache
              + 2 * batch * state * act)                 # state in and out
    return {
        "gemms": [(m, k, n, c * steps) for m, k, n, c in step if c],
        "elec_ops": float(elec * steps),
        "weight_bytes": params * w["weight_bits"] / 8.0 * steps,
        "act_io_bytes": act_io * steps,
        "max_act_bytes": batch * max(d_ff, 3 * d) * act,
    }
