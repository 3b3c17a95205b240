"""DeepSeek-V3-style decoder (latent attention and routed experts) lowered
to GEMMs, for one decode step repeated over the generated tokens.

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

The decode step (arXiv:2405.04434 §2.1, MLA with the up-projections
absorbed; arXiv:2412.19437 §2, DeepSeekMoE), for a batch of B sequences
that each add one token against a context of C cached positions:

  latent attention, every layer: the query's down- and up-projection
      (d -> q_lora -> H x (nope + rope)); the joint KV down-projection
      (d -> kv_lora + rope), whose output is the one token's cache entry;
      per head, the nope query times W_UK (nope -> kv_lora); per
      sequence, all H heads' scores against the one shared cache of C
      latents (H x (kv_lora + rope) by C) and the context over it
      (H x C by kv_lora); per head, W_UV (kv_lora -> v); the output
      projection (H x v -> d)
  feed-forward: the leading dense layers' gated FFN (gate and up d ->
      d_ff, down d_ff -> d); every later layer's router (d -> E), the
      routed experts' gated FFNs (d -> d_expert -> d) and the shared
      experts' (d -> d_shared -> d)
  output head: d -> vocabulary, once per step

Routing is uniform: the B x k routing slots of a step touch
D = round(E (1 - (1 - k/E)^B)) distinct experts, the expectation, held
within [1, min(E, B k)], and fill them in whole rows, r = (B k) // D rows
each and B k - r D of them one more. Off chip a step streams every weight
once, except that a MoE layer streams only its D touched experts; reads the
whole latent cache (kv_lora + rope values a position a layer); and moves
the step's activations in and out (B x d, twice). Element-wise work on the
electronic unit: ten d-wide passes a token a layer (norms, residuals),
three operations a score (softmax), and the activation at each layer's
real width (d_ff on the dense layers; B k d_expert routed plus
B n_shared d_shared shared on MoE layers). Activations, the cache and
weights are `act_bits` / `weight_bits` wide off chip.

Departures from the published model, each a pricing choice:
  * the multi-token-prediction module is not run (decoding without
    speculation runs none of it);
  * routing is uniform, with no skew across experts;
  * the context stays at C for every generated token (it grows by at most
    `new_tokens` positions);
  * norm weights, biases and the router's bias vectors are not counted
    among the streamed weights;
  * the largest activation (on-chip SRAM sizing) is B x max(d_ff, 3d).
"""
from __future__ import annotations


def program_workload(name: str, w: dict):
    """The program's `Workload` for one configuration's `workload` block."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    ShapeConfig)
    from repro.core.extract import workload_for

    if (w["act_bits"], w["weight_bits"]) != (4, 4):
        raise ValueError("the program's extraction prices 4-bit operands")
    cfg = ModelConfig(
        name=name, family=w["family"], n_layers=w["layers"],
        d_model=w["d_model"], n_heads=w["heads"], n_kv_heads=w["kv_heads"],
        d_ff=w["d_ff"], vocab=w["vocab"],
        mla=MLAConfig(q_lora_rank=w["q_lora_rank"],
                      kv_lora_rank=w["kv_lora_rank"],
                      rope_head_dim=w["rope_head_dim"],
                      nope_head_dim=w["nope_head_dim"],
                      v_head_dim=w["v_head_dim"]),
        moe=MoEConfig(n_experts=w["n_experts"], top_k=w["top_k"],
                      d_expert=w["d_expert"], n_shared=w["n_shared"],
                      d_shared=w["d_shared"],
                      first_dense_layers=w["first_dense_layers"]))
    wl = workload_for(cfg, ShapeConfig(name, w["seq_len"], w["batch"],
                                       w["kind"], new_tokens=w["new_tokens"]))
    differs = lowering_differences(wl, reference_workload(w))
    if differs:
        # A program that prices this step otherwise answers every box
        # wrongly: refuse at set-up instead of after a whole window.
        raise SystemExit(f"bench: the program's lowering of {name} differs "
                         f"from the reference in {differs}")
    return wl


def lowering_differences(wl, ref: dict) -> list:
    """Fields in which a program `Workload` and a reference lowering
    differ: the GEMM multiset, then each byte and operation count."""
    out = []
    if sorted(tuple(g) for g in wl.gemm_array.tolist()) != \
            sorted(tuple(g) for g in ref["gemms"]):
        out.append("gemms")
    return out + [k for k in ("elec_ops", "weight_bytes", "act_io_bytes",
                              "max_act_bytes") if getattr(wl, k) != ref[k]]


def experts_touched(n_experts: int, top_k: int, rows: int) -> int:
    """Distinct experts `rows` uniformly routed rows touch (expectation,
    rounded, within [1, min(E, rows k)])."""
    d = round(n_experts * (1.0 - (1.0 - top_k / n_experts) ** rows))
    return max(1, min(n_experts, rows * top_k, d))


def reference_workload(w: dict) -> dict:
    """{gemms: [(m, k, n, count)], elec_ops, weight_bytes, act_io_bytes,
    max_act_bytes} of the whole decode (`new_tokens` steps), in plain
    Python integers and floats."""
    if (w["family"], w["kind"]) != ("mla_moe", "decode"):
        raise ValueError("this lowering writes the MLA + MoE decode step")
    layers, d, heads = w["layers"], w["d_model"], w["heads"]
    batch, ctx, steps = w["batch"], w["seq_len"], w["new_tokens"]
    q_lora, kv_lora = w["q_lora_rank"], w["kv_lora_rank"]
    rope, nope, v = w["rope_head_dim"], w["nope_head_dim"], w["v_head_dim"]
    d_ff, vocab = w["d_ff"], w["vocab"]
    n_exp, top_k, d_exp = w["n_experts"], w["top_k"], w["d_expert"]
    n_sh, d_sh = w["n_shared"], w["d_shared"]
    dense = w["first_dense_layers"]
    moe = layers - dense
    latent = kv_lora + rope                    # one cached position
    touched = experts_touched(n_exp, top_k, batch)
    slots = batch * top_k
    r = slots // touched
    fuller = slots - r * touched               # experts given r + 1 rows

    step = []                                  # one decode step's GEMMs
    step += [
        (batch, d, q_lora, layers),                      # query down
        (batch, q_lora, heads * (nope + rope), layers),  # query up
        (batch, d, latent, layers),                      # KV down
        (batch, nope, kv_lora, layers * heads),          # W_UK absorbed
        (heads, latent, ctx, layers * batch),            # scores
        (heads, ctx, kv_lora, layers * batch),           # context
        (batch, kv_lora, v, layers * heads),             # W_UV
        (batch, heads * v, d, layers),                   # output
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
    step += [
        (batch, d, d_sh * n_sh, 2 * moe),                # shared gate, up
        (batch, d_sh * n_sh, d, moe),                    # shared down
        (batch, d, vocab, 1),                            # output head
    ]

    attention = (d * q_lora + q_lora * heads * (nope + rope) + d * latent
                 + kv_lora * heads * (nope + v) + heads * v * d)
    params = (2 * vocab * d                              # embedding, head
              + layers * attention
              + dense * 3 * d * d_ff
              + moe * (touched * 3 * d * d_exp + n_sh * 3 * d * d_sh
                       + d * n_exp))
    elec = (batch * d * 10 * layers                      # norms, residuals
            + batch * heads * ctx * 3 * layers           # softmax
            + batch * d_ff * dense                       # dense activation
            + (slots * d_exp + batch * d_sh * n_sh) * moe)
    act = w["act_bits"] / 8.0
    act_io = batch * d * 2 * act + batch * ctx * layers * latent * act
    return {
        "gemms": [(m, k, n, c * steps) for m, k, n, c in step],
        "elec_ops": float(elec * steps),
        "weight_bytes": params * w["weight_bits"] / 8.0 * steps,
        "act_io_bytes": act_io * steps,
        "max_act_bytes": batch * max(d_ff, 3 * d) * act,
    }
