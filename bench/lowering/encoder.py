"""Encoder transformer (DeiT / BERT / ViT backbone) lowered to GEMMs.

Two sides of one configuration file's `workload` block:

  * `program_workload` hands the sizes to the system under test, through
    its own lowering (`repro.core.workload.transformer_encoder_workload`),
    so the lowering is part of what each cell measures and checks.
  * `reference_workload` is the benchmark's own plain lowering of the
    same encoder, written from the layer equations and importing nothing
    of the program. The comparison that decides `correct` prices the
    design space from it.

Per layer (Vaswani et al. 2017, encoder block): a fused Q/K/V projection,
one score GEMM and one score-times-V GEMM per head, the output
projection, and the two feed-forward GEMMs. Softmax, the two LayerNorms,
the activation and the residual adds are element-wise work for the
electronic unit. Activations and weights are `act_bits` / `weight_bits`
wide off chip.
"""
from __future__ import annotations


def program_workload(name: str, w: dict):
    """The program's `Workload` for one configuration's `workload` block."""
    from repro.core.workload import Gemm, transformer_encoder_workload

    stem = w.get("stem_gemm")
    return transformer_encoder_workload(
        name, layers=w["layers"], d_model=w["d_model"], heads=w["heads"],
        d_ff=w["d_ff"], tokens=w["tokens"], batch=w["batch"],
        kv_heads=w.get("kv_heads"), vocab=w.get("vocab", 0),
        stem_gemm=Gemm(*stem) if stem else None,
        act_bits=w["act_bits"], weight_bits=w["weight_bits"],
        extra_gemms=tuple(Gemm(*g) for g in w.get("extra_gemms", ())),
        extra_elec_ops=w.get("extra_elec_ops", 0.0),
        extra_weight_bytes=w.get("extra_weight_bytes", 0.0))


def reference_workload(w: dict) -> dict:
    """{gemms: [(m, k, n, count)], elec_ops, weight_bytes, act_io_bytes,
    max_act_bytes} of one inference batch, in plain Python integers and
    floats."""
    layers, d, heads = w["layers"], w["d_model"], w["heads"]
    d_ff, tokens, batch = w["d_ff"], w["tokens"], w["batch"]
    kv_heads = w.get("kv_heads") or heads
    head_dim = d // heads
    rows = batch * tokens                      # token rows of the batch
    q_width = heads * head_dim
    kv_width = kv_heads * head_dim
    per_head = layers * batch * heads
    gemms = [
        (rows, d, q_width + 2 * kv_width, layers),   # Q, K and V at once
        (tokens, head_dim, tokens, per_head),        # Q times K transposed
        (tokens, tokens, head_dim, per_head),        # scores times V
        (rows, q_width, d, layers),                  # output projection
        (rows, d, d_ff, layers),                     # feed-forward up
        (rows, d_ff, d, layers),                     # feed-forward down
    ]
    params = layers * (d * (q_width + 2 * kv_width) + q_width * d
                       + 2 * d * d_ff)
    stem = w.get("stem_gemm")
    if stem:                                   # patch embedding, per image
        m, k, n = stem[:3]
        count = stem[3] if len(stem) > 3 else 1
        gemms.append((m, k, n, count * batch))
        params += k * n
    vocab = w.get("vocab", 0)
    if vocab:                                  # classifier head
        gemms.append((batch, d, vocab, 1))
        params += vocab * d
    for g in w.get("extra_gemms", ()):
        gemms.append(tuple(g) if len(g) == 4 else (*g, 1))

    elec_ops = (batch * heads * tokens * tokens * layers * 3   # softmax
                + rows * d * 2 * layers * 4                    # 2 LayerNorms
                + rows * d_ff * layers                         # activation
                + rows * d * 2 * layers                        # residuals
                + w.get("extra_elec_ops", 0.0))
    act_bytes = w["act_bits"] / 8.0
    return {
        "gemms": gemms,
        "elec_ops": float(elec_ops),
        "weight_bytes": params * w["weight_bits"] / 8.0
        + w.get("extra_weight_bytes", 0.0),
        "act_io_bytes": rows * d * 2 * act_bytes,            # in and out
        "max_act_bytes": rows * max(d_ff, q_width + 2 * kv_width)
        * act_bytes,
    }
