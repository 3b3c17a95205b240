"""Workload extraction from framework ModelConfigs — the HW/SW co-design
bridge (DESIGN.md §2): the same `--arch` config that drives JAX training/
serving lowers to a DxPTA Workload (GEMM list + electronic-unit ops + memory
traffic) so the paper's search runs over the assigned architectures.

Per-family GEMM decomposition notes (DESIGN.md §5):
  * attention-free recurrences (RWKV WKV, Mamba selective scan) are
    element-wise -> electronic unit; their projections are GEMMs;
  * sliding-window layers have window-bounded score GEMMs;
  * MoE experts are priced as a batch touches them under uniform routing:
    T routed token rows touch D = round(E*(1-(1-k/E)^T)) distinct experts
    (clamped to [1, min(E, T*k)]), and the T*k slots fill those D in
    integer rows (`expert_rows`); weights stream D experts a MoE layer;
  * MLA low-rank compress/expand are GEMMs;
  * kda_mla_moe (Kimi Linear) prices each layer by its own kind: the
    published KDA layers as a gated delta-rule step (`_kda_gemms`, decode
    only), the rest as MLA layers;
  * decode workloads have M = batch (tiny-M GEMMs -> poor DDot-array
    utilization; visible in the DSE results), and each step also reads the
    KV cache off chip (`_kv_cache_bytes`) and reads and writes the
    recurrent state of KDA layers (`_state_bytes`).

`workload_for` runs inside the span `extract`, whose stats are the GEMM
rows by layer kind (`rows_latent`: MLA; `rows_linear`: KDA; `rows_moe`:
router and experts; `rows_other`: the rest) and one decode step's
`cache_bytes` and `state_bytes` (0 outside decode).
"""
from __future__ import annotations

from typing import List

from repro.configs.base import (MOE_FAMILIES, ModelConfig, MoEConfig,
                                ShapeConfig)
from repro.tracing import span

from .workload import Gemm, Workload

MLA_FAMILIES = ("mla_moe", "kda_mla_moe")
ROW_KINDS = ("latent", "linear", "moe", "other")


def experts_touched(moe: MoEConfig, tokens: int) -> int:
    """Distinct routed experts `tokens` routed rows touch under uniform
    top-k routing: the expectation E*(1 - (1 - k/E)^T), rounded, clamped
    to [1, min(E, T*k)]."""
    e, k = moe.n_experts, moe.top_k
    d = round(e * (1.0 - (1.0 - k / e) ** tokens))
    return max(1, min(e, tokens * k, d))


def expert_rows(moe: MoEConfig, tokens: int) -> List[tuple]:
    """[(rows, experts)]: the T*k routing slots split over the D touched
    experts in integer rows, r = (T*k) // D each and T*k - r*D of them
    one row more."""
    d = experts_touched(moe, tokens)
    r, extra = divmod(tokens * moe.top_k, d)
    return [(rows, n) for rows, n in ((r + 1, extra), (r, d - extra)) if n]


def _attn_gemms(cfg, n_ctx, bt, batch, layers, gemms: List[Gemm],
                decode=False, window=None):
    """GQA attention GEMMs for `layers` layers. bt = batch*q_tokens."""
    dh = cfg.resolved_head_dim
    d = cfg.d_model
    d_q = cfg.n_heads * dh
    d_kv = cfg.n_kv_heads * dh
    q_tokens = bt // batch
    ctx = min(n_ctx, window) if window else n_ctx
    gemms.append(Gemm(bt, d, d_q + 2 * d_kv, layers))               # QKV
    gemms.append(Gemm(q_tokens, dh, ctx, layers * batch * cfg.n_heads))
    gemms.append(Gemm(q_tokens, ctx, dh, layers * batch * cfg.n_heads))
    gemms.append(Gemm(bt, d_q, d, layers))                          # out


def _mla_gemms(cfg, n_ctx, bt, batch, layers, gemms: List[Gemm],
               decode=False):
    m = cfg.mla
    d = cfg.d_model
    h = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    q_tokens = bt // batch
    if m.q_lora_rank:
        gemms.append(Gemm(bt, d, m.q_lora_rank, layers))
        gemms.append(Gemm(bt, m.q_lora_rank, h * qd, layers))
    else:
        gemms.append(Gemm(bt, d, h * qd, layers))
    gemms.append(Gemm(bt, d, m.kv_lora_rank + m.rope_head_dim, layers))
    if decode:
        # absorbed form: q->latent per head, then every head of a sequence
        # scores and reads the one shared rank-R cache in a single GEMM
        gemms.append(Gemm(bt, m.nope_head_dim, m.kv_lora_rank, layers * h))
        gemms.append(Gemm(q_tokens * h, m.kv_lora_rank + m.rope_head_dim,
                          n_ctx, layers * batch))
        gemms.append(Gemm(q_tokens * h, n_ctx, m.kv_lora_rank,
                          layers * batch))
        gemms.append(Gemm(bt, m.kv_lora_rank, m.v_head_dim, layers * h))
    else:
        gemms.append(Gemm(bt, m.kv_lora_rank,
                          h * (m.nope_head_dim + m.v_head_dim), layers))
        gemms.append(Gemm(q_tokens, qd, n_ctx, layers * batch * h))
        gemms.append(Gemm(q_tokens, n_ctx, m.v_head_dim, layers * batch * h))
    gemms.append(Gemm(bt, h * m.v_head_dim, d, layers))


def _kda_gemms(cfg, bt, batch, layers, gemms: List[Gemm]):
    """One decode step of Kimi Delta Attention (arXiv:2510.26692) in
    `layers` layers, bt = batch rows: the q, k, v projections; the decay
    and output gates' down-projections with beta, which share their input
    (d -> 2 d_k + H); the gates' up-projections (d_k -> H d_k); the state
    products; the output projection. Per head the state S (d_k x d_v)
    decays channel-wise, S' = Diag(alpha) S, and with
    o = S'^T q + beta (k^T q)(v - S'^T k) and
    S = S' + beta k (v - S'^T k)^T one (2, d_k, d_v) GEMM of [k; q]
    against S' per head per sequence gives both products exactly. The
    decay, the rank-1 update, the gates and the short convolution are
    element-wise (`_elec_ops`)."""
    la = cfg.linear_attn
    d, dk, h = cfg.d_model, la.head_dim, la.n_heads
    hk = h * dk
    gemms.append(Gemm(bt, d, 3 * hk, layers))                  # q, k, v
    gemms.append(Gemm(bt, d, 2 * dk + h, layers))              # gates, beta
    gemms.append(Gemm(bt, dk, hk, 2 * layers))                 # gate ups
    gemms.append(Gemm(2, dk, dk, layers * batch * h))          # [k; q] S'
    gemms.append(Gemm(bt, hk, d, layers))                      # output


def _ffn_gemms(cfg, bt, layers, gemms: List[Gemm]):
    gemms.append(Gemm(bt, cfg.d_model, cfg.d_ff, 2 * layers))  # wi + wg
    gemms.append(Gemm(bt, cfg.d_ff, cfg.d_model, layers))


def _moe_gemms(cfg, bt, layers, gemms: List[Gemm]):
    mo = cfg.moe
    d = cfg.d_model
    gemms.append(Gemm(bt, d, mo.n_experts, layers))            # router
    split = expert_rows(mo, bt)                                # bt routed rows
    for rows, n in split:
        gemms.append(Gemm(rows, d, mo.d_expert, 2 * layers * n))
    for rows, n in split:
        gemms.append(Gemm(rows, mo.d_expert, d, layers * n))
    if mo.n_shared:
        ds = (mo.d_shared or mo.d_expert) * mo.n_shared
        gemms.append(Gemm(bt, d, ds, 2 * layers))
        gemms.append(Gemm(bt, ds, d, layers))


def _mamba_gemms(cfg, bt, batch, layers, gemms: List[Gemm], decode=False):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    proj_out = 2 * d_in + 2 * s.d_state + nh
    gemms.append(Gemm(bt, d, proj_out, layers))
    gemms.append(Gemm(bt, d_in, d, layers))
    if not decode:
        # intra-chunk SSD GEMMs (C.B^T + score-weighted value aggregation);
        # decode uses the element-wise recurrence (electronic unit).
        q_tokens = bt // batch
        nch = max(1, q_tokens // s.chunk)
        gemms.append(Gemm(s.chunk, s.d_state, s.chunk, layers * batch * nch))
        gemms.append(Gemm(s.chunk, s.chunk, d_in, layers * batch * nch))


def _rwkv_gemms(cfg, bt, layers, gemms: List[Gemm]):
    d = cfg.d_model
    gemms.append(Gemm(bt, d, d, 5 * layers))   # r, k, v, g, out projections
    gemms.append(Gemm(bt, d, 64, layers))      # decay LoRA down
    gemms.append(Gemm(bt, 64, d, layers))      # decay LoRA up
    gemms.append(Gemm(bt, d, cfg.d_ff, layers))        # channel-mix k
    gemms.append(Gemm(bt, cfg.d_ff, d, layers))        # channel-mix v
    gemms.append(Gemm(bt, d, d, layers))               # channel-mix r


def _elec_ops(cfg, n_ctx, bt, batch, layers, decode=False, linear=0):
    """Softmax / LN / activations / recurrences on the electronic unit.
    `linear` of the `layers` are KDA layers, the rest attention layers.

    Every branch scales with the `layers` parameter, never `cfg.n_layers`:
    the two only coincide when the caller happens to pass the full depth,
    and an `cfg.n_layers` alias would double-count whenever a family's
    electronic depth differs from its config depth (enc-dec already does;
    partial-depth scenario extraction would too).
    """
    d = cfg.d_model
    q_tokens = bt // batch
    ops = bt * d * 10 * layers                              # norms/residual
    if cfg.family == "rwkv":
        kd = cfg.resolved_head_dim
        ops += bt * cfg.n_heads * kd * kd * 3 * layers      # WKV update
        ops += bt * cfg.d_ff
    elif cfg.family == "hybrid_ssm":
        s = cfg.ssm
        d_in = s.expand * d
        ops += bt * (d_in // s.head_dim) * s.d_state * s.head_dim // \
            max(s.chunk, 1) * 3 * layers                    # inter-chunk
        ops += bt * d_in * 2 * layers                       # conv + gates
    else:
        ops += batch * cfg.n_heads * q_tokens * n_ctx * 3 \
            * (layers - linear)                             # softmax
        if linear:
            # KDA: the short convolution of q, k and v, the decay and the
            # rank-1 state update, the gates and the gated output norm
            la = cfg.linear_attn
            hk = la.n_heads * la.head_dim
            ops += bt * (la.conv_kernel * 3 * hk + 2 * hk * la.head_dim
                         + 3 * hk) * linear
        if cfg.family in MOE_FAMILIES and cfg.moe:
            # activations at each layer's real width: the dense FFN of
            # the leading layers, then the T*k routed expert rows and the
            # shared experts' rows of every MoE layer
            mo = cfg.moe
            dense = min(mo.first_dense_layers, layers)
            ops += bt * cfg.d_ff * dense
            ops += (bt * mo.top_k * mo.d_expert
                    + bt * (mo.d_shared or mo.d_expert) * mo.n_shared) \
                * (layers - dense)
        else:
            ops += bt * cfg.d_ff * layers                   # activation
    return float(ops)


def _attn_layers(cfg):
    """(window or None, window-bounded layers, global layers): every
    swa_pattern-th layer global, the rest local; swa_pattern 0 with a
    window makes every layer local."""
    window = cfg.sliding_window or None
    n_global = (cfg.n_layers // cfg.swa_pattern
                if (window and cfg.swa_pattern) else
                (0 if window else cfg.n_layers))
    return window, cfg.n_layers - n_global, n_global


def _weight_bytes(cfg, tokens, weight_bits=4):
    """Weights streamed once for `tokens` routed rows: every parameter,
    except that a MoE layer streams only the experts the rows touch."""
    params = cfg.param_count()
    if cfg.family in MOE_FAMILIES and cfg.moe:
        mo = cfg.moe
        idle = mo.n_experts - experts_touched(mo, tokens)
        params -= 3 * cfg.d_model * mo.d_expert * idle \
            * (cfg.n_layers - mo.first_dense_layers)
    return params * weight_bits / 8.0


def _kv_cache_bytes(cfg, n_ctx, batch, act_bits=4):
    """KV cache one decode step reads off chip, at `act_bits`: MLA's
    latent (kv_lora + rope values a token an MLA layer); GQA's K and V of
    every attention layer, window-bounded layers capped at their window
    (and enc-dec's cross-attention K and V over its source); none for
    RWKV."""
    fam = cfg.family
    if fam == "rwkv":
        return 0.0
    if fam in MLA_FAMILIES:
        m = cfg.mla
        values = n_ctx * (cfg.n_layers - cfg.n_kda_layers) \
            * (m.kv_lora_rank + m.rope_head_dim)
    else:
        if fam == "hybrid_ssm":
            positions = n_ctx * (cfg.n_layers // cfg.ssm.attn_every)
        elif fam == "encdec":
            positions = (n_ctx + n_ctx // 2) * cfg.dec_layers
        else:
            window, n_local, n_global = _attn_layers(cfg)
            positions = (n_global * n_ctx
                         + n_local * (min(n_ctx, window) if window
                                      else n_ctx))
        values = positions * 2 * cfg.n_kv_heads * cfg.resolved_head_dim
    return batch * values * act_bits / 8.0


def _state_bytes(cfg, batch, act_bits=4):
    """Recurrent state one decode step reads and writes off chip, at
    `act_bits`: each KDA layer's H d_k d_v state and its short
    convolutions' last (kernel - 1) inputs of q, k and v. No other
    family's recurrent state is counted."""
    n = cfg.n_kda_layers
    if not n:
        return 0.0
    la = cfg.linear_attn
    hk = la.n_heads * la.head_dim
    values = n * (hk * la.head_dim + (la.conv_kernel - 1) * 3 * hk)
    return 2 * batch * values * act_bits / 8.0


def _build(cfg: ModelConfig, name, seq, batch, *, decode=False,
           n_ctx=None, act_bits=4, stats=None) -> Workload:
    """One forward pass (or one decode step). `stats`, when given, takes
    the `extract` span's stats (module docstring)."""
    n_ctx = n_ctx or seq
    if cfg.n_prefix_embeds and cfg.family != "encdec":
        # VLM/audio prefix embeddings are real sequence positions: in
        # prefill/train they flow through every layer alongside the text
        # tokens; in decode they sit in the attended context.
        if decode:
            n_ctx += cfg.n_prefix_embeds
        else:
            seq = seq + cfg.n_prefix_embeds
            n_ctx += cfg.n_prefix_embeds
    bt = batch * seq
    gemms: List[Gemm] = []
    kinds: List[str] = []             # the layer kind of each GEMM row
    fam = cfg.family

    def tag(kind):
        """Give `kind` to every row appended since the last tag."""
        kinds.extend([kind] * (len(gemms) - len(kinds)))

    attn_layers = cfg.n_layers
    if fam == "encdec":
        # prefill: encoder over seq/2 src frames + decoder over seq/2 tgt
        # tokens. decode: decoder only (cross-KV reused), src ctx = n_ctx/2.
        src = (n_ctx if decode else seq) // 2
        tgt = seq if decode else seq - src
        tgt_bt = batch * tgt
        if not decode:
            _attn_gemms(cfg, src, batch * src, batch, cfg.enc_layers, gemms)
            _ffn_gemms(cfg, batch * src, cfg.enc_layers, gemms)
        _attn_gemms(cfg, n_ctx if decode else tgt, tgt_bt, batch,
                    cfg.dec_layers, gemms, decode=decode)
        dh = cfg.resolved_head_dim
        gemms.append(Gemm(tgt, dh, src, cfg.dec_layers * batch * cfg.n_heads))
        gemms.append(Gemm(tgt, src, dh, cfg.dec_layers * batch * cfg.n_heads))
        _ffn_gemms(cfg, tgt_bt, cfg.dec_layers, gemms)
        layers_for_elec = cfg.enc_layers + cfg.dec_layers
    elif fam == "rwkv":
        _rwkv_gemms(cfg, bt, cfg.n_layers, gemms)
        layers_for_elec = cfg.n_layers
    elif fam == "hybrid_ssm":
        s = cfg.ssm
        n_shared = cfg.n_layers // s.attn_every
        _mamba_gemms(cfg, bt, batch, cfg.n_layers, gemms, decode=decode)
        _attn_gemms(cfg, n_ctx, bt, batch, n_shared, gemms, decode=decode)
        _ffn_gemms(cfg, bt, n_shared, gemms)
        layers_for_elec = cfg.n_layers
    else:
        window, n_local, n_global = _attn_layers(cfg)
        n_kda = cfg.n_kda_layers
        if fam in MLA_FAMILIES:
            if n_kda and not decode:
                raise ValueError(
                    f"{cfg.name}: prefill and training of KDA layers need "
                    f"the chunked KDA form (arXiv:2412.06464), which has "
                    f"no lowering yet; only decode is priced")
            if cfg.n_layers > n_kda:
                _mla_gemms(cfg, n_ctx, bt, batch, cfg.n_layers - n_kda,
                           gemms, decode=decode)
            tag("latent")
            if n_kda:
                _kda_gemms(cfg, bt, batch, n_kda, gemms)
            tag("linear")
        else:
            if n_local:
                _attn_gemms(cfg, n_ctx, bt, batch, n_local, gemms,
                            decode=decode, window=window)
            if n_global:
                _attn_gemms(cfg, n_ctx, bt, batch, n_global, gemms,
                            decode=decode)
        if fam in MOE_FAMILIES:
            mo = cfg.moe
            n_moe = cfg.n_layers - mo.first_dense_layers
            if mo.first_dense_layers:
                _ffn_gemms(cfg, bt, mo.first_dense_layers, gemms)
            tag("other")
            _moe_gemms(cfg, bt, n_moe, gemms)
            tag("moe")
        else:
            _ffn_gemms(cfg, bt, cfg.n_layers, gemms)
        layers_for_elec = cfg.n_layers

    gemms.append(Gemm(bt, cfg.d_model, cfg.vocab, 1))   # LM head
    tag("other")

    elec = _elec_ops(cfg, n_ctx, bt, batch, layers_for_elec, decode,
                     linear=cfg.n_kda_layers)
    wb = _weight_bytes(cfg, bt)
    max_act = bt * max(cfg.d_ff, 3 * cfg.d_model) * act_bits / 8.0
    cache = _kv_cache_bytes(cfg, n_ctx, batch, act_bits) if decode else 0.0
    state = _state_bytes(cfg, batch, act_bits) if decode else 0.0
    act_io = bt * cfg.d_model * 2 * act_bits / 8.0 + cache + state
    if stats is not None:
        stats.update({f"rows_{k}": kinds.count(k) for k in ROW_KINDS},
                     cache_bytes=cache, state_bytes=state)
    return Workload(name=name, gemms=tuple(gemms), elec_ops=elec,
                    weight_bytes=float(wb), act_io_bytes=float(act_io),
                    max_act_bytes=float(max_act), batch=batch)


def prefill_workload(cfg: ModelConfig, seq: int, batch: int,
                     stats=None) -> Workload:
    return _build(cfg, f"{cfg.name}-prefill{seq}b{batch}", seq, batch,
                  stats=stats)


def training_workload(cfg: ModelConfig, seq: int, batch: int,
                      stats=None) -> Workload:
    """Forward+backward ~ 3x forward GEMM MACs (standard accounting)."""
    fwd = _build(cfg, f"{cfg.name}-train{seq}b{batch}", seq, batch,
                 stats=stats)
    gemms = tuple(Gemm(g.m, g.k, g.n, g.count * 3) for g in fwd.gemms)
    return Workload(name=fwd.name, gemms=gemms, elec_ops=fwd.elec_ops * 2,
                    weight_bytes=fwd.weight_bytes * 3,
                    act_io_bytes=fwd.act_io_bytes * 2,
                    max_act_bytes=fwd.max_act_bytes, batch=batch)


def serving_workload(cfg: ModelConfig, seq_len: int, batch: int,
                     new_tokens: int, stats=None) -> Workload:
    """Decode of `new_tokens` tokens against a seq_len context: M = batch
    per GEMM per step, context-length score GEMMs, re-streamed (active)
    weights every step.

    The decode length is part of the workload *name* — two decode
    workloads of the same (seq, batch) but different `new_tokens` are
    different questions, and the serve layer's memo keys include the name,
    so the names must not collide.
    """
    one = _build(cfg, f"{cfg.name}-decode{seq_len}b{batch}n{new_tokens}",
                 1, batch, decode=True, n_ctx=seq_len, stats=stats)
    gemms = tuple(Gemm(g.m, g.k, g.n, g.count * new_tokens)
                  for g in one.gemms)
    return Workload(name=one.name, gemms=gemms,
                    elec_ops=one.elec_ops * new_tokens,
                    weight_bytes=one.weight_bytes * new_tokens,
                    act_io_bytes=one.act_io_bytes * new_tokens,
                    max_act_bytes=one.max_act_bytes, batch=batch)


def workload_for(cfg: ModelConfig, shape: ShapeConfig) -> Workload:
    """Lower a (model config, input shape) pair to a DxPTA `Workload`.

    `shape.kind` picks the extraction path; `shape.new_tokens` is the
    decode length ("decode" kind only). Historically the decode length was
    hard-coded to 32 here, which silently gave every decode shape —
    `decode_32k` and `long_500k` alike — the same generation length; now
    it threads through from the shape. Runs inside the span `extract`,
    whose stats the lowering gives (module docstring).
    """
    with span("extract") as sp:
        stats = {}
        if shape.kind == "train":
            wl = training_workload(cfg, shape.seq_len, shape.global_batch,
                                   stats)
        elif shape.kind == "prefill":
            wl = prefill_workload(cfg, shape.seq_len, shape.global_batch,
                                  stats)
        elif shape.kind == "decode":
            wl = serving_workload(cfg, shape.seq_len, shape.global_batch,
                                  new_tokens=shape.new_tokens, stats=stats)
        else:
            raise ValueError(f"unknown shape kind {shape.kind!r}; pick "
                             f"'train', 'prefill' or 'decode'")
        sp.set_metadata(**stats)
        return wl
