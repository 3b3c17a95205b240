"""Golden extraction pins: every model family x {train, prefill, decode}.

Each test hand-computes the expected total GEMM MAC count and
electronic-unit op count for a tiny, hand-sized config from the
documented per-family decomposition (DESIGN.md §5 / the formulas in
`core.extract`'s module docstring), written out *independently* here —
no extract helpers are called to produce the expectations. A change to
the extraction arithmetic therefore fails these pins with the exact
family x kind cell that moved.

All quantities are integer-valued and far below 2**53, so float64
equality is exact.

Also here: the `_elec_ops` layers-parameter regressions — pre-fix, the
rwkv and hybrid_ssm branches scaled their recurrence terms by
`cfg.n_layers` instead of the `layers` argument, so any caller passing a
partial depth got the full-depth electronic cost silently folded in.
"""
import dataclasses

import pytest

from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                ShapeConfig, SSMConfig)
from repro.core.extract import (_elec_ops, expert_rows, experts_touched,
                                workload_for)

S, B = 4, 2           # prefill/train tokens x batch
CTX, NT = 8, 3        # decode context x generated tokens
VOCAB = 10


def _wl(cfg, kind, seq=None, batch=B, new_tokens=NT):
    seq = seq if seq is not None else (CTX if kind == "decode" else S)
    return workload_for(cfg, ShapeConfig("g", seq, batch, kind,
                                         new_tokens=new_tokens))


def _check(cfg, prefill_macs, prefill_elec, decode_macs, decode_elec):
    """Pin all three kinds from the two forward-pass expectations.

    train is defined as 3x forward MACs / 2x forward elec (standard
    fwd+bwd accounting); decode expectations are per-step, scaled by NT.
    """
    wl = _wl(cfg, "prefill")
    assert wl.total_macs == prefill_macs, "prefill macs"
    assert wl.elec_ops == prefill_elec, "prefill elec"
    wl = _wl(cfg, "train")
    assert wl.total_macs == 3 * prefill_macs, "train macs"
    assert wl.elec_ops == 2 * prefill_elec, "train elec"
    wl = _wl(cfg, "decode")
    assert wl.total_macs == NT * decode_macs, "decode macs"
    assert wl.elec_ops == NT * decode_elec, "decode elec"


def _attn_macs(bt, q_tokens, ctx, d, heads, kv_heads, dh, layers, batch):
    """GQA attention: QKV proj + per-head scores + per-head AV + out."""
    d_q, d_kv = heads * dh, kv_heads * dh
    return (bt * d * (d_q + 2 * d_kv) * layers
            + q_tokens * dh * ctx * layers * batch * heads
            + q_tokens * ctx * dh * layers * batch * heads
            + bt * d_q * d * layers)


def _ffn_macs(bt, d, ff, layers):
    return bt * d * ff * 2 * layers + bt * ff * d * layers


def _elec(bt, d, ff, heads, q_tokens, ctx, batch, layers):
    """Softmax + norms/residual + activation (non-recurrent families)."""
    return (bt * d * 10 * layers
            + batch * heads * q_tokens * ctx * 3 * layers
            + bt * ff * layers)


# ---------------------------------------------------------------------------
# dense (GQA) — and the literal-number anchor for the whole suite.
# ---------------------------------------------------------------------------

DENSE = ModelConfig(name="g-dense", family="dense", n_layers=2, d_model=8,
                    n_heads=2, n_kv_heads=1, d_ff=16, vocab=VOCAB)


def test_dense_family_golden():
    bt = B * S
    pre_macs = (_attn_macs(bt, S, S, 8, 2, 1, 4, 2, B)
                + _ffn_macs(bt, 8, 16, 2) + bt * 8 * VOCAB)
    pre_elec = _elec(bt, 8, 16, 2, S, S, B, 2)
    dec_macs = (_attn_macs(B, 1, CTX, 8, 2, 1, 4, 2, B)
                + _ffn_macs(B, 8, 16, 2) + B * 8 * VOCAB)
    dec_elec = _elec(B, 8, 16, 2, 1, CTX, B, 2)
    # Fully hand-expanded anchors: QKV + scores + AV + out proj +
    # FFN up/gate + FFN down + LM head; norms + softmax + activation.
    assert pre_macs == 2048 + 512 + 512 + 1024 + 4096 + 2048 + 640 == 10880
    assert pre_elec == 1280 + 384 + 256 == 1920
    _check(DENSE, pre_macs, pre_elec, dec_macs, dec_elec)


def test_swa_family_golden():
    # Sliding-window dense: every swa_pattern-th layer global, the rest
    # window-bounded — only the score/AV context changes.
    cfg = dataclasses.replace(DENSE, name="g-swa", sliding_window=2,
                              swa_pattern=2)
    n_global, n_local, w = 1, 1, 2
    bt = B * S

    def attn(bt_, q, ctx):
        return (_attn_macs(bt_, q, min(ctx, w), 8, 2, 1, 4, n_local, B)
                + _attn_macs(bt_, q, ctx, 8, 2, 1, 4, n_global, B))

    pre_macs = attn(bt, S, S) + _ffn_macs(bt, 8, 16, 2) + bt * 8 * VOCAB
    pre_elec = _elec(bt, 8, 16, 2, S, S, B, 2)   # elec model ignores window
    dec_macs = attn(B, 1, CTX) + _ffn_macs(B, 8, 16, 2) + B * 8 * VOCAB
    dec_elec = _elec(B, 8, 16, 2, 1, CTX, B, 2)
    _check(cfg, pre_macs, pre_elec, dec_macs, dec_elec)


# ---------------------------------------------------------------------------
# moe
# ---------------------------------------------------------------------------

def _expert_macs(split, d, d_expert, layers):
    """Routed experts' up+gate and down GEMMs for [(rows, experts)]."""
    return sum(rows * d * d_expert * 2 * layers * n
               + rows * d_expert * d * layers * n for rows, n in split)


# Routing of the 4-expert top-2 goldens (E=4, k=2), worked by hand:
#   prefill/train, T = B*S = 8 rows: 4*(1 - (1/2)^8) = 3.98 -> D = 4,
#     16 slots over 4 experts -> 4 rows each;
#   decode, T = B = 2 rows: 4*(1 - (1/2)^2) = 3 -> D = 3, 4 slots over 3
#     experts -> r = 1, one expert with 2 rows and two with 1.
PREFILL_SPLIT = [(4, 4)]
DECODE_SPLIT = [(2, 1), (1, 2)]


def test_moe_family_golden():
    cfg = ModelConfig(
        name="g-moe", family="moe", n_layers=3, d_model=8, n_heads=2,
        n_kv_heads=2, d_ff=16, vocab=VOCAB,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=8, n_shared=1,
                      d_shared=8, first_dense_layers=1))

    def moe_macs(bt, split):
        n_moe = 2                                  # 3 layers - 1 dense
        return (_ffn_macs(bt, 8, 16, 1)            # leading dense FFN
                + bt * 8 * 4 * n_moe               # router
                + _expert_macs(split, 8, 8, n_moe)  # touched experts
                + bt * 8 * 8 * 2 * n_moe           # shared up+gate
                + bt * 8 * 8 * n_moe)              # shared down

    def elec(bt, q_tokens, ctx):
        # activations: dense d_ff on layer 1, then T*k*d_expert routed
        # plus T*d_shared shared on each of the 2 MoE layers
        return (bt * 8 * 10 * 3 + B * 2 * q_tokens * ctx * 3 * 3
                + bt * 16 * 1 + (bt * 2 * 8 + bt * 8 * 1) * 2)

    bt = B * S
    pre_macs = (_attn_macs(bt, S, S, 8, 2, 2, 4, 3, B)
                + moe_macs(bt, PREFILL_SPLIT) + bt * 8 * VOCAB)
    dec_macs = (_attn_macs(B, 1, CTX, 8, 2, 2, 4, 3, B)
                + moe_macs(B, DECODE_SPLIT) + B * 8 * VOCAB)
    assert elec(bt, S, S) == 1920 + 576 + 128 + 384 == 3008
    _check(cfg, pre_macs, elec(bt, S, S), dec_macs, elec(B, 1, CTX))


# ---------------------------------------------------------------------------
# mla_moe
# ---------------------------------------------------------------------------

MLA_CFG = ModelConfig(
    name="g-mla", family="mla_moe", n_layers=3, d_model=8, n_heads=2,
    d_ff=16, vocab=VOCAB,
    mla=MLAConfig(q_lora_rank=6, kv_lora_rank=5, rope_head_dim=2,
                  nope_head_dim=4, v_head_dim=4),
    moe=MoEConfig(n_experts=4, top_k=2, d_expert=8, first_dense_layers=1))


def test_mla_moe_family_golden():
    cfg = MLA_CFG
    L, H, qd = 3, 2, 4 + 2                         # qd = nope + rope

    def moe_macs(bt, split):
        n_moe = 2
        return (_ffn_macs(bt, 8, 16, 1) + bt * 8 * 4 * n_moe
                + _expert_macs(split, 8, 8, n_moe))

    def elec(bt, q_tokens, ctx):
        return (bt * 8 * 10 * L + B * H * q_tokens * ctx * 3 * L
                + bt * 16 * 1 + bt * 2 * 8 * 2)   # dense, then T*k*d_expert

    bt = B * S
    pre_macs = (bt * 8 * 6 * L + bt * 6 * (H * qd) * L     # Q down/up
                + bt * 8 * (5 + 2) * L                     # KV-latent down
                + bt * 5 * (H * (4 + 4)) * L               # KV up
                + S * qd * S * L * B * H                   # scores
                + S * S * 4 * L * B * H                    # AV
                + bt * (H * 4) * 8 * L                     # out proj
                + moe_macs(bt, PREFILL_SPLIT) + bt * 8 * VOCAB)
    dec_macs = (B * 8 * 6 * L + B * 6 * (H * qd) * L
                + B * 8 * 7 * L                            # KV-latent down
                + B * 4 * 5 * L * H                        # q absorb
                + 1 * 7 * CTX * L * B * H                  # latent scores
                + 1 * CTX * 5 * L * B * H                  # latent AV
                + B * 5 * 4 * L * H                        # V up
                + B * (H * 4) * 8 * L
                + moe_macs(B, DECODE_SPLIT) + B * 8 * VOCAB)
    _check(cfg, pre_macs, elec(bt, S, S), dec_macs, elec(B, 1, CTX))


def test_mla_decode_scores_every_head_against_the_shared_latent():
    # Absorbed MLA (arXiv:2405.04434 §2.1): a sequence's H heads attend
    # one cache of C latents, kv_lora + rope = 7 wide, so each sequence a
    # layer has one (H, 7, CTX) score GEMM and one (H, CTX, kv_lora)
    # context GEMM, not H of M = 1. Batch 3, so M = H = 2 is no batch:
    # counts are L * 3 sequence-layers a step, NT steps.
    wl = _wl(MLA_CFG, "decode", batch=3)
    shapes = {tuple(g[:3]): g[3] for g in wl.gemm_array.tolist()}
    assert shapes[(2, 7, CTX)] == 3 * 3 * NT
    assert shapes[(2, CTX, 5)] == 3 * 3 * NT
    assert (1, 7, CTX) not in shapes and (1, CTX, 5) not in shapes


# ---------------------------------------------------------------------------
# MoE routing, decode off-chip bytes, and DeepSeek-V3 at published sizes
# ---------------------------------------------------------------------------

DSV3_MOE = MoEConfig(n_experts=256, top_k=8, d_expert=2048)


@pytest.mark.parametrize("tokens,touched,split", [
    # T*k = 64 < E: 256*(1 - (31/32)^8) = 256*(1 - 0.77611) = 57.3 -> 57;
    # 64 slots over 57 experts: 7 with 2 rows, 50 with 1.
    (8, 57, [(2, 7), (1, 50)]),
    # T*k = 256 = E: 256*(1 - (31/32)^32) = 256*(1 - 0.36205) = 163.3
    # -> 163; 256 slots: 93 experts with 2 rows, 70 with 1.
    (32, 163, [(2, 93), (1, 70)]),
    # T*k = 1024 > E: 256*(1 - (31/32)^128) = 256*(1 - 0.01718) = 251.6
    # -> 252; 1024 slots: r = 4, 16 experts with 5 rows, 236 with 4.
    (128, 252, [(5, 16), (4, 236)]),
    # one row touches exactly its k experts
    (1, 8, [(1, 8)]),
])
def test_routing_prices_the_experts_a_batch_touches(tokens, touched, split):
    assert experts_touched(DSV3_MOE, tokens) == touched
    assert expert_rows(DSV3_MOE, tokens) == split
    assert sum(r * n for r, n in split) == tokens * 8


def test_decode_reads_the_kv_cache_off_chip():
    # act_io per step = activations in and out (B*d*2 at 4 bits = 16 B)
    # plus the cache the step reads, at 4 bits; decode scales by NT.
    # GQA: B * CTX * layers * 2 * kv_heads * head_dim = 2*8*2*2*1*4.
    assert _wl(DENSE, "decode").act_io_bytes == NT * (16 + 256 * 0.5) == 432
    # Sliding window 2 on one of the two layers: 8 + 2 positions a token.
    swa = dataclasses.replace(DENSE, sliding_window=2, swa_pattern=2)
    assert _wl(swa, "decode").act_io_bytes == NT * (16 + 2 * 10 * 8 * 0.5)
    # MLA: the latent, kv_lora + rope = 7 values a token a layer.
    assert _wl(MLA_CFG, "decode").act_io_bytes == \
        NT * (16 + 2 * 8 * 3 * 7 * 0.5) == 552
    # prefill reads no cache
    assert _wl(DENSE, "prefill").act_io_bytes == B * S * 8 * 2 * 0.5


def test_decode_streams_the_touched_experts_weights():
    cfg = ModelConfig(
        name="g-moe", family="moe", n_layers=3, d_model=8, n_heads=2,
        n_kv_heads=2, d_ff=16, vocab=VOCAB,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=8, n_shared=1,
                      d_shared=8, first_dense_layers=1))
    # params: embeddings 10*8*2 = 160; attention 8*(2+4)*4 + 8*8 = 256 a
    # layer; dense FFN 3*8*16 = 384; MoE FFN 4 experts * 3*8*8 + shared
    # 3*8*8 + router 8*4 = 992 a layer: 160 + (256+384) + 2*(256+992).
    assert cfg.param_count() == 3296
    # decode at B = 2 touches D = 3 of the 4 experts: one expert's 192
    # params idle in each of the 2 MoE layers; 4-bit weights, NT steps.
    assert _wl(cfg, "decode").weight_bytes == NT * (3296 - 2 * 192) * 0.5
    # prefill's 8 rows touch all 4
    assert _wl(cfg, "prefill").weight_bytes == 3296 * 0.5


def test_deepseek_v3_at_published_sizes():
    from repro.configs import get_config
    from repro.core.performance_model import require_i32_dims

    cfg = get_config("deepseek-v3-671b")
    assert abs(cfg.param_count() / 671e9 - 1) < 0.005
    assert abs(cfg.active_param_count() / 37e9 - 1) < 0.02
    wl = workload_for(cfg, ShapeConfig("decode32k", 32768, 32, "decode",
                                       new_tokens=32))
    assert len(wl.gemms) == 18
    require_i32_dims(wl.gemm_array)


def test_deepseek_v3_decode_32k_is_pinned_field_for_field():
    # The kda_mla_moe branch shares `_build`, `_mla_gemms`, `_moe_gemms`
    # and `_kv_cache_bytes` with mla_moe: DeepSeek-V3 at the assigned
    # decode_32k shape (B 128, C 32,768, 32 tokens) keeps every field.
    from repro.configs import SHAPES_BY_NAME, get_config
    from repro.core.workload import Gemm, Workload

    wl = workload_for(get_config("deepseek-v3-671b"),
                      SHAPES_BY_NAME["decode_32k"])
    rows = [(128, 7168, 1536, 1952), (128, 1536, 24576, 1952),
            (128, 7168, 576, 1952), (128, 128, 512, 249856),
            (128, 576, 32768, 249856), (128, 32768, 512, 249856),
            (128, 512, 128, 249856), (128, 16384, 7168, 1952),
            (128, 7168, 18432, 192), (128, 18432, 7168, 96),
            (128, 7168, 256, 1856), (5, 7168, 2048, 59392),
            (4, 7168, 2048, 876032), (5, 2048, 7168, 29696),
            (4, 2048, 7168, 438016), (128, 7168, 2048, 3712),
            (128, 2048, 7168, 1856), (128, 7168, 129280, 32)]
    assert wl == Workload(
        name="deepseek-v3-671b-decode32768b128n32",
        gemms=tuple(Gemm(*g) for g in rows), elec_ops=3166431084544.0,
        weight_bytes=10572929171456.0, act_io_bytes=2357966405632.0,
        max_act_bytes=1376256.0, batch=128)


# ---------------------------------------------------------------------------
# kda_mla_moe (Kimi Linear): KDA layers beside MLA layers
# ---------------------------------------------------------------------------

def _kimi():
    from repro.configs.archs import KIMI_LINEAR_48B
    return KIMI_LINEAR_48B


def test_kimi_linear_decode_at_published_widths():
    # One decode step at long_500k (B 1, C 524,288), 32 steps. Layers:
    # 20 KDA (H_k 32, d_k = d_v 128, conv 4), 7 MLA (H 32, no q-LoRA,
    # nope 128, rope 64, kv_lora 512, v 128); d 2,304; layer 1 dense
    # FFN 9,216; 26 MoE layers of 256 experts 1,024 wide, top-8, 1 shared.
    from repro.configs import SHAPES_BY_NAME

    d, C, T = 2304, 524288, 32
    Lm, H, Lk, Hk, dk = 7, 32, 20, 32, 128
    hk = Hk * dk
    wl = workload_for(_kimi(), SHAPES_BY_NAME["long_500k"])
    mla = (d * H * 192 * Lm                        # full-rank query
           + d * 576 * Lm                          # KV down
           + 128 * 512 * Lm * H                    # W_UK, per head
           + H * 576 * C * Lm                      # scores, per sequence
           + H * C * 512 * Lm                      # context
           + 512 * 128 * Lm * H                    # W_UV
           + H * 128 * d * Lm)                     # output
    kda = (d * 3 * hk * Lk                         # q, k, v
           + d * (2 * dk + Hk) * Lk                # gate downs, beta
           + dk * hk * 2 * Lk                      # gate ups
           + 2 * dk * dk * Lk * Hk                 # [k; q] against S'
           + hk * d * Lk)                          # output
    ffn = (d * 9216 * 3                            # dense layer 1
           + d * 256 * 26                          # router
           + d * 1024 * 3 * 8 * 26                 # B k = 8 slots, 8 experts
           + d * 1024 * 3 * 26                     # shared
           + d * 163840)                           # head
    assert mla + kda + ffn == 130902016000
    assert wl.total_macs == T * 130902016000
    assert len(wl.gemms) == 7 + 5 + 2 + 5 + 1 == 20
    # electronic: 10 d-wide passes x 27 layers; softmax over the 7 MLA
    # layers; KDA conv 4*3*4096 + decay and update 2*4096*128 + gates and
    # norm 3*4096 over 20 layers; activations 9,216 + (8 + 1) * 1,024 x 26
    elec = (d * 10 * 27 + H * C * 3 * Lm
            + (4 * 3 * hk + 2 * hk * dk + 3 * hk) * Lk
            + 9216 + 9 * 1024 * 26)
    assert elec == 622080 + 352321536 + 22200320 + 9216 + 239616
    assert wl.elec_ops == T * elec
    # weights: embeddings 754,974,720; MLA 29,114,368 x 7; KDA 39,510,016
    # x 20; dense FFN 63,700,992; each MoE layer 8 touched experts,
    # the shared one and the router, 64,290,816 x 26; 4-bit
    params = (2 * 163840 * d + 29114368 * 7 + 39510016 * 20 + 63700992
              + 64290816 * 26)
    assert params == 3484237824
    assert wl.weight_bytes == T * params * 0.5
    # off chip besides weights: activations 2 x 2,304; the latent cache
    # of the 7 MLA layers only, 576 values a position; KDA state, 32 x
    # 128 x 128 plus 3 x 3 x 4,096 conv values a layer, read and written
    cache = C * Lm * 576 * 0.5
    state = 2 * Lk * (Hk * dk * dk + 3 * 3 * hk) * 0.5
    assert (cache, state) == (1056964608, 11223040)
    assert wl.act_io_bytes == T * (d * 2 * 0.5 + cache + state)
    assert wl.max_act_bytes == 9216 * 0.5


def test_kimi_linear_params():
    cfg = _kimi()
    assert cfg.n_kda_layers == 20 and cfg.supports_long_context
    # 3.48 B active of 49.1 B (published: 48B total, 3B active)
    assert cfg.param_count() == 49122459648
    assert cfg.active_param_count() == 3484237824


def test_kda_family_with_no_kda_layer_lowers_as_mla_moe():
    from repro.configs.base import LinearAttnConfig

    hybrid = dataclasses.replace(MLA_CFG, family="kda_mla_moe",
                                 linear_attn=LinearAttnConfig(
                                     kda_layers=(), n_heads=2, head_dim=4))
    for kind in ("decode", "prefill", "train"):
        assert _wl(hybrid, kind) == _wl(MLA_CFG, kind), kind
    assert hybrid.param_count() == MLA_CFG.param_count()


def test_small_kda_hybrid_golden():
    # 3 layers: KDA, KDA, MLA (MLA_CFG's widths); KDA H_k 2, d_k 4,
    # conv 3. B = 2, CTX = 8, NT = 3 steps.
    from repro.configs.base import LinearAttnConfig

    cfg = dataclasses.replace(MLA_CFG, family="kda_mla_moe",
                              linear_attn=LinearAttnConfig(
                                  kda_layers=(1, 2), n_heads=2, head_dim=4,
                                  conv_kernel=3))
    wl = _wl(cfg, "decode")
    H, qd = 2, 6
    mla = (B * 8 * 6 + B * 6 * (H * qd) + B * 8 * 7 + B * 4 * 5 * H
           + H * 7 * CTX * B + H * CTX * 5 * B + B * 5 * 4 * H
           + B * (H * 4) * 8)                      # one MLA layer
    kda = 2 * (B * 8 * 24 + B * 8 * (8 + 2) + 2 * B * 4 * 8
               + 2 * 4 * 4 * B * 2 + B * 8 * 8)    # two KDA layers
    moe = (B * 8 * 16 * 3                          # dense FFN, layer 1
           + 2 * (B * 8 * 4                        # router, 2 MoE layers
                  + 3 * 8 * 8 * (2 * 1 + 1 * 2)))  # DECODE_SPLIT's rows
    assert wl.total_macs == NT * (mla + kda + moe + B * 8 * VOCAB)
    elec = (B * 8 * 10 * 3 + B * H * CTX * 3 * 1
            + B * (3 * 3 * 8 + 2 * 8 * 4 + 3 * 8) * 2
            + B * 16 + B * 2 * 8 * 2)
    assert wl.elec_ops == NT * elec
    cache = B * CTX * 1 * 7 * 0.5
    state = 2 * B * 2 * (8 * 4 + 2 * 3 * 8) * 0.5
    assert wl.act_io_bytes == NT * (B * 8 * 2 * 0.5 + cache + state)


@pytest.mark.parametrize("kind", ["prefill", "train"])
def test_kda_prefill_and_train_raise(kind):
    from repro.configs import SHAPES_BY_NAME

    shape = SHAPES_BY_NAME["prefill_32k" if kind == "prefill"
                           else "train_4k"]
    with pytest.raises(ValueError, match="chunked KDA form"):
        workload_for(_kimi(), shape)


def test_kimi_linear_long_500k_fits_the_int32_kernels():
    from repro.configs import SHAPES_BY_NAME
    from repro.core.performance_model import require_i32_dims

    wl = workload_for(_kimi(), SHAPES_BY_NAME["long_500k"])
    assert int(wl.gemm_array[:, :3].max()) == 524288
    require_i32_dims(wl.gemm_array)


# ---------------------------------------------------------------------------
# hybrid_ssm
# ---------------------------------------------------------------------------

def test_hybrid_ssm_family_golden():
    cfg = ModelConfig(
        name="g-ssm", family="hybrid_ssm", n_layers=4, d_model=8,
        n_heads=2, n_kv_heads=2, d_ff=16, vocab=VOCAB,
        ssm=SSMConfig(d_state=4, d_conv=4, expand=2, head_dim=4, chunk=2,
                      attn_every=2))
    L, d_in, nh, shared = 4, 16, 4, 2              # shared = L // attn_every
    proj_out = 2 * d_in + 2 * 4 + nh               # x/z + B/C + dt heads

    def mamba_macs(bt):
        return bt * 8 * proj_out * L + bt * d_in * 8 * L

    def ssd_macs(bt, q_tokens):                    # prefill/train only
        nch = max(1, q_tokens // 2)
        return (2 * 4 * 2 * L * B * nch            # C B^T per chunk
                + 2 * 2 * d_in * L * B * nch)      # score-weighted values

    def elec(bt, layers):
        return (bt * 8 * 10 * layers
                + bt * nh * 4 * 4 // 2 * 3 * layers  # inter-chunk scan
                + bt * d_in * 2 * layers)            # conv + gates

    bt = B * S
    pre_macs = (mamba_macs(bt) + ssd_macs(bt, S)
                + _attn_macs(bt, S, S, 8, 2, 2, 4, shared, B)
                + _ffn_macs(bt, 8, 16, shared) + bt * 8 * VOCAB)
    dec_macs = (mamba_macs(B)                      # decode: recurrence only
                + _attn_macs(B, 1, CTX, 8, 2, 2, 4, shared, B)
                + _ffn_macs(B, 8, 16, shared) + B * 8 * VOCAB)
    _check(cfg, pre_macs, elec(bt, L), dec_macs, elec(B, L))


# ---------------------------------------------------------------------------
# rwkv
# ---------------------------------------------------------------------------

RWKV = ModelConfig(name="g-rwkv", family="rwkv", n_layers=2, d_model=8,
                   n_heads=2, d_ff=16, vocab=VOCAB)


def test_rwkv_family_golden():
    L = 2

    def macs(bt):
        return (bt * 8 * 8 * 5 * L                 # r/k/v/g/out projections
                + bt * 8 * 64 * L + bt * 64 * 8 * L   # decay LoRA
                + bt * 8 * 16 * L + bt * 16 * 8 * L   # channel mix k/v
                + bt * 8 * 8 * L                      # channel mix r
                + bt * 8 * VOCAB)

    def elec(bt):
        return (bt * 8 * 10 * L
                + bt * 2 * 4 * 4 * 3 * L           # WKV state update
                + bt * 16)

    _check(RWKV, macs(B * S), elec(B * S), macs(B), elec(B))


# ---------------------------------------------------------------------------
# encdec
# ---------------------------------------------------------------------------

def test_encdec_family_golden():
    cfg = ModelConfig(name="g-ed", family="encdec", n_layers=3,
                      enc_layers=2, dec_layers=1, d_model=8, n_heads=2,
                      n_kv_heads=2, d_ff=16, vocab=VOCAB)
    bt = B * S
    src, tgt = S // 2, S - S // 2                  # prefill split
    pre_macs = (_attn_macs(B * src, src, src, 8, 2, 2, 4, 2, B)  # encoder
                + _ffn_macs(B * src, 8, 16, 2)
                + _attn_macs(B * tgt, tgt, tgt, 8, 2, 2, 4, 1, B)  # dec self
                + tgt * 4 * src * 1 * B * 2        # cross scores
                + tgt * src * 4 * 1 * B * 2        # cross AV
                + _ffn_macs(B * tgt, 8, 16, 1)
                + bt * 8 * VOCAB)
    pre_elec = _elec(bt, 8, 16, 2, S, S, B, 3)     # enc + dec depth
    d_src = CTX // 2                               # decode: cross-KV ctx
    dec_macs = (_attn_macs(B, 1, CTX, 8, 2, 2, 4, 1, B)
                + 1 * 4 * d_src * 1 * B * 2
                + 1 * d_src * 4 * 1 * B * 2
                + _ffn_macs(B, 8, 16, 1)
                + B * 8 * VOCAB)
    dec_elec = _elec(B, 8, 16, 2, 1, CTX, B, 3)
    _check(cfg, pre_macs, pre_elec, dec_macs, dec_elec)


# ---------------------------------------------------------------------------
# vlm
# ---------------------------------------------------------------------------

def test_vlm_family_golden():
    P = 3
    cfg = ModelConfig(name="g-vlm", family="vlm", n_layers=2, d_model=8,
                      n_heads=2, n_kv_heads=2, d_ff=16, vocab=VOCAB,
                      n_prefix_embeds=P)
    # Prefix embeddings are real positions: prefill runs seq+P tokens
    # through every layer; decode attends a CTX+P context.
    sp, bt = S + P, B * (S + P)
    pre_macs = (_attn_macs(bt, sp, sp, 8, 2, 2, 4, 2, B)
                + _ffn_macs(bt, 8, 16, 2) + bt * 8 * VOCAB)
    pre_elec = _elec(bt, 8, 16, 2, sp, sp, B, 2)
    dec_macs = (_attn_macs(B, 1, CTX + P, 8, 2, 2, 4, 2, B)
                + _ffn_macs(B, 8, 16, 2) + B * 8 * VOCAB)
    dec_elec = _elec(B, 8, 16, 2, 1, CTX + P, B, 2)
    _check(cfg, pre_macs, pre_elec, dec_macs, dec_elec)


# ---------------------------------------------------------------------------
# Bugfix regression: _elec_ops must scale with its `layers` argument.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", [1, 3])
def test_elec_ops_rwkv_scales_with_layers_argument(layers):
    # n_layers=7 never equals the passed depth, so the pre-fix aliasing
    # (WKV term scaled by cfg.n_layers) yields 7x the recurrence cost of
    # the depth actually requested — these equalities fail pre-fix.
    cfg = dataclasses.replace(RWKV, n_layers=7)
    bt = B * S
    expected = (bt * 8 * 10 * layers + bt * 2 * 4 * 4 * 3 * layers
                + bt * 16)
    assert _elec_ops(cfg, S, bt, B, layers) == expected


@pytest.mark.parametrize("layers", [1, 3])
def test_elec_ops_hybrid_ssm_scales_with_layers_argument(layers):
    cfg = ModelConfig(
        name="g-ssm7", family="hybrid_ssm", n_layers=7, d_model=8,
        d_ff=16, ssm=SSMConfig(d_state=4, expand=2, head_dim=4, chunk=2,
                               attn_every=2))
    bt, d_in, nh = B * S, 16, 4
    expected = (bt * 8 * 10 * layers
                + bt * nh * 4 * 4 // 2 * 3 * layers
                + bt * d_in * 2 * layers)
    assert _elec_ops(cfg, S, bt, B, layers) == expected
