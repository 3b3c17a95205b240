"""Pallas TPU kernel: fused (flash) attention forward.

Online-softmax tiling (FlashAttention, arXiv:2205.14135) adapted to TPU:
the (Sq, Skv) score matrix never materializes in HBM — Q blocks stay
resident in VMEM while K/V blocks stream through the innermost grid axis,
carrying running max/denominator in VMEM scratch. Block shapes are
MXU-aligned (128 lanes).

This is the attention analogue of the DDot GEMM mapping in DESIGN.md §3:
the transformer stack's second compute hot-spot after the projections.
Supports causal and bidirectional masking; GQA is handled in ops.py by
folding the group into the batch. Validated against ref.flash_attention_ref
in interpret mode (tests/test_flash_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(causal: bool, scale: float, nk: int, bq: int, bk: int,
                  q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: whole block strictly above the diagonal contributes nothing
    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, bq: int = 128,
                         bk: int = 128, interpret: Optional[bool] = None):
    """q, k, v: (BH, S, D) same-length self-attention -> (BH, S, D).

    S must be a multiple of the block sizes (ops.flash_attention pads).
    """
    bh, sq, d = q.shape
    skv = k.shape[1]
    assert sq % bq == 0 and skv % bk == 0
    grid = (bh, sq // bq, skv // bk)
    scale = d ** -0.5
    kernel = functools.partial(_flash_kernel, causal, scale, grid[2], bq, bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
