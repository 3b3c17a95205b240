"""Pallas TPU kernel: photonic DDot-array GEMM simulation.

The LT DPTC core computes, per photonic cycle, an (N_h x N_lambda) x
(N_lambda x N_v) partial GEMM via coherent interference — structurally a
systolic-array pass. This kernel is the TPU-native adaptation (DESIGN.md
Sec. 3): the *logical* loop mirrors the optical dataflow (M chunks -> tiles,
N chunks -> DDot columns, K chunks -> wavelengths), while the *physical*
BlockSpec tiling is MXU-aligned (multiples of 128 on the trailing dims).

Functional semantics (bit-faithful to a 4-bit dynamically-operated PTA):
  * both operands are symmetric-4-bit quantized per row-of-A / column-of-B
    (full-range dynamic encoding — the DPTC property),
  * the integer products accumulate exactly (photocurrent accumulation),
  * optional coherent shot noise: sigma proportional to sqrt(optical power),
    modeled as noise_rms * sqrt(|qA| @ |qB|) in quantized units.

Quantized values are carried in bfloat16 (ints <= 7 are exact) and
accumulated via the MXU in float32 — so the no-noise kernel is *exact*
vs the integer reference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

QMAX = 7.0  # symmetric 4-bit: values in [-7, 7]


def _ddot_kernel(noise_rms: float, nk: int,
                 qa_ref, qb_ref, sa_ref, sb_ref, z_ref, out_ref,
                 acc_ref, pow_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if noise_rms > 0.0:
            pow_ref[...] = jnp.zeros_like(pow_ref)

    a = qa_ref[...]
    b = qb_ref[...]
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)
    if noise_rms > 0.0:
        pow_ref[...] += jnp.dot(jnp.abs(a), jnp.abs(b),
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if noise_rms > 0.0:
            acc = acc + noise_rms * jnp.sqrt(pow_ref[...]) * z_ref[...]
        out_ref[...] = acc * sa_ref[...] * sb_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "noise_rms",
                                             "interpret"))
def ddot_gemm_quantized(qa, qb, sa, sb, z, *, bm=256, bn=256, bk=512,
                        noise_rms: float = 0.0, interpret: Optional[bool] = None):
    """Blocked quantized GEMM on pre-quantized operands.

    Args:
      qa: (M, K) bfloat16, integer values in [-QMAX, QMAX].
      qb: (K, N) bfloat16, same.
      sa: (M, 1) float32 dequant scale per row of A.
      sb: (1, N) float32 dequant scale per column of B.
      z:  (M, N) float32 standard-normal draws (ignored if noise_rms == 0).
    Returns:
      (M, N) float32 ~= (qa*sa) @ (qb*sb) (+ shot noise).
    """
    m, kdim = qa.shape
    _, n = qb.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        "operands must be padded to block multiples (ops.ddot_matmul does this)"
    grid = (m // bm, n // bn, kdim // bk)
    kernel = functools.partial(_ddot_kernel, float(noise_rms), grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(qa, qb, sa, sb, z)
