"""Flash-attention kernel vs plain-softmax oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ops import flash_attention
from repro.kernels.ref import flash_attention_ref


def _rand(shape, dtype, seed):
    return jax.random.normal(jax.random.key(seed), shape).astype(dtype)


@pytest.mark.parametrize("s,d,bq,bk", [
    (128, 64, 128, 128),     # single block
    (256, 64, 128, 128),     # multi-block, diagonal skipping
    (384, 128, 128, 128),    # 3 blocks, wider head
    (256, 64, 64, 32),       # uneven block shapes
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(s, d, bq, bk, causal):
    q = _rand((4, s, d), jnp.float32, 1)
    k = _rand((4, s, d), jnp.float32, 2)
    v = _rand((4, s, d), jnp.float32, 3)
    out = flash_attention_bhsd(q, k, v, causal=causal, bq=bq, bk=bk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_dtypes(dtype, tol):
    q = _rand((2, 128, 64), dtype, 4)
    k = _rand((2, 128, 64), dtype, 5)
    v = _rand((2, 128, 64), dtype, 6)
    out = flash_attention_bhsd(q, k, v, causal=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)


def test_flash_wrapper_gqa_and_padding():
    # (B, S, H, D) wrapper: 16 q heads, 4 kv heads, non-block-multiple seq
    b, s, hq, hkv, d = 2, 100, 8, 2, 64
    q = _rand((b, s, hq, d), jnp.float32, 7)
    k = _rand((b, s, hkv, d), jnp.float32, 8)
    v = _rand((b, s, hkv, d), jnp.float32, 9)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    # reference via repeat + per-head oracle
    kr = jnp.repeat(k, hq // hkv, axis=2)
    vr = jnp.repeat(v, hq // hkv, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kb = kr.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    vb = vr.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    ref = flash_attention_ref(qb, kb, vb, causal=True)
    ref = ref.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bidirectional_padding_guard():
    q = _rand((1, 100, 4, 64), jnp.float32, 0)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, causal=False, bq=64, bk=64)
