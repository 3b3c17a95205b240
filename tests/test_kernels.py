"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode on CPU).

Sweeps shapes (including non-block-multiples) and dtypes per the kernel
deliverable requirements.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover — CI images without hypothesis
    from _hypothesis_fallback import given, settings, st

from repro.core import Constraints, grid_search_vectorized
from repro.core.paper_workloads import load
from repro.core.performance_model import _ceil_div as _ceil_div_exact
from repro.core.performance_model import workload_statics
from repro.core.photonic_model import CONSTANTS
from repro.kernels import (ddot_matmul, ddot_matmul_ref, dse_eval_grid,
                           dse_eval_ref, dse_search_grid, dse_search_multi,
                           dse_search_ref, pallas_grid_search,
                           photonic_matmul, quantize4)
from repro.kernels.ddot_gemm import ddot_gemm_quantized
from repro.kernels.dse_eval import BLOCK, _ceil_div, dse_eval_padded


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


SHAPES = [
    (8, 16, 8),        # tiny
    (128, 128, 128),   # exactly one block
    (100, 200, 60),    # nothing divides the blocks
    (256, 512, 384),   # multiple blocks each axis
    (33, 1000, 257),   # prime-ish
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ddot_matches_ref_shapes_dtypes(m, k, n, dtype):
    a = _rand((m, k), dtype, 1)
    b = _rand((k, n), dtype, 2)
    out = ddot_matmul(a, b, bm=64, bn=128, bk=128)
    ref = ddot_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ddot_noise_matches_ref_same_draws():
    # Drive the raw kernel with an explicit z so the noise path is also
    # bit-comparable against the oracle formula.
    m, k, n = 64, 256, 128
    a = _rand((m, k), jnp.float32, 3)
    b = _rand((k, n), jnp.float32, 4)
    qa, sa = quantize4(a, axis=1)
    qb, sb = quantize4(b, axis=0)
    z = _rand((m, n), jnp.float32, 5)
    out = ddot_gemm_quantized(qa.astype(jnp.bfloat16), qb.astype(jnp.bfloat16),
                              sa, sb, z, bm=64, bn=128, bk=128,
                              noise_rms=0.1)
    ref = ddot_matmul_ref(a, b, noise_rms=0.1, z=z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ddot_quantization_error_bounded():
    # 4-bit per-channel quantization: relative Frobenius error of the
    # simulated GEMM vs the fp32 GEMM should be bounded (~1/QMAX scale).
    a = _rand((128, 512), jnp.float32, 6)
    b = _rand((512, 128), jnp.float32, 7)
    out = ddot_matmul(a, b)
    exact = a @ b
    rel = jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact)
    assert float(rel) < 0.25  # ~0.19 observed: typical W4A4 on N(0,1) data


def test_photonic_matmul_ste_gradients():
    a = _rand((32, 64), jnp.float32, 8)
    b = _rand((64, 16), jnp.float32, 9)

    def loss(a, b):
        return jnp.sum(photonic_matmul(a, b) ** 2)

    ga, gb = jax.grad(loss, argnums=(0, 1))(a, b)
    # STE: gradient equals the full-precision backward applied to the
    # (quantized) forward output.
    out = photonic_matmul(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(2 * out @ b.T),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(2 * a.T @ out),
                               rtol=1e-4, atol=1e-4)
    assert not np.any(np.isnan(ga)) and not np.any(np.isnan(gb))


def test_quantize4_properties():
    x = _rand((17, 33), jnp.float32, 10)
    q, s = quantize4(x, axis=1)
    assert float(jnp.max(jnp.abs(q))) <= 7.0
    np.testing.assert_allclose(np.asarray(q), np.round(np.asarray(q)))
    # zero rows get scale 1.0, not NaN
    q0, s0 = quantize4(jnp.zeros((4, 8)), axis=1)
    assert np.all(np.asarray(s0) == 1.0) and np.all(np.asarray(q0) == 0.0)


@pytest.mark.parametrize("wname", ["deit-t", "bert-l"])
@pytest.mark.parametrize("gsize", [7, 300, 2048, 5000])
def test_dse_kernel_matches_ref(wname, gsize):
    wl = load(wname)
    rng = np.random.default_rng(gsize)
    grid = rng.integers(1, 13, size=(gsize, 5))
    out = dse_eval_grid(grid, wl)
    ref = dse_eval_ref(grid, wl)
    np.testing.assert_allclose(out, ref, rtol=3e-4)


def test_pallas_grid_search_agrees_with_core():
    wl = load("deit-s")
    rng = np.random.default_rng(0)
    grid = np.unique(rng.integers(1, 13, size=(4000, 5)), axis=0)
    cons = Constraints()
    best, _ = pallas_grid_search(grid, wl, cons)
    ref = grid_search_vectorized(wl, cons, grid=grid)
    assert best == ref.best_cfg


@given(st.integers(1, 2**31 - 4096), st.integers(1, 4095))
@settings(max_examples=200, deadline=None)
def test_kernel_ceil_div_exact_for_large_dims(a, b):
    # The old float formulation floor((a + b - 1.0) / b) drifts once
    # a + b - 1 exceeds the 24-bit float32 mantissa; the int32 form must
    # match the reference integer ceil-division everywhere.
    got = int(_ceil_div(float(a), jnp.float32(b)))
    assert got == _ceil_div_exact(a, b, np)


def test_kernel_ceil_div_regression_example():
    # Concrete drift case: 2**24 + 1 is not float32-representable, so the
    # old floor((a + b - 1.0) / b) path loses it; the int path must not.
    a, b = 2**24 + 1, 1
    assert int(_ceil_div(float(a), jnp.float32(b))) == a
    old = float(jnp.floor((jnp.float32(a) + b - 1.0) / b))
    assert old != a  # documents why the fix exists


@pytest.mark.parametrize("gsize", [5, BLOCK - 3, BLOCK, BLOCK + 17])
def test_dse_eval_padded_arbitrary_sizes(gsize):
    # Direct wrapper call (no ops.py pre-padding): any G must work and the
    # mask/trim must keep padding out of the result.
    wl = load("deit-t")
    rng = np.random.default_rng(gsize)
    grid = rng.integers(1, 13, size=(gsize, 5))
    gemms, wl_scalars = workload_statics(wl, CONSTANTS)
    out = dse_eval_padded(jnp.asarray(grid.T, jnp.float32), gemms=gemms,
                          wl_scalars=wl_scalars, constants=CONSTANTS)
    assert out.shape == (4, gsize)
    np.testing.assert_allclose(np.asarray(out).T, dse_eval_ref(grid, wl),
                               rtol=3e-4)


@pytest.mark.parametrize("wname", ["deit-t", "bert-l"])
@pytest.mark.parametrize("gsize", [40, 2048, 5000])
def test_dse_search_kernel_matches_ref(wname, gsize):
    wl = load(wname)
    rng = np.random.default_rng(gsize)
    grid = rng.integers(1, 13, size=(gsize, 5))
    cons = Constraints()
    i, edp, nf = dse_search_grid(grid, wl, cons)
    assert (i, nf) == dse_search_ref(grid, wl, cons)
    assert np.isfinite(edp) == (nf > 0)


def test_dse_search_kernel_zero_feasible():
    wl = load("deit-b")
    grid = np.random.default_rng(0).integers(1, 13, size=(300, 5))
    impossible = Constraints(area_mm2=0.1, power_w=0.001)
    i, edp, nf = dse_search_grid(grid, wl, impossible)
    assert (i, nf) == (-1, 0)
    assert edp == float("inf")


def test_dse_search_multi_single_launch_matches_per_workload():
    wls = [load(n) for n in ("deit-t", "deit-b", "bert-b")]
    cons = [Constraints(), Constraints(power_w=3.0), Constraints()]
    grid = np.random.default_rng(1).integers(1, 13, size=(3000, 5))
    best, _, nf = dse_search_multi(grid, wls, cons)
    for w, (wl, cc) in enumerate(zip(wls, cons)):
        assert (best[w], nf[w]) == dse_search_ref(grid, wl, cc)


@pytest.mark.parametrize("gsize", [40, 2048, 5000])
def test_dse_pareto_kernel_candidates_cover_frontier(gsize):
    # The kernel's per-block reduction must return a candidate superset of
    # the true frontier (and the exact feasible count); refining the
    # candidates through the float64 oracle reproduces the frontier.
    from repro.core.pareto import pareto_mask
    from repro.core.search import evaluate_grid
    from repro.kernels import dse_pareto_multi, dse_pareto_ref

    wl = load("deit-t")
    cons = Constraints()
    grid = np.random.default_rng(gsize).integers(1, 13, size=(gsize, 5))
    (cand, nf, _), = dse_pareto_multi(grid, [wl], [cons])
    front_ref, nf_ref = dse_pareto_ref(grid, wl, cons)
    assert nf == nf_ref
    rows = np.asarray(grid)[cand]
    m = evaluate_grid(rows, wl, xp=np)
    ok = np.asarray(cons.satisfied(m["area"], m["power"], m["energy"],
                                   m["latency"]))
    pts = np.stack([np.asarray(m[k], np.float64)[ok]
                    for k in ("area", "power", "edp")], axis=1)
    refined = rows[ok][pareto_mask(pts)]
    refined = refined[np.lexsort(refined.T[::-1])]
    assert np.array_equal(refined, front_ref)


# ---------------------------------------------------------------------------
# Interpret mode follows the backend; the compile cache follows the env
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_backend(monkeypatch):
    from repro.kernels import backend
    assert backend.resolve_interpret() is True       # this suite: CPU
    assert backend.resolve_interpret(True) is True
    assert backend.resolve_interpret(False) is False  # described-TPU compile
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    assert backend.resolve_interpret() is False
    assert backend.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode was requested"):
        backend.resolve_interpret(True)


def test_no_public_entry_point_defaults_to_interpret():
    import importlib
    import inspect

    from repro.serve import SearchService
    search, pareto, sweep, dse_eval, ops = (
        importlib.import_module(m) for m in (
            "repro.core.search", "repro.core.pareto", "repro.scenarios.sweep",
            "repro.kernels.dse_eval", "repro.kernels.ops"))
    fns = [search.search, search.search_workloads, search.dxpta_search,
           pareto.pareto_front, pareto.pareto_search_refined, sweep.sweep,
           SearchService.__init__]
    fns += [f for m in (ops, dse_eval) for _, f in inspect.getmembers(m)
            if callable(f) and getattr(f, "__module__", "") == m.__name__]
    checked = 0
    for f in fns:
        p = inspect.signature(f).parameters.get("interpret")
        if p is not None and p.default is not inspect.Parameter.empty:
            assert p.default is None, f
            checked += 1
    assert checked >= 20


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    from repro.launch import compile_cache
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(compile_cache.REPO_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
        jax.config.update("jax_compilation_cache_dir", want)  # JAX's own read
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_enable_compilation_cache
        assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
        assert compile_cache.REPO_CACHE_DIR.parent == \
            compile_cache.Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_enable_compilation_cache", before[1])
