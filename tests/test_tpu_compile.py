"""Compile the DSE kernels for a described TPU v5e with Mosaic.

Interpret mode runs the kernel bodies as plain jax ops and accepts what
the chip's compiler refuses (1-lane blocks, 1-D gathers, sorts, dynamic
indices). These tests lower each search-path kernel at the launch shapes
`chip_smoke.py` drives — deit-b and bert-l statics over the 20^5 space and
the 12^5 grid-operand bucket — with `interpret=False` against a described
`v5e:2x2` topology, so a kernel the chip cannot compile fails here without
a chip. Nothing runs; results are pinned by the interpret-mode suites.

The topology is described inside a module-scoped fixture (never at import
or collection time): only the worker that runs this file loads the TPU
compiler library.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.paper_workloads import load
from repro.core.performance_model import workload_statics
from repro.core.photonic_model import CONSTANTS
from repro.kernels import dse_eval as K

R20 = (20,) * 5
OBJECTIVES = ("area", "power", "edp")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_v5e(one_chip, no_compile_cache):
    """compile_v5e(fn, *(shape, dtype)) -> the compiled executable."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()
    return run


def _workloads(name):
    return (workload_statics(load(name), CONSTANTS),)


def _f32(*shape):
    return shape, jnp.float32


def _custom_call(compiled, name: str) -> bool:
    """The compiled program runs a Mosaic custom call named `name` (the
    kernel's stable `pallas_call` name, which the device trace shows)."""
    return any(line.lstrip().removeprefix("ROOT ").startswith(f"%{name}")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in compiled.as_text().splitlines())


META = ((1, K.META_COLS), jnp.int32)
AXES_20 = _f32(5, 20)


@pytest.mark.parametrize("name", ["deit-b", "bert-l"])
def test_dse_eval_padded_compiles(compile_v5e, name):
    (gemms, wl_scalars), = _workloads(name)
    fn = functools.partial(K.dse_eval_padded, gemms=gemms,
                           wl_scalars=wl_scalars, constants=CONSTANTS,
                           interpret=False)
    assert _custom_call(compile_v5e(fn, _f32(5, 16 * K.BLOCK)),
                        "dse_eval_padded")


def test_dse_search_padded_compiles(compile_v5e):
    # Branch-and-bound's fine survivors: one 8-block grid-operand bucket.
    g = 8 * K.BLOCK
    fn = functools.partial(K.dse_search_padded, workloads=_workloads("deit-b"),
                           constants=CONSTANTS, interpret=False)
    assert _custom_call(
        compile_v5e(fn, _f32(5, g), _f32(1, g), _f32(1, 4), _f32(1, 1)),
        "dse_search_padded")


def _search_decoded():
    from repro.kernels.ops import SEARCH_TABLE_ROWS

    fn = functools.partial(K.dse_search_decoded, radices=(24,) * 5,
                           workloads=_workloads("deit-b"),
                           constants=CONSTANTS, interpret=False)
    return fn, ((SEARCH_TABLE_ROWS, K.META_COLS), jnp.int32)


def test_dse_search_decoded_compiles(compile_v5e):
    # Branch-and-bound leaf batches and single spans over 24^5 with DeiT-B
    # statics: one meta table of SEARCH_TABLE_ROWS rows, the grid as long
    # as its live rows.
    fn, table = _search_decoded()
    assert _custom_call(compile_v5e(fn, _f32(5, 24), table, _f32(1, 4),
                                    _f32(1, 1)), "dse_search_decoded")


def test_dse_search_decoded_compiles_sharded(topo, no_compile_cache):
    # The `shard=` fan-out: each chip of the 2x2 mesh runs the live rows of
    # its own share of the table.
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import CANDIDATE_AXIS, candidate_spec

    mesh = Mesh(np.array(topo.devices), (CANDIDATE_AXIS,))
    body, ((rows, cols), dtype) = _search_decoded()
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, None), candidate_spec(2, 0),
                                 P(None, None), P(None, None)),
                       out_specs=candidate_spec(2, 1), check_vma=False)

    def arg(shape, dtype=jnp.float32, spec=P(None, None)):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    compiled = jax.jit(fn).lower(
        arg((5, 24)), arg((mesh.size * rows, cols), dtype,
                          candidate_spec(2, 0)),
        arg((1, 4)), arg((1, 1))).compile()
    assert _custom_call(compiled, "dse_search_decoded")


def test_dse_pareto_padded_compiles(compile_v5e):
    # The materialized 12^5 grid, bucketed to 128 blocks, with a carry.
    g = 128 * K.BLOCK
    fn = functools.partial(K.dse_pareto_padded,
                           workloads=_workloads("bert-l"),
                           objectives=OBJECTIVES, has_carry=True,
                           constants=CONSTANTS, interpret=False)
    assert _custom_call(
        compile_v5e(fn, _f32(5, g), _f32(1, g), _f32(1, 4),
                    _f32(K.CARRY_FRONT, len(OBJECTIVES))),
        "dse_pareto_padded")


def test_dse_pareto_decoded_compiles(compile_v5e):
    # The whole 20^5 space in one launch: 2048 blocks of BLOCK lanes.
    fn = functools.partial(K.dse_pareto_decoded, radices=R20, n_blocks=2048,
                           workloads=_workloads("bert-l"),
                           objectives=OBJECTIVES, has_carry=False,
                           constants=CONSTANTS, interpret=False)
    assert _custom_call(
        compile_v5e(fn, AXES_20, META, _f32(1, 4),
                    _f32(K.CARRY_FRONT, len(OBJECTIVES))),
        "dse_pareto_decoded")


def test_dse_decode_rows_compiles(compile_v5e):
    fn = functools.partial(K.dse_decode_rows, radices=R20, n_blocks=4,
                           interpret=False)
    assert _custom_call(compile_v5e(fn, AXES_20, META), "dse_decode_rows")


def test_dse_pareto_decoded_compiles_deepseek_v3_decode(compile_v5e):
    # The DeepSeek-V3 decode cell's launch: 18 GEMM rows over all of 24^5.
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.extract import workload_for
    from repro.kernels.ops import _bucket_blocks

    wl = workload_for(get_config("deepseek-v3-671b"),
                      ShapeConfig("d", 32768, 32, "decode", new_tokens=32))
    assert len(wl.gemms) == 18
    fn = functools.partial(
        K.dse_pareto_decoded, radices=(24,) * 5,
        n_blocks=_bucket_blocks(24 ** 5, floor=8, block=K.BLOCK),
        workloads=(workload_statics(wl, CONSTANTS),), objectives=OBJECTIVES,
        has_carry=False, constants=CONSTANTS, interpret=False)
    assert _custom_call(
        compile_v5e(fn, _f32(5, 24), META, _f32(1, 4),
                    _f32(K.CARRY_FRONT, len(OBJECTIVES))),
        "dse_pareto_decoded")
