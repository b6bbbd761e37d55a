"""Compile the engine's kernels and programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed with jax, lowers
each program for a v5e that is described, not attached, and raises what
the chip's compiler would raise — Mosaic lowering failures, scoped-VMEM
overruns, block shapes that break the (8, 128) tiling rule, programs
that do not fit the device's HBM. Shapes are the paper's Netflix
deployment (`configs/paper_engine.py`: d 200, τ 500) at the serving tick
width B 16 and the shipped block sizes.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and a test file that
loaded it at collection time would break every other pytest worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_engine import DEFAULT_TABLE, NETFLIX
from repro.core import elastic
from repro.core import rank_table as rt_mod
from repro.core.pruning import DEFAULT_BLOCK
from repro.core.types import RankTableConfig, StorageSpec
from repro.kernels import ops

N, M, D = NETFLIX.n_users, NETFLIX.n_items, NETFLIX.d
TAU = DEFAULT_TABLE.tau
B = 16                      # the scheduler's tick width (max_batch)
V5E_HBM = 15.75 * 2**30     # bytes a v5e program may use (16 GiB chip)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the programs as they trace on a TPU: JAX's default backend
    reads "tpu", so kernels are compiled, not interpreted, and matmuls
    ask for full f32. The persistent compile cache stays off (a described
    chip's executables cannot be read back) and jit caches are cleared on
    both sides so no CPU-traced program leaks in or out."""
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        jax.clear_caches()


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _stored(spec: str, n: int, one_chip):
    """Abstract (users, rank table) in spec space for n rows."""
    st = StorageSpec.parse(spec)
    f32 = jax.ShapeDtypeStruct((n, D), jnp.float32)
    grid = jax.ShapeDtypeStruct((n, TAU), jnp.float32)
    rt = jax.eval_shape(st.pack_table, grid, grid)
    users = jax.eval_shape(st.pack_users, f32)
    return (_sds(f32 if users is None else users, one_chip),
            _sds(rt, one_chip))


@pytest.mark.parametrize("spec", ["f32", "bf16", "int8"])
def test_full_grid_kernel_compiles(spec, one_chip, compiled_kernels):
    users, rt = _stored(spec, N, one_chip)
    qs = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    c = _compile(lambda u, q, t: ops.bound_ranks_tile(u, q, t, m=M), users,
                 qs, rt)
    assert _has_kernel(c)


@pytest.mark.parametrize("spec", ["f32", "int8"])
def test_masked_grid_kernel_compiles(spec, one_chip, compiled_kernels):
    users, rt = _stored(spec, N, one_chip)
    qs = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    nk = 64                                      # kept tiles (a bucket)
    ids = jax.ShapeDtypeStruct((nk,), jnp.int32, sharding=one_chip)
    if spec == "f32":
        fn = lambda u, q, t, i: ops.bound_ranks_batched_pruned(
            u, q, t.thresholds, t.table, i, m=M, block_n=DEFAULT_BLOCK)
    else:
        fn = lambda u, q, t, i: ops._bound_ranks_batched_pruned_stored_impl(
            spec, u.rows, u.scale, u.row_slack, q, t.thresholds, t.table,
            t.thr_scale, t.thr_off, t.thr_dev, t.tab_scale, t.tab_off, i,
            m=M, block_n=DEFAULT_BLOCK)
    c = _compile(fn, users, qs, rt, ids)
    assert _has_kernel(c)


def test_elastic_fused_program_compiles(one_chip, compiled_kernels):
    """The whole compile-once serving program of `elastic:fused` at the
    Netflix capacity bucket: fori_loop over the Pallas tile + selection."""
    tile = elastic.default_tile()
    cap = elastic.capacity_for(N, tile)
    users, rt = _stored("f32", cap, one_chip)
    rt = rt._replace(m=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    qs = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    fn = lambda t, u, q, n, c: elastic._elastic_query_impl(
        t, u, q, n, None, c, tile=tile, use_kernel=True, m_kernel=M, k=10)
    c = _compile(fn, rt, users, qs, scalar(jnp.int32), scalar(jnp.float32))
    assert _has_kernel(c)
    ma = c.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM


@pytest.mark.parametrize("spec", ["f32", "int8"])
def test_netflix_build_fits_one_chip(spec, one_chip, compiled_kernels):
    """Algorithm 1 at the full Netflix shapes is one program on one chip;
    its arguments, outputs and temporaries must fit the device's HBM."""
    cfg = RankTableConfig(tau=TAU, omega=DEFAULT_TABLE.omega,
                          s=DEFAULT_TABLE.s, storage_dtype=spec)
    users = jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=one_chip)
    items = jax.ShapeDtypeStruct((M, D), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    c = rt_mod.build_rank_table_sorted.lower(users, items, cfg=cfg,
                                             key=key).compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < V5E_HBM, f"{total / 2**30:.2f} GiB"


def test_single_query_kernel_compiles(one_chip, compiled_kernels):
    users, rt = _stored("f32", N, one_chip)
    q = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)
    c = _compile(lambda u, q, t: ops.bound_ranks(u, q, t.thresholds,
                                                 t.table, m=M),
                 users, q, rt)
    assert _has_kernel(c)


def test_table_build_kernel_compiles(one_chip, compiled_kernels):
    S = DEFAULT_TABLE.omega * DEFAULT_TABLE.s
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    c = _compile(ops.build_table_rows, f32(N, D), f32(S, D), f32(S),
                 f32(N, TAU))
    assert _has_kernel(c)


def test_exact_rank_kernel_compiles(one_chip, compiled_kernels):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    c = _compile(ops.exact_ranks, f32(N, D), f32(M, D), f32(D))
    assert _has_kernel(c)
