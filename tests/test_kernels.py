"""Per-kernel validation: pallas_call (interpret=True) vs ref.py oracles,
swept over shapes and dtypes, plus integration vs repro.core."""
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.query import lookup_bounds, query
from repro.core.rank_table import build_rank_table
from repro.core.types import RankTable, RankTableConfig
from repro.kernels import ops, ref
from tests.conftest import make_problem


def _table_for(users, items, tau, key=0):
    cfg = RankTableConfig(tau=tau, omega=4, s=16)
    return build_rank_table(users, items, cfg, jax.random.PRNGKey(key))


# ---------------------------------------------------------------- user_scores
@pytest.mark.parametrize("n,d,tau", [
    (256, 128, 128),       # exact tile multiples
    (300, 200, 100),       # paper-ish d/τ, ragged n and τ (padding path)
    (1024, 64, 500),       # paper τ
    (64, 32, 7),           # tiny, heavy padding
    (257, 200, 37),        # one user into a ragged last tile, τ % 8 ≠ 0
    (1000, 64, 500),       # ragged last tile at the paper's τ
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bound_ranks_kernel_vs_ref(n, d, tau, dtype):
    users, items = make_problem(jax.random.PRNGKey(n + tau), n, 300, d,
                                dtype=dtype)
    rt = _table_for(users.astype(jnp.float32), items.astype(jnp.float32), tau)
    q = items[1]
    got = ops.bound_ranks(users, q, rt.thresholds, rt.table, m=int(rt.m))
    want = ref.ref_bound_ranks(users, q, rt.thresholds, rt.table, int(rt.m))
    # r_lo/r_up gather table entries (exact given the same bucketize); est
    # interpolates with frac = (score - t_j)/span, which divides a ~1-ulp
    # matmul-schedule difference (kernel row blocks vs one ref matmul) by
    # a span that shrinks as 1/τ — at τ=500 that amplifies to ~1e-4 in
    # rank units, so est gets a wider f32 absolute band than the bounds.
    for g, w, name, atol32 in zip(got, want, ("r_lo", "r_up", "est"),
                                  (1e-4, 1e-4, 1e-3)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                                   atol=2.0 if dtype == jnp.bfloat16 else atol32,
                                   err_msg=name)


@pytest.mark.parametrize("n,tau", [(257, 37), (1000, 500)])
@pytest.mark.parametrize("B", [1, 16])
def test_bound_ranks_batched_vs_ref(n, tau, B):
    """Every query row of the batched full grid matches the oracle where
    n leaves a ragged last tile and τ is no multiple of 8: the grid's
    last block stops at n, and the outputs are (B, n)."""
    users, items = make_problem(jax.random.PRNGKey(n + B), n, 300, 32)
    rt = _table_for(users, items, tau)
    qs = items[3:3 + B]
    got = ops.bound_ranks_batched(users, qs, rt.thresholds, rt.table,
                                  m=int(rt.m))
    for g in got:
        assert g.shape == (B, n)
    for j in range(B):
        want = ref.ref_bound_ranks(users, qs[j], rt.thresholds, rt.table,
                                   int(rt.m))
        # est's wider band: see test_bound_ranks_kernel_vs_ref
        for g, w, name, atol in zip(got, want, ("r_lo", "r_up", "est"),
                                    (1e-4, 1e-4, 1e-3)):
            np.testing.assert_allclose(np.asarray(g[j]), np.asarray(w),
                                       rtol=1e-5, atol=atol,
                                       err_msg=f"{name} query {j}")


@pytest.mark.parametrize("B", [1, 16])
def test_masked_grid_ragged_tail_matches_full_grid(B):
    """The masked grid with the ragged last tile in its block list: each
    compacted tile equals the full grid's columns of that tile bit for
    bit, and the lanes past n read m + 1, so no non-finite value reaches
    the selection."""
    n, tau, bn = 1000, 37, 256
    users, items = make_problem(jax.random.PRNGKey(11), n, 300, 32)
    rt = _table_for(users, items, tau)
    m = int(rt.m)
    qs = items[5:5 + B]
    ids = jnp.asarray([3, 0, 3], jnp.int32)     # tail first, repeated
    full = ops.bound_ranks_batched(users, qs, rt.thresholds, rt.table, m=m,
                                   block_n=bn)
    comp = ops.bound_ranks_batched_pruned(users, qs, rt.thresholds,
                                          rt.table, ids, m=m, block_n=bn)
    for f, c in zip(full, comp):
        f, c = np.asarray(f), np.asarray(c)
        assert c.shape == (B, 3 * bn) and np.all(np.isfinite(c))
        for j, b in enumerate(np.asarray(ids)):
            cols = np.arange(b * bn, min((b + 1) * bn, n))
            tile = c[:, j * bn:(j + 1) * bn]
            np.testing.assert_array_equal(_bits(tile[:, :cols.size]),
                                          _bits(f[:, cols]))
            assert np.all(tile[:, cols.size:] == m + 1)


def test_bound_ranks_matches_core_lookup():
    """Kernel path ≡ core.query.lookup_bounds on float32 (same bucketize)."""
    users, items = make_problem(jax.random.PRNGKey(5), 500, 400, 48)
    rt = _table_for(users, items, 200)
    q = items[9]
    uq = (users @ q).astype(jnp.float32)
    want = lookup_bounds(rt, uq)
    got = ops.bound_ranks(users, q, rt.thresholds, rt.table, m=int(rt.m))
    # est gets a wider absolute band than the bounds: the interpolation
    # frac divides ~1-ulp score-schedule differences by the τ-fine span
    # (see test_bound_ranks_kernel_vs_ref).
    for g, w, atol in zip(got, want, (1e-4, 1e-4, 1e-3)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=atol)


def test_query_fused_selection_matches_core():
    users, items = make_problem(jax.random.PRNGKey(6), 800, 600, 32)
    rt = _table_for(users, items, 128)
    q = items[17]
    a = query(rt, users, q, k=13, c=2.0)
    b = ops.query_fused(rt, users, q, k=13, c=2.0)
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    np.testing.assert_allclose(np.asarray(a.est_rank),
                               np.asarray(b.est_rank), rtol=1e-5)


def _bits(x) -> np.ndarray:
    """Bit pattern of an array, so -0.0 and NaN compare exactly."""
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("k", [1, 10])
def test_compiled_selection_bit_identical_to_eager(B, k):
    """The compiled §4.3 selection returns the eager `select_topk`'s
    QueryResult bit for bit, on bounds with many ties; and the fused
    backend's batched query selects as the dense one does."""
    from repro.core import backends as BK
    from repro.core.query import _select_topk_jit, select_topk
    n, m = 600, 40
    kl, kw, ke = jax.random.split(jax.random.PRNGKey(100 * B + k), 3)
    # integer bounds from a narrow range: every order statistic is tied
    r_lo = jax.random.randint(kl, (B, n), 1, 12).astype(jnp.float32)
    r_up = r_lo + jax.random.randint(kw, (B, n), 0, 6).astype(jnp.float32)
    est = jnp.round(4.0 * jax.random.uniform(ke, (B, n), minval=r_lo,
                                             maxval=r_up)) / 4.0
    m_items = jnp.asarray(m, jnp.int32)
    for c in (1.0, 2.0):
        want = select_topk(r_lo, r_up, est, k=k, c=c, m_items=m_items)
        got = _select_topk_jit(r_lo, r_up, est, m_items, k, c)
        for name in want._fields:
            np.testing.assert_array_equal(
                _bits(getattr(got, name)), _bits(getattr(want, name)),
                err_msg=name)

    users, items = make_problem(jax.random.PRNGKey(42), 512, 400, 16)
    rt = _table_for(users, items, 16)
    base = items[(1 + jnp.arange(B) * 17) % items.shape[0]]
    # off the threshold grid, so both step-1 paths bucketize alike
    qs = base * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7 + B), base.shape, jnp.float32))
    dense = BK.get_backend("dense").query_batch(rt, users, qs, k=k, c=1.0)
    fused = BK.get_backend("fused").query_batch(rt, users, qs, k=k, c=1.0)
    for name in ("indices", "R_lo_k", "R_up_k"):
        np.testing.assert_array_equal(
            _bits(getattr(fused, name)), _bits(getattr(dense, name)),
            err_msg=name)


def test_fused_selection_runs_compiled(monkeypatch):
    """The fused path selects inside one compiled program: `select_topk`
    only ever sees tracers, and the compiled selection at (16, 4096),
    k 10, holds no array n − k wide — the half of `jnp.partition` that
    sorts the rest of the axis is gone. Eager, that half is returned."""
    query_mod = sys.modules["repro.core.query"]  # the package exports query()
    eager_calls = []
    real = query_mod.select_topk

    def spy(r_lo, *args, **kw):
        if not isinstance(r_lo, jax.core.Tracer):
            eager_calls.append(r_lo.shape)
        return real(r_lo, *args, **kw)

    monkeypatch.setattr(query_mod, "select_topk", spy)
    users, items = make_problem(jax.random.PRNGKey(3), 300, 200, 16)
    rt = _table_for(users, items, 16)
    ops.query_fused_batch(rt, users, items[:4], k=10, c=2.0)
    ops.query_fused(rt, users, items[5], k=10, c=2.0)
    assert eager_calls == []

    B, n, k = 16, 4096, 10
    bounds = jax.ShapeDtypeStruct((B, n), jnp.float32)
    m_items = jax.ShapeDtypeStruct((), jnp.int32)
    hlo = query_mod._select_topk_jit.lower(
        bounds, bounds, bounds, m_items, k, 2.0).compile().as_text()
    assert re.search(rf"\b{n - k}\b", hlo) is None
    # control: the same partition with its whole output live holds it
    full = jax.jit(lambda x: jnp.partition(x, k - 1, axis=-1)).lower(
        bounds).compile().as_text()
    assert re.search(rf"\b{n - k}\b", full) is not None


# ---------------------------------------------------------------- table_build
@pytest.mark.parametrize("n,d,S,tau", [
    (128, 128, 64, 128),
    (200, 200, 40, 100),   # ragged everything
    (384, 64, 96, 33),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_table_build_kernel_vs_ref(n, d, S, tau, dtype):
    key = jax.random.PRNGKey(n + S)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    users = jax.random.normal(k1, (n, d), jnp.float32).astype(dtype)
    samples = jax.random.normal(k2, (S, d), jnp.float32).astype(dtype)
    weights = jax.random.uniform(k3, (S,), jnp.float32, 0.5, 3.0)
    thresholds = jnp.sort(
        jax.random.normal(k4, (n, tau), jnp.float32) * d ** 0.5, axis=1)
    got = ops.build_table_rows(users, samples, weights, thresholds)
    want = ref.ref_table_rows(users, samples, weights, thresholds)
    # bf16 inputs round scores; near-threshold indicators may flip, so allow
    # a small absolute rank slack; f32 must match to float accuracy.
    if dtype == jnp.bfloat16:
        assert np.mean(np.abs(np.asarray(got) - np.asarray(want))) < 3.0
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)


def test_table_build_matches_core_estimator():
    """Kernel ≡ core.rank_table.estimate_table_rows (sort+suffix path)."""
    from repro.core.rank_table import estimate_table_rows
    key = jax.random.PRNGKey(77)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n, d, S, tau = 100, 50, 32, 64
    users = jax.random.normal(k1, (n, d))
    samples = jax.random.normal(k2, (S, d))
    weights = jax.random.uniform(k3, (S,), minval=1.0, maxval=2.0)
    thresholds = jnp.sort(jax.random.normal(k4, (n, tau)) * 7.0, axis=1)
    got = ops.build_table_rows(users, samples, weights, thresholds)
    scores = (users @ samples.T).astype(jnp.float32)
    want = estimate_table_rows(scores, weights, thresholds)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-3)


# ---------------------------------------------------------------- exact_rank
@pytest.mark.parametrize("n,m,d", [
    (256, 512, 64),        # exact multiples
    (300, 700, 100),       # ragged n and m (zero-row padding correction)
    (64, 100, 200),        # paper d
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exact_rank_kernel_vs_ref(n, m, d, dtype):
    users, items = make_problem(jax.random.PRNGKey(m + d), n, m, d,
                                dtype=dtype)
    q = items[2]
    got = ops.exact_ranks(users, items, q)
    want = 1.0 + ref.ref_exact_counts(users, items, q)
    if dtype == jnp.bfloat16:
        # bf16 rounds u·p; ranks shift only at near-ties.
        assert np.mean(np.abs(np.asarray(got) - np.asarray(want))) < 2.0
    else:
        # q ∈ P ⇒ a mathematical tie at the self-item; different matmul
        # tilings (kernel blocks vs one ref matmul) round it either way.
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1.0)


def test_exact_rank_kernel_vs_core(small_problem):
    from repro.core.exact import exact_ranks as core_exact
    users, items = small_problem
    # Random q (∉ P): no structural tie, so the two schedules agree almost
    # everywhere (residual near-ties are rounding-level rare).
    q = jax.random.normal(jax.random.PRNGKey(123), items[0].shape)
    got = np.asarray(ops.exact_ranks(users, items, q))
    want = np.asarray(core_exact(users, items, q)).astype(np.float32)
    assert np.mean(np.abs(got - want)) < 0.05
    assert np.max(np.abs(got - want)) <= 1.0

    # q ∈ P: every user carries a mathematical self-tie; each schedule may
    # round it either way, so ranks agree only to the ±1 tie band.
    q2 = items[4]
    got2 = np.asarray(ops.exact_ranks(users, items, q2))
    want2 = np.asarray(core_exact(users, items, q2)).astype(np.float32)
    assert np.max(np.abs(got2 - want2)) <= 1.0


# ------------------------------------------------------------------ property
try:  # optional test extra — `pip install repro[test]` (see pyproject.toml)
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None

if given is not None:
    @given(n=st.integers(16, 300), tau=st.integers(3, 140),
           seed=st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def test_bound_ranks_property(n, tau, seed):
        """Kernel == oracle for arbitrary ragged shapes (padding invariance).

        The kernel pads users/τ and computes u·q per 256-row block; a score
        landing within 1 ulp of a threshold can bucketize ±1 vs the unpadded
        oracle matvec, shifting that user's bound by one table cell. Allow a
        vanishing fraction of such tie flips; everything else must be exact.
        """
        users, items = make_problem(jax.random.PRNGKey(seed), n, 64, 24)
        rt = _table_for(users, items, tau, key=seed)
        q = items[seed % 64]
        got = ops.bound_ranks(users, q, rt.thresholds, rt.table, m=int(rt.m))
        want = ref.ref_bound_ranks(users, q, rt.thresholds, rt.table,
                                   int(rt.m))
        for g, w in zip(got, want):
            d = np.abs(np.asarray(g) - np.asarray(w))
            exact = d <= 1e-4 + 1e-5 * np.abs(np.asarray(w))
            assert exact.mean() >= 1.0 - 2.0 / n, \
                f"{(~exact).sum()} mismatches of {n}"

else:
    @pytest.mark.skip(reason="hypothesis not installed (optional test extra)")
    def test_bound_ranks_property():
        pass
