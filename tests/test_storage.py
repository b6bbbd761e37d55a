"""Precision-polymorphic storage tier (PR 5).

Three contracts, per StorageSpec × backend × batch shape:

  * f32 is a NO-OP REFACTOR: selected indices, bounds and order
    statistics are bit-identical to the pre-refactor code, pinned by the
    committed goldens (tests/goldens/pr5_f32.npz, generated on the
    pre-refactor tree by make_pr5_goldens.py) — including the delta path.
  * bf16/int8 are CERTIFIED: the widened (r↓, r↑) CONTAIN the f32-spec
    bounds for every user and every query (r↓ rounds down, r↑ up), so
    Lemma-1 selection over them stays sound — including the delta path,
    where quantized correction rows yield certified count ranges.
  * the quantizer itself: per-row affine int8 codes reconstruct within
    half a step, packing preserves sortedness, and the absent sentinel
    (−128 / −inf) can never be counted by the delta count brackets.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as BK
from repro.core.engine import ReverseKRanksEngine
from repro.core.rank_table import build_rank_table
from repro.core.types import RankTableConfig, StorageSpec, StoredUsers
from tests.conftest import make_problem

GOLDEN = os.path.join(os.path.dirname(__file__), "goldens", "pr5_f32.npz")
SPECS = ("float32", "bfloat16", "int8")
BACKENDS = ("dense", "fused", "sharded", "pruned", "pruned:fused")
K = 7


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


@pytest.fixture(scope="module")
def problem():
    return make_problem(jax.random.PRNGKey(42), n=512, m=400, d=16)


def _cfg(spec: str, **kw) -> RankTableConfig:
    base = dict(tau=16, omega=4, s=8)
    base.update(kw)
    return RankTableConfig(storage_dtype=spec, **base)


@pytest.fixture(scope="module")
def tables(problem):
    """Rank tables for both Lemma-1 regimes × every storage spec, built
    from the SAME f32 estimation pass (same key)."""
    users, items = problem
    out = {}
    for spec in SPECS:
        exact_cfg = _cfg(spec, tau=128, s=items.shape[0] // 4,
                         threshold_mode="exact")
        coarse_cfg = _cfg(spec)
        out[("guaranteed", spec)] = (
            exact_cfg,
            build_rank_table(users, items, exact_cfg, jax.random.PRNGKey(0)),
            4.0)
        out[("non_guaranteed", spec)] = (
            coarse_cfg,
            build_rank_table(users, items, coarse_cfg,
                             jax.random.PRNGKey(1)), 1.0)
    return out


def _engine(problem, tables, regime, spec, backend):
    users, _ = problem
    cfg, rt, c = tables[(regime, spec)]
    return ReverseKRanksEngine(users=users, rank_table=rt, config=cfg,
                               backend=backend), c


def _golden_qs(golden, regime, B):
    return jnp.asarray(golden[f"{regime}_B{B}_qs"])


# ------------------------------------------------------------ f32 goldens
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("regime", ["guaranteed", "non_guaranteed"])
def test_f32_bit_parity_with_prerefactor_goldens(problem, tables, golden,
                                                 backend, B, regime):
    """The f32 spec is provably a no-op: every backend reproduces the
    PRE-REFACTOR dense results bitwise (indices, table-derived bounds,
    order statistics; est at float accuracy)."""
    eng, c = _engine(problem, tables, regime, "float32", backend)
    qs = _golden_qs(golden, regime, B)
    res = eng.query_batch(qs, k=K, c=c)
    tag = f"{regime}_B{B}"
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  golden[f"{tag}_indices"])
    np.testing.assert_array_equal(np.asarray(res.R_lo_k),
                                  golden[f"{tag}_R_lo_k"])
    np.testing.assert_array_equal(np.asarray(res.R_up_k),
                                  golden[f"{tag}_R_up_k"])
    np.testing.assert_allclose(np.asarray(res.est_rank),
                               golden[f"{tag}_est_rank"], rtol=1e-5,
                               atol=1e-4)
    if res.r_lo.shape == golden[f"{tag}_r_lo"].shape:   # not candidate-set
        np.testing.assert_array_equal(np.asarray(res.r_lo),
                                      golden[f"{tag}_r_lo"])
        np.testing.assert_array_equal(np.asarray(res.r_up),
                                      golden[f"{tag}_r_up"])


def test_f32_delta_bit_parity_with_goldens(problem, golden):
    """Delta path (inserts + deletes + dead users) at the f32 spec is
    bit-identical to the pre-refactor code."""
    users, items = problem
    eng = ReverseKRanksEngine.build(users, items, _cfg("float32"),
                                    jax.random.PRNGKey(1))
    eng.insert_items(jnp.asarray(golden["delta_new_items"]))
    eng.delete_items([3, 44, 101, 257])
    eng.delete_users([7, 300])
    res = eng.query_batch(_golden_qs(golden, "non_guaranteed", 16), k=K,
                          c=1.0)
    for f in ("indices", "r_lo", "r_up", "R_lo_k", "R_up_k", "est_rank"):
        np.testing.assert_array_equal(np.asarray(getattr(res, f)),
                                      golden[f"delta_B16_{f}"])


# --------------------------------------------------- certified containment
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("spec", ["bfloat16", "int8"])
@pytest.mark.parametrize("regime", ["guaranteed", "non_guaranteed"])
def test_certified_containment(problem, tables, golden, backend, B, regime,
                               spec):
    """Quantized specs widen certifiably: r↓ ≤ f32 r↓ and r↑ ≥ f32 r↑
    for EVERY user and query, est stays inside the widened interval, and
    every returned user is admissible under the widened bounds."""
    eng, c = _engine(problem, tables, regime, spec, backend)
    ref, _ = _engine(problem, tables, regime, "float32", "dense")
    qs = _golden_qs(golden, regime, B)
    res = eng.query_batch(qs, k=K, c=c)
    want = ref.query_batch(qs, k=K, c=c)
    if res.r_lo.shape == want.r_lo.shape:       # full (B, n) bound fields
        r_lo, r_up = np.asarray(res.r_lo), np.asarray(res.r_up)
        assert np.all(r_lo <= np.asarray(want.r_lo) + 1e-4)
        assert np.all(r_up >= np.asarray(want.r_up) - 1e-4)
        # returned users: inside the widened interval (the sub-unit
        # above-range tie-break dips est up to 0.5 below r↓ by design —
        # same as the f32 path) and admissible
        est = np.asarray(res.est_rank)
        idx = np.asarray(res.indices)
        take = lambda a: np.take_along_axis(
            np.atleast_2d(a), np.atleast_2d(idx), axis=-1)
        assert np.all(take(r_lo) - 0.5 - 1e-4 <= np.atleast_2d(est))
        assert np.all(np.atleast_2d(est) <= take(r_up) + 1e-4)
    # the order statistics must bracket the f32 ones in the widened
    # direction on every backend (sharded included)
    assert np.all(np.asarray(res.R_lo_k) <= np.asarray(want.R_lo_k) + 1e-4)
    assert np.all(np.asarray(res.R_up_k) >= np.asarray(want.R_up_k) - 1e-4)


@pytest.mark.parametrize("backend", ["dense", "fused", "sharded", "pruned",
                                     "pruned:fused"])
@pytest.mark.parametrize("spec", ["bfloat16", "int8"])
def test_certified_containment_delta(problem, golden, backend, spec):
    """Containment survives the delta path: quantized correction rows
    yield certified count ranges, so corrected bounds still bracket the
    f32 engine's corrected bounds; dead users are +inf everywhere."""
    users, items = problem

    def mutate(engine):
        engine.insert_items(jnp.asarray(golden["delta_new_items"]))
        engine.delete_items([3, 44, 101, 257])
        engine.delete_users([7, 300])
        return engine

    eng = mutate(ReverseKRanksEngine.build(users, items, _cfg(spec),
                                           jax.random.PRNGKey(1),
                                           backend=backend))
    ref = mutate(ReverseKRanksEngine.build(users, items, _cfg("float32"),
                                           jax.random.PRNGKey(1)))
    qs = _golden_qs(golden, "non_guaranteed", 16)
    res = eng.query_batch(qs, k=K, c=1.0)
    want = ref.query_batch(qs, k=K, c=1.0)
    if res.r_lo.shape == want.r_lo.shape:
        rl, ru = np.asarray(res.r_lo), np.asarray(res.r_up)
        wl, wu = np.asarray(want.r_lo), np.asarray(want.r_up)
        fin = np.isfinite(wl)
        assert np.all(rl[fin] <= wl[fin] + 1e-4)
        assert np.all(ru[fin] >= wu[fin] - 1e-4)
        assert np.all(~np.isfinite(rl[~fin]))   # dead users stay +inf
        assert not np.isin(np.asarray(res.indices), [7, 300]).any()
    assert np.all(np.asarray(res.R_lo_k) <= np.asarray(want.R_lo_k) + 1e-4)
    assert np.all(np.asarray(res.R_up_k) >= np.asarray(want.R_up_k) - 1e-4)


# ----------------------------------------------------- quantizer contracts
def test_storage_spec_parse_and_validation():
    assert StorageSpec.parse("float32").kind == "f32"
    assert StorageSpec.parse("bf16").kind == "bf16"
    assert StorageSpec.parse(StorageSpec(kind="int8")).kind == "int8"
    with pytest.raises(ValueError, match="unknown storage spec"):
        StorageSpec.parse("fp4")
    with pytest.raises(ValueError, match="unknown StorageSpec kind"):
        StorageSpec(kind="f16")
    with pytest.raises(ValueError):
        RankTableConfig(storage_dtype="no-such-dtype")
    assert RankTableConfig(storage_dtype="int8").storage.kind == "int8"


def test_pack_table_roundtrip_error_bound():
    """int8 affine codes reconstruct within half a quantization step and
    preserve per-row monotonicity."""
    key = jax.random.PRNGKey(0)
    thr = jnp.sort(jax.random.normal(key, (32, 40)) * 3.0, axis=1)
    tab = jnp.sort(jax.random.uniform(key, (32, 40)) * 100 + 1.0,
                   axis=1)[:, ::-1]
    rt = StorageSpec(kind="int8").pack_table(thr, tab)
    deq_thr = (rt.thresholds.astype(jnp.float32) * rt.thr_scale
               + rt.thr_off)
    deq_tab = rt.table.astype(jnp.float32) * rt.tab_scale + rt.tab_off
    assert rt.thresholds.dtype == jnp.int8
    assert np.all(np.abs(np.asarray(deq_thr - thr))
                  <= np.asarray(rt.thr_scale) * 0.5 + 1e-6)
    assert np.all(np.abs(np.asarray(deq_tab - tab))
                  <= np.asarray(rt.tab_scale) * 0.5 + 1e-6)
    assert np.all(np.diff(np.asarray(deq_thr), axis=1) >= 0)
    assert np.all(np.diff(np.asarray(deq_tab), axis=1) <= 0)


def test_pack_users_slack_bound():
    """The per-row slack certifies the score error: for random queries,
    |stored-score − f32-score| ≤ row_slack · ‖q‖₁."""
    key = jax.random.PRNGKey(1)
    users = jax.random.normal(key, (64, 24)) * 2.0
    qs = jax.random.normal(jax.random.PRNGKey(2), (8, 24))
    for spec in ("bf16", "int8"):
        stored = StorageSpec(kind=spec).pack_users(users)
        assert isinstance(stored, StoredUsers)
        rows = stored.rows.astype(jnp.float32)
        if stored.scale is not None:
            rows = rows * stored.scale
        err = np.abs(np.asarray(rows @ qs.T - users @ qs.T))
        bound = np.asarray(stored.row_slack) * np.asarray(
            jnp.sum(jnp.abs(qs), axis=1))[None, :]
        assert np.all(err <= bound + 1e-5)
    assert StorageSpec(kind="f32").pack_users(users) is None


def test_pack_scores_sentinel_never_counted():
    """Delta count brackets: [count_lo, count_hi] contains the exact f32
    count for every spec, and left-padding sentinels cannot inflate
    either side even for scores below every stored value."""
    from repro.core.rank_table import _count_above, _count_above_range
    key = jax.random.PRNGKey(3)
    raw = jnp.sort(jax.random.normal(key, (16, 5)) * 2.0, axis=1)
    scores = jnp.concatenate([
        jax.random.normal(jax.random.PRNGKey(4), (16, 6)) * 2.0,
        jnp.full((16, 1), -50.0), jnp.full((16, 1), 50.0)], axis=1)
    exact = np.asarray(_count_above(raw, scores))
    for spec in ("f32", "bf16", "int8"):
        rows, sc, off = StorageSpec(kind=spec).pack_scores(raw, pad=3)
        lo, hi = _count_above_range(rows, sc, off, scores, None)
        assert np.all(np.asarray(lo) <= exact + 1e-6), spec
        assert np.all(exact <= np.asarray(hi) + 1e-6), spec
        assert np.all(np.asarray(hi) <= raw.shape[1]), spec   # pads excluded
        assert np.all(np.asarray(lo) >= 0.0), spec


# ----------------------------------------------------- mutation lifecycle
@pytest.mark.parametrize("spec", ["bfloat16", "int8"])
def test_upsert_users_quantized_spec(problem, spec):
    """Upserts re-estimate rows in f32 and re-pack through the ONE pack
    path: replaced rows behave like a from-scratch build's rows."""
    users, items = problem
    cfg = _cfg(spec)
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    new_rows = users[:3] * 1.5
    eng.upsert_users(new_rows, indices=[5, 9, 300])
    users_new = np.array(users)
    users_new[[5, 9, 300]] = np.asarray(new_rows)
    scratch = ReverseKRanksEngine.build(jnp.asarray(users_new), items, cfg,
                                        jax.random.PRNGKey(1))
    q = items[11]
    got = eng.query(q, k=K, c=2.0)
    want = scratch.query(q, k=K, c=2.0)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.r_lo),
                                  np.asarray(want.r_lo))
    # appended users land in the stored tier too
    eng.upsert_users(users[:2] * 0.5)
    assert eng.current_snapshot().stored_users.rows.shape[0] == eng.n


@pytest.mark.parametrize("spec", SPECS)
def test_rebuild_quantized_spec(problem, spec):
    """rebuild() over a mutated quantized engine equals a from-scratch
    build over the merged item set, bitwise."""
    users, items = problem
    cfg = _cfg(spec)
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    _, new_items = make_problem(jax.random.PRNGKey(9), n=1, m=12, d=16)
    eng.insert_items(new_items)
    rec = eng.rebuild()
    assert rec is not None
    scratch = ReverseKRanksEngine.build(users, eng.live_items(), cfg,
                                        jax.random.PRNGKey(1))
    q = items[3]
    got = eng.query(q, k=K, c=2.0)
    want = scratch.query(q, k=K, c=2.0)
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_array_equal(np.asarray(got.r_lo),
                                  np.asarray(want.r_lo))


def test_stored_users_lifecycle(problem):
    users, items = problem
    eng32 = ReverseKRanksEngine.build(users, items, _cfg("float32"),
                                      jax.random.PRNGKey(1))
    assert eng32.current_snapshot().stored_users is None    # no-op path
    eng8 = ReverseKRanksEngine.build(users, items, _cfg("int8"),
                                     jax.random.PRNGKey(1))
    su = eng8.current_snapshot().stored_users
    assert su is not None and su.rows.dtype == jnp.int8
    assert eng8.memory_bytes() < eng32.memory_bytes()
    # user mutation repacks the stored tier; item mutation carries it
    snap0 = eng8.current_snapshot()
    eng8.insert_items(items[:2] * 0.9)
    assert eng8.current_snapshot().stored_users is snap0.stored_users
    eng8.upsert_users(users[:1] * 2.0, indices=[0])
    assert eng8.current_snapshot().stored_users is not snap0.stored_users


# ------------------------------------------------- near-duplicate caching
def test_near_duplicate_cache_key(problem, tables):
    from repro.serve.cache import CachingBackend
    users, items = problem
    cfg, rt, c = tables[("non_guaranteed", "float32")]
    snap_users = users
    q = items[5]
    jit = q * (1.0 + 1e-5)
    far = items[77]
    exact = CachingBackend("dense")
    for qq in (q, jit):
        exact.query_batch(rt, snap_users, qq[None, :], k=K, c=c)
    assert exact.hits == 0                      # exact keys never alias
    coarse = CachingBackend("dense", quantize_key_bits=6)
    r1 = coarse.query_batch(rt, snap_users, q[None, :], k=K, c=c)
    r2 = coarse.query_batch(rt, snap_users, jit[None, :], k=K, c=c)
    assert coarse.hits == 1                     # near-duplicate reused
    np.testing.assert_array_equal(np.asarray(r1.indices),
                                  np.asarray(r2.indices))
    coarse.query_batch(rt, snap_users, far[None, :], k=K, c=c)
    assert coarse.misses == 2                   # distinct queries miss
    with pytest.raises(ValueError, match="quantize_key_bits"):
        CachingBackend("dense", quantize_key_bits=1)


def test_round_bf16_is_round_to_nearest_even():
    """`round_bf16` rounds f32 to bf16 as an IEEE cast does — ties to
    even, both signs — for the ties and near-ties the bf16 bucketize
    meets; host numpy (ml_dtypes) is the reference."""
    from repro.core.types import round_bf16
    rng = np.random.default_rng(0)
    hi = rng.integers(0x3000, 0x4800, size=512).astype(np.uint32) << 16
    low = np.array([0x8000, 0x7FFF, 0x8001, 0x0000, 0xFFFF], np.uint32)
    bits = (hi[:, None] | low[None, :]).reshape(-1)
    x = np.concatenate([bits, bits | 0x80000000]).view(np.float32)
    want = x.astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(round_bf16(jnp.asarray(x))),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(round_bf16)(jnp.asarray(x))), want)


def test_interpret_env_override(monkeypatch):
    """Kernels interpret if and only if the backend is the CPU, decided
    when a kernel is traced. The old REPRO_INTERPRET variable overrides
    nothing: on the CPU kernels still interpret with it set to 0, and off
    the CPU nothing turns interpret mode on."""
    from repro.kernels import interpret_mode
    from repro.kernels import user_scores as us

    def traced_interpret():
        args = (jnp.zeros((256, 8)), jnp.zeros((8, 8)),
                jnp.zeros((256, 128)), jnp.ones((256, 128)))
        jaxpr = jax.make_jaxpr(functools.partial(
            us.bound_ranks_batched_kernel_call, m=10, tau_valid=100))(*args)
        calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return bool(calls[0].params["interpret"])

    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True and traced_interpret() is True
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False and traced_interpret() is False
