"""Block-pruned query execution (the PR-4 tentpole, `repro.core.pruning`).

Parity contract (matching tests/test_backends.py): `"pruned:<inner>"`
must return BIT-IDENTICAL selected indices and table-derived statistics
(R↓_k / R↑_k, integer-valued in rank space) to the UNPRUNED inner
backend on every case — both Lemma-1 regimes, B ∈ {1, 16}, static and
mutated indexes. `est_rank` compares at float accuracy: est is
continuous in the score u·q, whose LOW BITS legitimately differ between
the full-matrix matmul and the gathered kept-row matmul (same reason
batched-vs-single est differs repo-wide). The full r↓/r↑ arrays carry
the skip sentinel for pruned users and the n_accepted/n_pruned
diagnostics count sentinels, so those compare only within the pruned
backend itself, where per-query masking makes them B-independent.

Problem geometry: users are drawn from cluster-contiguous Gaussian
blobs, so summary blocks are coherent and phase A genuinely prunes
(asserted); the adversarial case uses i.i.d. users where every block
looks alike and the keep-everything fallback must engage. Sizes keep n
divisible by 8 shards × block_size so the suite also runs under the CI
job forcing 8 host devices (per-shard summaries + the pruned tree-merge).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as BK
from repro.core import pruning as PR
from repro.core.engine import ReverseKRanksEngine
from repro.core.query import lookup_bounds_batch
from repro.core.rank_table import build_rank_table
from repro.core.types import RankTableConfig

INNERS = ("dense", "fused", "sharded")
K, BS = 7, 64                   # small block size so n=2048 has 32 blocks
N, M, D, NCL = 2048, 512, 16, 16
CFG_COARSE = RankTableConfig(tau=16, omega=4, s=8)


def clustered_problem(key, n=N, m=M, d=D, n_clusters=NCL, spread=0.1):
    """Cluster-contiguous users (the block-coherent favorable case)."""
    kc, ku, ki, kn = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (n_clusters, d), jnp.float32) * 2.0
    assign = jnp.arange(n) * n_clusters // n        # contiguous, any n
    users = (centers[assign]
             + spread * jax.random.normal(ku, (n, d), jnp.float32))
    items = (centers[jax.random.randint(ki, (m,), 0, n_clusters)]
             + spread * jax.random.normal(kn, (m, d), jnp.float32))
    return users, items


@pytest.fixture(scope="module")
def problem():
    return clustered_problem(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def regimes(problem):
    """(cfg, rank_table, c) pinning both Lemma-1 cases (cf.
    tests/test_backends.py)."""
    users, items = problem
    exact_cfg = RankTableConfig(tau=64, omega=4, s=M // 4,
                                threshold_mode="exact")
    # clustered rank distributions are heavy-tailed, so closing the
    # search (c·R↓_k ≥ R↑_k) for EVERY query needs a generous c
    return {
        "guaranteed": (exact_cfg,
                       build_rank_table(users, items, exact_cfg,
                                        jax.random.PRNGKey(0)), 32.0),
        "non_guaranteed": (CFG_COARSE,
                           build_rank_table(users, items, CFG_COARSE,
                                            jax.random.PRNGKey(1)), 1.0),
    }


def off_grid_queries(items, B, seed=7):
    # offset 18: item 1 happens to close the coarse-table search even at
    # c = 1 on the clustered problem; starting at 18 keeps the anchor
    # query (and the B = 1 case) in the non-guaranteed regime
    base = items[(18 + jnp.arange(B) * 17) % items.shape[0]]
    return base * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(seed), base.shape, jnp.float32))


def pruned_engine(users, rt, cfg, inner, **knobs):
    eng = ReverseKRanksEngine(users=users, rank_table=rt, config=cfg,
                              backend=f"pruned:{inner}")
    eng._backend.block_size = knobs.pop("block_size", BS)
    for k, v in knobs.items():
        setattr(eng._backend, k, v)
    return eng


def assert_selected_parity(got, want):
    np.testing.assert_array_equal(np.asarray(got.indices),
                                  np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(got.est_rank),
                               np.asarray(want.est_rank), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got.R_lo_k),
                                  np.asarray(want.R_lo_k))
    np.testing.assert_array_equal(np.asarray(got.R_up_k),
                                  np.asarray(want.R_up_k))
    np.testing.assert_array_equal(np.asarray(got.guaranteed),
                                  np.asarray(want.guaranteed))


# ------------------------------------------------------------ summaries
def test_envelopes_certify_members(problem, regimes):
    """Every user's (r↓, r↑) must lie inside its block's phase-A
    envelope bounds — the invariant all pruning correctness rests on."""
    users, _ = problem
    _, rt, _ = regimes["non_guaranteed"]
    summ = PR.build_block_summary(users, rt, block_size=BS)
    qs = off_grid_queries(problem[1], 8)
    scores = (users @ qs.T).astype(jnp.float32)
    r_lo, r_up, _ = lookup_bounds_batch(rt, scores)         # (n, B)
    r_lo_opt, r_up_pes = PR._envelope_bounds(summ, qs)      # (nb, B)
    r_lo, r_up = np.asarray(r_lo), np.asarray(r_up)
    lo_env, up_env = np.asarray(r_lo_opt), np.asarray(r_up_pes)
    for blk in range(summ.n_blocks):
        rows = slice(blk * BS, min((blk + 1) * BS, N))
        assert np.all(lo_env[blk] <= r_lo[rows].min(axis=0) + 1e-6)
        assert np.all(up_env[blk] >= r_up[rows].max(axis=0) - 1e-6)


def test_rhat_bounds_true_Rupk(problem, regimes):
    users, _ = problem
    _, rt, c = regimes["non_guaranteed"]
    summ = PR.build_block_summary(users, rt, block_size=BS)
    qs = off_grid_queries(problem[1], 8)
    _, r_hat = PR.phase_a(summ, qs, k=K, block_size=BS)
    ref = ReverseKRanksEngine(users=users, rank_table=rt,
                              config=CFG_COARSE)
    true_up = np.asarray(ref.query_batch(qs, k=K, c=c).R_up_k)
    assert np.all(np.asarray(r_hat) >= true_up - 1e-6)


@pytest.mark.parametrize("inner", INNERS)
def test_tail_block_summary(inner):
    """n not a multiple of block_size: the partial tail block's rows
    count is exact and parity still holds, on the masked-grid kernel's
    ragged last tile too."""
    users, items = clustered_problem(jax.random.PRNGKey(3), n=1000, m=256)
    rt = build_rank_table(users, items, CFG_COARSE, jax.random.PRNGKey(1))
    summ = PR.build_block_summary(users, rt, block_size=BS)
    rows = np.asarray(summ.rows)
    assert rows.sum() == 1000 and rows[-1] == 1000 - (1000 // BS) * BS
    ref = ReverseKRanksEngine(users=users, rank_table=rt,
                              config=CFG_COARSE)
    eng = pruned_engine(users, rt, CFG_COARSE, inner,
                        max_union_frac=1.1)
    qs = off_grid_queries(items, 4)
    assert_selected_parity(eng.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))


# ------------------------------------------------------- static parity
@pytest.mark.parametrize("inner", INNERS)
@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("regime", ["guaranteed", "non_guaranteed"])
def test_pruned_matches_inner(problem, regimes, inner, B, regime):
    users, items = problem
    cfg, rt, c = regimes[regime]
    ref = ReverseKRanksEngine(users=users, rank_table=rt, config=cfg,
                              backend=inner)
    eng = pruned_engine(users, rt, cfg, inner)
    qs = off_grid_queries(items, B)
    want = ref.query_batch(qs, k=K, c=c)
    got = eng.query_batch(qs, k=K, c=c)
    if regime == "guaranteed":
        assert bool(np.all(np.asarray(want.guaranteed)))
    else:
        assert not bool(np.asarray(want.guaranteed)[0])
    assert_selected_parity(got, want)
    st = eng._backend.stats
    assert st.n_blocks == N // BS
    # single-query == batched column (per-query masking makes the pruned
    # result independent of its batch-mates)
    one = eng.query(qs[0], k=K, c=c)
    np.testing.assert_array_equal(np.asarray(one.indices),
                                  np.asarray(got.indices[0]))
    np.testing.assert_allclose(np.asarray(one.est_rank),
                               np.asarray(got.est_rank[0]), rtol=1e-5,
                               atol=1e-4)


def test_pruning_actually_skips(problem, regimes):
    """Clustered users + clustered queries: phase A must certify real
    skips (the whole point), and phase B must still be exact."""
    users, items = problem
    cfg, rt, c = regimes["non_guaranteed"]
    eng = pruned_engine(users, rt, cfg, "dense")
    ref = ReverseKRanksEngine(users=users, rank_table=rt, config=cfg)
    # queries from ONE cluster → the union keep set stays small
    qs = items[:8] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(5), (8, D), jnp.float32))
    assert_selected_parity(eng.query_batch(qs, k=K, c=c),
                           ref.query_batch(qs, k=K, c=c))
    st = eng._backend.stats
    assert st.fallback in ("", "dense")
    assert st.kept_per_query < 0.8          # per-query pruning engaged
    if not st.fallback:
        assert st.kept_union < st.n_blocks


def test_adversarial_all_blocks_survive():
    """i.i.d. users: every block looks alike, phase A keeps everything,
    and the dense fallback dispatches the inner backend unpruned."""
    from tests.conftest import make_problem
    users, items = make_problem(jax.random.PRNGKey(9), n=1024, m=256, d=D)
    rt = build_rank_table(users, items, CFG_COARSE, jax.random.PRNGKey(1))
    ref = ReverseKRanksEngine(users=users, rank_table=rt,
                              config=CFG_COARSE)
    eng = pruned_engine(users, rt, CFG_COARSE, "dense")
    qs = off_grid_queries(items, 8)
    assert_selected_parity(eng.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))
    st = eng._backend.stats
    assert st.fallback == "dense" and st.kept_per_query > 0.5
    # forcing phase B past the fallback must still be exact
    eng2 = pruned_engine(users, rt, CFG_COARSE, "dense",
                         max_union_frac=1.1)
    assert_selected_parity(eng2.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))
    assert eng2._backend.stats.fallback == ""


# -------------------------------------------------------- delta parity
def churn(eng):
    new = jax.random.normal(jax.random.PRNGKey(11), (16, D), jnp.float32)
    ids = eng.insert_items(new)
    eng.delete_items([3, 17, int(ids[1])])
    eng.delete_users([9, N - 100])
    return ids


@pytest.mark.parametrize("inner", INNERS)
@pytest.mark.parametrize("B", [1, 16])
def test_delta_path_parity(problem, inner, B):
    users, items = problem
    ref = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1), backend=inner)
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend=f"pruned:{inner}")
    eng._backend.block_size = BS
    churn(ref)
    churn(eng)
    qs = off_grid_queries(items, B)
    want = ref.query_batch(qs, k=K, c=1.0)
    got = eng.query_batch(qs, k=K, c=1.0)
    assert eng._backend.stats.fallback in ("", "dense")
    assert_selected_parity(got, want)


def test_delta_guard_falls_back_to_full_scan(problem):
    users, items = problem
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense")
    eng._backend.block_size = BS
    ref = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1))
    big = jax.random.normal(jax.random.PRNGKey(5), (M // 3, D),
                            jnp.float32)          # |delta|/m > guard 0.25
    eng.insert_items(big)
    ref.insert_items(big)
    qs = off_grid_queries(items, 4)
    assert_selected_parity(eng.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))
    assert eng._backend.stats.fallback == "delta-guard"


def test_dead_users_never_selected(problem):
    """Deleting a would-be winner: the pruned path must exclude it via
    the live-count-aware R̂ seed exactly like the full scan."""
    users, items = problem
    ref = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1))
    qs = off_grid_queries(items, 4)
    winners = np.unique(np.asarray(ref.query_batch(qs, k=K, c=1.0).indices))
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense")
    eng._backend.block_size = BS
    ref.delete_users(winners[:3].tolist())
    eng.delete_users(winners[:3].tolist())
    got = eng.query_batch(qs, k=K, c=1.0)
    assert_selected_parity(got, ref.query_batch(qs, k=K, c=1.0))
    assert not np.isin(winners[:3], np.asarray(got.indices)).any()


# ------------------------------------------------- lifecycle / registry
def test_rebuild_regenerates_summaries(problem):
    """A rebuild hot-swap changes the index generation; the summary
    cache must miss and rebuild over the new arrays (identity-keyed)."""
    users, items = problem
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense")
    bk = eng._backend
    snap0 = eng.current_snapshot()
    s0 = bk.summary_for(snap0.rank_table, snap0.users)
    assert bk.summary_for(snap0.rank_table, snap0.users) is s0  # cached
    eng.insert_items(jax.random.normal(jax.random.PRNGKey(2), (8, D)))
    eng.rebuild(reason="test")
    snap1 = eng.current_snapshot()
    s1 = bk.summary_for(snap1.rank_table, snap1.users)
    assert s1 is not s0
    assert int(s1.m) == int(snap1.rank_table.m) == M + 8
    # queries on the rebuilt index still parity-exact
    ref = ReverseKRanksEngine(users=snap1.users,
                              rank_table=snap1.rank_table,
                              config=CFG_COARSE)
    qs = off_grid_queries(items, 4)
    assert_selected_parity(eng.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))


def test_upsert_users_regenerates_summaries(problem):
    """User mutations change the user-array identity without a rebuild —
    the stale box would mis-certify the upserted row's scores."""
    users, items = problem
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense")
    eng._backend.block_size = BS
    ref = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1))
    vec = 3.0 * jax.random.normal(jax.random.PRNGKey(13), (1, D))
    eng.upsert_users(vec, indices=[100])
    ref.upsert_users(vec, indices=[100])
    qs = off_grid_queries(items, 4)
    assert_selected_parity(eng.query_batch(qs, k=K, c=1.0),
                           ref.query_batch(qs, k=K, c=1.0))


def test_registry_and_engine_spec():
    assert "pruned" in BK.available_backends()
    bk = BK.get_backend("pruned")
    assert isinstance(bk, BK.PrunedBackend)
    assert bk.inner.name == "dense"
    assert BK.get_backend("pruned:fused").inner.name == "fused"
    with pytest.raises(ValueError, match="unknown query backend"):
        BK.get_backend("pruned:no-such-inner")


# --------------------------------------------------- spans / counters
def test_keep_sync_is_timed_inside_phase_a(problem, regimes):
    """The blocking read of phase A's keep mask is its own span inside
    `prune.phase_a`, and every prune span carries the id of the tick
    it runs inside."""
    from repro.obs import trace
    users, items = problem
    cfg, rt, c = regimes["non_guaranteed"]
    eng = pruned_engine(users, rt, cfg, "dense", max_union_frac=1.1)
    qs = items[:8] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(5), (8, D), jnp.float32))
    trace.disable()
    trace.clear()
    trace.enable()
    try:
        with trace.span("serve.tick", tick=5):
            eng.query_batch(qs, k=K, c=c)
        recs = trace.spans()
    finally:
        trace.disable()
        trace.clear()
    (sync,) = [r for r in recs if r.name == "prune.keep_sync"]
    (phase_a,) = [r for r in recs if r.name == "prune.phase_a"]
    assert sync.parent == "prune.phase_a" and sync.depth == phase_a.depth + 1
    assert phase_a.t_start <= sync.t_start
    assert (sync.t_start + sync.duration_s
            <= phase_a.t_start + phase_a.duration_s)
    prune = [r for r in recs if r.name.startswith("prune.")]
    assert {r.name for r in prune} == {"prune.query", "prune.phase_a",
                                       "prune.keep_sync", "prune.phase_b"}
    assert all(dict(r.attrs)["tick"] == 5 for r in prune)


def test_block_counters_give_the_window_skip_rate(problem, regimes):
    """`prune_blocks_total` and `prune_blocks_executed_total` move by the
    blocks of each batch and the blocks it scanned (all of them on a
    fallback), so one counter delta gives any window's skip rate."""
    from repro.obs import registry as obs
    users, items = problem
    cfg, rt, c = regimes["non_guaranteed"]
    pruning = pruned_engine(users, rt, cfg, "dense", max_union_frac=1.1)
    falling_back = pruned_engine(users, rt, cfg, "dense", max_union_frac=0.0)
    batches = [(pruning, items[:8]), (falling_back, items[:8]),
               (pruning, off_grid_queries(items, 8)),
               (pruning, items[100:104])]
    old = obs.get_default()
    reg = obs.MetricsRegistry()
    obs.set_default(reg)
    total = executed = 0
    fallbacks = []
    try:
        for eng, qs in batches:
            eng.query_batch(qs, k=K, c=c)
            st = eng._backend.stats
            fallbacks.append(st.fallback)
            total += st.n_blocks
            executed += st.n_blocks if st.fallback else st.kept_union
    finally:
        obs.set_default(old)
    assert "dense" in fallbacks and "" in fallbacks
    assert reg.counter("prune_blocks_total").value == total
    assert reg.counter("prune_blocks_executed_total").value == executed
    skip = 1.0 - (reg.counter("prune_blocks_executed_total").value
                  / reg.counter("prune_blocks_total").value)
    assert skip == pytest.approx(1.0 - executed / total)
    assert 0.0 < skip < 1.0


# --------------------------------------- geometry sketches (PR 6)
SPECS = ("float32", "bfloat16", "int8")


def test_cone_envelopes_tighter_than_box(problem, regimes):
    """The cone∩box envelope is an INTERSECTION: never looser than the
    box alone in rank space, and measurably tighter on clustered blocks
    (the mechanism the PR 6 speedup rests on)."""
    users, items = problem
    _, rt, _ = regimes["non_guaranteed"]
    box = PR.build_block_summary(users, rt, block_size=BS,
                                 with_cones=False)
    cone = PR.build_block_summary(users, rt, block_size=BS)
    assert box.norm_min is None and cone.norm_min is not None
    # μ̂ rows are unit (or exactly 0 — the vacuous cone) and every
    # member's norm sits inside its block's band
    mu_n = np.linalg.norm(np.asarray(cone.mu), axis=1)
    assert np.all((np.abs(mu_n - 1.0) < 1e-5) | (mu_n == 0.0))
    norms = np.linalg.norm(np.asarray(users, np.float32), axis=1)
    for blk in range(cone.n_blocks):
        rows = slice(blk * BS, min((blk + 1) * BS, N))
        assert np.asarray(cone.norm_min)[blk, 0] <= norms[rows].min() + 1e-5
        assert np.asarray(cone.norm_max)[blk, 0] >= norms[rows].max() - 1e-5
    qs = off_grid_queries(items, 8)
    lo_b, up_b = (np.asarray(a) for a in PR._envelope_bounds(box, qs))
    lo_c, up_c = (np.asarray(a) for a in PR._envelope_bounds(cone, qs))
    assert np.all(lo_c >= lo_b - 1e-6) and np.all(up_c <= up_b + 1e-6)
    assert (up_c - lo_c).mean() < (up_b - lo_b).mean()


def _assert_block_containment(summ, r_lo, r_up, lo_env, up_env, n,
                              widen_lo=0.0, widen_up=0.0):
    r_lo, r_up = np.asarray(r_lo), np.asarray(r_up)
    for blk in range(summ.n_blocks):
        rows = slice(blk * BS, min((blk + 1) * BS, n))
        assert np.all(lo_env[blk] - widen_lo
                      <= r_lo[rows].min(axis=0) + 1e-6)
        assert np.all(up_env[blk] + widen_up
                      >= r_up[rows].max(axis=0) - 1e-6)


@pytest.mark.parametrize("spec", SPECS)
def test_cone_band_containment_every_spec(problem, spec):
    """Cone+band envelopes bracket every member's dequant-aware (r↓, r↑)
    at every StorageSpec, and keep bracketing the delta-corrected bounds
    once widened by the phase-A (n_add, n_del) terms — the PR 5 → PR 6
    composition the docstring proof claims."""
    from repro.core.query import user_scores_batch
    from repro.core.rank_table import apply_delta_corrections
    users, items = problem
    cfg = RankTableConfig(tau=16, omega=4, s=8, storage_dtype=spec)
    eng = ReverseKRanksEngine.build(users, items, cfg,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense")
    eng._backend.block_size = BS
    qs = off_grid_queries(items, 8)

    def member_bounds(snap, corr=None):
        su = snap.query_users()
        scores, slack = user_scores_batch(su, qs)
        r_lo, r_up, est = lookup_bounds_batch(snap.rank_table, scores,
                                              slack)
        if corr is not None:
            r_lo, r_up, est = apply_delta_corrections(
                scores, r_lo, r_up, est, corr, slack)
        return r_lo, r_up

    snap = eng.current_snapshot()
    summ = PR.build_block_summary(snap.query_users(), snap.rank_table,
                                  block_size=BS)
    lo_env, up_env = (np.asarray(a)
                      for a in PR._envelope_bounds(summ, qs))
    r_lo, r_up = member_bounds(snap)
    _assert_block_containment(summ, r_lo, r_up, lo_env, up_env, N)

    # item churn: the corrected bounds shift by at most (+n_add, −n_del),
    # exactly the widening phase A applies to the STATIC envelopes
    eng.insert_items(jax.random.normal(jax.random.PRNGKey(3), (12, D),
                                       jnp.float32))
    eng.delete_items([5, 29, 131])
    snap2 = eng.current_snapshot()
    assert snap2.corr is not None
    r_lo_c, r_up_c = member_bounds(snap2, corr=snap2.corr)
    n_add, n_del = snap2.delta.n_added, snap2.delta.n_deleted
    assert n_add == 12 and n_del == 3
    _assert_block_containment(summ, r_lo_c, r_up_c, lo_env, up_env, N,
                              widen_lo=n_del, widen_up=n_add)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           spec=st.sampled_from(SPECS),
           block_size=st.sampled_from([32, 64]),
           scale=st.floats(0.1, 10.0))
    def test_cone_band_containment_property(seed, spec, block_size,
                                            scale):
        """Random problems × specs × block sizes × data scales: the
        cone+band envelopes must contain the true per-block (r↓, r↑)
        range — including blocks holding near-antipodal or near-zero
        rows, where the cone math has its branch points."""
        from repro.core.query import user_scores_batch
        key = jax.random.PRNGKey(seed)
        ku, ki, kz, kq = jax.random.split(key, 4)
        n, m, d = 192, 96, 8
        users = scale * jax.random.normal(ku, (n, d), jnp.float32)
        # a few exactly-zero and antipodal rows to hit the degenerate
        # branches (vacuous cone, n↓ = 0, cosθ ≤ −cos r)
        users = users.at[:2].set(0.0).at[2].set(-users[3])
        items = scale * jax.random.normal(ki, (m, d), jnp.float32)
        cfg = RankTableConfig(tau=8, omega=2, s=8, storage_dtype=spec)
        rt = build_rank_table(users, items, cfg, kz)
        su = cfg.storage.pack_users(users)
        su = users if su is None else su
        summ = PR.build_block_summary(su, rt, block_size=block_size)
        qs = items[:4] * (1.0 + 1e-3 * jax.random.normal(
            kq, (4, d), jnp.float32))
        scores, slack = user_scores_batch(su, qs)
        r_lo, r_up, _ = lookup_bounds_batch(rt, scores, slack)
        lo_env, up_env = (np.asarray(a)
                          for a in PR._envelope_bounds(summ, qs))
        r_lo, r_up = np.asarray(r_lo), np.asarray(r_up)
        for blk in range(summ.n_blocks):
            rows = slice(blk * block_size,
                         min((blk + 1) * block_size, n))
            assert np.all(lo_env[blk] <= r_lo[rows].min(axis=0) + 1e-6)
            assert np.all(up_env[blk] >= r_up[rows].max(axis=0) - 1e-6)
else:
    @pytest.mark.skip(reason="hypothesis not installed (optional test "
                             "extra)")
    def test_cone_band_containment_property():
        pass


# ------------------------------------------ k-means layout (PR 6)
def shuffled_clustered(key):
    """Clustered users whose ROW ORDER carries no structure — the layout
    the build-time reorder exists to fix."""
    users, items = clustered_problem(key)
    sh = jax.random.permutation(jax.random.fold_in(key, 99), N)
    return users[sh], items


def test_kmeans_layout_recovers_contiguity():
    users, items = shuffled_clustered(jax.random.PRNGKey(21))
    perm = PR.kmeans_layout(users, block_size=BS, n_clusters=32)
    assert perm is not None and perm.dtype == np.int64
    assert np.array_equal(np.sort(perm), np.arange(N))      # a permutation
    # too-small matrices refuse to reorder (nothing to tile)
    assert PR.kmeans_layout(users[:BS], block_size=BS) is None
    rt = build_rank_table(users, items, CFG_COARSE, jax.random.PRNGKey(1))
    j = jnp.asarray(perm)
    s_raw = PR.build_block_summary(users, rt, block_size=BS)
    s_re = PR.build_block_summary(users[j], rt.take_rows(j), block_size=BS)
    qs = off_grid_queries(items, 8)
    lo_raw, up_raw = (np.asarray(a) for a in PR._envelope_bounds(s_raw, qs))
    lo_re, up_re = (np.asarray(a) for a in PR._envelope_bounds(s_re, qs))
    # shuffled blocks mix all 16 clusters → near-vacuous envelopes;
    # reordered blocks are (near-)single-cluster → strictly tighter
    assert (up_re - lo_re).mean() < (up_raw - lo_raw).mean()


@pytest.mark.parametrize("inner", INNERS)
@pytest.mark.parametrize("B", [1, 16])
def test_reordered_parity(inner, B):
    """build(cluster_reorder=True): bit-identical to the unpruned inner
    on the SAME reordered layout, and remap-translated indices identical
    to an engine that never reordered (pre-remap user coordinates).

    The cross-layout check needs the exact-threshold table: per-user
    (r↓, r↑, est) are then layout-invariant bit-for-bit (per-row ops),
    so selections can only differ through index TIE-BREAKS — and exact-
    mode est is continuous, so clustered Gaussian users don't tie. A
    coarse sampled grid quantizes est into genuine ties whose index
    tie-break legitimately differs between layouts (same reason the
    repo's parity contract is per-layout, not cross-layout)."""
    users, items = shuffled_clustered(jax.random.PRNGKey(23))
    exact_cfg = RankTableConfig(tau=64, omega=4, s=M // 4,
                                threshold_mode="exact")
    eng = ReverseKRanksEngine.build(users, items, exact_cfg,
                                    jax.random.PRNGKey(1),
                                    backend=f"pruned:{inner}",
                                    cluster_reorder=True)
    eng._backend.block_size = BS
    raw = ReverseKRanksEngine.build(users, items, exact_cfg,
                                    jax.random.PRNGKey(1), backend=inner)
    snap = eng.current_snapshot()
    remap = snap.user_remap
    assert remap is not None and np.array_equal(np.sort(remap),
                                                np.arange(N))
    ref = ReverseKRanksEngine(users=snap.users,
                              rank_table=snap.rank_table,
                              config=exact_cfg, backend=inner)
    qs = off_grid_queries(items, B)
    got = eng.query_batch(qs, k=K, c=1.0)
    assert_selected_parity(got, ref.query_batch(qs, k=K, c=1.0))
    np.testing.assert_array_equal(
        snap.client_user_ids(np.asarray(got.indices)),
        np.asarray(raw.query_batch(qs, k=K, c=1.0).indices))


def test_reorder_then_mutate_parity():
    """The remap keeps translating across post-reorder churn, and user
    mutations address CURRENT coordinates (the documented contract)."""
    users, items = shuffled_clustered(jax.random.PRNGKey(29))
    eng = ReverseKRanksEngine.build(users, items, CFG_COARSE,
                                    jax.random.PRNGKey(1),
                                    backend="pruned:dense",
                                    cluster_reorder=True)
    eng._backend.block_size = BS
    snap = eng.current_snapshot()
    ref = ReverseKRanksEngine(users=snap.users,
                              rank_table=snap.rank_table,
                              config=CFG_COARSE, items=items,
                              build_key=jax.random.PRNGKey(1))
    churn(eng)
    new = jax.random.normal(jax.random.PRNGKey(11), (16, D), jnp.float32)
    ids = ref.insert_items(new)
    ref.delete_items([3, 17, int(ids[1])])
    ref.delete_users([9, N - 100])
    qs = off_grid_queries(items, 8)
    got = eng.query_batch(qs, k=K, c=1.0)
    assert_selected_parity(got, ref.query_batch(qs, k=K, c=1.0))
    # translation still goes through the (unchanged) epoch-0 remap
    tr = eng.current_snapshot().client_user_ids(np.asarray(got.indices))
    assert np.array_equal(np.asarray(snap.user_remap)[tr],
                          np.asarray(got.indices))


def test_sharded_alignment_fallback(problem):
    """Tiles straddling shard boundaries are refused up front: the
    sharded inner runs unpruned rather than mis-gathering."""
    users, items = problem
    rt = build_rank_table(users, items, CFG_COARSE, jax.random.PRNGKey(1))
    eng = pruned_engine(users, rt, CFG_COARSE, "sharded",
                        block_size=3 * BS)  # n % (P·bs) != 0 for any P>1
    ref = ReverseKRanksEngine(users=users, rank_table=rt,
                              config=CFG_COARSE, backend="sharded")
    qs = off_grid_queries(items, 4)
    got = eng.query_batch(qs, k=K, c=1.0)
    assert_selected_parity(got, ref.query_batch(qs, k=K, c=1.0))
    if jax.device_count() > 1:
        assert eng._backend.stats.fallback == "align"
