"""Serving-subsystem tests (the PR-2 tentpole): micro-batching scheduler
partial-tick padding, caching-backend dedupe/LRU, and the wrapper
registry — all pinned to a BIT-IDENTITY contract against direct
`engine.query_batch` execution.

Why bit-identity is attainable: a batched matmul's output column (i, j)
depends only on user row i, query column j, and the accumulation order —
never on the other columns' VALUES — so padding a partial tick to the
compiled batch shape (or deduping duplicates out of it) cannot perturb
the real queries' scores, and everything downstream (bucketize, bounds,
top-k) is per-row deterministic. The one platform caveat: a width-1
dispatch lowers as a matvec with a DIFFERENT accumulation order (see the
PR-1 note in tests/test_backends.py), so width-1 blocks compare on the
table-derived integer-valued fields with `est` at float accuracy, and
the serving paths never shrink a multi-query dispatch below width 2.

Queries are perturbed off the items so no score lands exactly on a
threshold-grid point (where a 1-ulp difference could legitimately flip
the bucketize) — same convention as tests/test_backends.py.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test extra — `pip install repro[test]` (see pyproject.toml)
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None

from repro.core import backends as BK
from repro.core.engine import ReverseKRanksEngine
from repro.core.rank_table import build_rank_table
from repro.core.types import RankTableConfig
from repro.serve import CachingBackend, MicroBatcher, QueueFull, pad_block
from tests.conftest import make_problem

ALL_BACKENDS = ("dense", "fused", "sharded")
K, C = 7, 2.0
MAX_BATCH = 8

# integer-valued-in-rank-space fields: must match bitwise even across the
# width-1 matvec lowering; `est` is continuous in the score's low bits.
_EXACT_FIELDS = ("indices", "r_lo", "r_up", "R_lo_k", "R_up_k",
                 "guaranteed", "n_accepted", "n_pruned")


def assert_bitwise(got, want, fields=None):
    for f in (fields or want._fields):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"field {f!r} not bit-identical")


@pytest.fixture(scope="module")
def problem():
    return make_problem(jax.random.PRNGKey(42), n=512, m=400, d=16)


@pytest.fixture(scope="module")
def rank_table(problem):
    users, items = problem
    return build_rank_table(users, items, RankTableConfig(tau=16, omega=4,
                                                          s=8),
                            jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def queries(problem):
    """MAX_BATCH off-grid queries (see module docstring)."""
    _, items = problem
    base = items[(1 + jnp.arange(MAX_BATCH) * 13) % items.shape[0]]
    return base * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), base.shape, jnp.float32))


def _engine(problem, rank_table, backend):
    users, _ = problem
    return ReverseKRanksEngine(users=users, rank_table=rank_table,
                               config=RankTableConfig(tau=16, omega=4, s=8),
                               backend=backend)


# ------------------------------------------------------------- scheduler
@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("size", [2, 3, MAX_BATCH - 1, MAX_BATCH])
def test_padded_partial_tick_bitwise(problem, rank_table, queries, backend,
                                     size):
    """(a) A partial tick padded to the compiled max_batch shape returns
    results bit-identical to direct query_batch on the UNPADDED block."""
    eng = _engine(problem, rank_table, backend)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=25.0) as mb:
        futs = [mb.submit(q, K, C) for q in queries[:size]]
        results = [f.result(timeout=120) for f in futs]
    direct = eng.query_batch(queries[:size], k=K, c=C)
    for i, res in enumerate(results):
        want = jax.tree_util.tree_map(lambda x, i=i: x[i], direct)
        assert_bitwise(res, want)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_singleton_tick_matches_direct(problem, rank_table, queries,
                                       backend):
    """A width-1 tick is padded like any other; vs direct B = 1 execution
    (a matvec lowering with different accumulation order) the table-
    derived fields still match exactly, `est` at float accuracy."""
    eng = _engine(problem, rank_table, backend)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=5.0) as mb:
        res = mb.submit(queries[0], K, C).result(timeout=120)
    direct = eng.query_batch(queries[:1], k=K, c=C)
    want = jax.tree_util.tree_map(lambda x: x[0], direct)
    assert_bitwise(res, want, fields=_EXACT_FIELDS)
    np.testing.assert_allclose(np.asarray(res.est_rank),
                               np.asarray(want.est_rank), rtol=1e-5,
                               atol=1e-4)


def test_scheduler_coalesces_and_reports(problem, rank_table, queries):
    """Full bursts dispatch as full ticks; stats see every request."""
    eng = _engine(problem, rank_table, "dense")
    eng.query_batch(queries, k=K, c=C)          # pre-compile the tick shape
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=200.0) as mb:
        futs = [mb.submit(q, K, C) for q in queries] * 1
        futs += [mb.submit(q, K, C) for q in queries]
        for f in futs:
            f.result(timeout=120)
        st = mb.stats()
    assert st.requests == 2 * MAX_BATCH
    assert st.ticks == 2                        # coalesced, not 16 ticks
    assert st.mean_fill == 1.0
    assert st.p99_ms >= st.p50_ms >= 0.0
    log = mb.tick_log
    assert all(t.batch == MAX_BATCH for t in log)
    assert all(len(t.latencies_ms) == t.batch for t in log)


def test_tick_log_and_stats_return_copies(problem, rank_table, queries):
    """`tick_log`/`stats()` hand out SNAPSHOTS: mutating the returned
    list (or calling them concurrently with dispatches) must never
    reach the scheduler's live `_ticks` deque."""
    eng = _engine(problem, rank_table, "dense")
    # a wait no run reaches: each burst of MAX_BATCH cuts one tick when
    # it is full, however slowly the submits arrive
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=600e3) as mb:
        for f in [mb.submit(q, K, C) for q in queries]:
            f.result(timeout=120)
        log = mb.tick_log
        assert log is not mb._ticks
        log.clear()                             # vandalize the copy
        log.append("junk")
        assert len(mb.tick_log) == 1            # live state untouched
        st_before = mb.stats()
        for f in [mb.submit(q, K, C) for q in queries]:
            f.result(timeout=120)
        # the earlier snapshots are immutable history, not live views
        assert st_before.requests == MAX_BATCH
        assert mb.stats().requests == 2 * MAX_BATCH
        assert len(mb.tick_log) == 2


def test_scheduler_separates_static_args(problem, rank_table, queries):
    """Requests with different (k, c) never share a tick (they cannot
    share a compiled batch program), yet all resolve correctly."""
    eng = _engine(problem, rank_table, "dense")
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=10.0) as mb:
        f1 = mb.submit(queries[0], K, C)
        f2 = mb.submit(queries[1], K + 2, C)
        f3 = mb.submit(queries[2], K, 1.0)
        r1, r2, r3 = (f.result(timeout=120) for f in (f1, f2, f3))
        assert len(mb.tick_log) == 3
    assert r1.indices.shape == (K,)
    assert r2.indices.shape == (K + 2,)
    assert r3.indices.shape == (K,)


def test_full_group_preempts_straggler_head(problem, rank_table, queries):
    """A FULL (k, c) group queued behind a lone different-key head
    dispatches immediately instead of waiting out the head's deadline
    (no head-of-line blocking); the head still dispatches by deadline."""
    eng = _engine(problem, rank_table, "dense")
    eng.query_batch(queries, k=K, c=C)          # pre-compile both shapes
    eng.query_batch(queries, k=K, c=1.0)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=400.0) as mb:
        t0 = time.monotonic()
        straggler = mb.submit(queries[0], K, 1.0)
        group = [mb.submit(q, K, C) for q in queries]   # fills max_batch
        for f in group:
            f.result(timeout=120)
        group_done = time.monotonic() - t0
        straggler.result(timeout=120)
        log = mb.tick_log
    assert group_done < 0.4, f"full group waited on the head ({group_done})"
    assert log[0].batch == MAX_BATCH            # the group went first
    assert [t.batch for t in log] == [MAX_BATCH, 1]


def test_scheduler_error_propagates(problem, rank_table):
    """A failing dispatch resolves every Future of the tick with the
    exception instead of hanging the client."""
    eng = _engine(problem, rank_table, "dense")
    with MicroBatcher(eng, max_batch=4, max_wait_ms=5.0) as mb:
        bad = mb.submit(jnp.zeros(3), K, C)     # wrong d: jit shape error
        with pytest.raises(Exception):
            bad.result(timeout=120)


def test_pad_block_shapes(queries):
    assert pad_block(queries[:3], MAX_BATCH).shape == (MAX_BATCH, 16)
    assert pad_block(queries, MAX_BATCH) is queries
    padded = np.asarray(pad_block(queries[:2], 4))
    np.testing.assert_array_equal(padded[2], padded[1])   # edge padding
    np.testing.assert_array_equal(padded[3], padded[1])
    with pytest.raises(ValueError, match="does not fit"):
        pad_block(queries, 4)


# ----------------------------------------------------------------- cache
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_cached_bitwise_all_backends(problem, rank_table, queries, backend):
    """(b) Dedupe + LRU-cached results are bit-identical to uncached
    dispatch: duplicate-heavy first tick (dedupe path), full-hit second
    tick (LRU path), overlapping third tick (mixed hit/miss path)."""
    eng = _engine(problem, rank_table, f"cached:{backend}")
    ref = _engine(problem, rank_table, backend)
    assert eng.backend_name == f"cached:{backend}"

    dup = queries[jnp.asarray([0, 1, 0, 2, 1, 0])]        # 6 rows, 3 unique
    assert_bitwise(eng.query_batch(dup, k=K, c=C),
                   ref.query_batch(dup, k=K, c=C))
    cache = eng._backend
    assert cache.misses == 6 and cache.hits == 0          # all cold rows

    assert_bitwise(eng.query_batch(dup, k=K, c=C),        # pure LRU hits
                   ref.query_batch(dup, k=K, c=C))
    assert cache.hits == 6

    mixed = queries[jnp.asarray([2, 3, 4, 0])]            # 2 hits, 2 misses
    assert_bitwise(eng.query_batch(mixed, k=K, c=C),
                   ref.query_batch(mixed, k=K, c=C))
    assert cache.hits == 8 and cache.misses == 8


def test_cached_keyed_by_k_and_c(problem, rank_table, queries):
    """Same query bytes under different (k, c) are different cache
    entries — the selection depends on both."""
    eng = _engine(problem, rank_table, "cached:dense")
    ref = _engine(problem, rank_table, "dense")
    qs = queries[:2]
    eng.query_batch(qs, k=K, c=C)
    for k, c in ((K, 1.0), (K + 2, C)):
        assert_bitwise(eng.query_batch(qs, k=k, c=c),
                       ref.query_batch(qs, k=k, c=c))
    assert eng._backend.hits == 0                         # no false sharing


def test_cached_lru_eviction_and_invalidation(problem, rank_table, queries):
    users, items = problem
    cache = CachingBackend("dense", capacity=2)
    rt = rank_table
    cache.query_batch(rt, users, queries[:3], k=K, c=C)
    assert cache.evictions == 1 and len(cache._lru) == 2
    # evicted head misses again; the two surviving entries hit
    cache.query_batch(rt, users, queries[:3], k=K, c=C)
    assert cache.hits == 2 and cache.misses == 4

    # rebuilding the index invalidates every cached result
    rt2 = build_rank_table(users, items,
                           RankTableConfig(tau=32, omega=4, s=8),
                           jax.random.PRNGKey(3))
    ref = BK.get_backend("dense")
    got = cache.query_batch(rt2, users, queries[:2], k=K, c=C)
    assert_bitwise(got, ref.query_batch(rt2, users, queries[:2], k=K, c=C))


def test_cached_through_scheduler_bitwise(problem, rank_table, queries):
    """The full serving stack — scheduler padding + cache dedupe (pad
    rows collapse into the last real query) — stays bit-identical to
    direct uncached execution of the unpadded block."""
    eng = _engine(problem, rank_table, "cached:dense")
    ref = _engine(problem, rank_table, "dense")
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=25.0) as mb:
        futs = [mb.submit(q, K, C) for q in queries[:3]]
        results = [f.result(timeout=120) for f in futs]
    direct = ref.query_batch(queries[:3], k=K, c=C)
    for i, res in enumerate(results):
        assert_bitwise(res, jax.tree_util.tree_map(lambda x, i=i: x[i],
                                                   direct))


# -------------------------------------------------- registry edge cases
def test_cached_unknown_inner_raises():
    """"cached:<unknown>" surfaces the available-backends ValueError."""
    with pytest.raises(ValueError, match="unknown query backend"):
        BK.get_backend("cached:no-such-backend")
    with pytest.raises(ValueError) as ei:
        BK.get_backend("cached:no-such-backend")
    for name in ALL_BACKENDS:
        assert name in str(ei.value)


def test_unknown_wrapper_prefix_raises():
    with pytest.raises(ValueError, match="unknown query backend"):
        BK.get_backend("zip:dense")


def test_cached_sharded_preserves_candidate_shape(problem, rank_table,
                                                  queries):
    """Wrapping "sharded" preserves its (B, k·P) candidate-set result
    shape — the cache stacks per-query slices, it does not reshape."""
    eng = _engine(problem, rank_table, "cached:sharded")
    P = jax.device_count()
    B = 4
    res = eng.query_batch(queries[:B], k=K, c=C)
    want = _engine(problem, rank_table, "sharded").query_batch(
        queries[:B], k=K, c=C)
    assert want.r_lo.shape == (B, K * P)      # sharded contract, uncached
    assert res.r_lo.shape == (B, K * P)
    assert res.r_up.shape == (B, K * P)
    assert res.indices.shape == (B, K)
    assert_bitwise(res, want)


def test_wrapper_backend_accepted_by_engine_build(problem):
    users, items = problem
    eng = ReverseKRanksEngine.build(
        users, items, RankTableConfig(tau=16, omega=4, s=8),
        jax.random.PRNGKey(0), backend="cached:dense")
    assert eng.backend_name == "cached:dense"
    res = eng.query(items[3], k=K, c=C)
    assert res.indices.shape == (K,)


# -------------------------------------------- PR 7 satellite regressions
def test_cache_key_canonicalizes_negzero_and_nan():
    """`_key_bytes` must give one key per semantically-equal query row:
    −0.0 vs +0.0 and differing NaN payloads score identically, so keying
    the raw f32 bit pattern (the old behavior) made such re-asks LRU
    misses — in both the raw and quantized key paths."""
    raw = CachingBackend("dense")
    quant = CachingBackend("dense", quantize_key_bits=8)
    d = 8
    a = np.linspace(-1.0, 1.0, d).astype(np.float32)
    a[0] = np.float32(0.0)
    b = a.copy()
    b[0] = np.float32(-0.0)
    assert a.tobytes() != b.tobytes()           # distinct raw bit patterns
    assert raw._key_bytes(a) == raw._key_bytes(b)
    assert quant._key_bytes(a) == quant._key_bytes(b)

    n1, n2 = a.copy(), a.copy()
    n1.view(np.uint32)[1] = np.uint32(0x7FC00001)   # qNaN, payload 1
    n2.view(np.uint32)[1] = np.uint32(0xFFC00000)   # −qNaN, payload 0
    assert np.isnan(n1[1]) and np.isnan(n2[1])
    assert n1.tobytes() != n2.tobytes()
    assert raw._key_bytes(n1) == raw._key_bytes(n2)
    # quantized path: NaN rows take the non-finite raw-bytes fallback,
    # which must ALSO see canonical bytes
    assert quant._key_bytes(n1) == quant._key_bytes(n2)

    # all-zero rows take the amax == 0 fallback — same requirement
    z1 = np.zeros(d, np.float32)
    z2 = np.full(d, -0.0, np.float32)
    assert z1.tobytes() != z2.tobytes()
    assert quant._key_bytes(z1) == quant._key_bytes(z2)

    # canonicalization works on a copy, never the caller's row
    keep = b.tobytes()
    raw._key_bytes(b)
    assert b.tobytes() == keep


def test_cache_hits_on_negzero_requery(problem, rank_table, queries):
    """End-to-end: re-asking a cached query with −0.0 instead of +0.0 in
    a coordinate is an LRU HIT serving the identical result."""
    users, _ = problem
    cache = CachingBackend("dense")
    q1 = np.asarray(queries[:1]).copy()
    q1[0, 0] = np.float32(0.0)
    q2 = q1.copy()
    q2[0, 0] = np.float32(-0.0)
    r1 = cache.query_batch(rank_table, users, jnp.asarray(q1), k=K, c=C)
    assert cache.misses == 1 and cache.hits == 0
    r2 = cache.query_batch(rank_table, users, jnp.asarray(q2), k=K, c=C)
    assert cache.misses == 1 and cache.hits == 1
    assert_bitwise(r2, r1)


def test_microbatcher_rejects_width_one(problem, rank_table, queries):
    """Boundary (satellite): max_batch=1 contradicts the module's
    "dispatches never shrink below width 2" invariant and is rejected;
    max_batch=2 — the boundary the invariant allows — works."""
    eng = _engine(problem, rank_table, "dense")
    with pytest.raises(ValueError, match="max_batch must be >= 2"):
        MicroBatcher(eng, max_batch=1)
    with MicroBatcher(eng, max_batch=2, max_wait_ms=5.0) as mb:
        res = mb.submit(queries[0], K, C).result(timeout=120)
    assert res.indices.shape == (K,)


def test_pad_block_width_boundaries(queries):
    """`pad_block` rejects the b = 0 / b > max_batch caller errors AND
    the max_batch < 2 target the old check let through."""
    with pytest.raises(ValueError, match="max_batch must be >= 2"):
        pad_block(queries[:1], 1)
    with pytest.raises(ValueError, match="does not fit"):
        pad_block(queries[:0], 4)
    with pytest.raises(ValueError, match="does not fit"):
        pad_block(queries, 4)


class _FailingEngine:
    """query_batch always raises — exercises the dispatch error path."""

    def query_batch(self, qs, *, k, c):
        raise RuntimeError("induced dispatch failure")


def test_close_under_rejection_flushes_terminal_tick():
    """Satellite: rejects carried by a tick whose dispatch FAILS are
    re-credited, and rejects left after the final tick are flushed into
    a terminal TickStats at close() — no rejection ever vanishes from
    the accounting, and stats() survives a latency-free log."""
    mb = MicroBatcher(_FailingEngine(), max_batch=2, max_wait_ms=60_000.0,
                      max_depth=1)
    try:
        fut = mb.submit(jnp.zeros(4, jnp.float32), K, C)   # queued (head)
        with pytest.raises(QueueFull):
            mb.submit(jnp.ones(4, jnp.float32), K, C)      # depth bound
    finally:
        mb.close()      # cuts the head tick; its dispatch raises
    with pytest.raises(RuntimeError, match="induced dispatch failure"):
        fut.result(timeout=120)
    log = mb.tick_log
    # the failed dispatch recorded no TickStats; the terminal record
    # carries its re-credited rejection
    assert len(log) == 1
    assert log[0].batch == 0 and log[0].latencies_ms == ()
    assert log[0].rejected == 1
    st = mb.stats()
    assert st.rejected == 1 and st.requests == 0 and st.ticks == 1
    assert st.p50_ms == 0.0 and st.p99_ms == 0.0      # no percentile crash
    assert sum(t.rejected for t in log) == st.rejected


def test_tick_compile_counter_flat_after_warmup(problem, rank_table,
                                                queries):
    """Tentpole observability: `TickStats.compiles` samples the query
    stack's compiled-program count around each dispatch. On the elastic
    backend a steady-state tick compiles NOTHING; the warm-up tick (a
    never-seen k makes it a guaranteed fresh trace) is where the programs
    appear."""
    eng = _engine(problem, rank_table, "elastic:dense")
    k_fresh = K + 3                 # unique static k → tick 1 must trace
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=200.0) as mb:
        for _ in range(2):
            futs = [mb.submit(q, k_fresh, C) for q in queries]
            for f in futs:
                f.result(timeout=120)
    log = mb.tick_log
    assert len(log) == 2
    assert log[0].compiles >= 1     # warm-up trace observed
    assert log[1].compiles == 0     # steady state: compile-once holds


# ------------------------------------------------- hypothesis property
if given is not None:
    @settings(max_examples=12, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, MAX_BATCH - 1),   # query id
                              st.sampled_from([0.0, 0.5, 2.0])),  # gap ms
                    min_size=1, max_size=12))
    def test_random_arrival_patterns(arrivals):
        """(c) Under arbitrary arrival patterns (bursts, stragglers,
        duplicates) every request resolves to the direct per-query
        reference, and the tick accounting adds up."""
        import time
        users, items = make_problem(jax.random.PRNGKey(42), n=512, m=400,
                                    d=16)
        rt = build_rank_table(users, items,
                              RankTableConfig(tau=16, omega=4, s=8),
                              jax.random.PRNGKey(1))
        eng = ReverseKRanksEngine(
            users=users, rank_table=rt,
            config=RankTableConfig(tau=16, omega=4, s=8), backend="dense")
        base = items[(1 + jnp.arange(MAX_BATCH) * 13) % items.shape[0]]
        qs = base * (1.0 + 1e-4 * jax.random.normal(
            jax.random.PRNGKey(7), base.shape, jnp.float32))
        refs = eng.query_batch(qs, k=K, c=C)

        with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=3.0) as mb:
            futs = []
            for qi, gap_ms in arrivals:
                if gap_ms:
                    time.sleep(gap_ms / 1e3)
                futs.append((qi, mb.submit(qs[qi], K, C)))
            results = [(qi, f.result(timeout=120)) for qi, f in futs]
            st_agg = mb.stats()

        for qi, res in results:
            want = jax.tree_util.tree_map(lambda x: x[qi], refs)
            assert_bitwise(res, want, fields=_EXACT_FIELDS)
            np.testing.assert_allclose(np.asarray(res.est_rank),
                                       np.asarray(want.est_rank),
                                       rtol=1e-5, atol=1e-4)
        assert st_agg.requests == len(arrivals)
        log = mb.tick_log
        assert sum(t.batch for t in log) == len(arrivals)
        assert all(0 < t.fill_ratio <= 1.0 for t in log)
else:  # pragma: no cover - optional dep absent
    @pytest.mark.skip(reason="hypothesis not installed (optional test extra)")
    def test_random_arrival_patterns():
        pass


# ---------------------------------------------- overlapped pipeline (PR 10)
class _SlowLeaf:
    """A host-readback leaf whose materialization sleeps: models a device
    result whose D2H is slow, so the completion stage lags dispatch and
    ticks verifiably pile up in flight — without touching real devices."""

    def __init__(self, arr, delay_s):
        self.arr = np.asarray(arr)
        self.delay_s = float(delay_s)

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.arr, dtype)


class _EchoResult(tuple):
    pass


from typing import NamedTuple as _NamedTuple


class _Echo(_NamedTuple):
    rows: object


class _SlowReadbackEngine:
    """Duck engine: dispatch is instant (async-dispatch analogue), the
    result's host readback sleeps `delay_s`. Echoes the query block so
    per-request results identify their query."""

    def __init__(self, delay_s=0.03):
        self.delay_s = float(delay_s)
        self.calls = 0

    def query_batch(self, qs, *, k, c):
        self.calls += 1
        return _Echo(_SlowLeaf(np.asarray(qs), self.delay_s))


def _pipe_engine(problem, rank_table, backend, storage="float32"):
    users, items = problem
    cfg = RankTableConfig(tau=16, omega=4, s=8, storage_dtype=storage)
    rt = (rank_table if storage == "float32"
          else build_rank_table(users, items, cfg, jax.random.PRNGKey(1)))
    return ReverseKRanksEngine(users=users, rank_table=rt, config=cfg,
                               backend=backend)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("storage", ["float32", "int8"])
@pytest.mark.parametrize("size", [3, 2 * MAX_BATCH + 3])
def test_pipelined_vs_sync_bit_identity(problem, rank_table, backend,
                                        storage, size):
    """The tentpole contract: the double-buffered pipeline returns
    results bit-identical to the synchronous schedule (pipeline_depth=1)
    AND to direct query_batch, per backend × storage spec, for partial
    and multi-tick request streams."""
    eng = _pipe_engine(problem, rank_table, backend, storage)
    users, items = problem
    base = items[(1 + jnp.arange(size) * 7) % items.shape[0]]
    qs = base * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(11), base.shape, jnp.float32))
    direct = eng.query_batch(qs, k=K, c=C)

    def run(depth):
        with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=25.0,
                          pipeline_depth=depth) as mb:
            futs = [mb.submit(q, K, C) for q in qs]
            return [f.result(timeout=120) for f in futs]

    piped, sync = run(2), run(1)
    for i, (p, s) in enumerate(zip(piped, sync)):
        want = jax.tree_util.tree_map(lambda x, i=i: x[i], direct)
        assert_bitwise(p, want)
        assert_bitwise(p, s)


def test_pipeline_depth_validation(problem, rank_table):
    eng = _engine(problem, rank_table, "dense")
    with pytest.raises(ValueError, match="pipeline_depth"):
        MicroBatcher(eng, max_batch=MAX_BATCH, pipeline_depth=0)


@pytest.mark.concurrency
def test_pipeline_overlaps_ticks_and_bounds_inflight():
    """With a slow completion stage, the dispatcher keeps cutting ticks
    until `pipeline_depth` are in flight — and never past it; the
    synchronous schedule (depth 1) never overlaps."""
    d = 8
    qs = np.random.default_rng(0).standard_normal(
        (4 * MAX_BATCH, d)).astype(np.float32)

    def run(depth):
        eng = _SlowReadbackEngine(delay_s=0.03)
        with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=1.0,
                          pipeline_depth=depth) as mb:
            futs = [mb.submit(q, K, C) for q in qs]
            for i, f in enumerate(futs):
                got = f.result(timeout=60)
                np.testing.assert_array_equal(got.rows, qs[i])
        return mb.tick_log, mb.stats()

    log2, st2 = run(2)
    assert max(t.inflight for t in log2) == 2      # overlapped, bounded
    assert st2.overlap_efficiency > 0.0
    log1, st1 = run(1)
    assert max(t.inflight for t in log1) == 1      # sync baseline
    assert st1.overlap_efficiency == 0.0


@pytest.mark.concurrency
def test_futures_resolve_in_dispatch_order():
    """Completion consumes in-flight ticks FIFO: futures resolve in
    submission order even with several ticks in flight."""
    d = 8
    qs = np.random.default_rng(1).standard_normal(
        (3 * MAX_BATCH, d)).astype(np.float32)
    order: list = []
    eng = _SlowReadbackEngine(delay_s=0.02)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=1.0,
                      pipeline_depth=3) as mb:
        futs = []
        for i, q in enumerate(qs):
            f = mb.submit(q, K, C)
            f.add_done_callback(lambda _, i=i: order.append(i))
            futs.append(f)
        for f in futs:
            f.result(timeout=60)
    assert order == sorted(order)


@pytest.mark.concurrency
def test_deadline_under_overlap_only_sheds_undispatched():
    """A request whose budget lapses while its tick is IN FLIGHT still
    resolves (dispatched = committed); one that lapses in the queue
    behind a busy pipeline is swept with the typed error."""
    d = 8
    qs = np.random.default_rng(2).standard_normal(
        (MAX_BATCH + 1, d)).astype(np.float32)
    eng = _SlowReadbackEngine(delay_s=0.05)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=1.0,
                      pipeline_depth=1) as mb:
        # full tick: cuts immediately, completes after ~50 ms — well past
        # its 20 ms budgets, but dispatch already committed it
        committed = [mb.submit(q, K, C, deadline_ms=20.0)
                     for q in qs[:MAX_BATCH]]
        # straggler: queued behind the busy pipeline, budget lapses there
        from repro.serve import DeadlineExceeded
        doomed = mb.submit(qs[-1], K, C, deadline_ms=10.0)
        for i, f in enumerate(committed):
            np.testing.assert_array_equal(f.result(timeout=60).rows, qs[i])
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
    st = mb.stats()
    assert st.expired == 1
    assert sum(t.expired for t in mb.tick_log) == 1


def test_admission_hit_resolves_without_tick(problem, rank_table):
    """PR 10 admission path: an exact LRU hit resolves at submit —
    bitwise the cached result — occupying no queue or tick slot."""
    eng = _engine(problem, rank_table, "cached:dense")
    users, items = problem
    hot = items[0] * 1.0001
    want = eng.query(hot, k=K, c=C)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=5.0) as mb:
        before = mb._m_admission.value    # registry counter is process-global
        f = mb.submit(hot, K, C)
        got = f.result(timeout=10)
        assert_bitwise(got, want)
        st = mb.stats()
        after = mb._m_admission.value
    assert st.admission_hits == 1
    assert st.requests == 1
    assert mb.tick_log == []            # never became a tick
    assert after == before + 1.0


def test_admission_miss_takes_normal_path(problem, rank_table):
    """A cold query under a cached backend still coalesces into a tick,
    and the NEXT ask of the same query hits at admission."""
    eng = _engine(problem, rank_table, "cached:dense")
    users, items = problem
    q = items[3] * 1.0001
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=5.0) as mb:
        first = mb.submit(q, K, C).result(timeout=60)
        mb.flush()
        second = mb.submit(q, K, C).result(timeout=60)
        st = mb.stats()
    assert_bitwise(second, first)
    assert st.admission_hits == 1
    assert st.requests == 2
    assert sum(t.batch for t in mb.tick_log) == 1
