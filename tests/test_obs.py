"""Observability-subsystem tests (the PR-8 tentpole): metrics registry
semantics, histogram percentile reconstruction, trace-span nesting under
concurrent serving, the HTTP exporter, the elastic compiled-program scan
cache, and the online quality auditor — including the end-to-end
acceptance run (live audited overall-ratio inside the PR-5 bench
envelope on a churning `cached:pruned:dense` int8 serve).

Registry tests use PRIVATE `MetricsRegistry()` instances so they cannot
perturb the process-global one the serving modules publish into; the one
test that reads the global registry (the elastic callback gauge) is
read-only. Trace tests run behind a fixture that force-disables and
clears the ring buffer on both sides.
"""
import json
import math
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.obs import registry as obs
from repro.obs import trace
from repro.obs.audit import QualityAuditor


# ---------------------------------------------------------------- fixtures
@pytest.fixture
def reg():
    return obs.MetricsRegistry()


@pytest.fixture
def clean_trace():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# ------------------------------------------------------ counters / gauges
def test_counter_monotone(reg):
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc(reg):
    g = reg.gauge("g")
    g.set(4.0)
    g.inc()
    g.inc(-2.0)                 # gauges may go down
    assert g.value == 3.0


def test_callback_gauge_and_explicit_set_wins(reg):
    g = reg.gauge("g_cb", set_fn=lambda: 42.0)
    assert g.value == 42.0
    g.set(5.0)                  # explicit set clears the callback
    assert g.value == 5.0
    # re-registering with a set_fn must NOT clobber an explicitly set
    # value (re-attach only happens on a pristine gauge)
    assert reg.gauge("g_cb", set_fn=lambda: 99.0).value == 5.0


def test_callback_gauge_exception_is_nan_and_survives_reset(reg):
    bad = reg.gauge("g_bad", set_fn=lambda: 1 / 0)
    assert math.isnan(bad.value)
    good = reg.gauge("g_good", set_fn=lambda: 7.0)
    reg.reset()                 # reset zeroes values, keeps callbacks
    assert good.value == 7.0
    assert math.isnan(bad.value)


def test_get_or_create_identity_and_conflicts(reg):
    c = reg.counter("name_a")
    assert reg.counter("name_a") is c
    with pytest.raises(TypeError):
        reg.gauge("name_a")     # same name, different kind
    h = reg.histogram("h", bounds=(1.0, 2.0))
    assert reg.histogram("h") is h          # bounds=None: no conflict
    with pytest.raises(ValueError):
        reg.histogram("h", bounds=(1.0, 3.0))
    # labels split series: distinct instruments, same name
    l1 = reg.counter("lbl_total", labels={"mode": "a"})
    l2 = reg.counter("lbl_total", labels={"mode": "b"})
    assert l1 is not l2
    assert reg.counter("lbl_total", labels={"mode": "a"}) is l1


def test_reset_in_place_keeps_references(reg):
    c = reg.counter("c_total")
    h = reg.histogram("h_ms", bounds=(1.0, 2.0))
    c.inc(3)
    h.observe(1.5)
    reg.reset()
    assert c.value == 0.0 and h.count == 0 and h.sum == 0.0
    assert reg.counter("c_total") is c      # same object, zeroed in place
    c.inc()
    assert c.value == 1.0


# ------------------------------------------------------------- histograms
def test_default_latency_bounds_shape():
    b = obs.default_latency_bounds()
    assert b[0] == 1e-3 and b[-1] >= 60_000.0
    assert all(b[i] < b[i + 1] for i in range(len(b) - 1))
    # ~4 buckets per octave: consecutive ratio is 2^(1/4)
    np.testing.assert_allclose(b[1] / b[0], 2.0 ** 0.25, rtol=1e-12)
    assert len(b) > 50


def test_histogram_bucket_boundaries():
    """Observations exactly AT a bound land in that bound's bucket
    (bucket i holds bounds[i-1] < v <= bounds[i])."""
    h = obs.Histogram("h", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    cum = dict(h._cumulative())
    assert cum[1.0] == 2        # 0.5 and the boundary hit 1.0
    assert cum[2.0] == 4        # + 1.5 and the boundary hit 2.0
    assert cum[4.0] == 5        # + the boundary hit 4.0
    assert cum[math.inf] == 6   # 9.0 overflows into +Inf
    assert h.count == 6 and h.sum == pytest.approx(18.0)


def test_percentile_exact_on_boundary_stream():
    """Any stream drawn from the bucket bounds themselves makes every
    bucket degenerate, so nearest-rank reconstruction is EXACT."""
    bounds = (1.0, 2.0, 4.0, 8.0)
    h = obs.Histogram("h", bounds=bounds)
    data = [1.0] * 3 + [2.0] * 5 + [4.0] * 1 + [8.0] * 11
    for v in data:
        h.observe(v)
    data.sort()
    for p in (0.0, 10.0, 25.0, 50.0, 75.0, 90.0, 99.0, 100.0):
        rank = max(0, math.ceil(p / 100.0 * len(data)) - 1)
        assert h.percentile(p) == data[rank], f"p{p}"
    assert h.p50() == 8.0 and h.p99() == 8.0


def test_percentile_interpolation_bounded_by_bucket_width():
    """Arbitrary streams reconstruct within ONE bucket's observed
    min/max span of the true nearest-rank value."""
    rng = np.random.default_rng(0)
    bounds = tuple(obs.default_latency_bounds(0.1, 100.0, per_octave=4))
    h = obs.Histogram("h", bounds=bounds)
    data = np.concatenate([rng.uniform(0.2, 5.0, 400),
                           rng.uniform(20.0, 90.0, 100)])
    for v in data:
        h.observe(float(v))
    data.sort()
    for p in (1.0, 25.0, 50.0, 75.0, 95.0, 99.0):
        rank = max(0, math.ceil(p / 100.0 * data.size) - 1)
        true = data[rank]
        i = np.searchsorted(bounds, true)           # bisect_left
        lo = bounds[i - 1] if i > 0 else 0.0
        hi = bounds[i] if i < len(bounds) else math.inf
        assert abs(h.percentile(p) - true) <= hi - lo, f"p{p}"


def test_percentile_edge_cases():
    h = obs.Histogram("h", bounds=(1.0, 2.0))
    assert h.percentile(50.0) == 0.0        # empty histogram
    h.observe(1.5)
    assert h.percentile(0.0) == 1.5 and h.percentile(100.0) == 1.5
    with pytest.raises(ValueError):
        h.percentile(101.0)
    with pytest.raises(ValueError):
        obs.Histogram("bad", bounds=(2.0, 1.0))     # not increasing
    with pytest.raises(ValueError):
        obs.Histogram("bad", bounds=())             # empty


# -------------------------------------------------------------- exporters
def test_snapshot_and_prometheus_text(reg):
    reg.counter("req_total", "requests").inc(3)
    reg.gauge("depth", labels={"mode": "serve"}).set(2.0)
    h = reg.histogram("lat_ms", bounds=(1.0, 2.0, 4.0))
    h.observe(1.5)
    h.observe(3.0)

    snap = reg.snapshot()
    assert snap["req_total"][0]["value"] == 3.0
    assert snap["req_total"][0]["type"] == "counter"
    assert snap["depth"][0]["labels"] == {"mode": "serve"}
    hist = snap["lat_ms"][0]
    assert hist["count"] == 2 and hist["sum"] == pytest.approx(4.5)
    les = [b["le"] for b in hist["buckets"]]
    assert 2.0 in les and math.inf in les
    assert 1.0 not in les                   # empty buckets elided
    json.dumps(snap, default=str)           # must be JSON-able

    text = reg.to_prometheus_text()
    assert "# TYPE req_total counter" in text
    assert "req_total 3.0" in text
    assert 'depth{mode="serve"} 2.0' in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert 'lat_ms_bucket{le="2.0"} 1' in text
    assert "lat_ms_count 2" in text


def test_http_exporter_serves_both_formats(reg):
    reg.counter("scrape_total").inc(7)
    srv = obs.start_http_server(0, registry=reg)    # ephemeral port
    try:
        port = srv.server_address[1]
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert "scrape_total 7.0" in r.read().decode()
        with urllib.request.urlopen(f"{base}/metrics.json", timeout=10) as r:
            payload = json.loads(r.read().decode())
        assert payload["metrics"]["scrape_total"][0]["value"] == 7.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=10)
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------------------------ trace
def test_disabled_trace_is_shared_null_span(clean_trace):
    assert not trace.is_enabled()
    sp = trace.span("x", a=1)
    assert sp is trace.span("y")            # one shared no-op object
    with sp as s:
        s.set(b=2)
    trace.event("e", 0.0, 1.0)
    assert trace.spans() == []


def test_span_nesting_and_attrs(clean_trace):
    trace.enable()
    with trace.span("outer", a=1) as sp:
        sp.set(b=2)                         # attrs may land mid-span
        with trace.span("inner"):
            pass
    recs = trace.spans()
    inner = [r for r in recs if r.name == "inner"][0]
    outer = [r for r in recs if r.name == "outer"][0]
    assert inner.depth == 1 and inner.parent == "outer"
    assert outer.depth == 0 and outer.parent is None
    assert outer.attrs == (("a", 1), ("b", 2))
    assert outer.duration_s >= 0 and outer.duration_ms >= 0


def test_event_is_retroactive_and_stack_attributed(clean_trace):
    trace.enable()
    with trace.span("tick"):
        trace.event("queue_wait", 123.0, 0.25, k=5)
    (ev,) = trace.spans("queue_wait")
    assert ev.t_start == 123.0 and ev.duration_s == 0.25
    assert ev.parent == "tick" and ev.depth == 1
    assert ev.attrs == (("k", 5),)


def test_span_nesting_under_concurrent_threads(clean_trace):
    """Each thread gets its OWN span stack: depth/parent never leak
    across threads no matter how the bodies interleave."""
    trace.enable()
    barrier = threading.Barrier(4)

    def work(tid):
        for _ in range(25):
            with trace.span("outer", tid=tid):
                barrier.wait(timeout=30)    # force interleaving
                with trace.span("inner", tid=tid):
                    pass

    threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = trace.spans()
    assert len([r for r in recs if r.name == "inner"]) == 100
    for r in recs:
        if r.name == "inner":
            assert r.depth == 1 and r.parent == "outer"
        else:
            assert r.depth == 0 and r.parent is None
        # attribution stays on the recording thread
        tid = dict(r.attrs)["tid"]
        assert r.thread == f"w{tid}"


def test_ring_buffer_capacity_and_clear(clean_trace):
    trace.enable()
    trace.set_capacity(8)
    try:
        for i in range(20):
            with trace.span("s", i=i):
                pass
        recs = trace.spans("s")
        assert len(recs) == 8               # only the most recent kept
        assert dict(recs[-1].attrs)["i"] == 19
        trace.clear()
        assert trace.spans() == []
        with pytest.raises(ValueError):
            trace.set_capacity(0)
    finally:
        trace.set_capacity(4096)


# --------------------------------------------- elastic compiled-programs
def test_elastic_jit_scan_cache_and_gauge():
    from repro.core import elastic

    n0 = elastic.compiled_program_count()
    entries = elastic._jit_entries()
    assert elastic._jit_entries() is entries        # memoized scan
    # the module-registered callback gauge samples the same scan
    g = obs.get_default().gauge("query_compiled_programs")
    assert int(g.value) == elastic.compiled_program_count() >= n0
    # mutating a counted module's namespace invalidates the cache key
    mod = sys.modules["repro.core.query"]
    mod._obs_scan_probe = 1
    try:
        assert elastic._jit_entries() is not entries
        assert elastic.compiled_program_count() == n0
    finally:
        del mod._obs_scan_probe


# ---------------------------------------------------------------- auditor
class _NoSnapshotEngine:
    """Engine stub with no `current_snapshot` — every sampled query is
    skipped by the scorer, which is exactly what the sampling-determinism
    tests need (no jax work, just the RNG/queue machinery)."""


def _observe_sequence(seed, n, fraction):
    reg = obs.MetricsRegistry()
    with QualityAuditor(_NoSnapshotEngine(), fraction=fraction, seed=seed,
                        registry=reg) as aud:
        picks = [aud.observe(np.zeros(4, np.float32), None, k=5, c=2.0)
                 for _ in range(n)]
        assert aud.flush(timeout=30)
        skipped = reg.counter("audit_skipped_total").value
        observed = reg.counter("audit_observed_total").value
        sampled = reg.counter("audit_sampled_total").value
    return picks, observed, sampled, skipped


def test_auditor_sampling_deterministic_under_seed():
    a, obs_a, samp_a, skip_a = _observe_sequence(seed=0, n=200, fraction=0.5)
    b, *_ = _observe_sequence(seed=0, n=200, fraction=0.5)
    c, *_ = _observe_sequence(seed=1, n=200, fraction=0.5)
    assert a == b                   # same seed + order → same subset
    assert a != c                   # a different seed moves the subset
    assert obs_a == 200 and samp_a == sum(a)
    assert 0 < samp_a < 200
    # snapshot-less samples are all counted as skips, never scored
    assert skip_a == samp_a


def test_auditor_fraction_endpoints():
    none, _, samp0, _ = _observe_sequence(seed=3, n=50, fraction=0.0)
    assert not any(none) and samp0 == 0
    every, _, samp1, _ = _observe_sequence(seed=3, n=50, fraction=1.0)
    assert all(every) and samp1 == 50


def test_auditor_rejects_bad_args():
    with pytest.raises(ValueError):
        QualityAuditor(_NoSnapshotEngine(), fraction=1.5,
                       registry=obs.MetricsRegistry())
    with pytest.raises(ValueError):
        QualityAuditor(_NoSnapshotEngine(), window=0,
                       registry=obs.MetricsRegistry())


def test_auditor_results_nan_before_first_score():
    with QualityAuditor(_NoSnapshotEngine(), fraction=0.0,
                        registry=obs.MetricsRegistry()) as aud:
        assert math.isnan(aud.overall_ratio)
        assert math.isnan(aud.accuracy)
        assert math.isnan(aud.bound_width)
        assert aud.scored == 0


# --------------------------------------------------- serving integration
@pytest.fixture(scope="module")
def serve_setup():
    import jax
    from repro.core.engine import ReverseKRanksEngine
    from repro.core.rank_table import build_rank_table
    from repro.core.types import RankTableConfig
    from tests.conftest import make_problem

    users, items = make_problem(jax.random.PRNGKey(42), n=512, m=400, d=16)
    cfg = RankTableConfig(tau=16, omega=4, s=8)
    rt = build_rank_table(users, items, cfg, jax.random.PRNGKey(1))
    eng = ReverseKRanksEngine(users=users, rank_table=rt, config=cfg,
                              backend="cached:dense")
    qs = items[(1 + np.arange(8) * 13) % items.shape[0]]
    qs = qs * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), qs.shape))
    return eng, np.asarray(qs)


def test_serving_spans_nest_under_concurrent_submissions(serve_setup,
                                                         clean_trace):
    """The scheduler's tick span encloses the cache lookup, and every
    QUEUED request's queue wait is recorded, while 4 client threads
    hammer `submit` concurrently with the dispatcher. Since PR 10 folded
    the LRU probe into admission, a repeat of an already-cached query
    resolves at submit and never enters the queue — so the invariant is
    conservation (queue waits + admission hits == submissions), not one
    wait per request."""
    from repro.serve import MicroBatcher

    eng, qs = serve_setup
    trace.enable()
    with MicroBatcher(eng, max_batch=8, max_wait_ms=10.0) as mb:
        def client(rounds):
            for _ in range(rounds):
                futs = [mb.submit(q, 7, 2.0) for q in qs[:4]]
                for f in futs:
                    f.result(timeout=120)

        threads = [threading.Thread(target=client, args=(3,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = mb.stats()
    ticks = trace.spans("serve.tick")
    lookups = trace.spans("cache.lookup")
    waits = trace.spans("serve.queue_wait")
    assert ticks and lookups
    assert waits                             # first-round misses queued
    assert len(waits) + st.admission_hits == 4 * 3 * 4
    for r in ticks:
        assert r.depth == 0 and r.parent is None
    for r in lookups:
        assert r.parent == "serve.tick" and r.depth == 1
    for r in waits:
        assert r.parent == "serve.tick" and r.duration_s >= 0


def test_serving_metrics_flow_into_default_registry(serve_setup):
    from repro.serve import MicroBatcher

    reg = obs.get_default()
    before = reg.counter("serve_requests_total").value
    eng, qs = serve_setup
    with MicroBatcher(eng, max_batch=8, max_wait_ms=10.0) as mb:
        for f in [mb.submit(q, 7, 2.0) for q in qs]:
            f.result(timeout=120)
    assert reg.counter("serve_requests_total").value == before + len(qs)
    assert reg.histogram("serve_request_latency_ms").count > 0
    assert reg.histogram("serve_queue_wait_ms").count > 0


WAIT_SPANS = ("serve.idle", "serve.fill_wait", "serve.pipeline_full")


def _wait_run(serve_setup, traced):
    """One run that makes the dispatcher wait in each way: a lone
    request is held as a partial head tick for `max_wait_ms`, and a full
    group arrives while that tick's transfer sleeps with one slot in
    flight (`pipeline_depth` 1). Returns the span records, the threads
    that called `jax.block_until_ready`, and the ticks' batch sizes."""
    import jax
    from repro.core.engine import ReverseKRanksEngine
    from repro.serve import MicroBatcher, faults

    cached, qs = serve_setup
    eng = ReverseKRanksEngine(users=cached.users, rank_table=cached.rank_table,
                              config=cached.config, backend="dense")
    real = jax.block_until_ready
    callers = []

    def counting(x):
        callers.append(threading.current_thread().name)
        return real(x)

    trace.disable()
    trace.clear()
    faults.install(faults.FaultPlan(seed=0, rules=[
        faults.FaultRule("serve.transfer", mode="sleep", rate=1.0,
                         latency_ms=300.0)]))
    try:
        if traced:
            trace.enable()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "block_until_ready", counting)
            with MicroBatcher(eng, max_batch=8, max_wait_ms=200.0,
                              pipeline_depth=1) as mb:
                futs = [mb.submit(qs[0], 7, 2.0)]
                deadline = time.monotonic() + 60
                while not mb._inflight and time.monotonic() < deadline:
                    time.sleep(0.001)
                futs += [mb.submit(q, 7, 2.0) for q in qs]
                for f in futs:
                    assert f.result(timeout=120).indices.shape == (7,)
                batches = [t.batch for t in mb.tick_log]
        return trace.spans(), callers, batches
    finally:
        faults.clear()
        trace.disable()
        trace.clear()


@pytest.fixture(scope="module")
def traced_waits(serve_setup):
    return _wait_run(serve_setup, traced=True)


def test_dispatcher_waits_are_spans(traced_waits):
    """Each way the dispatcher waits is a top-level span on its thread:
    an empty queue, a partial head tick held for `max_wait_ms`, and a
    full pipeline."""
    recs, _, batches = traced_waits
    assert batches == [1, 8]
    for name in WAIT_SPANS:
        rs = [r for r in recs if r.name == name]
        assert rs, f"no {name} span"
        for r in rs:
            assert r.thread == "microbatcher" and r.depth == 0
    (fill,) = [r for r in recs if r.name == "serve.fill_wait"]
    assert fill.duration_s >= 0.15          # held for max_wait_ms 200
    assert all(r.duration_s > 0 for r in recs
               if r.name == "serve.pipeline_full")


def test_ready_and_d2h_split_the_transfer(traced_waits):
    """Traced, the completion stage's blocking transfer is the wait for
    the tick's device work (`serve.ready`), then the copy alone
    (`serve.d2h`), both children of `serve.transfer`."""
    recs, callers, batches = traced_waits
    ready = [r for r in recs if r.name == "serve.ready"]
    d2h = [r for r in recs if r.name == "serve.d2h"]
    assert len(ready) == len(d2h) == len(batches)
    for r in ready + d2h:
        assert r.parent == "serve.transfer" and r.depth == 1
        assert r.thread == "microbatcher-complete"
    for a, b in zip(ready, d2h):
        assert dict(a.attrs)["tick"] == dict(b.attrs)["tick"]
        assert a.t_start + a.duration_s <= b.t_start
    assert callers.count("microbatcher-complete") == len(batches)


def test_a_ticks_spans_share_its_id(traced_waits):
    """Every span of a tick carries its `tick` id, and every queue-wait
    event names a dispatched tick, one per request it served."""
    recs, _, batches = traced_waits
    ticks = {dict(r.attrs)["tick"]: dict(r.attrs)["batch"]
             for r in recs if r.name == "serve.tick"}
    assert sorted(ticks) == list(range(len(batches)))
    for name in ("serve.transfer", "serve.ready", "serve.d2h"):
        assert sorted(dict(r.attrs)["tick"] for r in recs
                      if r.name == name) == sorted(ticks)
    waits = [dict(r.attrs)["tick"] for r in recs
             if r.name == "serve.queue_wait"]
    for tick, batch in ticks.items():
        assert waits.count(tick) == batch
    assert len(waits) == sum(batches)


def test_untraced_completion_makes_the_one_transfer_call(serve_setup):
    """With tracing off the same run records nothing, and the completion
    stage makes no `block_until_ready` call: its single `device_get` is
    the whole transfer, as without the spans."""
    recs, callers, batches = _wait_run(serve_setup, traced=False)
    assert recs == []
    assert batches == [1, 8]
    assert "microbatcher-complete" not in callers


@pytest.mark.slow
def test_live_audit_ratio_within_envelope_end_to_end():
    """ACCEPTANCE: a churning `cached:pruned:dense` int8 serve on
    zipf-clustered data (the PR-5 smoke layout: d=64, τ=128, ω=8, s=32)
    audited at fraction 1.0 keeps the rolling overall-ratio inside the
    bench envelope (BENCH_PR5.json int8: 1.109; gate ≤ 1.15)."""
    import jax
    from benchmarks.common import zipf_clustered
    from repro.core.engine import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.serve import MicroBatcher

    users, items, _ = zipf_clustered(jax.random.PRNGKey(0), 4096, 1024, 64)
    cfg = RankTableConfig(tau=128, omega=8, s=32, storage_dtype="int8")
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1),
                                    backend="cached:pruned:dense")
    qs = np.asarray(items[:32] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), items[:32].shape)))
    churn_key = jax.random.PRNGKey(9)

    reg = obs.MetricsRegistry()
    with QualityAuditor(eng, fraction=1.0, seed=0, window=64,
                        registry=reg) as aud:
        with MicroBatcher(eng, max_batch=8, max_wait_ms=20.0,
                          auditor=aud) as mb:
            futs = []
            for i, q in enumerate(qs):
                if i and i % 8 == 0:        # churn between bursts
                    churn_key, sub = jax.random.split(churn_key)
                    eng.insert_items(jax.random.normal(sub, (4, 64)))
                    eng.delete_items(eng.live_item_ids()[:2])
                futs.append(mb.submit(q, 10, 2.0))
            for f in futs:
                f.result(timeout=300)
        assert aud.flush(timeout=300)
        assert aud.scored == len(qs)
        assert 1.0 <= aud.overall_ratio <= 1.15
        assert aud.accuracy >= 0.9
        assert np.isfinite(aud.bound_width)
        # the gauges mirror the rolling windows
        assert reg.gauge("audit_overall_ratio").value == pytest.approx(
            aud.overall_ratio)
        assert reg.gauge("audit_accuracy").value == pytest.approx(
            aud.accuracy)
