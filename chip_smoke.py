"""Chip smoke test: the serving path, compiled, on a TPU at the paper's
Netflix scale.

    python chip_smoke.py              # one chip: every kernel path
    python chip_smoke.py --chips 4    # four chips: the row-sharded mesh path

One chip: the §5 Netflix deployment (`configs/paper_engine.py`: n 480,189
users, m 17,770 items, d 200, τ 500, ω 10, s 64; k 10, c 2.0), vectors
made from `--seed` by `synthetic_embeddings`. Each kernel path —
`fused` at f32, bf16 and int8 storage, `elastic:fused`, and
`pruned:fused` on clustered users — is built with
`ReverseKRanksEngine.build` and serves Zipf-hot item queries submitted
one at a time through `MicroBatcher` (max_batch 16, pipeline_depth 2).
The first tick's answers are checked against the `dense` backend on the
same index: table-derived bounds bit for bit wherever the two scores
agree, and otherwise only within the f32 rounding of the score;
selections up to float ties. A few queries per path are checked against
the exact oracle for the c-approximation contract.

Four chips: the same shapes trimmed so n divides 4·256 and m divides 4,
built row-sharded over the mesh and served through `sharded` and
`pruned:sharded`, checked against single-device `dense` on the same
index.

Earlier lines are information; the last line of stdout is one JSON
object with the device. Without a TPU, or if any check fails, the
script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

K, C = 10, 2.0
MAX_BATCH = 16          # the scheduler's tick width (serve.py's default)
N_QUERIES = 48          # three full ticks
N_CHECKED = MAX_BATCH   # answers per path held to `dense`: the first tick
N_EXACT = 4             # queries per path scored by the exact oracle
MIN_ACCURACY = 0.9      # §5 accuracy floor for the c-approximation check
TIE_TOL = 0.05          # ranks: est differences that count as a float tie


@dataclasses.dataclass(frozen=True)
class Shape:
    n: int
    m: int
    d: int
    tau: int
    omega: int
    s: int


class CheckFailed(Exception):
    pass


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def zipf_pick(rng, pool, count: int, a: float = 1.1):
    """`count` draws from `pool` with Zipf popularity: a random
    popularity order, rank r drawn with weight r^-a."""
    import numpy as np
    order = rng.permutation(pool)
    w = 1.0 / np.arange(1, order.size + 1) ** a
    return order[rng.choice(order.size, size=count, p=w / w.sum())]


# ------------------------------------------------------------- serving
def serve(eng, qs):
    """Warm the serving entry with one tick, then submit every query on
    its own through MicroBatcher. Returns (host results, seconds of the
    warm tick — compile included, per-request latencies in ms)."""
    import jax
    import numpy as np
    from repro.serve import MicroBatcher

    t0 = time.perf_counter()
    warm = eng.dispatch_batch_at(eng.current_snapshot(), qs[:MAX_BATCH],
                                 K, C)
    jax.block_until_ready(warm.indices)
    first_tick_s = time.perf_counter() - t0
    done = [None] * len(qs)
    with MicroBatcher(eng, max_batch=MAX_BATCH, max_wait_ms=2.0,
                      pipeline_depth=2) as mb:
        futs = []
        for i, q in enumerate(qs):
            t = time.perf_counter()
            f = mb.submit(q, K, C)
            f.add_done_callback(
                lambda _, i=i, t=t: done.__setitem__(
                    i, (time.perf_counter() - t) * 1e3))
            futs.append(f)
        results = [f.result() for f in futs]
    # close() joined the completion thread, so every callback has run
    require(all(x is not None for x in done), "a request never resolved")
    return results, first_tick_s, np.asarray(done)


def latency_fields(lat_ms) -> dict:
    import numpy as np
    return {"p50_ms": float(np.percentile(lat_ms, 50)),
            "p99_ms": float(np.percentile(lat_ms, 99))}


# -------------------------------------------------------------- checks
def check_vs_dense(snap, qs, served, *, full_bounds: bool = True,
                   sentinel=None) -> dict:
    """Hold the first N_CHECKED served answers to the `dense` backend on
    the same index.

    Bounds (when the served result carries them per user): equal bit for
    bit, or — where the kernel's and XLA's f32 scores differ in their
    last bits and the score sits on a threshold — between the dense
    bounds of the score moved by its f32 rounding bound either way.
    Selections: every served user the
    dense answer does not hold must be a float tie — its dense selection
    key within TIE_TOL of the k-th key, or a user whose bounds differ.
    `sentinel` marks users a pruned path skipped (their bounds are the
    dominated sentinel, checked elsewhere)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.backends import get_backend
    from repro.core.query import lemma1_key, lookup_bounds_batch, \
        user_scores_batch
    from repro.core.types import matmul

    @jax.jit
    def reference(rt, users, users_f32, qb, R_lo_k, R_up_k):
        """Dense selection keys of every user, and the dense bounds at
        the score moved by twice its f32 rounding bound either way —
        compiled, as the dense backend is (eager and compiled int8
        lookups differ where XLA rewrites the code-space arithmetic)."""
        scores, slack = user_scores_batch(users, qb)
        r_lo, r_up, est = lookup_bounds_batch(rt, scores, slack)
        key = lemma1_key(r_lo.T, r_up.T, est.T, R_lo_k=R_lo_k,
                         R_up_k=R_up_k, c=C, m_items=rt.m)[0]
        tol = (users_f32.shape[1] * 2.0 ** -23) * matmul(
            jnp.abs(users_f32), jnp.abs(qb).T)
        hi = lookup_bounds_batch(rt, scores + tol, slack)
        lo = lookup_bounds_batch(rt, scores - tol, slack)
        return key, hi[0].T, lo[0].T, hi[1].T, lo[1].T

    def between(v, a, b):
        # the bounds are monotone in the score except where a widened
        # table read meets the m + 1 or 1 edge, so order the ends here
        return (np.minimum(a, b) <= v) & (v <= np.maximum(a, b))

    rt, users = snap.rank_table, snap.query_users()
    dense = get_backend("dense")
    n_pairs = n_exact = n_skipped = n_sel_equal = n_outside = 0
    worst_excess = 0.0
    outside, wrong = [], []
    for i0 in range(0, N_CHECKED, MAX_BATCH):
        qb = jnp.asarray(qs[i0:i0 + MAX_BATCH])
        ref = dense.query_batch(rt, users, qb, k=K, c=C)
        key, lo_min, lo_max, up_min, up_max = map(np.asarray, reference(
            rt, users, snap.users, qb, ref.R_lo_k, ref.R_up_k))
        ref_lo, ref_up = np.asarray(ref.r_lo), np.asarray(ref.r_up)
        for j in range(qb.shape[0]):
            r = served[i0 + j]
            kth = np.partition(key[j], K - 1)[K - 1]
            tied = np.zeros(key.shape[1], bool)
            if full_bounds:
                live = (np.ones_like(r.r_lo, bool) if sentinel is None
                        else r.r_lo != sentinel)
                same = ((r.r_lo == ref_lo[j]) & (r.r_up == ref_up[j]))
                inside = same | (between(r.r_lo, lo_min[j], lo_max[j])
                                 & between(r.r_up, up_min[j], up_max[j]))
                out_ = np.flatnonzero(~inside & live)
                for u in out_[:2]:
                    outside.append(
                        f"query {i0 + j} user {u}: served r_lo {r.r_lo[u]} "
                        f"r_up {r.r_up[u]}, dense {ref_lo[j][u]} "
                        f"{ref_up[j][u]}, bracket r_lo [{lo_min[j][u]}, "
                        f"{lo_max[j][u]}] r_up [{up_min[j][u]}, "
                        f"{up_max[j][u]}]")
                n_outside += out_.size
                n_pairs += int(live.sum())
                n_exact += int((same & live).sum())
                n_skipped += int((~live).sum())
                tied = ~same & live
            extra = np.setdiff1d(r.indices, np.asarray(ref.indices[j]))
            if extra.size == 0:
                n_sel_equal += 1
                continue
            excess = key[j][extra] - kth
            ok = (excess <= TIE_TOL) | tied[extra]
            worst_excess = max(worst_excess, float(excess.max()))
            if not np.all(ok):
                wrong.append(f"query {i0 + j}: served users "
                             f"{extra[~ok].tolist()} are not in the dense "
                             f"top-{K} (key excess {excess[~ok].tolist()})")
    require(not n_outside, f"{n_outside} (user, query) bounds outside the "
            f"dense bracket, e.g. " + "; ".join(outside[:4]))
    require(not wrong, f"{len(wrong)} selections differ beyond float ties: "
            + "; ".join(wrong[:4]))
    out = {"selections_equal": f"{n_sel_equal}/{N_CHECKED}",
           "worst_tie_excess": worst_excess}
    if full_bounds:
        out["bounds_bitwise"] = f"{n_exact}/{n_pairs}"
        if sentinel is not None:
            out["skipped_user_bounds"] = n_skipped
    return out


def exact_truths(users, items, qs):
    """Definition-1 ranks of every user for the first N_EXACT queries."""
    import numpy as np
    from repro.core.exact import exact_ranks
    return [np.asarray(exact_ranks(users, items, q)) for q in qs[:N_EXACT]]


def check_vs_oracle(served, truths) -> dict:
    """§5 accuracy and overall ratio against the exact answer."""
    import numpy as np
    from repro.core import metrics
    acc, ratio = [], []
    for r, truth in zip(served, truths):
        ex_idx = np.argsort(truth, kind="stable")[:K]
        acc.append(metrics.accuracy(r.indices, ex_idx, truth, C))
        ratio.append(metrics.overall_ratio(r.indices, ex_idx, truth))
    out = {"accuracy": float(np.mean(acc)),
           "overall_ratio": float(np.mean(ratio))}
    require(out["accuracy"] >= MIN_ACCURACY,
            f"accuracy {out['accuracy']} < {MIN_ACCURACY} at c={C}")
    return out


class pruned_only:
    """Context: the pruned batches served inside it all took the pruned
    path — no fallback to the unpruned scan (read from the
    `prune_batches_total` counters, labelled by fallback reason)."""

    REASONS = ("none", "dense", "delta-guard", "align")

    def __init__(self, name: str):
        from repro.obs import registry as obs
        self.name, self.reg = name, obs.get_default()

    def _counts(self):
        return {why: self.reg.counter("prune_batches_total",
                                      labels={"fallback": why}).value
                for why in self.REASONS}

    def __enter__(self):
        self.before = self._counts()

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        delta = {why: int(v - self.before[why])
                 for why, v in self._counts().items()}
        log(self.name + " phase A", pruned_batches=delta.pop("none"),
            fallbacks=delta,
            skip_rate=round(self.reg.gauge("prune_skip_rate").value, 4))
        require(not any(delta.values()),
                f"{self.name} fell back to the unpruned scan: {delta}")
        return False


# ----------------------------------------------------------- one chip
def run_one_chip(shape: Shape, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import make_regime
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.data.pipeline import synthetic_embeddings
    from repro.kernels import interpret_mode

    require(not interpret_mode(), "kernels would run interpreted")
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    build_key = jax.random.PRNGKey(seed + 1)
    users, items = synthetic_embeddings(key, shape.n, shape.m, shape.d)
    qs = np.asarray(items)[zipf_pick(rng, np.arange(shape.m), N_QUERIES)]
    t0 = time.perf_counter()
    truths = exact_truths(users, items, qs)
    log("oracle", queries=len(truths),
        seconds=round(time.perf_counter() - t0, 3))

    failed = []

    def run_path(name, eng, build_s, qs, truths, sentinel=None):
        """Serve and check one path; a failed check is logged and kept,
        and the remaining paths still run."""
        try:
            served, first_tick_s, lat = serve(eng, qs)
            log(name, build_s=round(build_s, 3),
                first_tick_s=round(first_tick_s, 3),
                **{k: round(v, 3) for k, v in latency_fields(lat).items()})
            snap = eng.current_snapshot()
            t0 = time.perf_counter()
            fields = check_vs_dense(snap, qs, served, sentinel=sentinel)
            log(name + " vs dense", **fields,
                seconds=round(time.perf_counter() - t0, 3))
            log(name + " vs oracle", **check_vs_oracle(served, truths))
        except CheckFailed as e:
            log(name + " FAILED", reason=e)
            failed.append(name)

    for spec in ("f32", "bf16", "int8"):
        cfg = RankTableConfig(tau=shape.tau, omega=shape.omega, s=shape.s,
                              storage_dtype=spec)
        t0 = time.perf_counter()
        eng = ReverseKRanksEngine.build(users, items, cfg, build_key,
                                        backend="fused")
        jax.block_until_ready(eng.rank_table.table)
        run_path(f"fused/{spec}", eng, time.perf_counter() - t0, qs,
                 truths)
        if spec == "f32":
            # the compile-once elastic program over the same index
            t0 = time.perf_counter()
            el = ReverseKRanksEngine(users=eng.users,
                                     rank_table=eng.rank_table, config=cfg,
                                     backend="elastic:fused", items=items,
                                     build_key=build_key)
            run_path("elastic:fused/f32", el, time.perf_counter() - t0, qs,
                     truths)
            del el
        del eng
        gc.collect()
    del users, items
    gc.collect()

    # pruned:fused on clustered users, queried by promoted items of the
    # hottest cluster — the traffic whose answers concentrate in a few
    # user tiles (the pruning bench's hot-cluster batch), so phase A
    # keeps few tiles and the masked-grid kernel runs
    users, items, icl = make_regime("clustered", key, shape.n, shape.m,
                                    shape.d)
    hot = np.flatnonzero(np.asarray(icl) == 0)
    qs = np.asarray(items)[zipf_pick(rng, hot, N_QUERIES)] * 1.2
    truths = exact_truths(users, items, qs)
    cfg = RankTableConfig(tau=shape.tau, omega=shape.omega, s=shape.s)
    t0 = time.perf_counter()
    eng = ReverseKRanksEngine.build(users, items, cfg, build_key,
                                    backend="pruned:fused")
    jax.block_until_ready(eng.rank_table.table)
    build_s = time.perf_counter() - t0
    try:
        with pruned_only("pruned:fused/f32"):
            run_path("pruned:fused/f32", eng, build_s, qs, truths,
                     sentinel=float(int(eng.rank_table.m) + 2))
    except CheckFailed as e:
        log("pruned:fused/f32 FAILED", reason=e)
        failed.append("pruned:fused/f32")
    require(not failed, f"paths failed: {failed}")


# --------------------------------------------------------- four chips
def run_four_chips(shape: Shape, seed: int) -> None:
    import jax
    import numpy as np
    from benchmarks.common import make_regime
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.obs import registry as obs

    devs = jax.devices()
    require(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    build_key = jax.random.PRNGKey(seed + 1)
    users, items, icl = make_regime("clustered", key, shape.n, shape.m,
                                    shape.d)
    hot = np.flatnonzero(np.asarray(icl) == 0)
    qs = np.asarray(items)[zipf_pick(rng, hot, N_QUERIES)] * 1.2
    cfg = RankTableConfig(tau=shape.tau, omega=shape.omega, s=shape.s)
    t0 = time.perf_counter()
    eng = ReverseKRanksEngine.build(users, items, cfg, build_key,
                                    backend="sharded")
    jax.block_until_ready(eng.rank_table.table)
    build_s = time.perf_counter() - t0
    spans = len(eng.rank_table.table.sharding.device_set)
    single = obs.get_default().counter(
        "sharded_single_device_builds_total").value
    log("sharded build", build_s=round(build_s, 3), table_devices=spans,
        single_device_builds=int(single))
    require(spans == 4 and single == 0,
            "the sharded build did not span the 4 devices")

    # the single-device reference: the same index moved to one chip
    ref_eng = ReverseKRanksEngine(
        users=jax.device_put(eng.users, devs[0]),
        rank_table=jax.device_put(eng.rank_table, devs[0]), config=cfg)
    ref_snap = ref_eng.current_snapshot()
    for name in ("sharded", "pruned:sharded"):
        e = eng if name == "sharded" else ReverseKRanksEngine(
            users=eng.users, rank_table=eng.rank_table, config=cfg,
            backend=name, items=items, build_key=build_key)
        if name == "sharded":
            served, first_tick_s, lat = serve(e, qs)
        else:
            with pruned_only(name):
                served, first_tick_s, lat = serve(e, qs)
        log(name, first_tick_s=round(first_tick_s, 3),
            **{k: round(v, 3) for k, v in latency_fields(lat).items()})
        log(name + " vs dense (one device)",
            **check_vs_dense(ref_snap, qs, served, full_bounds=False))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every kernel path on one chip; 4: the "
                         "row-sharded mesh path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro.configs.paper_engine import DEFAULT_TABLE, NETFLIX
        from repro.launch import compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repo's sources are not beside this "
                 f"script ({e})")
    import jax

    cache_dir = compile_cache.enable()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found {len(devs)} "
                 f"{devs[0].platform} device(s); this test runs only on "
                 f"the chip")
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    shape = Shape(NETFLIX.n_users, NETFLIX.n_items, NETFLIX.d,
                  DEFAULT_TABLE.tau, DEFAULT_TABLE.omega, DEFAULT_TABLE.s)
    if args.chips == 4:
        # n divides 4·256 (whole pruning tiles per shard), m divides 4
        shape = dataclasses.replace(shape, n=shape.n - shape.n % 1024,
                                    m=shape.m - shape.m % 4)
        log("cut", n=f"{NETFLIX.n_users}->{shape.n}",
            m=f"{NETFLIX.n_items}->{shape.m}")
    log("shapes", **dataclasses.asdict(shape), k=K, c=C,
        queries=N_QUERIES, max_batch=MAX_BATCH)
    try:
        if args.chips == 4:
            run_four_chips(shape, args.seed)
        else:
            run_one_chip(shape, args.seed)
    except CheckFailed as e:
        sys.exit(f"chip_smoke: check failed: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
