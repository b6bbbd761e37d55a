"""Run one benchmark cell and print one JSON result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of `BENCHMARK.json` names a configuration (`bench/configs/`), a
traffic mix (`bench/traffic/<mix>.json`) and, through `per_layer`, the
readers of its per-layer metrics (`bench/metrics/<metric>.py`); all are
found by name, so a new cell is new files and entries.

One run is one process, on the chip it finds (never the CPU):

1. JAX's persistent compilation cache goes to `$JAX_COMPILATION_CACHE_DIR`
   when set, else to `<checkout>/.jax_cache`.
2. Users and items are made on the device from `--seed`.
3. `ReverseKRanksEngine.build` runs Algorithm 1 (`build_s`).
4. Warm-up: ticks of every fill through `MicroBatcher` until a round
   compiles nothing, then `warm_seconds` of the mix itself.
5. The window: `--seconds` of the mix through `MicroBatcher.submit`.
   Latency runs from each request's due time to its answer.
6. Each answer due in the window is checked for shape, and a sample
   drawn from the seed is held to the plain reference
   (`bench/check.py`), after the program's state is freed.
7. The last stdout line is the result; the last stderr lines are the
   compared numbers beside their limits.

`--trace 1` enables the program's spans with profiler annotations and
traces the window; the result then carries the per-layer metrics, the
device's busy and window seconds, and a breakdown. `--storage` runs
the configuration at another storage width: the lower-precision control.
"""
from __future__ import annotations

import time

T_ORIGIN = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

from bench import check  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
COLLECT_S = 60.0        # how long past the close a due answer is awaited
# JAX's duration events for tracing, lowering and compiling a program,
# and for loading it from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/cache_retr")


class NoProgram(Exception):
    """The system under test is not beside the benchmark."""


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- the plan
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """Everything a cell needs, found by name: its configuration, its
    traffic mix and the end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(root, "bench", "traffic",
                                 cell["traffic"] + ".json"))

    def here(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if here(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in reported and here(m)]
    return {"cell": cell, "config": config, "mix": mix, "end_to_end": e2e,
            "per_layer": layer, "root": root}


def load_reader(name: str, root: str = ROOT):
    """`bench/metrics/<name>.py`'s `read(window)`."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def import_program(root: str):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoProgram(f"no program under {src}")
    sys.path.insert(0, src)


def enable_compile_cache(root: str) -> str:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return devs


# ---------------------------------------------------------------- load
class Load:
    """Drives a mix through `MicroBatcher.submit` and records each
    request: due time, send time, answer time, outcome. A closed loop
    sends its next request from the collector thread as each answer
    comes; an open loop sends from its own thread on the schedule."""

    def __init__(self, mb, queries: np.ndarray, *, k: int, c: float,
                 n: int, mix: dict, schedule: Optional[np.ndarray],
                 t_start: float, t0: float, t1: float, sample: int,
                 rng: np.random.Generator, annotate: bool):
        self.mb, self.queries, self.k, self.c, self.n = mb, queries, k, c, n
        self.mix, self.schedule = mix, schedule
        self.t_start, self.t0, self.t1 = t_start, t0, t1
        self.sample, self.rng, self.annotate = sample, rng, annotate
        self.lock = threading.Lock()
        self.due: List[float] = []
        self.sent: Dict[int, float] = {}
        self.qid: List[int] = []
        self.done: Dict[int, float] = {}
        self.bad: Dict[int, str] = {}
        self.kept: List[dict] = []          # reservoir of full answers
        self.seen = 0                       # window answers offered to it
        self.answer_bytes = 0
        self.answers = queue.SimpleQueue()
        self.stop = threading.Event()
        self.threads: List[threading.Thread] = []

    def in_window(self, i: int) -> bool:
        return self.t0 <= self.due[i] < self.t1

    def _send(self, due: float) -> None:
        with self.lock:
            i = len(self.due)
            self.due.append(due)
            self.qid.append(i % len(self.queries))
        q = self.queries[i % len(self.queries)]
        t = time.monotonic()
        try:
            if self.annotate:
                import jax
                with jax.profiler.TraceAnnotation("bench.submit"):
                    fut = self.mb.submit(q, self.k, self.c)
            else:
                fut = self.mb.submit(q, self.k, self.c)
        except Exception as e:      # a refused request is a failed one
            with self.lock:
                self.sent[i] = t
                self.bad[i] = f"submit: {type(e).__name__}: {e}"
            self.answers.put((i, time.monotonic(), None))
            return
        with self.lock:
            self.sent[i] = t
        fut.add_done_callback(lambda f, i=i: self._answered(i, f))

    def _answered(self, i: int, fut) -> None:
        """Runs where the future resolves: queue the answer for the
        collector and, in a closed loop, send the next request at once."""
        t_done = time.monotonic()
        self.answers.put((i, t_done, fut))
        if (self.mix["loop"] == "closed" and not self.stop.is_set()
                and t_done < self.t1):
            self._send(t_done)

    def _take(self, i: int, t_done: float, fut) -> None:
        res, err = None, None
        if fut is not None:
            err = fut.exception()
            if err is None:
                res = fut.result()
        with self.lock:
            self.done[i] = t_done
            if fut is None:
                return
            if err is not None:
                self.bad[i] = f"{type(err).__name__}: {err}"
                return
            if not self.answer_bytes:
                self.answer_bytes = sum(
                    np.asarray(x).nbytes for x in res)
            idx = np.asarray(res.indices)
            if not check.answer_ok(idx, self.n, self.k):
                self.bad[i] = f"malformed answer {idx.tolist()}"
                return
            if not self.in_window(i):
                return
            self.seen += 1
            slot = (len(self.kept) if len(self.kept) < self.sample
                    else int(self.rng.integers(self.seen)))
        if slot < self.sample:
            full = {"i": i, "indices": idx.copy(),
                    "r_lo": np.array(res.r_lo, np.float32),
                    "r_up": np.array(res.r_up, np.float32),
                    "R_lo_k": float(res.R_lo_k), "R_up_k": float(res.R_up_k)}
            with self.lock:
                if slot < len(self.kept):
                    self.kept[slot] = full
                else:
                    self.kept.append(full)

    def _collect(self) -> None:
        while True:
            item = self.answers.get()
            if item is None:
                return
            i, t_done, fut = item
            if self.annotate:
                import jax
                with jax.profiler.TraceAnnotation("bench.result"):
                    self._take(i, t_done, fut)
            else:
                self._take(i, t_done, fut)

    def _generate(self) -> None:
        for off in self.schedule:
            due = self.t_start + float(off)
            if due >= self.t1 or self.stop.is_set():
                return
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self._send(due)

    def start(self) -> None:
        self.threads.append(threading.Thread(target=self._collect,
                                             name="bench-collect"))
        if self.mix["loop"] == "open":
            self.threads.append(threading.Thread(target=self._generate,
                                                 name="bench-generate"))
        for t in self.threads:
            t.start()
        if self.mix["loop"] == "closed":
            for _ in range(int(self.mix["outstanding"])):
                self._send(self.t_start)

    def finish(self) -> None:
        """Stop sending; wait for every window request's answer, at most
        COLLECT_S past the close; stop the threads."""
        self.stop.set()
        if len(self.threads) > 1:
            self.threads[1].join()
        deadline = self.t1 + COLLECT_S
        while time.monotonic() < deadline:
            with self.lock:
                pending = [i for i in range(len(self.due))
                           if self.in_window(i) and i not in self.done]
            if not pending:
                break
            time.sleep(0.01)

    def join(self) -> None:
        self.answers.put(None)
        self.threads[0].join()

    def summary(self) -> dict:
        with self.lock:
            win = [i for i in range(len(self.due)) if self.in_window(i)]
            lat, failed = [], 0
            for i in win:
                if i in self.done and i not in self.bad:
                    lat.append((self.done[i] - self.due[i]) * 1e3)
                else:
                    failed += 1
                    lat.append(math.inf)
            answered = sum(1 for i, t in self.done.items()
                           if self.t0 <= t < self.t1 and i not in self.bad)
            late = [(self.sent[i] - self.due[i]) * 1e3 for i in win
                    if i in self.sent]
            bad = {i: self.bad[i] for i in win if i in self.bad}
            backlog = [sum(1 for i, due in enumerate(self.due)
                           if due < t and self.done.get(i, math.inf) >= t)
                       for t in (self.t0, self.t1)]
            lost = [i for i in win if i not in self.done]
        return {"attempted": len(win), "failed": failed, "latency_ms": lat,
                "answered": answered, "late_ms": late, "bad": bad,
                "lost": lost, "backlog": backlog}


# --------------------------------------------------------------- window
class Window:
    """What the per-layer readers read: the window's ticks, the program's
    counters at its edges, its spans, and the trace's reduction."""

    def __init__(self, *, ticks, max_batch, counters0, counters1, spans,
                 trace, config, seconds, peaks):
        self.ticks, self.max_batch = ticks, max_batch
        self._c0, self._c1 = counters0, counters1
        self.spans, self.trace, self.config = spans, trace, config
        self.seconds, self.peaks = seconds, peaks

    def counter_delta(self, name: str, **labels) -> float:
        """Window delta of a counter, summed over every label set that
        matches `labels` (a value prefixed "!" matches any other)."""
        def match(lab):
            for k_, v in labels.items():
                if v.startswith("!"):
                    if lab.get(k_) == v[1:]:
                        return False
                elif lab.get(k_) != v:
                    return False
            return True

        def total(snap):
            return sum(e["value"] for e in snap.get(name, [])
                       if match(e["labels"]))
        return total(self._c1) - total(self._c0)


def counters():
    from repro.obs import registry
    snap = registry.get_default().snapshot()
    return {k: [e for e in v if e["type"] == "counter"]
            for k, v in snap.items()}


def _span_dicts(t0: float, t1: float) -> List[dict]:
    from repro.obs import trace as otrace
    return [{"name": s.name, "t_start": s.t_start,
             "duration_s": s.duration_s, "attrs": dict(s.attrs)}
            for s in otrace.spans() if t0 <= s.t_start < t1]


# ------------------------------------------------------------------ run
def _warm(mb, queries: np.ndarray, k: int, c: float, max_batch: int,
          rounds: int = 6) -> int:
    """Ticks of every fill through the serving entry until a round
    compiles nothing; returns the rounds run."""
    fills = sorted({max_batch} | {max(max_batch >> s, 1)
                                  for s in range(1, 5)} | {1, 3}, key=lambda
                   b: -b)
    cursor = 0
    for r in range(rounds):
        before = len(mb.tick_log)
        for b in fills:
            futs = []
            for _ in range(b):
                futs.append(mb.submit(queries[cursor % len(queries)], k, c))
                cursor += 1
            mb.flush()
            for f in futs:
                f.result()
        if not any(t.compiles for t in mb.tick_log[before:]):
            return r + 1
    return rounds


def run(plan: dict, seed: int, seconds: float, trace: bool,
        storage: Optional[str] = None, log=print) -> dict:
    """One run of a cell on whatever devices JAX has. Returns the result
    dict, with the compared numbers under "check"."""
    import jax
    from bench import data, reference, traffic
    from bench import stats as bstats
    from bench import trace as btrace
    from repro.core import ReverseKRanksEngine
    from repro.core.types import RankTableConfig
    from repro.obs import trace as otrace
    from repro.serve import MicroBatcher

    cfg, mix = dict(plan["config"]), dict(plan["mix"])
    if storage is not None:
        cfg["storage"] = storage
    n, m, d = cfg["n_users"], cfg["n_items"], cfg["d"]
    k, c, mb_cfg = cfg["k"], float(cfg["c"]), cfg["serving"]
    dev = jax.devices()[0]

    # ---- data, on the device, from the seed
    data_key, build_key = jax.random.split(data.prng_key(seed))
    users, items, icl = data.make_vectors(cfg["vectors"], data_key, n, m, d)
    jax.block_until_ready((users, items))
    rng = np.random.default_rng(seed)
    rng_items, rng_sched, rng_warm, rng_sample = rng.spawn(4)
    icl_np = None if icl is None else np.asarray(icl)
    items_np = np.asarray(items)
    scale = traffic.query_scale(mix)
    count = traffic.planned_requests(mix, seconds, m)
    qids = traffic.item_sequence(mix, rng_items, count, m, icl_np)
    queries = (items_np[qids] * scale).astype(np.float32)
    warm_q = (items_np[traffic.item_sequence(
        mix, rng_warm, 64 * mb_cfg["max_batch"], m, icl_np)] * scale
    ).astype(np.float32)

    # ---- build (its compilation, or its load from the cache, is set-up)
    compile_s = []

    def on_compile(event, secs, **_):
        if event.startswith(COMPILE_EVENTS):
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t = time.monotonic()
    table_cfg = RankTableConfig(
        tau=cfg["tau"], omega=cfg["omega"], s=cfg["s"],
        threshold_mode=cfg["threshold_mode"], range_pad=cfg["range_pad"],
        storage_dtype=cfg["storage"])
    eng = ReverseKRanksEngine.build(users, items, table_cfg, build_key,
                                    backend=cfg["backend"])
    jax.block_until_ready(eng.rank_table.table)
    build_wall = time.monotonic() - t
    jax.monitoring.unregister_event_duration_listener(on_compile)
    build_compile_s = sum(compile_s)
    build_s = build_wall - build_compile_s

    mb = MicroBatcher(eng, max_batch=mb_cfg["max_batch"],
                      max_wait_ms=mb_cfg["max_wait_ms"],
                      pipeline_depth=mb_cfg["pipeline_depth"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        warm_rounds = _warm(mb, warm_q, k, c, mb_cfg["max_batch"])
        schedule = (traffic.open_schedule(mix, seconds, rng_sched)
                    if mix["loop"] == "open" else None)
        t_start = time.monotonic() + 0.05
        t0 = t_start + float(mix["warm_seconds"])
        t1 = t0 + seconds
        load = Load(mb, queries, k=k, c=c, n=n, mix=mix, schedule=schedule,
                    t_start=t_start, t0=t0, t1=t1,
                    sample=int(cfg["check"]["sampled_answers"]),
                    rng=rng_sample, annotate=trace)
        # set-up's objects leave the collector's sweeps, so a full
        # collection in the window only walks the window's own garbage
        gc.collect()
        gc.freeze()
        load.start()
        if trace:
            time.sleep(max(t0 - 0.5 - time.monotonic(), 0.0))
            otrace.set_capacity(1 << 20)
            otrace.enable(profiler=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        time.sleep(max(t0 - time.monotonic(), 0.0))
        ticks0, c0 = len(mb.tick_log), counters()
        if trace:
            with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
                time.sleep(max(t1 - time.monotonic(), 0.0))
        else:
            time.sleep(max(t1 - time.monotonic(), 0.0))
        ticks1, c1 = len(mb.tick_log), counters()
        setup_s = t0 - T_ORIGIN
        if trace:
            jax.profiler.stop_trace()
            otrace.disable()
        load.finish()
        mb.close()
        load.join()
        gc.unfreeze()
        ticks = mb.tick_log[ticks0:ticks1]
        spans = _span_dicts(t0, t1) if trace else []
        stats = dev.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        mb.close()
    s = load.summary()
    kept = sorted(load.kept, key=lambda a: a["i"])
    sampled_q = np.stack([queries[load.qid[a["i"]]] for a in kept]) \
        if kept else np.zeros((0, d), np.float32)
    load.mb = None
    del eng, mb
    gc.collect()

    # ---- the metrics
    lat = s["latency_ms"]
    finite = [x for x in lat if math.isfinite(x)]
    cap = (COLLECT_S + seconds) * 1e3
    lat = [x if math.isfinite(x) else cap for x in lat]
    e2e = {"qps": bstats.rate(s["answered"], seconds), "build_s": build_s,
           "setup_s": setup_s}
    if lat:
        e2e["p50_ms"] = bstats.percentile(lat, 50)
        e2e["p95_ms"] = bstats.percentile(lat, 95)
    log(f"[window] attempted={s['attempted']} answered_in_window="
        f"{s['answered']} failed={s['failed']} latency_samples={len(lat)} "
        f"finite={len(finite)} beyond_p95={len(lat) - math.ceil(0.95 * len(lat)) if lat else 0}")
    log(f"[backlog] at_open={s['backlog'][0]} at_close={s['backlog'][1]}")
    if s["late_ms"]:
        log(f"[generator] late_p99_ms={bstats.percentile(s['late_ms'], 99)}"
            f" late_max_ms={max(s['late_ms'])}")
    log(f"[ticks] window_ticks={len(ticks)} compiles_in_window="
        f"{sum(t.compiles for t in ticks)} warm_rounds={warm_rounds} "
        f"d2h_bytes_per_tick={load.answer_bytes * mb_cfg['max_batch']}")
    log(f"[setup] build_s={build_s} build_wall_s={build_wall} "
        f"build_compile_s={build_compile_s} setup_s={setup_s} "
        f"memory_peak_bytes={peak}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": None, "attempted": s["attempted"],
              "failed": s["failed"]}
    breakdown = None
    if trace:
        red = btrace.reduce(btrace.extract(btrace.xplane_file(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
        win = Window(ticks=ticks, max_batch=mb_cfg["max_batch"],
                     counters0=c0, counters1=c1, spans=spans, trace=red,
                     config=cfg, seconds=seconds,
                     peaks=peaks_for(peaks, dev.device_kind))
        metrics = {}
        for mt in plan["per_layer"]:
            v = load_reader(mt["name"], plan["root"])(win)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    else:
        metrics = {mt["name"]: {"value": e2e[mt["name"]], "unit": mt["unit"]}
                   for mt in plan["end_to_end"] if mt["name"] in e2e}

    # ---- correct: the window's own answers against the reference
    t = time.monotonic()
    if kept:
        qs = jax.numpy.asarray(sampled_q)
        exact = np.asarray(reference.exact_ranks(users, items, qs))
        samples, weights = reference.samples_and_weights(
            items, build_key, cfg["omega"], cfg["s"])
        ref_lo, ref_up, ref_est = map(np.asarray, reference.table_bounds(
            users, samples, weights, qs, m, tau=cfg["tau"],
            range_pad=float(cfg["range_pad"])))
        numbers, info = check.compare(kept, exact, ref_lo, ref_up, ref_est,
                                      m=m, k=k, c=c)
        log(f"[exact ranks] accuracy={info['accuracy']} overall_ratio="
            f"{info['overall_ratio']} ref_accuracy={info['ref_accuracy']} "
            f"ref_overall_ratio={info['ref_overall_ratio']} c={c} "
            f"(not compared)")
    else:
        numbers = {"bounds_off": 1.0, "select_off": 1.0}
    numbers["bad_answers"] = float(len(s["bad"]) + len(s["lost"]))
    limits = {"bounds_off": float(cfg["check"]["bounds_off_limit"]),
              "select_off": 0.0, "bad_answers": 0.0}
    for i, why in list(s["bad"].items())[:3]:
        log(f"[bad answer] request {i}: {why}")
    log(f"[check] sampled_answers={len(kept)} reference_s="
        f"{time.monotonic() - t}")
    result["correct"] = check.verdict(numbers, limits)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {name: {"value": numbers[name], "limit": limits[name]}
                       for name in limits}
    return result


def peaks_for(peaks: dict, kind: str) -> dict:
    if kind not in peaks["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks["devices"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--storage", choices=("f32", "bf16", "int8"),
                    help="run the configuration at this storage (control)")
    args = ap.parse_args(argv)
    try:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        plan = resolve(spec, args.workload)
        import_program(ROOT)
        enable_compile_cache(ROOT)
        require_chips(int(plan["cell"]["chips"]))
    except (NoProgram, NoChip, KeyError, OSError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    result = run(plan, args.seed, args.seconds, bool(args.trace),
                 storage=args.storage,
                 log=lambda line: print(line, flush=True))
    for name, v in result["check"].items():
        print(f"[check] {name}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
