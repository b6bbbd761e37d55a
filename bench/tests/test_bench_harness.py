"""The harness's plumbing: cells resolve by name, new files are found
with no edit, schedules repeat for a seed, the arithmetic, the trace
reduction, the byte count, and a run without a chip."""
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import bytes as bbytes
from bench import run as brun
from bench import stats, traffic
from bench import trace as btrace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def spec():
    return brun.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cell_resolves_by_name(cell):
    plan = brun.resolve(spec(), cell, ROOT)
    assert plan["config"]["name"] == plan["cell"]["config"]
    assert plan["mix"]["loop"] in ("open", "closed")
    names = {m["name"] for m in plan["end_to_end"]}
    assert {"setup_s", "qps", "p50_ms", "p95_ms", "build_s"} <= names
    assert plan["per_layer"], "every cell reports a per-layer metric"
    for m in plan["per_layer"]:
        assert callable(brun.load_reader(m["name"], ROOT))


def test_config_files_match_the_spec():
    s = spec()
    for entry in s["configs"]:
        cfg = brun.load_json(os.path.join(ROOT, entry["file"]))
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"] == []
        assert len(cfg["source"]) <= 200 and "2504.13446" in cfg["source"]
        assert {"vectors", "cut"} <= set(cfg["assumed"])


def test_added_files_are_found_without_edits(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/netflix-f32.json").read_text())
    cfg["name"] = "netflix-bf16"
    cfg["storage"] = "bf16"
    (root / "bench/configs/netflix-bf16.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 50.0, "warm_seconds": 1.0,
         "items": {"dist": "permutation"}}))
    (root / "bench/metrics/tick_count.py").write_text(
        "def read(window):\n    return float(len(window.ticks))\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "netflix-bf16",
                         "source": "https://arxiv.org/abs/2504.13446",
                         "file": "bench/configs/netflix-bf16.json",
                         "reduced": [], "why": "a later PR's config"})
    s["workloads"].append({"name": "netflix-bf16.burst",
                           "config": "netflix-bf16", "traffic": "burst",
                           "chips": 1, "why": "a later PR's cell"})
    s["per_layer"].append({"name": "tick_count", "unit": "ticks",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "qps",
                           "workloads": ["netflix-bf16.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    plan = brun.resolve(s, "netflix-bf16.burst", str(root))
    assert plan["config"]["storage"] == "bf16"
    assert plan["mix"]["rate_qps"] == 50.0
    assert [m["name"] for m in plan["per_layer"]] == ["tick_count"]
    read = brun.load_reader("tick_count", str(root))
    assert read(type("W", (), {"ticks": [1, 2]})()) == 2.0
    for p, b in before.items():
        assert p.read_bytes() == b, f"{p} was edited"


def test_open_schedule_repeats_for_a_seed():
    mix = {"loop": "open", "rate_qps": 300.0, "warm_seconds": 2.0}
    a = traffic.open_schedule(mix, 10, np.random.default_rng(7))
    b = traffic.open_schedule(mix, 10, np.random.default_rng(7))
    c = traffic.open_schedule(mix, 10, np.random.default_rng(8))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # every seed offers the same arrivals in another order, and the same
    # number of them in the warm-up and in the window
    assert a.size == c.size == 3600
    for x in (a, c):
        assert np.sum(x < 2.0) == 600 and np.sum((x >= 2) & (x < 12)) == 3000
    def gaps(x):
        return np.sort(np.diff(x[600:], prepend=2.0, append=12.0))
    assert np.allclose(gaps(a), gaps(c))
    assert 0 < a[0] and a[-1] < 12.0
    assert traffic.planned_requests(mix, 10, 1) == 3600


def test_item_sequences_repeat_for_a_seed():
    icl = np.repeat(np.arange(4), [40, 30, 20, 10])
    zipf = {"items": {"dist": "zipf", "cluster": 0, "a": 1.1}}
    sweep = {"items": {"dist": "permutation"}}
    for mix in (zipf, sweep):
        a = traffic.item_sequence(mix, np.random.default_rng(3), 500, 100,
                                  icl)
        b = traffic.item_sequence(mix, np.random.default_rng(3), 500, 100,
                                  icl)
        assert np.array_equal(a, b)
    assert set(np.unique(a)) == set(range(100))       # the sweep
    z = traffic.item_sequence(zipf, np.random.default_rng(3), 500, 100, icl)
    assert set(np.unique(z)) <= set(range(40))
    # Zipf: the most popular item takes about 1 / H(40, 1.1) of draws
    top = np.bincount(z).max() / z.size
    h = np.sum(1.0 / np.arange(1, 41) ** 1.1)
    assert abs(top - 1 / h) < 0.01


def test_a_catalog_zipf_cell_is_data_files_alone(tmp_path):
    """`netflix.zipf-open` (open loop, Zipf over every item of a
    configuration without clusters) needs a traffic file and an entry."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    (root / "bench/traffic/zipf-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 150.0, "warm_seconds": 3.0,
         "items": {"dist": "zipf", "a": 1.1}}))
    s = spec()
    s["workloads"].append({"name": "netflix.zipf-open",
                           "config": "netflix-f32", "traffic": "zipf-open",
                           "chips": 1, "why": "a later PR's cell"})
    plan = brun.resolve(s, "netflix.zipf-open", str(root))
    mix, m = plan["mix"], plan["config"]["n_items"]
    count = traffic.planned_requests(mix, 10, m)
    assert count == 150 * 13
    ids = traffic.item_sequence(mix, np.random.default_rng(5), count, m,
                                None)
    assert ids.size == count and 0 <= ids.min() and ids.max() < m
    assert np.array_equal(ids, traffic.item_sequence(
        mix, np.random.default_rng(5), count, m, None))
    h = np.sum(1.0 / np.arange(1, m + 1) ** 1.1)
    assert abs(np.bincount(ids).max() / count - 1 / h) < 0.01
    assert traffic.open_schedule(mix, 10, np.random.default_rng(5)).size \
        == count


def test_percentile_and_rate():
    lat = list(range(1, 101))                  # 1 .. 100 ms
    assert stats.percentile(lat, 50) == 50
    assert stats.percentile(lat, 95) == 95
    assert stats.percentile(lat, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    # a failed request misses any limit
    assert stats.percentile(lat[:94] + [math.inf] * 6, 95) == math.inf
    assert stats.rate(250, 10.0) == 25.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(1.0)


def _busy_by_raster(tr, step=1000.0):
    lo, hi = tr["window"]
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(t.size, bool)
    for _, s, d in tr["devices"]["/device:TPU:0"]:
        busy |= (t >= s) & (t < s + d)
    return busy, t


def test_trace_reduction_on_a_recorded_trace():
    with open(os.path.join(DATA, "trace_v5e.json")) as f:
        tr = json.load(f)
    red = btrace.reduce(tr)
    busy, t = _busy_by_raster(tr)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(busy.mean() * 0.1, rel=1e-3)
    assert red["idle_share"] == pytest.approx(1 - busy.mean(), abs=1e-3)
    # programs run one at a time: the top ten's time fits in the union
    assert red["device_ops"][0][0] == "jit_bound_ranks_batched"
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] * 1.001
    # idle time by host activity adds up to the idle time
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    labels = dict(red["idle_gaps"])
    assert "prune.query" in labels
    # attribution, worked out plainly: each idle run of the raster goes
    # to the innermost span open at its midpoint on each thread
    want = {}
    step = np.diff(np.concatenate([[0], (~busy).astype(int), [0]]))
    for a, b in zip(np.flatnonzero(step == 1), np.flatnonzero(step == -1)):
        mid = (t[a] + t[b - 1]) / 2
        names = set()
        for thread in tr["host"]:
            open_ = [sp for sp in thread if sp[1] <= mid < sp[1] + sp[2]]
            if open_:
                names.add(max(open_, key=lambda sp: sp[1])[0])
        label = " + ".join(sorted(names)) or "no span open"
        want[label] = want.get(label, 0.0) + (b - a) * 1e-6
    assert set(want) == set(labels)
    for label, v in want.items():
        assert labels[label] == pytest.approx(v, rel=0.02, abs=2e-5)


def test_tick_bytes_at_the_netflix_shapes():
    n, d, tau = 480189, 200, 500
    assert n * d * 4 == 384_151_200
    assert 2 * n * tau * 4 == 1_920_756_000
    assert bbytes.tick_bytes(n, d, tau, "f32", 16) == (
        384_151_200 + 1_920_756_000 + 16 * 200 * 4)
    assert bbytes.tick_bytes(n, d, tau, "bf16", 16) == (
        384_151_200 // 2 + 1_920_756_000 // 2 + 16 * 200 * 4)
    with pytest.raises(ValueError):
        bbytes.tick_bytes(n, d, tau, "fp8", 16)


def test_peaks_table_names_its_source():
    peaks = brun.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    assert "TPU v5e" in peaks["source"]
    v5e = brun.peaks_for(peaks, "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        brun.peaks_for(peaks, "TPU v9")


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "netflix.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_exits_nonzero_with_no_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and not p.stdout.strip()
    assert "accelerator" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()
