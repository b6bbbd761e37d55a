"""Reduction from a JAX profiler trace to device busy time, idle share,
the programs that took the most device time, and the idle gaps by what
the host was doing.

The profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`.
`extract` reads it with `jax.profiler.ProfileData` into a small plain
form (lists of [name, start_ns, duration_ns]), and `reduce` works on
that form alone, so a recorded trace can be checked without a chip:

- device: each device plane (`/device:TPU:<i>`) contributes the
  intervals of its `XLA Modules` line, one per program run on it.
- host: the spans the program and the harness annotate
  (`jax.profiler.TraceAnnotation`: `serve.*`, `prune.*`, `bench.*`),
  one list per host thread.
- window: the harness's `bench.window` span, which brackets the
  measured window; everything is clipped to it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("serve.", "prune.", "bench.")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_HASH = re.compile(r"\(\d+\)$")


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def extract(path: str) -> dict:
    """The plain form of one profiler trace (module doc)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    window = None
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[plane.name] = [
                        [_HASH.sub("", e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
                for s in spans:
                    if s[0] == WINDOW_SPAN:
                        window = [s[1], s[1] + s[2]]
                spans = [s for s in spans if s[0] != WINDOW_SPAN]
                if spans:
                    host.append(spans)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    return {"window": window, "devices": devices, "host": host}


def _union(intervals, lo: float, hi: float) -> List[List[float]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_label(host: List[list], starts: List[list], t: float) -> str:
    """What the host was doing at time t: on each thread, the innermost
    annotated span open at t (spans on one thread nest, so it is the
    latest-starting one that contains t); threads joined by ' + '."""
    names = set()
    for spans, st in zip(host, starts):
        i = bisect.bisect_right(st, t) - 1
        while i >= 0:
            name, s, d = spans[i]
            if t < s + d:
                names.add(name)
                break
            i -= 1
    return " + ".join(sorted(names)) or "no span open"


def reduce(tr: dict, top: int = 10) -> dict:
    """busy_s (averaged over devices), window_s, idle share, the programs
    with the most device time, and idle time by host activity."""
    lo, hi = tr["window"]
    window_s = (hi - lo) / 1e9
    if window_s <= 0:
        raise ValueError("empty trace window")
    busy, ops, gaps = [], {}, {}
    host = [sorted(spans, key=lambda sp: sp[1]) for spans in tr["host"]]
    starts = [[sp[1] for sp in spans] for spans in host]
    for events in tr["devices"].values():
        merged = _union(((s, s + d) for _, s, d in events), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, d in events:
            clip = min(s + d, hi) - max(s, lo)
            if clip > 0:
                ops[name] = ops.get(name, 0.0) + clip / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                label = _host_label(host, starts, (g0 + g1) / 2)
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    n_dev = max(len(tr["devices"]), 1)
    busy_s = sum(busy) / n_dev
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
