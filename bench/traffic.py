"""One general traffic generator, driven by a mix's data file
(`bench/traffic/<mix>.json`).

A mix names a `loop` and an `items` distribution:

- `"loop": "closed"` keeps `outstanding` requests in flight: each answer
  makes the next request due at once (callers that wait for replies).
- `"loop": "open"` sends at `rate_qps` on a schedule, whatever the
  answers do (independent users). The warm-up and the window each get
  round(rate × length) arrivals: N arrivals split a span into N + 1
  gaps, the exponential distribution's quantiles at (i + ½)/(N + 1)
  scaled to fill the span exactly, shuffled by the seed. So every seed
  offers the same arrivals in another order, and the same number of
  them inside the window.

- `"items": {"dist": "permutation"}`: every item once in a seeded order,
  then again (a sweep over the catalog).
- `"items": {"dist": "zipf", "a": a, "cluster": c, "scale": x}`: Zipf(a)
  popularity over a seeded popularity order of the whole catalog or,
  where `cluster` is given, of that cluster's items (the hottest is 0);
  each query vector scaled by x (default 1). The rank of each draw is
  the Zipf quantile at (i + ½)/N, shuffled by the seed, so every seed
  draws the same popularity ranks.

`warm_seconds` of the mix run before the measured window, so the window
starts in a steady state.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def item_sequence(mix: dict, rng: np.random.Generator, count: int,
                  m: int, item_cluster: Optional[np.ndarray]) -> np.ndarray:
    """`count` item ids in request order."""
    spec = mix["items"]
    if spec["dist"] == "permutation":
        reps = -(-count // m)
        return np.concatenate([rng.permutation(m) for _ in range(reps)]
                              )[:count]
    if spec["dist"] == "zipf":
        if "cluster" not in spec:
            pool = np.arange(m)
        elif item_cluster is None:
            raise ValueError("items of a cluster need a clustered "
                             "configuration")
        else:
            pool = np.flatnonzero(np.asarray(item_cluster)
                                  == spec["cluster"])
        if pool.size == 0:
            raise ValueError(f"cluster {spec['cluster']} has no items")
        order = rng.permutation(pool)
        w = 1.0 / np.arange(1, order.size + 1) ** float(spec["a"])
        cdf = np.cumsum(w / w.sum())
        u = (np.arange(count) + 0.5) / count
        ranks = np.minimum(np.searchsorted(cdf, u), order.size - 1)
        return order[rng.permutation(ranks)]
    raise ValueError(f"unknown item distribution {spec['dist']!r}")


def query_scale(mix: dict) -> float:
    return float(mix["items"].get("scale", 1.0))


def open_count(rate_qps: float, span: float) -> int:
    """Arrivals an open loop offers in `span` seconds."""
    if rate_qps <= 0:
        raise ValueError("an open loop needs a positive rate")
    return int(round(rate_qps * span))


def open_arrivals(rate_qps: float, span: float,
                  rng: np.random.Generator) -> np.ndarray:
    """The arrival times in (0, span) of one stretch (module doc)."""
    count = open_count(rate_qps, span)
    u = (np.arange(count + 1) + 0.5) / (count + 1)
    gaps = -np.log1p(-u)
    gaps *= span / gaps.sum()
    return np.cumsum(rng.permutation(gaps))[:count]


def open_schedule(mix: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the start of the warm-up) of every request
    of an open loop: the warm-up's, then the window's, drawn apart."""
    warm = float(mix["warm_seconds"])
    return np.concatenate([
        open_arrivals(mix["rate_qps"], warm, rng),
        warm + open_arrivals(mix["rate_qps"], seconds, rng)])


def planned_requests(mix: dict, seconds: float, m: int) -> int:
    """How many items to draw: every request of an open loop; one sweep
    of the catalog for a closed loop, which cycles through it."""
    if mix["loop"] == "open":
        return (open_count(mix["rate_qps"], float(mix["warm_seconds"]))
                + open_count(mix["rate_qps"], seconds))
    if mix["loop"] == "closed":
        return m
    raise ValueError(f"unknown loop {mix['loop']!r}")
