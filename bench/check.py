"""The comparison that decides `correct`.

Every answer due in the window is checked for shape: it came, without
error, with k distinct users in range (`bad_answers`, limit 0). A
sample of the window's answers, drawn from the seed, is held in full to
the plain reference (`bench/reference.py`), run after the window once
the program's state is freed:

- `bounds_off`: the share of (user, query) pairs whose served bracket
  (r↓, r↑) differs from the reference's Algorithm-1 bracket. A user the
  pruned path skipped (its bracket reads the sentinel m + 2) counts as
  differing unless the reference shows it prunable by Lemma 1
  (r↓ > R↑_k). This covers the table, the scan kernel's bounds and
  pruning's skipped tiles.
- `select_off`: the share of sampled answers whose selection breaks
  §4.3 against their own served bounds (an exact check, limit 0):
  R↓_k and R↑_k are the k-th smallest r↓ and r↑; no selected user is
  pruned by Lemma 1 (r↓ > R↑_k); no unselected user's r↑ lies half a
  rank or more below a selected user's r↓ (the estimate that orders the
  selection lies in [r↓ − ½, r↑]); and, where Lemma 1 does not close
  the search (c·R↓_k < R↑_k), the accepted users (r↑ ≤ c·R↓_k) come
  first.

The c-approximation against exact ranks (Definition 3, the §5 accuracy
and overall ratio) is reported beside them and not compared: the
sampled table gives it no deterministic floor, and the lower-precision
control reads as the program does (PERF.md). Beside the served answers'
accuracy stands that of the reference's own §4.3 selection over its own
bracket (`reference.select`), the witness that a shortfall is the
algorithm's and not the program's.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference


def answer_ok(indices, n: int, k: int) -> bool:
    idx = np.asarray(indices)
    return (idx.shape == (k,) and bool(np.all((idx >= 0) & (idx < n)))
            and np.unique(idx).size == k)


def kth(x: np.ndarray, k: int) -> float:
    return float(np.partition(x, k - 1)[k - 1])


def selection_ok(ans: dict, *, m: int, k: int, c: float) -> bool:
    """§4.3 steps 2-3 hold for one served answer (module doc)."""
    lo, up = ans["r_lo"], ans["r_up"]
    sel = np.asarray(ans["indices"])
    if not answer_ok(sel, lo.size, k):
        return False
    R_lo, R_up = float(ans["R_lo_k"]), float(ans["R_up_k"])
    if R_lo != kth(lo, k) or R_up != kth(up, k):
        return False
    if np.any(lo[sel] > R_up):
        return False
    chosen = np.zeros(lo.size, bool)
    chosen[sel] = True
    if np.min(up[~chosen]) < np.max(lo[sel]) - 0.5:
        return False
    if c * R_lo < R_up:
        accepted = (up <= c * R_lo) & (lo != float(m + 2))
        if accepted.sum() >= k:
            return bool(np.all(accepted[sel]))
        return bool(np.all(chosen[accepted]))
    return True


def c_quality(exact: np.ndarray, sel: np.ndarray, *, k: int, c: float):
    """§5 accuracy (share of selected users within c of the k-th best
    exact rank at their position) and overall ratio of one selection."""
    best = np.sort(exact)[:k].astype(np.float64)
    ours = np.sort(exact[sel]).astype(np.float64)
    return (float(np.mean(ours <= c * best)),
            float(np.mean(ours / np.maximum(best, 1.0))))


def compare(sampled: List[dict], exact: np.ndarray, ref_lo: np.ndarray,
            ref_up: np.ndarray, ref_est: np.ndarray, *, m: int, k: int,
            c: float) -> Dict:
    """Numbers over the sampled answers. `sampled[j]` holds the served
    `indices`, `r_lo`, `r_up`, `R_lo_k`, `R_up_k`; row j of `exact`,
    `ref_lo`, `ref_up`, `ref_est` is the reference for the same query.
    Returns the compared numbers and, apart, the §5 accuracy and overall
    ratio of the served selections and of the reference's."""
    sentinel = float(m + 2)
    off = pairs = bad_sel = 0
    acc, ratio, ref_q = [], [], []
    for j, ans in enumerate(sampled):
        lo, up = ans["r_lo"], ans["r_up"]
        rlo, rup = ref_lo[j], ref_up[j]
        prunable = rlo > kth(rup, k)
        differs = (lo != rlo) | (up != rup)
        off += int(np.sum(np.where(lo == sentinel, ~prunable, differs)))
        pairs += rlo.size
        ok = selection_ok(ans, m=m, k=k, c=c)
        bad_sel += not ok
        if ok:
            a, r = c_quality(exact[j], ans["indices"], k=k, c=c)
            acc.append(a)
            ratio.append(r)
        ref_q.append(c_quality(exact[j], reference.select(
            rlo, rup, ref_est[j], k=k, c=c, m=m), k=k, c=c))
    numbers = {"bounds_off": off / max(pairs, 1),
               "select_off": bad_sel / max(len(sampled), 1)}

    def mean(x):
        return float(np.mean(x)) if len(x) else float("nan")
    info = {"accuracy": mean(acc), "overall_ratio": mean(ratio),
            "ref_accuracy": mean([a for a, _ in ref_q]),
            "ref_overall_ratio": mean([r for _, r in ref_q])}
    return numbers, info


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[name] <= limits[name] for name in limits)
