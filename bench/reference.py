"""The plain reference the served answers are held to.

Straight `jax.numpy` at HIGHEST matmul precision, blocked over users so
it fits beside the data. It imports nothing of the program and takes
nothing the program made: it re-derives Algorithm 1 (arXiv:2504.13446
§4.2) from the items, the configuration and the build key, and computes
exact ranks (Definition 1) from the vectors.

- `exact_ranks`: r(q, u, P) = 1 + #{p ∈ P : u·p > u·q} for every user.
- `table_bounds`: the Algorithm-1 bracket (r↓, r↑) of every user's score
  u·q. Items sorted by descending norm, ω equal partitions (the first
  m mod ω one larger), s samples drawn from each without replacement
  with `jax.random.choice` under `split(build_key, ω)`; per user, τ
  uniform thresholds over the sampled score range widened by
  `range_pad` of it on both sides; Eq. (1) table entries
  T_j = 1 + Σ (|P_l|/s)·[u·p > t_j] over the samples; and the §4.3
  lookup t_j ≤ u·q < t_{j+1} ⇒ (r↓, r↑) = (T_{j+1}, T_j), with
  (T_1, m+1) below the range and (1, T_τ) above it; and the §4.3 step-3
  estimate, linear in u·q between t_j and t_{j+1} and, outside the
  range, decaying with the score's margin beyond it (the estimate the
  program documents for out-of-range scores).
- `select`: §4.3 steps 2-3 over one query's bracket and estimate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST,
                   preferred_element_type=jnp.float32)


def _blocks(x: jax.Array, block: int) -> jax.Array:
    """(n, ...) → (nb, block, ...), zero-padded at the tail."""
    n = x.shape[0]
    nb = -(-n // block)
    pad = [(0, nb * block - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad).reshape((nb, block) + x.shape[1:])


@functools.partial(jax.jit, static_argnames=("block",))
def exact_ranks(users, items, qs, block: int = 512) -> jax.Array:
    """(S, n) int32 exact ranks of every user for each of S queries."""
    n = users.shape[0]

    def one(ub):
        up = _dot(ub, items.T)                        # (block, m)
        uq = _dot(ub, qs.T)                           # (block, S)
        return 1 + jnp.sum(up[:, :, None] > uq[:, None, :], axis=1,
                           dtype=jnp.int32)

    r = jax.lax.map(one, _blocks(users, block))       # (nb, block, S)
    return r.reshape(-1, qs.shape[0])[:n].T


def sample_plan(m: int, omega: int, s: int):
    """(partition starts, sizes) of the ω norm-descending partitions."""
    base, extra = divmod(m, omega)
    sizes = [base + (1 if l < extra else 0) for l in range(omega)]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(int)
    return starts, sizes


def samples_and_weights(items, build_key, omega: int, s: int):
    """The stratified sample of Algorithm 1 (lines 1-6): (ω·s, d) sampled
    item vectors and their (ω·s,) Eq. (1) weights |P_l| / s."""
    m = items.shape[0]
    norms = jnp.linalg.norm(items.astype(jnp.float32), axis=1)
    items_sorted = items[jnp.argsort(-norms)]
    starts, sizes = sample_plan(m, omega, s)
    keys = jax.random.split(build_key, omega)
    pos, w = [], []
    for l in range(omega):
        local = jax.random.choice(keys[l], sizes[l], (s,),
                                  replace=s > sizes[l])
        pos.append(starts[l] + local)
        w.append(jnp.full((s,), sizes[l] / s, jnp.float32))
    return items_sorted[jnp.concatenate(pos)], jnp.concatenate(w)


@functools.partial(jax.jit, static_argnames=("tau", "range_pad", "block"))
def table_bounds(users, samples, weights, qs, m, tau: int,
                 range_pad: float, block: int = 2048):
    """(r↓, r↑, est), each (S, n) float32: the Algorithm-1 bracket of
    every user's score, and its estimate, for each of S queries (module
    doc)."""
    n = users.shape[0]
    frac = jnp.arange(tau, dtype=jnp.float32) / (tau - 1)
    m1 = jnp.asarray(m, jnp.float32) + 1.0

    def one(ub):
        sc = _dot(ub, samples.T)                      # (block, ω·s)
        lo, hi = sc.min(axis=1), sc.max(axis=1)
        pad = range_pad * jnp.maximum(hi - lo, 1e-6)
        lo, hi = lo - pad, hi + pad
        thr = lo[:, None] + frac[None, :] * (hi - lo)[:, None]   # (bl, τ)
        uq = _dot(ub, qs.T)                           # (block, S)
        idx = jnp.sum(thr[:, None, :] <= uq[:, :, None], axis=2,
                      dtype=jnp.int32)                # (block, S) in [0, τ]

        def entry(j):                                 # T_{j+1}, j 0-based
            t = jnp.take_along_axis(thr, jnp.clip(j, 0, tau - 1), axis=1)
            above = sc[:, None, :] > t[:, :, None]    # (block, S, ω·s)
            return 1.0 + jnp.sum(jnp.where(above, weights, 0.0), axis=2)

        r_up = jnp.where(idx == 0, m1, entry(idx - 1))
        r_lo = jnp.where(idx == tau, 1.0, entry(idx))

        def thr_at(j):
            return jnp.take_along_axis(thr, jnp.clip(j, 0, tau - 1), axis=1)

        t_j, t_j1 = thr_at(idx - 1), thr_at(idx)
        pos = jnp.clip((uq - t_j) / jnp.maximum(t_j1 - t_j, 1e-12), 0, 1)
        width = jnp.maximum(thr[:, -1:] - thr[:, :1], 1e-12)
        above = jnp.maximum(uq - thr[:, -1:], 0.0) / width
        below = jnp.maximum(thr[:, :1] - uq, 0.0) / width
        est = jnp.where(
            (idx > 0) & (idx < tau), r_up + (r_lo - r_up) * pos,
            jnp.where(idx == tau, 1.0 + (r_up - 1.0) / (1.0 + tau * above),
                      m1 - (m1 - r_lo) * jnp.exp(-tau * below)))
        est = jnp.clip(est, r_lo, r_up) - 0.5 * above / (1.0 + above)
        return r_lo, r_up, est

    out = jax.lax.map(one, _blocks(users, block))
    s_ = qs.shape[0]
    return tuple(x.reshape(-1, s_)[:n].T for x in out)


def select(r_lo: np.ndarray, r_up: np.ndarray, est: np.ndarray, *, k: int,
           c: float, m: int) -> np.ndarray:
    """The k users §4.3 selects for one query: R↓_k and R↑_k are the k-th
    smallest bounds; where c·R↓_k ≥ R↑_k every user is c-approximate and
    the k smallest estimates win; otherwise Lemma-1 accepted users
    (r↑ ≤ c·R↓_k) come first, then undecided ones, pruned users
    (r↓ > R↑_k) last, each class by estimate."""
    R_lo = np.partition(r_lo, k - 1)[k - 1]
    R_up = np.partition(r_up, k - 1)[k - 1]
    key = est.astype(np.float64)
    if c * R_lo < R_up:
        prio = np.where(r_up <= c * R_lo, 0.0,
                        np.where(r_lo > R_up, 2.0, 1.0))
        key = prio * (m + 2) + key
    return np.argsort(key, kind="stable")[:k]
