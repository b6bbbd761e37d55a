"""Exact reverse k-ranks (Definitions 1 & 2) — the O(nmd) oracle.

This is both (a) the correctness oracle every approximate path is tested
against and (b) the "straightforward algorithm" baseline from §1 of the
paper. Users are processed in fixed-size blocks so the (n, m) score matrix
never materializes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import matmul


def exact_ranks(users: jax.Array, items: jax.Array, q: jax.Array,
                block: int = 4096) -> jax.Array:
    """r(q, u, P) for every u ∈ U (Definition 1).

    Args:
      users: (n, d) user vectors U.
      items: (m, d) item vectors P.
      q:     (d,) query item vector.
      block: user-block size (controls peak memory: block × m scores).

    Returns:
      (n,) int32 ranks, r = 1 + #{p ∈ P : u·p > u·q}.
    """
    n = users.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    upad = jnp.pad(users, ((0, pad), (0, 0)))

    def body(_, ublk):
        uq = matmul(ublk, q)                            # (block,)
        up = matmul(ublk, items.T)                      # (block, m)
        r = 1 + jnp.sum(up > uq[:, None], axis=1)
        return None, r.astype(jnp.int32)

    _, ranks = jax.lax.scan(body, None, upad.reshape(nb, block, -1))
    return ranks.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("k", "block"))
def reverse_k_ranks(users: jax.Array, items: jax.Array, q: jax.Array,
                    k: int, block: int = 4096) -> tuple[jax.Array, jax.Array]:
    """Exact reverse k-ranks query (Definition 2).

    Returns:
      (indices, ranks): the k users with the smallest r(q, ·, P), rank-
      ascending, ties broken by user index (deterministic).
    """
    ranks = exact_ranks(users, items, q, block=block)
    neg_topk, idx = jax.lax.top_k(-ranks, k)
    # top_k is stable w.r.t. index on ties of the key, which gives the
    # deterministic ordering we document.
    return idx.astype(jnp.int32), -neg_topk


def exact_rank_single(u: jax.Array, items: jax.Array, q: jax.Array) -> jax.Array:
    """r(q, u, P) for one user — the literal Definition 1."""
    return 1 + jnp.sum(matmul(items, u) > matmul(u, q)).astype(jnp.int32)
