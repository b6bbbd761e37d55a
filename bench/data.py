"""Seeded vector generators: the yardstick's copy of the data the cells
serve, so that no change to the program can move what is measured.

Copied from the program at the time the benchmark was defined:
`mf_like` is `repro.data.pipeline.synthetic_embeddings` and `clustered`
is `benchmarks/common.py`'s `zipf_clustered` (what its
`make_regime("clustered")` returns). Each generator runs on the device
in one jitted call. The Zipf item draw of `chip_smoke.py`'s `zipf_pick`
is `bench/traffic.py`'s `zipf` distribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def prng_key(seed: int) -> jax.Array:
    """A JAX key that uses every bit of a seed up to 64 bits
    (`PRNGKey` alone keeps only the low 32)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


@functools.partial(jax.jit, static_argnames=("n", "m", "d"))
def mf_like(key, n: int, m: int, d: int):
    """MF-like users and items: a Gaussian norm profile (paper Fig. 2)
    plus 32 shared latent clusters, users and items drawn iid."""
    norm_spread, n_clusters, cluster_strength = 0.3, 32, 1.0
    ku, ki, ks, kc, kcu, kci = jax.random.split(key, 6)
    centers = jax.random.normal(kc, (n_clusters, d), jnp.float32)
    cu = jax.random.randint(kcu, (n,), 0, n_clusters)
    ci = jax.random.randint(kci, (m,), 0, n_clusters)
    users = jax.random.normal(ku, (n, d), jnp.float32) \
        + cluster_strength * centers[cu]
    items = jax.random.normal(ki, (m, d), jnp.float32) \
        + cluster_strength * centers[ci]
    scale = 1.0 + norm_spread * jax.random.normal(ks, (m, 1), jnp.float32)
    return users, items * jnp.abs(scale)


@functools.partial(jax.jit, static_argnames=("n", "m", "d"))
def _clustered(key, n: int, m: int, d: int):
    a, user_spread, item_spread = 1.1, 0.05, 0.5
    n_clusters = max(8, min(64, n // 4096))
    w = np.arange(1, n_clusters + 1, dtype=np.float64) ** -a
    w /= w.sum()
    counts = np.floor(w * n).astype(int)
    counts[0] += n - counts.sum()
    kc, ku, ki, kn = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (n_clusters, d), jnp.float32) * 2.0
    assign = jnp.asarray(np.repeat(np.arange(n_clusters), counts))
    users = (centers[assign]
             + user_spread * jax.random.normal(ku, (n, d), jnp.float32))
    icl = jax.random.categorical(
        ki, jnp.log(jnp.asarray(w, jnp.float32)), shape=(m,))
    items = (centers[icl]
             + item_spread * jax.random.normal(kn, (m, d), jnp.float32))
    return users, items, icl


def clustered(key, n: int, m: int, d: int):
    """Zipf-sized (a 1.1) Gaussian user clusters in cluster-contiguous row
    order, max(8, min(64, n // 4096)) of them; items near the same
    centers with Zipf popularity. Returns (users, items, item_cluster)."""
    return _clustered(key, n=n, m=m, d=d)


GENERATORS = {
    "mf_like": lambda key, n, m, d: (*mf_like(key, n=n, m=m, d=d), None),
    "clustered": clustered,
}


def make_vectors(kind: str, key, n: int, m: int, d: int):
    """(users, items, item_cluster or None) for a configuration's
    `vectors` generator."""
    if kind not in GENERATORS:
        raise ValueError(f"unknown vector generator {kind!r}; one of "
                         f"{sorted(GENERATORS)}")
    return GENERATORS[kind](key, n, m, d)

