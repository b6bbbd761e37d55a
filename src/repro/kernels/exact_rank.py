"""Streaming exact-rank kernel — Definition 1 for a block of users.

Used by the refinement path (boundary users whose table bounds are too
loose) and as the in-framework exact oracle. The item set P streams
HBM→VMEM in tiles along the second grid axis; each (user-tile, item-tile)
cell adds its count into the user tile's (Bn, 1) output block, which
stays resident across the item stream:

    grid = (n/Bn, m/Bm)
    counts[i] += Σ_{p ∈ P_j} I[ U_i · p > U_i · q ]        (Bn, 1) per cell

The scores u·q come in precomputed (one XLA matvec in the wrapper, which
also uses them to correct for padded items). The item axis j is the
innermost grid dimension, so U_i and the accumulator stay VMEM-resident
across the whole item stream (block re-use), giving the classic
compute-bound streaming schedule: arithmetic intensity ≈ Bn FLOP/byte of
P traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import dot_precision
from repro.kernels import interpret_mode


def _exact_rank_kernel(u_ref, p_ref, uq_ref, out_ref, *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    u = u_ref[...].astype(jnp.float32)                     # (Bn, d)
    p = p_ref[...].astype(jnp.float32)                     # (Bm, d)
    up = jax.lax.dot_general(
        u, p, (((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)                # (Bn, Bm) MXU
    out_ref[...] += jnp.sum((up > uq_ref[...]).astype(jnp.float32), axis=1,
                            keepdims=True)


def exact_counts_kernel_call(users: jax.Array, items: jax.Array,
                             uq: jax.Array, *, block_n: int = 256,
                             block_m: int = 512
                             ) -> jax.Array:
    """Raw pallas_call; users (n,d) [n % Bn == 0], items (m,d) [m % Bm == 0],
    uq (n, 1) the users' scores against the query.

    Returns (n, 1) float32 counts #{p : u·p > u·q}. Padded items count
    wherever 0 > u·q; ops.exact_ranks subtracts exactly that.
    """
    n, d = users.shape
    m = items.shape[0]
    return pl.pallas_call(
        functools.partial(_exact_rank_kernel,
                          precision=dot_precision()),
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret_mode(),
    )(users, items, uq)
