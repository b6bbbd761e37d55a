"""Rank-table build kernel — the Eq. (1) hot loop of Algorithm 1.

For a tile of Bn users, fuses

    scores = Samples @ U_tileᵀ          (S, Bn)  one MXU matmul
    T̂[j, :] = 1 + Σ_s w_s·I[score > t_j]  ∀j     VPU loop over τ rows

into a single VMEM-resident pass: the (S, Bn) score tile is produced and
consumed on-chip, never written to HBM. The kernel works in the
TRANSPOSED layout — users on lanes, thresholds and table columns on
sublanes — so the τ-loop's per-threshold read and write are dynamic
sublane rows (`pl.ds(j, 1)`), which the TPU lowers, where a dynamic lane
column is not. Each iteration is an (S, Bn) compare + weighted reduce,
keeping the working set at S·Bn floats instead of the naive (Bn, S, τ)
indicator tensor.

Samples are small (S = ω·s ≈ 640 for paper parameters), so the (S, d)
sample matrix is replicated into VMEM for every user tile: S·d·4B ≈ 0.5 MB
at d = 200.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import dot_precision
from repro.kernels import interpret_mode


def _table_build_kernel(u_ref, smp_ref, w_ref, thr_ref, out_ref, *,
                        precision):
    u = u_ref[...].astype(jnp.float32)                     # (Bn, d)
    smp = smp_ref[...].astype(jnp.float32)                 # (S, d)
    w = w_ref[...].astype(jnp.float32)                     # (S, 1)

    scores = jax.lax.dot_general(
        smp, u, (((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)                # (S, Bn) on MXU

    def body(j, carry):
        t_j = thr_ref[pl.ds(j, 1), :]                      # (1, Bn)
        cnt = jnp.sum(jnp.where(scores > t_j, w, 0.0), axis=0,
                      keepdims=True)                       # (1, Bn)
        out_ref[pl.ds(j, 1), :] = 1.0 + cnt
        return carry

    jax.lax.fori_loop(0, thr_ref.shape[0], body, 0)


def table_build_kernel_call(users: jax.Array, samples: jax.Array,
                            weights: jax.Array, thresholds_t: jax.Array, *,
                            block_n: int = 128) -> jax.Array:
    """Raw pallas_call; inputs pre-padded (ops.build_table_rows).

    users (n, d) [n % block_n == 0, block_n a lane multiple], samples
    (S, d), weights (S, 1), thresholds_t (τ, n) — the thresholds
    transposed → table_t (τ, n) float32.
    """
    n, d = users.shape
    s_cnt = samples.shape[0]
    tau = thresholds_t.shape[0]
    return pl.pallas_call(
        functools.partial(_table_build_kernel,
                          precision=dot_precision()),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((s_cnt, d), lambda i: (0, 0)),    # replicated
            pl.BlockSpec((s_cnt, 1), lambda i: (0, 0)),
            pl.BlockSpec((tau, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((tau, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((tau, n), jnp.float32),
        interpret=interpret_mode(),
    )(users, samples, weights, thresholds_t)
