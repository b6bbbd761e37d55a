"""jit'd public wrappers around the Pallas kernels (padding + reduction).

These are the entry points the engine uses; each pads inputs to kernel
tile multiples, invokes the raw pallas_call, and undoes the padding.
Kernels run compiled on an accelerator and interpreted on the CPU
(`repro.kernels.interpret_mode`); the same code path serves both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.types import QueryResult, RankTable, StoredUsers, \
    dot_precision
from repro.kernels import exact_rank as _er
from repro.kernels import table_build as _tb
from repro.kernels import user_scores as _us


_LANE = 128     # TPU lane width: pad τ and other minor dims to multiples.


def _pad_rows(x: jax.Array, mult: int, value: float = 0.0) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=value)


def _pad_cols_edge(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)), mode="edge")


@functools.partial(jax.jit, static_argnames=("m", "block_n"))
def bound_ranks(users: jax.Array, q: jax.Array, thresholds: jax.Array,
                table: jax.Array, *, m: int, block_n: int = 256
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused u·q + rank-table lookup for all users → (r↓, r↑, est), each
    (n,): the B = 1 case of `bound_ranks_batched`."""
    r_lo, r_up, est = bound_ranks_batched(users, q[None, :], thresholds,
                                          table, m=m, block_n=block_n)
    return r_lo[0], r_up[0], est[0]


@functools.partial(jax.jit, static_argnames=("m", "block_n"))
def bound_ranks_batched(users: jax.Array, qs: jax.Array,
                        thresholds: jax.Array, table: jax.Array, *, m: int,
                        block_n: int = 256
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched fused step 1: one (block_n, d) × (d, B) MXU matmul per user
    tile, all B queries bucketized against the same VMEM-resident
    threshold/table tile — the (n, d+2τ) HBM stream is read ONCE for the
    whole batch instead of once per query.

    qs is (B, d); returns (r↓, r↑, est), each (B, n) float32 (query-major,
    the `QueryBackend.bound_ranks` orientation).
    """
    n, tau = thresholds.shape[0], thresholds.shape[1]
    B = qs.shape[0]
    up = _pad_rows(users.astype(jnp.float32), block_n)
    tp = _pad_cols_edge(_pad_rows(thresholds, block_n, value=0.0), _LANE)
    bp = _pad_cols_edge(_pad_rows(table, block_n, value=1.0), _LANE)
    # B pads to a sublane multiple with zero queries; their score columns
    # are well-defined (score 0 against edge-padded thresholds) and are
    # sliced off below.
    qt = _pad_rows(qs.astype(jnp.float32), 8).T             # (d, Bp)
    r_lo, r_up, est = _us.bound_ranks_batched_kernel_call(
        up, qt, tp, bp, m=m, tau_valid=tau, block_n=block_n)
    return r_lo[:n, :B].T, r_up[:n, :B].T, est[:n, :B].T


@functools.partial(jax.jit, static_argnames=("m", "block_n"))
def bound_ranks_batched_pruned(users: jax.Array, qs: jax.Array,
                               thresholds: jax.Array, table: jax.Array,
                               block_ids: jax.Array, *, m: int,
                               block_n: int = 256
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Masked-grid batched step 1 (PR 4): like `bound_ranks_batched`, but
    the Pallas grid runs only over the user tiles named in `block_ids`
    ((nk,) int32, one id per block_n-row tile) via a scalar-prefetch
    block index map — skipped tiles are never read from HBM.

    Returns COMPACTED (r↓, r↑, est), each (B, nk·block_n) float32 in
    block-list order (tile j of the outputs is user tile block_ids[j]);
    the caller scatters back to user coordinates
    (`core.pruning.scatter_select`). Tail-tile padding rows carry
    well-defined junk exactly like the unpruned wrapper's — the scatter
    drops them.
    """
    tau = thresholds.shape[1]
    up = _pad_rows(users.astype(jnp.float32), block_n)
    tp = _pad_cols_edge(_pad_rows(thresholds, block_n, value=0.0), _LANE)
    bp = _pad_cols_edge(_pad_rows(table, block_n, value=1.0), _LANE)
    qt = _pad_rows(qs.astype(jnp.float32), 8).T             # (d, Bp)
    B = qs.shape[0]
    r_lo, r_up, est = _us.bound_ranks_batched_masked_kernel_call(
        up, qt, tp, bp, block_ids.astype(jnp.int32), m=m, tau_valid=tau,
        block_n=block_n)
    return r_lo[:, :B].T, r_up[:, :B].T, est[:, :B].T


@functools.partial(jax.jit, static_argnames=("block_n",))
def build_table_rows(users: jax.Array, samples: jax.Array,
                     weights: jax.Array, thresholds: jax.Array, *,
                     block_n: int = 128) -> jax.Array:
    """Eq. (1) table rows for all users (fused matmul + weighted counts).

    The kernel works transposed (users on lanes), so block_n is a lane
    multiple and the thresholds go in as (τ, n)."""
    n = thresholds.shape[0]
    up = _pad_rows(users.astype(jnp.float32), block_n)
    thr_t = _pad_rows(thresholds, block_n).T
    # Padded samples carry weight 0 ⇒ contribute nothing to Eq. (1).
    sp = _pad_rows(samples.astype(jnp.float32), 8)
    wp = _pad_rows(weights.astype(jnp.float32), 8, value=0.0)[:, None]
    out = _tb.table_build_kernel_call(up, sp, wp, thr_t, block_n=block_n)
    return out[:, :n].T


@functools.partial(jax.jit, static_argnames=("block_n", "block_m"))
def exact_ranks(users: jax.Array, items: jax.Array, q: jax.Array, *,
                block_n: int = 256, block_m: int = 512) -> jax.Array:
    """Definition-1 ranks via the streaming kernel. Returns (n,) float32."""
    n, m = users.shape[0], items.shape[0]
    up = _pad_rows(users.astype(jnp.float32), block_n)
    uq = jax.lax.dot_general(
        up, q.astype(jnp.float32)[:, None], (((1,), (0,)), ((), ())),
        precision=dot_precision(),
        preferred_element_type=jnp.float32)                 # (n_pad, 1)
    # P pads with zero rows: a padded item contributes I[0 > u·q], which is
    # subtracted exactly below (the kernel compares against the same uq).
    ip = _pad_rows(items.astype(jnp.float32), block_m)
    m_pad = ip.shape[0] - m
    counts = _er.exact_counts_kernel_call(up, ip, uq, block_n=block_n,
                                          block_m=block_m)
    counts = counts[:n, 0]
    if m_pad:
        counts = counts - m_pad * (0.0 > uq[:n, 0]).astype(jnp.float32)
    return 1.0 + counts


def query_fused(rt: RankTable, users: jax.Array, q: jax.Array, k: int,
                c: float) -> QueryResult:
    """§4.3 query with step 1 on the fused Pallas kernel; steps 2-3 (the
    top-k/filter tail) as one compiled selection program — identical
    selection semantics to repro.core.query.query."""
    from repro.core.query import _select_topk_jit
    m = int(rt.m)
    r_lo, r_up, est = bound_ranks(users, q, rt.thresholds, rt.table, m=m)
    return _select_topk_jit(r_lo, r_up, est, rt.m, k, c)


def query_fused_batch(rt: RankTable, users, qs: jax.Array,
                      k: int, c: float) -> QueryResult:
    """Batched §4.3 queries with step 1 on the batched Pallas kernel —
    one table pass for the whole (B, d) query block; selection (steps 2-3)
    via the shared shape-polymorphic `select_topk`, compiled as one program
    (`_select_topk_jit`). Every QueryResult field gains a leading B axis.
    Dispatches on the storage spec (`bound_ranks_batched_stored`); the f32
    spec is the pre-spec path."""
    from repro.core.query import _select_topk_jit
    r_lo, r_up, est = bound_ranks_batched_stored(users, qs, rt)
    return _select_topk_jit(r_lo, r_up, est, rt.m, k, c)


# NOTE: there is deliberately no query_fused_*_delta here — the fused
# delta path is the generic `QueryBackend._delta_query` composed over
# `bound_ranks_batched` (see `repro.core.backends.FusedBackend`), so the
# delta pipeline exists exactly once.


# --------------------------------------------- storage-spec dispatch (PR 5)
def _stored_parts(users, rt: RankTable):
    """Normalize (users, rt) into the quantized kernels' operand set.

    Raw f32 user matrices against a quantized table are served with
    identity scale and zero slack — the kernels' dequant math degenerates
    to the exact path, so mixed inputs (tests, debugging) stay correct.
    """
    if isinstance(users, StoredUsers):
        rows = users.rows
        n = rows.shape[0]
        uscale = (jnp.ones((n, 1), jnp.float32) if users.scale is None
                  else users.scale)
        uslack = (jnp.zeros((n, 1), jnp.float32) if users.row_slack is None
                  else users.row_slack)
    else:
        rows = users
        n = rows.shape[0]
        uscale = jnp.ones((n, 1), jnp.float32)
        uslack = jnp.zeros((n, 1), jnp.float32)
    return rows, uscale, uslack


def _pad_vec(x: jax.Array, mult: int, value: float) -> jax.Array:
    return _pad_rows(x, mult, value=value)



def _pad_quant_operands(kind: str, rows, uscale, uslack, thresholds,
                        table, thr_sc, thr_off, thr_dev, tab_sc, tab_off,
                        block_n: int):
    """Shared operand padding for the quantized kernel wrappers (full-grid
    and masked-grid) — the pad VALUES encode kernel soundness assumptions:
    scale pads 1.0 (no div-by-zero on junk rows), slack/offset/dev pad
    0.0, table pads 1.0, thresholds edge-pad to stay ascending. The int8
    kernel's closed-form bucketize never reads thresholds, so no padded
    copy is materialized for it."""
    up = _pad_rows(rows, block_n)
    usc = _pad_vec(uscale, block_n, 1.0)
    usl = _pad_vec(uslack, block_n, 0.0)
    tp = (None if kind == "int8" else
          _pad_cols_edge(_pad_rows(thresholds, block_n, value=0.0), _LANE))
    bp = _pad_cols_edge(_pad_rows(table, block_n, value=1.0), _LANE)
    if kind == "int8":
        quant = (_pad_vec(thr_sc, block_n, 1.0),
                 _pad_vec(thr_off, block_n, 0.0),
                 _pad_vec(thr_dev, block_n, 0.0),
                 _pad_vec(tab_sc, block_n, 1.0),
                 _pad_vec(tab_off, block_n, 0.0))
    else:
        quant = (None,) * 5
    return (up, usc, usl, tp, bp) + quant


@functools.partial(jax.jit, static_argnames=("kind", "m", "block_n"))
def _bound_ranks_batched_stored_impl(kind: str, rows, uscale, uslack, qs,
                                     thresholds, table, thr_sc, thr_off,
                                     thr_dev, tab_sc, tab_off, *, m: int,
                                     block_n: int = 256):
    """Pad + invoke the quantized batched kernel; returns (B, n) f32."""
    n, tau = thresholds.shape[0], thresholds.shape[1]
    B = qs.shape[0]
    up, usc, usl, tp, bp, tsc, tof, tdv, bsc, bof = _pad_quant_operands(
        kind, rows, uscale, uslack, thresholds, table, thr_sc, thr_off,
        thr_dev, tab_sc, tab_off, block_n)
    qt = _pad_rows(qs.astype(jnp.float32), 8).T             # (d, Bp)
    r_lo, r_up, est = _us.bound_ranks_batched_quant_kernel_call(
        kind, up, usc, usl, qt, tp, bp, tsc, tof, tdv, bsc, bof, m=m,
        tau_valid=tau, block_n=block_n)
    return r_lo[:n, :B].T, r_up[:n, :B].T, est[:n, :B].T


def bound_ranks_batched_stored(users, qs: jax.Array, rt: RankTable, *,
                               block_n: int = 256
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Spec-dispatched batched fused step 1 — THE fused-backend entry.

    f32 storage with a raw user matrix routes to the pre-spec
    `bound_ranks_batched` (bit-identical no-op); bf16/int8 route to the
    quantized kernels, whose outputs carry the certified widening (r↓
    rounded down, r↑ up) exactly like the dense dequant-aware lookup.
    """
    kind = rt.spec_kind
    if kind == "f32" and not isinstance(users, StoredUsers):
        return bound_ranks_batched(users, qs, rt.thresholds, rt.table,
                                   m=int(rt.m), block_n=block_n)
    if kind == "f32":
        raise ValueError("quantized user storage requires a quantized "
                         "rank table (uniform StorageSpec)")
    rows, uscale, uslack = _stored_parts(users, rt)
    return _bound_ranks_batched_stored_impl(
        kind, rows, uscale, uslack, qs, rt.thresholds, rt.table,
        rt.thr_scale, rt.thr_off, rt.thr_dev, rt.tab_scale, rt.tab_off,
        m=int(rt.m), block_n=block_n)


def bound_ranks_tile(users, qs: jax.Array, rt: RankTable, *, m: int,
                     block_n: int = 256
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Spec-dispatched fused step 1 for ONE fixed-size user tile — the
    kernel unit of the compile-once elastic scan (`repro.core.elastic`).

    Identical math to `bound_ranks_batched_stored`, with two contract
    changes for use inside a traced fori_loop body:

      * `m` is an explicit STATIC argument (the caller cannot concretize
        the traced `rt.m` mid-trace, and the kernel wrappers take m
        statically);
      * returns USER-major (tile, B) float32 arrays, the orientation the
        scan accumulates in.

    The compile key of the underlying kernel program is
    (tile, d, B, τ, spec) — never the served n; every tile of every
    capacity bucket re-dispatches the same program.
    """
    kind = rt.spec_kind
    if kind == "f32" and not isinstance(users, StoredUsers):
        r_lo, r_up, est = bound_ranks_batched(
            users, qs, rt.thresholds, rt.table, m=m, block_n=block_n)
    elif kind == "f32":
        raise ValueError("quantized user storage requires a quantized "
                         "rank table (uniform StorageSpec)")
    else:
        rows, uscale, uslack = _stored_parts(users, rt)
        r_lo, r_up, est = _bound_ranks_batched_stored_impl(
            kind, rows, uscale, uslack, qs, rt.thresholds, rt.table,
            rt.thr_scale, rt.thr_off, rt.thr_dev, rt.tab_scale,
            rt.tab_off, m=m, block_n=block_n)
    return r_lo.T, r_up.T, est.T


@functools.partial(jax.jit, static_argnames=("kind", "m", "block_n"))
def _bound_ranks_batched_pruned_stored_impl(kind: str, rows, uscale,
                                            uslack, qs, thresholds, table,
                                            thr_sc, thr_off, thr_dev,
                                            tab_sc, tab_off, block_ids, *,
                                            m: int, block_n: int = 256):
    tau = thresholds.shape[1]
    B = qs.shape[0]
    up, usc, usl, tp, bp, tsc, tof, tdv, bsc, bof = _pad_quant_operands(
        kind, rows, uscale, uslack, thresholds, table, thr_sc, thr_off,
        thr_dev, tab_sc, tab_off, block_n)
    qt = _pad_rows(qs.astype(jnp.float32), 8).T
    r_lo, r_up, est = _us.bound_ranks_batched_quant_masked_kernel_call(
        kind, up, usc, usl, qt, tp, bp, tsc, tof, tdv, bsc, bof,
        block_ids.astype(jnp.int32), m=m, tau_valid=tau, block_n=block_n)
    return r_lo[:, :B].T, r_up[:, :B].T, est[:, :B].T


def bound_ranks_batched_pruned_stored(users, qs: jax.Array, rt: RankTable,
                                      block_ids: jax.Array, *,
                                      block_n: int = 256
                                      ) -> tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """Spec-dispatched masked-grid (pruned) step 1: skipped tiles are
    never DMA'd at ANY storage spec; kept tiles match the full-grid
    quantized kernel exactly. Returns compacted (B, nk·block_n) arrays
    in block-list order (see `bound_ranks_batched_pruned`)."""
    kind = rt.spec_kind
    if kind == "f32" and not isinstance(users, StoredUsers):
        return bound_ranks_batched_pruned(users, qs, rt.thresholds,
                                          rt.table, block_ids,
                                          m=int(rt.m), block_n=block_n)
    if kind == "f32":
        raise ValueError("quantized user storage requires a quantized "
                         "rank table (uniform StorageSpec)")
    rows, uscale, uslack = _stored_parts(users, rt)
    return _bound_ranks_batched_pruned_stored_impl(
        kind, rows, uscale, uslack, qs, rt.thresholds, rt.table,
        rt.thr_scale, rt.thr_off, rt.thr_dev, rt.tab_scale, rt.tab_off,
        block_ids, m=int(rt.m), block_n=block_n)
