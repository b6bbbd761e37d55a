"""c-approximate reverse k-ranks query processing — §4.3 of the paper.

Three steps, all shape-stable (no data-dependent branches, so the whole
query jits into one XLA program and the Lemma-1 cases become masks):

  1. u·q for every user (the only O(nd) stage) + rank-table lookup →
     per-user bound ranks (r↓, r↑) and an interpolated estimate;
  2. R↓_k / R↑_k via top-k, Lemma-1 accept/prune masks;
  3. a single composite-key top-k realizes the paper's insertion order:
     in the guaranteed case (c·R↓_k ≥ R↑_k) users are ranked purely by the
     interpolated estimate; otherwise Lemma-1-accepted users come first,
     undetermined users (U_temp) fill by estimate, pruned users are pushed
     past every admissible key.

Total O(nd) — matching the paper's complexity claim; steps 2-3 are O(n).

BATCHED-FIRST (PR 1): the primitive unit of work is a (B, d) query block.
Step 1 for a batch is one (n, d) × (d, B) MXU matmul plus a SINGLE pass
over the (n, τ) thresholds/table serving all B queries — the n·(d + 2τ)
byte stream is read once per batch instead of once per query, a ~B×
reduction in HBM traffic for the memory-bound online phase. `query` is
literally the B = 1 case of `query_batch`; `select_topk` and
`lemma1_select` are shape-polymorphic over a leading batch axis so the
dense, fused-Pallas, and sharded backends (see `repro.core.backends`)
share one selection semantics.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import DeltaCorrection, EPS_BF16, QueryResult, \
    RankTable, StoredUsers, _I8_TRANSFORM_PAD, kth_smallest, matmul, \
    round_bf16

# §Perf H4b (REFUTED): a gather-based bisection was hypothesized to touch
# only ~log2(τ)·n elements instead of streaming the full (n, τ) rows.
# XLA's cost model (and TPU HBM reality — gathers are line-quantized)
# charges each gather round at full-operand bytes, making bisect ~3×
# WORSE than the vectorized searchsorted. Kept as an option for the
# record; the winning lever is τ itself (see EXPERIMENTS.md §Perf H4).
LOOKUP = "searchsorted"


def _bucketize(thresholds: jax.Array, uq: jax.Array) -> jax.Array:
    """idx = #{j : t_j ≤ uq} per (row, query), ascending per-row thresholds.

    thresholds (n, τ); uq (n, B) — one score column per batched query.
    Returns (n, B) int in [0, τ].
    """
    n, tau = thresholds.shape
    if LOOKUP == "searchsorted":
        return jax.vmap(functools.partial(jnp.searchsorted, side="right"))(
            thresholds, uq.astype(thresholds.dtype))
    rows = jnp.arange(n)[:, None]
    uq_c = uq.astype(thresholds.dtype)
    lo = jnp.zeros(uq.shape, jnp.int32)
    hi = jnp.full(uq.shape, tau, jnp.int32)
    for _ in range(int(math.ceil(math.log2(max(tau, 2)))) + 1):
        mid = (lo + hi) // 2
        v = thresholds[rows, jnp.clip(mid, 0, tau - 1)]
        go_right = (v <= uq_c) & (mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


# Row-block size for the tiled dequantizing matmul: XLA CPU lowers a
# convert feeding a dot into a NAIVE (non-GEMM) loop, and a standalone
# full-matrix convert is a DRAM-streaming write of the 4× f32 copy —
# both measured several times slower than f32 GEMM at (256k, 64). A
# sequential lax.map over row blocks keeps each converted tile
# cache-resident between its convert and its oneDNN GEMM: 24 ms vs 83 ms
# fused / 73 ms barrier at (262144, 64) × (64, 16).
_DEQUANT_MM_BLOCK = 1024


def _dequant_matmul(rows: jax.Array, scale: Optional[jax.Array],
                    qs: jax.Array) -> jax.Array:
    """(rows·qs.T)·scale with rows in a storage dtype — f32 accumulate,
    tiled so the dequantized copy never round-trips through DRAM."""
    n = rows.shape[0]
    qt = qs.T.astype(jnp.float32)

    def block(args):
        rb, sb = args
        out = matmul(rb.astype(jnp.float32), qt)
        return out if sb is None else out * sb

    nb = n // _DEQUANT_MM_BLOCK
    if nb < 2:
        return block((rows, scale))
    head = nb * _DEQUANT_MM_BLOCK
    rb = rows[:head].reshape(nb, _DEQUANT_MM_BLOCK, rows.shape[1])
    sb = (None if scale is None
          else scale[:head].reshape(nb, _DEQUANT_MM_BLOCK, 1))
    out = jax.lax.map(block, (rb, sb)).reshape(head, -1)
    if head < n:
        out = jnp.concatenate([out, block((rows[head:],
                                           None if scale is None
                                           else scale[head:]))])
    return out


def user_scores_batch(users, qs: jax.Array
                      ) -> tuple[jax.Array, Optional[jax.Array]]:
    """Step-1 scores for either user representation.

    `users` is a raw (n, d) array (f32 spec — the expression is exactly
    the pre-spec `(users @ qs.T).astype(f32)`, so the f32 path stays
    bit-identical) or a `StoredUsers` (bf16/int8 rows dequantized with
    f32 accumulation, tiled — see `_dequant_matmul`). Returns
    (scores, slack), each (n, B); slack is the certified
    |stored-score − f32-score| bound (None when exact) that the
    dequant-aware lookup folds into the (r↓, r↑) widening.
    """
    if not isinstance(users, StoredUsers):
        return matmul(users, qs.T).astype(jnp.float32), None
    scores = _dequant_matmul(users.rows, users.scale, qs)   # (n, B)
    slack = users.row_slack * jnp.sum(jnp.abs(qs), axis=1)[None, :]
    return scores, slack


def _searchsorted_rows(rows: jax.Array, vals: jax.Array, side: str
                       ) -> jax.Array:
    """Vmapped per-row searchsorted: rows (n, τ) ascending, vals (n, B)."""
    return jax.vmap(functools.partial(jnp.searchsorted, side=side))(rows,
                                                                    vals)


def _est_from_grid(uq: jax.Array, idx: jax.Array, thr_up: jax.Array,
                   thr_lo: jax.Array, thr_edge_lo: jax.Array,
                   thr_edge_hi: jax.Array, r_lo: jax.Array,
                   r_up: jax.Array, tau: int, m_plus_1: jax.Array
                   ) -> jax.Array:
    """The §4.3-step-3 interpolated estimate + margin-decayed out-of-range
    refinement + sub-unit tie-break, on caller-supplied DEQUANTIZED f32
    grid values (shared by the bf16 and int8 lookup paths; the f32 path
    keeps its original inline body for bit-identity).

    thr_up/thr_lo are the thresholds bracketing `idx`; thr_edge_lo/hi the
    per-row grid endpoints, (n, 1). The estimate interpolates between the
    CERTIFIED (widened) bounds, so clip keeps it admissible.
    """
    span = jnp.maximum(thr_lo - thr_up, 1e-12)
    frac = jnp.clip((uq - thr_up) / span, 0.0, 1.0)
    interior = (idx > 0) & (idx < tau)
    est_in = r_up + (r_lo - r_up) * frac
    rng = jnp.maximum(thr_edge_hi - thr_edge_lo, 1e-12)
    m_above = jnp.maximum(uq - thr_edge_hi, 0.0) / rng
    m_below = jnp.maximum(thr_edge_lo - uq, 0.0) / rng
    est_above = 1.0 + (r_up - 1.0) / (1.0 + tau * m_above)
    est_below = m_plus_1 - (m_plus_1 - r_lo) * jnp.exp(-tau * m_below)
    est = jnp.where(interior, est_in,
                    jnp.where(idx == tau, est_above, est_below))
    est = jnp.clip(est, r_lo, r_up)
    return est - 0.5 * m_above / (1.0 + m_above)


def _lookup_bounds_bf16(rt: RankTable, uq: jax.Array,
                        slack: Optional[jax.Array]
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Certified lookup on a bf16-stored table.

    Bucketize via the MONOTONE CAST, two-sided: with t̃ = bf16(t) and a
    score interval [s−δ, s+δ] around the true f32 score,
      t ≤ s+δ ⟹ t̃ ≤ bf16(s+δ)   so  idx_hi = #{t̃ ≤ bf16(s+δ)} ≥ idx*;
      t̃ < bf16(s−δ) ⟹ t < s−δ   so  idx_lo = #{t̃ < bf16(s−δ)} ≤ idx*.
    Table reads widen by EPS_BF16 in the certified direction:
    r↑ = T̃[idx_lo−1]·(1+ε) ≥ T[idx*−1] (T non-increasing, idx_lo ≤ idx*)
    and r↓ = T̃[idx_hi]·(1−ε) ≤ T[idx*] — quantization error is folded
    into the bounds, never into the selection semantics.
    """
    n, tau = rt.thresholds.shape
    thr, tab = rt.thresholds, rt.table
    s_hi = uq if slack is None else uq + slack
    s_lo = uq if slack is None else uq - slack
    # round_bf16 first: a bare cast may be dropped as excess precision
    idx_hi = _searchsorted_rows(thr, round_bf16(s_hi).astype(thr.dtype),
                                "right")
    idx_lo = _searchsorted_rows(thr, round_bf16(s_lo).astype(thr.dtype),
                                "left")
    m_plus_1 = (rt.m + 1).astype(jnp.float32)
    up_col = jnp.clip(idx_lo - 1, 0, tau - 1)
    lo_col = jnp.clip(idx_hi, 0, tau - 1)
    t_up = jnp.take_along_axis(tab, up_col, axis=1).astype(jnp.float32)
    t_lo = jnp.take_along_axis(tab, lo_col, axis=1).astype(jnp.float32)
    r_up = jnp.where(idx_lo == 0, m_plus_1, t_up * (1.0 + EPS_BF16))
    r_lo = jnp.where(idx_hi == tau, 1.0, t_lo * (1.0 - EPS_BF16))
    thr32 = lambda c: jnp.take_along_axis(thr, c, axis=1).astype(jnp.float32)
    est = _est_from_grid(
        uq, idx_hi, thr32(jnp.clip(idx_hi - 1, 0, tau - 1)), thr32(lo_col),
        thr[:, :1].astype(jnp.float32),
        thr[:, tau - 1:tau].astype(jnp.float32), r_lo, r_up, tau, m_plus_1)
    return r_lo, r_up, est


def _lookup_bounds_int8(rt: RankTable, uq: jax.Array,
                        slack: Optional[jax.Array]
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Certified lookup on an int8-stored table — CLOSED-FORM bucketize.

    Algorithm 1 builds each row's thresholds as a UNIFORM grid
    (`threshold_grid`), so in the row's code units the true thresholds
    sit within `thr_dev` (measured at pack time, ~f32-rounding tiny) of
    the exact affine grid G_j = −127 + j·Δ, Δ = 254/(τ−1). The bucketize
    therefore needs NO search and NO threshold-stream read at all:

        idx_hi = #{j : G_j − dev' ≤ s' + δ'} = ⌊(s'+δ'+127+dev')/Δ⌋ + 1
        idx_lo = #{j : G_j + dev' ≤ s' − δ'} = ⌊(s'−δ'−127−dev')/Δ⌋ + 1

    (clipped to [0, τ]), with s' = (s−off)/sc, δ' the user-quantization
    score slack in code units, and dev' = thr_dev + pad covering the f32
    rounding of the transform and the division. Since thr_dev bounds the
    TRUE-threshold deviation, idx_lo ≤ idx* ≤ idx_hi is certified even
    for a non-uniform packed table (dev is then just large). Table reads
    dequantize and widen by (½+pad)·scale in the certified direction —
    r↓ rounds down, r↑ rounds up. HBM traffic of the whole lookup: the
    int8 TABLE gathers plus five (n, 1) vectors — the thresholds array
    is never touched on the query path.
    """
    n, tau = rt.thresholds.shape
    tab_q = rt.table
    sc_t, off_t = rt.thr_scale, rt.thr_off                  # (n, 1)
    sc_b, off_b = rt.tab_scale, rt.tab_off
    s_n = (uq - off_t) / sc_t                               # (n, B) in codes
    d_n = 0.0 if slack is None else slack / sc_t
    dev = rt.thr_dev + 20.0 * _I8_TRANSFORM_PAD             # (n, 1)
    delta = 254.0 / (tau - 1)
    # #{j : −127 + jΔ ≤ v} = ⌊(v + 127)/Δ⌋ + 1, v = s' ± (δ' + dev);
    # the float-side clip guards the int32 cast against overflow when a
    # degenerate row scale blows s' up
    count = lambda v: jnp.clip(
        jnp.floor((v + 127.0) / delta), -1.0, float(tau)
    ).astype(jnp.int32) + 1
    idx_hi = jnp.clip(count(s_n + d_n + dev), 0, tau)
    idx_lo = jnp.clip(count(s_n - d_n - dev), 0, tau)
    m_plus_1 = (rt.m + 1).astype(jnp.float32)
    up_col = jnp.clip(idx_lo - 1, 0, tau - 1)
    lo_col = jnp.clip(idx_hi, 0, tau - 1)
    deq_tab = lambda c: (jnp.take_along_axis(tab_q, c, axis=1).astype(
        jnp.float32) * sc_b + off_b)
    widen = (0.5 + _I8_TRANSFORM_PAD) * sc_b                # (n, 1)
    r_up = jnp.where(idx_lo == 0, m_plus_1, deq_tab(up_col) + widen)
    r_lo = jnp.where(idx_hi == tau, 1.0, deq_tab(lo_col) - widen)
    # est thresholds in closed form too (G_c·sc + off) — zero gathers
    grid_at = lambda c: ((c.astype(jnp.float32) * delta - 127.0) * sc_t
                         + off_t)
    est = _est_from_grid(
        uq, idx_hi, grid_at(jnp.clip(idx_hi - 1, 0, tau - 1)),
        grid_at(lo_col),
        -127.0 * sc_t + off_t, 127.0 * sc_t + off_t,
        r_lo, r_up, tau, m_plus_1)
    return r_lo, r_up, est


def lookup_bounds_batch(rt: RankTable, uq: jax.Array,
                        slack: Optional[jax.Array] = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-table lookup (§4.3 step 1) for a (n, B) score block — THE one
    dequant-aware bound path: every backend (dense, fused-generic,
    sharded per shard, pruned per gathered block) lands here, dispatched
    on the table's storage spec (`RankTable.spec_kind`, static at trace).

    uq[i, b] = u_i · q_b; each threshold/table ROW is streamed once and
    bucketizes all B queries — the bandwidth amortization the batched
    engine is built around.

    With ascending thresholds t_1..t_τ and non-increasing table T_1..T_τ:
      t_j ≤ u·q ≤ t_{j+1}  ⇒  T_{j+1} ≤ r(q,u,P) ≤ T_j.
    Out-of-range: u·q < t_1 ⇒ (r↓, r↑) = (T_1, m+1);
                  u·q ≥ t_τ ⇒ (r↓, r↑) = (1, T_τ).

    `slack` (quantized user matrices) is the certified per-(row, query)
    score-error bound; quantized specs fold it plus their own storage
    error into the returned bounds — r↓ rounds DOWN, r↑ rounds UP — so
    the f32-spec bounds (and hence the table's true bracketing) are
    certifiably contained in the returned interval, and Lemma-1 selection
    over them stays sound (the bound-widening proof obligation; see
    `types.StorageSpec`).

    Returns (r_lo, r_up, est), each (n, B) — bounds plus the §4.3-step-3
    linear interpolation of the rank at u·q's position between its two
    thresholds.
    """
    kind = rt.spec_kind
    if kind == "int8":
        return _lookup_bounds_int8(rt, uq, slack)
    if kind == "bf16":
        return _lookup_bounds_bf16(rt, uq, slack)
    if slack is not None:
        raise ValueError("score slack requires a quantized rank table "
                         "(an exact f32 table cannot widen its bounds)")
    n, tau = rt.thresholds.shape
    # _bucketize compares in the table's storage dtype: promotion to f32
    # would materialize a full-size HBM copy of a bf16 table, erasing the
    # §Perf-H4 bandwidth win (refuted-hypothesis lesson).
    idx = _bucketize(rt.thresholds, uq)                     # (n, B) in [0, τ]
    m_plus_1 = (rt.m + 1).astype(jnp.float32)
    up_col = jnp.clip(idx - 1, 0, tau - 1)
    lo_col = jnp.clip(idx, 0, tau - 1)
    t_up = jnp.take_along_axis(rt.table, up_col, axis=1).astype(jnp.float32)
    t_lo = jnp.take_along_axis(rt.table, lo_col, axis=1).astype(jnp.float32)
    r_up = jnp.where(idx == 0, m_plus_1, t_up)               # T_j (j = idx)
    r_lo = jnp.where(idx == tau, 1.0, t_lo)                  # T_{j+1}

    # Linear interpolation between the bracketing thresholds (step 3).
    lo_thr = jnp.take_along_axis(rt.thresholds, up_col, axis=1).astype(
        jnp.float32)
    hi_thr = jnp.take_along_axis(rt.thresholds, lo_col, axis=1).astype(
        jnp.float32)
    span = jnp.maximum(hi_thr - lo_thr, 1e-12)
    frac = jnp.clip((uq - lo_thr) / span, 0.0, 1.0)
    interior = (idx > 0) & (idx < tau)
    est_in = r_up + (r_lo - r_up) * frac
    # Out-of-range scores (beyond-paper refinement): the paper's midpoint
    # collapses every above-range user to the same estimate, making the
    # final top-k an arbitrary tie-break (hurts popular-item queries where
    # many users exceed t_τ). Decay the estimate with the score's margin
    # beyond the range instead — monotone, consistent at the boundary
    # (margin 0 ⇒ the bound), and still within [r↓, r↑].
    t_lo_edge = rt.thresholds[:, :1].astype(jnp.float32)     # (n, 1)
    t_hi_edge = rt.thresholds[:, tau - 1:tau].astype(jnp.float32)
    rng = jnp.maximum(t_hi_edge - t_lo_edge, 1e-12)
    m_above = jnp.maximum(uq - t_hi_edge, 0.0) / rng
    m_below = jnp.maximum(t_lo_edge - uq, 0.0) / rng
    est_above = 1.0 + (r_up - 1.0) / (1.0 + tau * m_above)
    est_below = m_plus_1 - (m_plus_1 - r_lo) * jnp.exp(-tau * m_below)
    est = jnp.where(interior, est_in,
                    jnp.where(idx == tau, est_above, est_below))
    est = jnp.clip(est, r_lo, r_up)
    # Sub-unit tie-break: when the top table entry is already rank 1, every
    # above-range user collapses to est = 1; order them by how far their
    # score clears the threshold range (larger margin ⇒ fewer items can
    # still beat q for that user). Stays within (est-0.5, est], so it never
    # reorders users whose estimates differ by ≥ 1 rank.
    return r_lo, r_up, est - 0.5 * m_above / (1.0 + m_above)


def lookup_bounds(rt: RankTable, uq: jax.Array
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-query rank-table lookup: the B = 1 column of
    `lookup_bounds_batch`. Returns (r_lo, r_up, est), each (n,)."""
    r_lo, r_up, est = lookup_bounds_batch(rt, uq[:, None])
    return r_lo[:, 0], r_up[:, 0], est[:, 0]


def tile_bounds(rt_tile: RankTable, users_tile, qs: jax.Array,
                corr_tile: Optional[DeltaCorrection] = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """§4.3 step 1 (scores → dequant-aware lookup → optional delta
    correction) for ONE fixed-size user tile — the dense unit of work of
    the compile-once elastic scan (`repro.core.elastic`).

    Exactly `user_scores_batch` ∘ `lookup_bounds_batch`
    [∘ `apply_delta_corrections`] on a (tile, ·) row slice. Every
    operation in that composition is ROW-LOCAL (the matmul row, the
    per-row bucketize, the per-row correction counts touch only their own
    user's data), which is the property that makes tiling bit-identical:
    computing rows 0..n in ⌈n/tile⌉ fixed slices produces the same f32
    words as one (n, ·) call. (The one n-sensitive branch in the stack,
    `_dequant_matmul`'s blocked remainder split, takes its direct branch
    for any tile < 2·`_DEQUANT_MM_BLOCK` — asserted in
    tests/test_elastic.py.)

    Returns (r↓, r↑, est), each USER-major (tile, B) — the orientation
    the scan accumulates in.
    """
    scores, slack = user_scores_batch(users_tile, qs)
    r_lo, r_up, est = lookup_bounds_batch(rt_tile, scores, slack)
    if corr_tile is not None:
        from repro.core import rank_table as rt_mod
        r_lo, r_up, est = rt_mod.apply_delta_corrections(
            scores, r_lo, r_up, est, corr_tile, slack=slack)
    return r_lo, r_up, est


@jax.jit
def bound_ranks_batch(rt: RankTable, users, qs: jax.Array
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Dense-backend step 1 for a (B, d) query block.

    One (n, d) × (d, B) MXU matmul + one streamed pass over the table.
    `users` is a raw (n, d) array or a `StoredUsers` (quantized specs).
    Returns (r_lo, r_up, est), each (B, n) — the `QueryBackend.bound_ranks`
    orientation (query-major, user axis last, ready for per-query top-k).
    """
    scores, slack = user_scores_batch(users, qs)            # (n, B)
    r_lo, r_up, est = lookup_bounds_batch(rt, scores, slack)
    return r_lo.T, r_up.T, est.T


def lemma1_key(r_lo: jax.Array, r_up: jax.Array, est: jax.Array, *,
               R_lo_k: jax.Array, R_up_k: jax.Array, c: float,
               m_items: jax.Array
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The §4.3 composite selection key (smaller = better), plus the
    guaranteed/accepted/pruned masks it is built from.

    THE single definition of the selection ordering: `lemma1_select`
    (dense/fused global selection) and the sharded per-shard candidate
    pick (`distributed.make_batch_query_fn`) both call it, so the local
    top-k and the global merge cannot drift apart.

    Class separation: `big = m_items + 2` strictly dominates any static
    est ∈ [1, m+1]. On the DELTA path the unclipped shifted estimate
    spans [1 − n_del, m_base + 1 + n_add] instead, so delta callers pass
    the WIDENED `DeltaCorrection.selection_m` (≥ that range's width) as
    `m_items` — with a bare m'+2 offset and ≥ 2 deletions, a U_temp user
    at the top of the est range could out-key a pruned user at the
    bottom, inverting the class order.
    """
    guaranteed = c * R_lo_k >= R_up_k
    accepted = r_up <= (c * R_lo_k)[..., None]              # Lemma 1 (1)
    pruned = r_lo > R_up_k[..., None]                       # Lemma 1 (2)
    prio = jnp.where(accepted, 0.0, jnp.where(pruned, 2.0, 1.0))
    big = (m_items + 2).astype(jnp.float32)
    key_val = jnp.where(guaranteed[..., None], est, prio * big + est)
    return key_val, guaranteed, accepted, pruned


def lemma1_select(r_lo: jax.Array, r_up: jax.Array, est: jax.Array, *,
                  R_lo_k: jax.Array, R_up_k: jax.Array, k: int, c: float,
                  m_items: jax.Array
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """§4.3 step 3 as one composite-key top-k, given the step-2 statistics.

    Shape-polymorphic over leading batch axes: the candidate axis is LAST
    (r_lo/r_up/est are (..., n); R_lo_k/R_up_k are (...,)). Shared by the
    in-memory backends (candidates = all n users) and the distributed
    tree-merge (candidates = the gathered (B, k·P) per-shard winners).

    Returns (selected indices into the candidate axis, guaranteed mask,
    accepted mask, pruned mask).
    """
    key_val, guaranteed, accepted, pruned = lemma1_key(
        r_lo, r_up, est, R_lo_k=R_lo_k, R_up_k=R_up_k, c=c,
        m_items=m_items)
    _, indices = jax.lax.top_k(-key_val, k)
    return indices.astype(jnp.int32), guaranteed, accepted, pruned


def select_topk(r_lo: jax.Array, r_up: jax.Array, est: jax.Array, *, k: int,
                c: float, m_items: jax.Array) -> QueryResult:
    """Steps 2-3 of §4.3 given per-user bounds — shared by the dense path
    (`query`/`query_batch`) and the Pallas fused path
    (`kernels.ops.query_fused*`).

    Shape-polymorphic: pass (n,) arrays for one query or (B, n) arrays for
    a batch; every QueryResult field gains the same leading axes. Call it
    inside a jit (or through `_select_topk_jit`): step 2 is O(n) only
    compiled (see `kth_smallest`).
    """
    R_lo_k = kth_smallest(r_lo, k)                          # step 2: O(n)
    R_up_k = kth_smallest(r_up, k)
    indices, guaranteed, accepted, pruned = lemma1_select(
        r_lo, r_up, est, R_lo_k=R_lo_k, R_up_k=R_up_k, k=k, c=c,
        m_items=m_items)
    return QueryResult(
        indices=indices,
        est_rank=jnp.take_along_axis(est, indices, axis=-1),
        r_lo=r_lo, r_up=r_up,
        R_lo_k=R_lo_k, R_up_k=R_up_k,
        guaranteed=guaranteed,
        n_accepted=jnp.sum(accepted, axis=-1).astype(jnp.int32),
        n_pruned=jnp.sum(pruned, axis=-1).astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("k",))
def query_batch(rt: RankTable, users, qs: jax.Array, k: int,
                c: float) -> QueryResult:
    """Batched c-approximate reverse k-ranks queries (Definition 3, §4.3).

    qs is (B, d); every QueryResult field gains a leading B axis. Step 1
    is ONE matmul + ONE pass over the rank table for the whole batch (not
    B re-reads — see the module docstring).
    """
    scores, slack = user_scores_batch(users, qs)            # step 1: O(nd·B)
    r_lo, r_up, est = lookup_bounds_batch(rt, scores, slack)
    return select_topk(r_lo.T, r_up.T, est.T, k=k, c=c, m_items=rt.m)


@jax.jit
def _delta_bounds_batch(rt: RankTable, users, qs: jax.Array,
                        corr: DeltaCorrection
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step 1 + delta correction for a (B, d) block → corrected
    (r↓, r↑, est), each (B, n)."""
    from repro.core import rank_table as rt_mod
    scores, slack = user_scores_batch(users, qs)            # (n, B)
    r_lo, r_up, est = lookup_bounds_batch(rt, scores, slack)
    r_lo, r_up, est = rt_mod.apply_delta_corrections(scores, r_lo, r_up,
                                                     est, corr, slack=slack)
    return r_lo.T, r_up.T, est.T


@functools.partial(jax.jit, static_argnames=("k",))
def _select_topk_jit(r_lo, r_up, est, m_items, k: int, c: float
                     ) -> QueryResult:
    """`select_topk` as one compiled program, for callers whose bounds come
    from outside a jit (the fused kernel path, the backends' generic
    select and delta paths). Compiled, XLA drops the unused half of
    `kth_smallest`'s partition; called eagerly, that half sorts every
    (B, n) bound array in full."""
    return select_topk(r_lo, r_up, est, k=k, c=c, m_items=m_items)


def query_batch_delta(rt: RankTable, users: jax.Array, qs: jax.Array,
                      corr: DeltaCorrection, k: int, c: float) -> QueryResult:
    """`query_batch` over a mutated index: the same one-pass batched step 1
    plus the delta-buffer correction (`rank_table.apply_delta_corrections`)
    between the table lookup and the selection. The correction reuses the
    step-1 score matrix, so the only extra work is the O(n·B·log|delta|)
    counting pass; selection uses the delta-widened class offset
    `corr.selection_m()` (see `lemma1_key`).

    TWO jit regions, deliberately (unlike the static one-region
    `query_batch`): selection fans the corrected bounds out to ~6
    consumers (two order statistics, the composite key, the accept/prune
    sums), and XLA CPU re-fuses the whole O(n·(τ + |delta|)) bound/count
    producer chain into each of them — measured 1.8× end-to-end
    (optimization_barrier does not stop it). The region break materializes
    the corrected (B, n) bounds ONCE; the second dispatch costs µs and
    holds the delta path at ≤ 1.3× the static query (perf_engine
    --updates acceptance)."""
    r_lo, r_up, est = _delta_bounds_batch(rt, users, qs, corr)
    return _select_topk_jit(r_lo, r_up, est, corr.selection_m(), k, c)


@functools.partial(jax.jit, static_argnames=("k",))
def query(rt: RankTable, users: jax.Array, q: jax.Array, k: int,
          c: float) -> QueryResult:
    """One c-approximate reverse k-ranks query: the B = 1 case of
    `query_batch` (same code path, leading axis squeezed)."""
    res = query_batch(rt, users, q[None, :], k, c)
    return jax.tree_util.tree_map(lambda x: x[0], res)
