"""Block-summary pruning — the two-phase coarse-to-fine §4.3 scan (PR 4).

Every full-scan backend streams the whole (n, d) user matrix and (n, τ)
rank table per batch even though Lemma 1 proves most users are prunable:
any user with r↓ > R↑_k can never enter the answer set. This module lifts
the Lemma-1 prune test from per-user to per-BLOCK granularity so whole
user tiles are skipped before their bytes are ever read:

  build time   `build_block_summary` folds each block of `block_size`
               consecutive users into a tiny sketch — per-dimension
               coordinate extremes (a box around the block's user
               vectors) and column-wise envelopes of the block's
               threshold/table rows;
  phase A      `phase_a` scores every block against the whole (B, d)
               query batch in one (n/block, d)-shaped pass: the box gives
               a certified score range [s↓, s↑] per (block, query), the
               envelopes turn s↑ into a LOWER bound on every member's r↓
               and s↓ into an UPPER bound on every member's r↑. Sorting
               blocks by that r↑ bound and accumulating live row counts
               to k seeds a certified upper bound R̂ ≥ R↑_k, and a block
               is kept iff its r↓ bound ≤ R̂ — every user Lemma 1 could
               possibly retain lives in a kept block;
  phase B      the existing step-1 math runs only over kept blocks
               (gathered rows on the dense path, a scalar-prefetch
               masked-grid Pallas kernel on the fused path); skipped
               users are materialized at the dominated sentinel
               m_sel + 2, which `query.lemma1_key` orders past every
               admissible key, so `select_topk` returns bit-identical
               selected indices to the full scan.

Why the selection stays exact (the invariants the tests pin):

  * ≥ k users satisfy r↑ ≤ R↑_k ≤ R̂, and each of them (indeed any user
    with r↓ ≤ R̂) forces its block to be kept — so the k smallest r↓ and
    r↑ all come from kept rows and `kth_smallest` over the materialized
    arrays reproduces the exact R↓_k / R↑_k;
  * a skipped user has r↓ > R̂ ≥ R↑_k: in the non-guaranteed regime it is
    Lemma-1 pruned (and can never simultaneously pass the accept test,
    which would need c·R↓_k ≥ r↑ ≥ r↓ > R↑_k > c·R↓_k); in the
    guaranteed regime its est ≥ r↓ > R̂ ≥ R↑_k ≥ the k-th smallest est.
    Either way its key strictly exceeds every possible winner's, so the
    sentinel never perturbs the top-k. (The n_accepted/n_pruned
    DIAGNOSTIC counters can differ from the full scan's — a skipped
    user's true bounds are unknown — but indices, est_rank and the
    R↓_k/R↑_k statistics are exact.)

Floating point: the per-user score is an MXU dot product, the block
bound a different summation order, so phase A widens the score range by
a relative slack covering worst-case f32 accumulation error before the
comparison — a borderline user can only be kept, never lost. The
envelope bucketize reuses `query._bucketize`, so the storage-dtype cast
(bf16 tables) is applied on both sides of the comparison and stays
monotone.

Geometry sketches (PR 6): every block additionally stores a NORM BAND
[n↓, n↑] ⊇ {‖u‖₂ : u ∈ block} and an ANGULAR CONE (μ̂, cos r) with
û·μ̂ ≥ cos r for every member direction û = u/‖u‖. In exact arithmetic
s = u·q = ‖u‖·‖q‖·cos∠(u, q), and the spherical triangle inequality
gives ∠(u, q) ∈ [max(0, θ − r), min(π, θ + r)] with θ = ∠(q, μ̂), so the
block score range is also contained in

    ‖q‖ · [ n(c↓)·c↓ , n(c↑)·c↑ ],   c↑ = cos(max(0, θ − r)),
                                     c↓ = cos(min(π, θ + r)),

where n(c) = n↑ if c ≥ 0 else n↓ (the norm extremizing a signed
cosine). Phase A INTERSECTS this range with the coordinate-box range:
the true score lies in both, so the intersection is certified and never
looser than either sketch alone — boxes win on axis-aligned mass,
cones on tight direction bundles with spread coordinates. cos(θ ∓ r)
is evaluated trig-free through the cosine addition formulas, with the
clamped boundary cases selected by the equivalent tests cosθ ≥ cos r
(θ ≤ r) and cosθ ≤ −cos r (θ + r ≥ π). Certification under f32:

  * every unit-vector dot (cos r at build, cosθ at query) is widened by
    a rounding slack covering the d-term accumulation AND the operand
    normalizations (build-side cos r rounds DOWN — the cone only
    widens; query-side cosθ widens in the direction that extremizes
    each bound);
  * n↓/n↑ and ‖q‖ carry relative slacks for the sum-of-squares + sqrt;
  * the final products add the same member-dot slack the box path uses,
    with Σ|u_j·q_j| ≤ ‖u‖·‖q‖ ≤ n↑·‖q‖ (Cauchy-Schwarz), so a member's
    COMPUTED phase-B score — not just its exact value — stays inside;
  * degenerate blocks are safe by construction: a (near-)zero mean
    direction is stored as μ̂ = 0, which forces cosθ = 0 and cos r < 0
    and relaxes the cone to the vacuous ±n↑·‖q‖; a zero-norm member
    forces n↓ = 0, so the band always brackets its score 0; a zero
    query zeroes both cone bounds around the true score 0.

The PR 5 storage widenings compose unchanged: `user_slack` (quantized
user rows) widens the INTERSECTED range — the member's certified score
interval is ± row_slack·‖q‖₁ around the dequantized score that BOTH
sketches bound — and `score_eps`/widened thr/tab envelopes act after
the score range is formed, exactly as for the box alone.

Build-time layout (`kmeans_layout`): both sketches only pay when
blocks are geometrically TIGHT, which the caller's row order does not
guarantee (i.i.d. or shuffled-mixture users defeat any per-tile
sketch). `Engine.build/rebuild` can k-means-cluster the f32 user
matrix and PHYSICALLY REORDER rows so consecutive `block_size` tiles
hold like users, publishing the old→new permutation through
`IndexSnapshot.user_remap` (composed over the lineage, exactly like
compaction). The reorder changes WHERE a user row lives, never what a
query returns for it: selected indices stay bit-identical to the
unpruned inner backend on the same (reordered) snapshot, and clients
translate to pre-remap ids via the composed remap.

Delta path (`repro.index`): the correction shifts every rank by
[-n_del, +n_add], so phase A widens the block bounds by the padded
correction widths and subtracts per-block dead-user counts from the live
row counts; `PrunedBackend` falls back to the full scan past a
delta-ratio guard where the widened envelopes stop paying.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Direct-from-module imports (not `from repro.core import query`): the
# package __init__ rebinds the `query` attribute to the query FUNCTION.
from repro.core import rank_table as rt_mod
from repro.core.query import _bucketize, lemma1_select, \
    lookup_bounds_batch, user_scores_batch
from repro.core.types import DeltaCorrection, EPS_BF16, QueryResult, \
    RankTable, StoredUsers, _I8_TRANSFORM_PAD, kth_smallest, matmul, \
    take_user_rows

# Summary block size. MUST match the fused kernel's user-tile block_n so a
# kept block is exactly one kernel grid step (and the per-tile matmul is
# bit-identical to the full scan's — same tile composition, same
# accumulation order).
DEFAULT_BLOCK = 256

# Relative widening of the certified score range per unit of dimension:
# f32 dot-product rounding is bounded by ~d·2^-24 of the absolute-value
# bound Σ|u_j·q_j|; 4e-7·d covers it with a 6x margin, the absolute term
# guards all-zero rows.
_SCORE_SLACK = 4e-7
_SCORE_SLACK_ABS = 1e-6

# Absolute floor of the unit-vector dot slack (cone sketches): cos r and
# cos θ are dots of normalized operands, so magnitudes are ≤ 1 and the
# d-term accumulation bound _SCORE_SLACK·d plus this floor covers the
# dot, both normalizations and the sin = sqrt(1 − c²) evaluation.
_COS_SLACK_ABS = 1e-6


def _cos_slack(d: int) -> float:
    """f32 rounding slack for a dot product of two unit vectors of
    dimension d (see _COS_SLACK_ABS)."""
    return _SCORE_SLACK * d + _COS_SLACK_ABS


class BlockSummary(NamedTuple):
    """Per-block sketch of the user matrix + rank table (a pytree).

    dim_min/dim_max: (nb, d) float32 — coordinate extremes of the block's
                     user vectors: for any q, every member's score lies in
                     [dim_min·q⁺ + dim_max·q⁻, dim_max·q⁺ + dim_min·q⁻].
    thr_min/thr_max: (nb, τ) storage dtype — column-wise envelope of the
                     block's threshold rows (ascending along τ).
    tab_min/tab_max: (nb, τ) storage dtype — column-wise envelope of the
                     block's table rows (non-increasing along τ).
    rows:            (nb,) int32 — real rows in the block (the tail block
                     of a non-multiple n is partial).
    m:               () int32 — |P|, for the out-of-range bound m + 1.
    """

    dim_min: jax.Array
    dim_max: jax.Array
    thr_min: jax.Array
    thr_max: jax.Array
    tab_min: jax.Array
    tab_max: jax.Array
    rows: jax.Array
    m: jax.Array
    # Storage-spec extensions (PR 5), None on an exact f32 index:
    #   user_slack: (nb, 1) f32 — max per-row certified score-error
    #     coefficient in the block (quantized user rows); phase A widens
    #     the box score range by user_slack · ‖q‖₁.
    #   score_eps: () f32 — marks CERTIFIED-WIDENED f32 envelopes (the
    #     quantized-table summary form): thr/tab envelopes are built over
    #     dequantized ± quantization-error rows, and phase A additionally
    #     widens the score side by score_eps · max|s| (the bf16
    #     monotone-cast rounding; 0 for int8).
    user_slack: Optional[jax.Array] = None
    score_eps: Optional[jax.Array] = None
    # Geometry sketches (PR 6), None when built with with_cones=False:
    #   norm_min/norm_max: (nb, 1) f32 — certified band around every
    #     member's ‖u‖₂ (f32-rounding widened at build).
    #   mu: (nb, d) f32 — unit mean member direction (exact 0 rows when
    #     the directions cancel — the cone then reads as vacuous).
    #   cos_r: (nb, 1) f32 — certified LOWER bound on û·μ̂ over member
    #     directions û, i.e. cos of the cone's max angular radius,
    #     rounding-widened DOWN at build.
    norm_min: Optional[jax.Array] = None
    norm_max: Optional[jax.Array] = None
    mu: Optional[jax.Array] = None
    cos_r: Optional[jax.Array] = None

    @property
    def n_blocks(self) -> int:
        return self.dim_min.shape[0]

    @property
    def tau(self) -> int:
        return self.thr_min.shape[1]


@dataclasses.dataclass
class PruneStats:
    """Skip-rate accounting for one pruned `query_batch` call."""

    n_blocks: int = 0           # summary blocks in the index
    kept_union: int = 0         # blocks phase B executed (union over B)
    kept_per_query: float = 0.0  # mean per-query kept fraction
    # "" (pruned), "dense" (union too big), "delta-guard" (|delta|/m over
    # the guard), "align" (sharded tiles straddle shard boundaries)
    fallback: str = ""

    @property
    def union_fraction(self) -> float:
        return self.kept_union / max(self.n_blocks, 1)

    @property
    def skip_rate(self) -> float:
        return 1.0 - self.union_fraction

    def publish(self, registry=None) -> None:
        """Mirror this batch's accounting into the metrics registry: the
        `prune_skip_rate` gauge (last batch), per-reason fallback
        counters, and the block counters whose deltas give any window's
        skip rate, 1 − Δprune_blocks_executed_total / Δprune_blocks_total."""
        from repro.obs import registry as obs
        reg = registry if registry is not None else obs.get_default()
        reg.gauge("prune_skip_rate",
                  "1 - kept-union fraction of the last pruned batch"
                  ).set(self.skip_rate)
        reg.counter("prune_batches_total",
                    "pruned query_batch calls",
                    labels={"fallback": self.fallback or "none"}).inc()
        reg.counter("prune_blocks_total",
                    "summary blocks over pruned query_batch calls"
                    ).inc(self.n_blocks)
        # a fallback scanned every block, whatever phase A kept
        reg.counter("prune_blocks_executed_total",
                    "blocks scanned: the kept union, or all on fallback"
                    ).inc(self.n_blocks if self.fallback else self.kept_union)


def _pad_rows(x: jax.Array, total: int, value) -> jax.Array:
    pad = total - x.shape[0]
    if pad == 0:
        return x
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_size", "with_cones"))
def build_block_summary(users, rt: RankTable,
                        block_size: int = DEFAULT_BLOCK,
                        with_cones: bool = True) -> BlockSummary:
    """Fold (users, rank table) into per-block sketches — one O(n·(d+τ))
    pass at build/rebuild time, O(n/block · (d+τ)) resident thereafter.

    On an exact f32 index the envelopes are computed over the STORED
    threshold/table values (exact under min/max), so phase A's
    comparisons see exactly what the per-user lookup sees — the pre-spec
    path, bit-identical. On a quantized index (bf16/int8 storage spec)
    the envelopes are CERTIFIED f32 intervals: each stored row is widened
    to the interval provably containing its true f32 values (± half a
    quantization step for int8 codes, ± EPS_BF16 relative for bf16 table
    entries) BEFORE the column min/max, so the phase-A bounds bracket
    every member's widened (r↓, r↑) from the dequant-aware lookup —
    Lemma-1 tile pruning stays exact at every spec.

    `with_cones` adds the PR 6 norm-band + angular-cone fields (built
    over the same dequantized f32 rows the box sees — the quantized-user
    `user_slack` widening then covers both sketches identically).
    """
    if isinstance(users, StoredUsers):
        u32 = users.rows.astype(jnp.float32)
        if users.scale is not None:
            u32 = u32 * users.scale
        slack_rows = users.row_slack
    else:
        u32 = users.astype(jnp.float32)
        slack_rows = None
    n, d = u32.shape
    nb = -(-n // block_size)
    total = nb * block_size
    inf = jnp.inf
    u_lo = _pad_rows(u32, total, inf).reshape(nb, block_size, d)
    u_hi = _pad_rows(u32, total, -inf).reshape(nb, block_size, d)
    tau = rt.thresholds.shape[1]
    kind = rt.spec_kind
    if kind == "f32":
        if slack_rows is not None:
            raise ValueError("quantized user storage requires a quantized "
                             "rank table (uniform StorageSpec)")
        thr_lo_rows = thr_hi_rows = rt.thresholds
        tab_lo_rows = tab_hi_rows = rt.table
        user_slack = score_eps = None
        st = rt.thresholds.dtype
    elif kind == "bf16":
        thr32 = rt.thresholds.astype(jnp.float32)
        tab32 = rt.table.astype(jnp.float32)
        thr_lo_rows = thr_hi_rows = thr32
        tab_lo_rows = tab32 * (1.0 - EPS_BF16)
        tab_hi_rows = tab32 * (1.0 + EPS_BF16)
        score_eps = jnp.asarray(EPS_BF16, jnp.float32)
        st = jnp.float32
    else:                                       # int8 per-row affine codes
        half = 0.5 + _I8_TRANSFORM_PAD
        thr32 = rt.thresholds.astype(jnp.float32) * rt.thr_scale + rt.thr_off
        tab32 = rt.table.astype(jnp.float32) * rt.tab_scale + rt.tab_off
        thr_lo_rows = thr32 - half * rt.thr_scale
        thr_hi_rows = thr32 + half * rt.thr_scale
        tab_lo_rows = tab32 - half * rt.tab_scale
        tab_hi_rows = tab32 + half * rt.tab_scale
        score_eps = jnp.asarray(0.0, jnp.float32)
        st = jnp.float32
    if kind != "f32":
        user_slack = (None if slack_rows is None else _pad_rows(
            slack_rows.astype(jnp.float32), total, 0.0
        ).reshape(nb, block_size).max(axis=1, keepdims=True))
    thr_lo = _pad_rows(thr_lo_rows, total,
                       jnp.asarray(inf, st)).reshape(nb, block_size, tau)
    thr_hi = _pad_rows(thr_hi_rows, total,
                       jnp.asarray(-inf, st)).reshape(nb, block_size, tau)
    tab_lo = _pad_rows(tab_lo_rows, total,
                       jnp.asarray(inf, st)).reshape(nb, block_size, tau)
    tab_hi = _pad_rows(tab_hi_rows, total,
                       jnp.asarray(-inf, st)).reshape(nb, block_size, tau)
    rows = jnp.minimum(
        jnp.full((nb,), block_size, jnp.int32),
        (n - jnp.arange(nb) * block_size).astype(jnp.int32))
    norm_min = norm_max = mu = cos_r = None
    if with_cones:
        cs = _cos_slack(d)
        norms = jnp.sqrt(jnp.sum(u32 * u32, axis=1))        # (n,)
        # band widened for the sum-of-squares + sqrt rounding; zero rows
        # keep n↓ = 0 exactly (their score 0 must stay bracketed)
        norm_min = _pad_rows(norms * (1.0 - cs), total, inf
                             ).reshape(nb, block_size).min(
                                 axis=1, keepdims=True)
        norm_max = _pad_rows(norms * (1.0 + cs), total, 0.0
                             ).reshape(nb, block_size).max(
                                 axis=1, keepdims=True)
        # unit directions; exact-zero rows map to the zero direction
        # (their dot with μ̂ is 0, which only widens the cone)
        uhat = u32 / jnp.maximum(norms, 1e-30)[:, None]
        uh = _pad_rows(uhat, total, 0.0).reshape(nb, block_size, d)
        mu_raw = uh.sum(axis=1)                             # (nb, d)
        mu_n = jnp.sqrt(jnp.sum(mu_raw * mu_raw, axis=1, keepdims=True))
        # a cancelled mean direction is stored as EXACTLY 0: the query
        # side then sees cosθ = 0 and cos_r < 0 — the vacuous cone —
        # instead of an ill-normalized reference axis
        mu = jnp.where(mu_n > 1e-20,
                       mu_raw / jnp.maximum(mu_n, 1e-30), 0.0)
        dots = (uh * mu[:, None, :]).sum(axis=2)            # (nb, bs)
        valid = jnp.arange(block_size)[None, :] < rows[:, None]
        dots = jnp.where(valid, dots, 2.0)
        cos_r = jnp.clip(dots.min(axis=1, keepdims=True) - cs,
                         -1.0, 1.0)
    return BlockSummary(
        dim_min=u_lo.min(axis=1), dim_max=u_hi.max(axis=1),
        thr_min=thr_lo.min(axis=1), thr_max=thr_hi.max(axis=1),
        tab_min=tab_lo.min(axis=1), tab_max=tab_hi.max(axis=1),
        rows=rows, m=rt.m, user_slack=user_slack, score_eps=score_eps,
        norm_min=norm_min, norm_max=norm_max, mu=mu, cos_r=cos_r)


@jax.jit
def _kmeans_step(u: jax.Array, centers: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    """One Lloyd iteration: assign rows to nearest center (expanded
    ‖u − c‖² = ‖u‖² − 2u·c + ‖c‖², one (n, d) × (d, K) matmul), then
    recenter; empty clusters keep their old center."""
    K = centers.shape[0]
    d2 = (jnp.sum(u * u, axis=1, keepdims=True)
          - 2.0 * (u @ centers.T)
          + jnp.sum(centers * centers, axis=1)[None, :])
    assign = jnp.argmin(d2, axis=1)
    sums = jax.ops.segment_sum(u, assign, num_segments=K)
    counts = jax.ops.segment_sum(jnp.ones((u.shape[0],), jnp.float32),
                                 assign, num_segments=K)
    new = jnp.where(counts[:, None] > 0.0,
                    sums / jnp.maximum(counts, 1.0)[:, None], centers)
    return assign, new


def kmeans_layout(users, *, block_size: int = DEFAULT_BLOCK,
                  n_clusters: Optional[int] = None, iters: int = 8,
                  seed: int = 0) -> Optional[np.ndarray]:
    """Build-time geometry-aware row layout (PR 6, module docstring).

    K-means-clusters the f32 user matrix (fixed PRNG seed — rebuilds are
    deterministic) and returns the permutation that groups each cluster
    into consecutive rows, ordered WITHIN each cluster by distance to its
    center: `perm[new] = old`. The secondary sort matters for mixed
    populations — rows only loosely attached to their cluster (a noise
    floor, stragglers between blobs) sink to the tail blocks of each
    segment instead of polluting every block's envelope, so the damage
    of unclusterable rows is confined to the few blocks that hold them.
    Ties (equal distance) break by original row id, keeping the layout
    deterministic. Returns None when the matrix spans fewer than two
    summary blocks (nothing to tighten).

    The caller applies `users[perm]` / `rank_table.take_rows(perm)` and
    publishes the inverse old→new map through the snapshot's
    `user_remap` channel; n is unchanged, so every backend shape
    contract (sharded divisibility included) survives the reorder.
    """
    u = jnp.asarray(users, jnp.float32)
    n = u.shape[0]
    if -(-n // block_size) < 2:
        return None
    K = int(n_clusters) if n_clusters else int(
        np.clip(n // (4 * block_size), 2, 128))
    K = min(K, n)
    key = jax.random.PRNGKey(seed)
    centers = u[jax.random.choice(key, n, shape=(K,), replace=False)]
    assign = jnp.zeros((n,), jnp.int32)
    for _ in range(max(int(iters), 1)):
        assign, centers = _kmeans_step(u, centers)
    d2 = jnp.sum((u - centers[assign]) ** 2, axis=1)
    # np.lexsort sorts by the LAST key first: assign, then distance,
    # then row id (lexsort's index tie-break is positional ⇒ stable)
    return np.lexsort((np.asarray(d2), np.asarray(assign))).astype(
        np.int64)


def _envelope_bounds(summary: BlockSummary, qs: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """Certified per-(block, query) bounds: (r_lo_opt, r_up_pes), each
    (nb, B), with r_lo_opt ≤ min r↓ and r_up_pes ≥ max r↑ over members.

    The score range is the box range intersected with the norm-band ×
    angular-cone range when the summary carries geometry sketches (PR 6;
    certification in the module docstring) — strictly no looser, often
    much tighter on direction-bundled blocks.

    Derivation mirrors `query.lookup_bounds_batch`: for a member with
    score s and bucketize index idx = #{t_j ≤ s}, the envelope score s↑
    and column-min thresholds give idx ≤ idx↑ := #{thr_min_j ≤ s↑}, and
    the table's non-increasing columns give r↓ = T[idx] ≥ tab_min[idx↑];
    symmetrically s↓ with thr_max bounds idx from below and tab_max
    bounds r↑ from above. Sharing `query._bucketize` keeps the
    storage-dtype cast identical (and monotone) on both sides.
    """
    d = qs.shape[1]
    qp = jnp.maximum(qs, 0.0).astype(jnp.float32)          # (B, d)
    qn = jnp.minimum(qs, 0.0).astype(jnp.float32)
    s_hi = (matmul(summary.dim_max, qp.T)
            + matmul(summary.dim_min, qn.T))               # (nb, B)
    s_lo = matmul(summary.dim_min, qp.T) + matmul(summary.dim_max, qn.T)
    absmax = jnp.maximum(jnp.abs(summary.dim_min), jnp.abs(summary.dim_max))
    slack = ((_SCORE_SLACK * d) * matmul(absmax, jnp.abs(qs).T)
             + _SCORE_SLACK_ABS)
    s_hi = s_hi + slack
    s_lo = s_lo - slack
    if summary.norm_min is not None:
        # cone ∩ box (module docstring): s = ‖u‖·‖q‖·cos∠(u, q) with
        # ∠(u, q) ∈ [max(0, θ − r), min(π, θ + r)] — evaluated trig-free
        # via the cosine addition formulas, every cosine/norm widened in
        # the direction that can only loosen the bound
        cs = _cos_slack(d)
        q32 = qs.astype(jnp.float32)
        q_norm = jnp.sqrt(jnp.sum(q32 * q32, axis=1))       # (B,)
        q_hat = q32 / jnp.maximum(q_norm, 1e-30)[:, None]
        cos_t = matmul(summary.mu, q_hat.T)                 # (nb, B)
        cos_r = summary.cos_r                               # (nb, 1)
        sin_r = jnp.sqrt(jnp.maximum(1.0 - cos_r * cos_r, 0.0))
        ct_hi = jnp.clip(cos_t + cs, -1.0, 1.0)     # θ rounded down
        ct_lo = jnp.clip(cos_t - cs, -1.0, 1.0)     # θ rounded up
        st_hi = jnp.sqrt(jnp.maximum(1.0 - ct_hi * ct_hi, 0.0))
        st_lo = jnp.sqrt(jnp.maximum(1.0 - ct_lo * ct_lo, 0.0))
        # θ ≤ r ⇒ the cone contains q̂'s direction: cos max is 1;
        # θ + r ≥ π ⇒ it contains −q̂: cos min is −1
        c_hi = jnp.where(ct_hi >= cos_r, 1.0,
                         ct_hi * cos_r + st_hi * sin_r) + cs
        c_lo = jnp.where(ct_lo <= -cos_r, -1.0,
                         ct_lo * cos_r - st_lo * sin_r) - cs
        n_lo, n_hi = summary.norm_min, summary.norm_max     # (nb, 1)
        q_lo = (q_norm * (1.0 - cs))[None, :]
        q_up = (q_norm * (1.0 + cs))[None, :]
        # member-dot rounding, Cauchy-Schwarz-bounded: Σ|u_j·q_j| ≤
        # ‖u‖·‖q‖ ≤ n↑·‖q‖ — the cone analogue of the box's absmax term
        pad = (_SCORE_SLACK * d) * (n_hi * q_up) + _SCORE_SLACK_ABS
        s_hi_cone = jnp.where(c_hi >= 0.0, n_hi * c_hi * q_up,
                              n_lo * c_hi * q_lo) + pad
        s_lo_cone = jnp.where(c_lo >= 0.0, n_lo * c_lo * q_lo,
                              n_hi * c_lo * q_up) - pad
        s_hi = jnp.minimum(s_hi, s_hi_cone)
        s_lo = jnp.maximum(s_lo, s_lo_cone)
    if summary.user_slack is not None:
        # quantized user rows: the members' certified score intervals are
        # ± row_slack·‖q‖₁ around the dequantized score the box bounds
        extra = summary.user_slack * jnp.sum(jnp.abs(qs), axis=1)[None, :]
        s_hi = s_hi + extra
        s_lo = s_lo - extra

    tau = summary.tau
    m_plus_1 = (summary.m + 1).astype(jnp.float32)
    if summary.score_eps is not None:
        # CERTIFIED-WIDENED envelopes (quantized table): thr/tab already
        # carry the per-row quantization widening; the score side adds
        # the bf16 monotone-cast rounding of the member comparison (the
        # member compares in bf16, which can move a score by eps·|s|)
        e = summary.score_eps * jnp.maximum(jnp.abs(s_lo), jnp.abs(s_hi)) \
            + _SCORE_SLACK_ABS
        idx_hi = _bucketize(summary.thr_min, s_hi + e)    # ≥ member idx_hi
        # above-all-thresholds branch: a member BELOW its top threshold
        # still looks up a widened table entry, and quantization widening
        # can push a rank-1 entry below 1.0 (bf16: 1·(1−eps)) — the
        # envelope must floor at the widened minimum (last column of the
        # non-increasing tab_min), not at the exact 1.0
        r_lo_opt = jnp.where(
            idx_hi == tau, jnp.minimum(1.0, summary.tab_min[:, -1:]),
            jnp.take_along_axis(summary.tab_min,
                                jnp.clip(idx_hi, 0, tau - 1), axis=1))
        idx_lo = _bucketize(summary.thr_max, s_lo - e)    # ≤ member idx_lo
        top = jnp.maximum(m_plus_1, summary.tab_max[:, :1])
        r_up_pes = jnp.where(
            idx_lo == 0, top,
            jnp.take_along_axis(summary.tab_max,
                                jnp.clip(idx_lo - 1, 0, tau - 1), axis=1))
        # the widened thr/tab values are RECOMPUTED on the member path
        # (dequant + half-step pad inside the lookup) and XLA is free to
        # re-associate/fuse that arithmetic differently there, so the two
        # sides agree only to a few f32 ulp — pad one ppm relative
        # (≲ 1e-2 rank units at any practical m) to keep the envelopes a
        # certified superset of what the member lookup actually returns.
        # The f32 branch below needs none of this: both sides read the
        # same stored values and only min/max/compare them.
        return r_lo_opt * (1.0 - 1e-6), r_up_pes * (1.0 + 1e-6)
    idx_hi = _bucketize(summary.thr_min, s_hi)    # ≥ member idx
    tab_min = summary.tab_min.astype(jnp.float32)
    r_lo_opt = jnp.where(
        idx_hi == tau, 1.0,
        jnp.take_along_axis(tab_min, jnp.clip(idx_hi, 0, tau - 1), axis=1))
    idx_lo = _bucketize(summary.thr_max, s_lo)    # ≤ member idx
    tab_max = summary.tab_max.astype(jnp.float32)
    # max(m+1, column-0 envelope): a bf16 table entry can round a hair
    # above m+1, and the idx==0 branch must still dominate it
    top = jnp.maximum(m_plus_1, tab_max[:, :1])
    r_up_pes = jnp.where(
        idx_lo == 0, top,
        jnp.take_along_axis(tab_max, jnp.clip(idx_lo - 1, 0, tau - 1),
                            axis=1))
    return r_lo_opt, r_up_pes


@functools.partial(jax.jit,
                   static_argnames=("k", "block_size", "with_live"))
def phase_a(summary: BlockSummary, qs: jax.Array, *, k: int,
            block_size: int, n_add=0.0, n_del=0.0,
            user_live: Optional[jax.Array] = None, with_live: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """Coarse pass: certify, per query, which blocks can hold answers.

    Returns (keep, R̂): keep is (B, nb) bool — True where the block might
    contain a non-Lemma-1-pruned user for that query; R̂ is the (B,)
    certified upper bound on R↑_k that seeds the test. n_add/n_del widen
    the envelopes for a delta correction (padded widths — conservative);
    `user_live` (with_live=True) subtracts per-block dead rows from the
    live counts so R̂ never leans on deleted users.
    """
    r_lo_opt, r_up_pes = _envelope_bounds(summary, qs)      # (nb, B)
    r_lo_eff = r_lo_opt - jnp.asarray(n_del, jnp.float32)
    r_up_eff = r_up_pes + jnp.asarray(n_add, jnp.float32)
    live = summary.rows
    if with_live:
        nb = summary.n_blocks
        dead = _pad_rows(~user_live, nb * block_size, False)
        live = live - dead.reshape(nb, block_size).sum(
            axis=1).astype(jnp.int32)
    # R̂ seed: sort blocks by pessimistic r↑, accumulate live rows to k —
    # the k-th smallest r↑ over all users is ≤ the bound of the block
    # where the cumulative count crosses k.
    order = jnp.argsort(r_up_eff, axis=0)                   # (nb, B)
    vals = jnp.take_along_axis(r_up_eff, order, axis=0)
    cum = jnp.cumsum(live[order], axis=0)                   # (nb, B)
    enough = cum >= k
    pos = jnp.argmax(enough, axis=0)                        # first crossing
    B = qs.shape[0]
    r_hat = jnp.where(enough[-1], vals[pos, jnp.arange(B)], jnp.inf)
    keep = (r_lo_eff <= r_hat[None, :]) & (live > 0)[:, None]
    return keep.T, r_hat


# --------------------------------------------------------------- phase B
def bucket_width(count: int, *, n_blocks: int, min_blocks: int = 1) -> int:
    """Round a kept-block count up to a bucketed execution width so
    streaming keep-mask churn reuses compiled phase-B programs (the
    delta buffer's `_bucket` trick). Granularity is n_blocks/16 (floor 8)
    rather than powers of two: a pow-2 bucket can nearly DOUBLE the
    executed tile count (283 kept → 512 executed at nb = 1024), wiping
    out most of the skip win, while 1/16-granularity caps the padding
    overhead at ~6% of the index for ≤ ~16 compiled variants."""
    g = max(8, n_blocks // 16)
    target = max(count, int(min_blocks), 1)
    return min(max(-(-target // g) * g, target), max(n_blocks, target))


def bucket_blocks(kept: np.ndarray, *, n_blocks: int, min_blocks: int = 1
                  ) -> np.ndarray:
    """Pad the kept-block id list to the bucketed width. Padding repeats
    kept ids — duplicates recompute identical values, and the per-query
    keep mask (not the id list) decides what survives materialization."""
    kept = np.asarray(kept, np.int32)
    if kept.size == 0:
        kept = np.zeros(1, np.int32)            # degenerate: nothing live
    width = bucket_width(kept.size, n_blocks=n_blocks,
                         min_blocks=min_blocks)
    reps = -(-width // kept.size)
    return np.tile(kept, reps)[:width]


def row_indices(block_ids: jax.Array, block_size: int) -> jax.Array:
    """(nk,) block ids → (nk·block_size,) row ids (may exceed n on the
    tail block; gathers clip, scatters drop)."""
    return (block_ids[:, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)[None, :]).reshape(-1)


def materialize(vals: jax.Array, block_ids: jax.Array, keep_q: jax.Array,
                n: int, sentinel, block_size: int) -> jax.Array:
    """Expand compacted (B, nk·bs) phase-B values into dense (B, n)
    arrays, then re-mask with the PER-QUERY keep mask.

    Implemented as a GATHER through the inverse block map (XLA CPU
    lowers scatters to serial element loops — gathering the (B, n)
    output from a sentinel-extended source is several times faster and
    handles duplicate padding ids for free). Global columns of unkept
    blocks read the appended sentinel column.

    The per-query mask (not the executed union) decides sentinel vs
    computed: a user computed only because another query in the batch
    kept its block still reads as sentinel for queries that pruned it —
    which makes every query's materialized arrays independent of its
    batch-mates, so B = 1 and B = 16 execution are bit-identical.
    """
    B = vals.shape[0]
    nk = block_ids.shape[0]
    nb = keep_q.shape[1]
    inv = jnp.full((nb,), nk * block_size, jnp.int32)
    inv = inv.at[block_ids].set(
        jnp.arange(nk, dtype=jnp.int32) * block_size, mode="drop")
    cols = jnp.arange(n, dtype=jnp.int32)
    blk_of = cols // block_size
    src = jnp.minimum(inv[blk_of] + cols % block_size, nk * block_size)
    padded = jnp.concatenate(
        [vals, jnp.full((B, 1), sentinel, jnp.float32)], axis=1)
    out = jnp.take(padded, src, axis=1)
    keep_rows = jnp.take(keep_q, blk_of, axis=1)            # (B, n)
    return jnp.where(keep_rows, out, sentinel)


def _finish_impl(r_lo_c: jax.Array, r_up_c: jax.Array, est_c: jax.Array,
                 block_ids: jax.Array, blk_valid: jax.Array,
                 keep_q: jax.Array, m_items, k: int, c: float, n: int,
                 block_size: int) -> QueryResult:
    """§4.3 steps 2-3 on the COMPACTED (B, nk·bs) phase-B arrays.

    Selecting on the compacted arrays instead of a scattered (B, n) copy
    cuts the selection from O(B·n) to O(B·n_kept) — at a 72% skip rate
    that is most of the remaining non-step-1 time. Exactness carries over
    from the materialized argument (module docstring): every user that
    can influence R↓_k/R↑_k or the top-k is kept FOR ITS QUERY, rows not
    kept-for-this-query (including duplicate padding tiles and tail
    padding past n, masked via `blk_valid`/row bounds) read the dominated
    sentinel, and the compacted row order restricted to valid tiles is
    ascending in global index, so `top_k` tie-breaking matches the full
    scan's. Only the two (B, n) bound fields of the result contract are
    materialized (through the gather in `materialize`); the diagnostic
    accept/prune counts are recomputed from them with the same formulas
    `select_topk` uses, so they equal the scattered path's bit-for-bit.
    """
    ridx = row_indices(block_ids, block_size)               # (nk·bs,)
    sentinel = (jnp.asarray(m_items) + 2).astype(jnp.float32)
    live_blk = keep_q[:, block_ids] & blk_valid[None, :]    # (B, nk)
    live = (jnp.repeat(live_blk, block_size, axis=1)
            & (ridx < n)[None, :])                          # (B, nk·bs)
    r_lo_s = jnp.where(live, r_lo_c, sentinel)
    r_up_s = jnp.where(live, r_up_c, sentinel)
    est_s = jnp.where(live, est_c, sentinel)
    R_lo_k = kth_smallest(r_lo_s, k)                        # exact globals
    R_up_k = kth_smallest(r_up_s, k)
    sel, guaranteed, _, _ = lemma1_select(
        r_lo_s, r_up_s, est_s, R_lo_k=R_lo_k, R_up_k=R_up_k, k=k, c=c,
        m_items=jnp.asarray(m_items))
    indices = jnp.take(ridx, sel).astype(jnp.int32)         # global rows
    est_rank = jnp.take_along_axis(est_s, sel, axis=-1)
    r_lo_m = materialize(r_lo_c, block_ids, keep_q, n, sentinel,
                         block_size)
    r_up_m = materialize(r_up_c, block_ids, keep_q, n, sentinel,
                         block_size)
    accepted = r_up_m <= (c * R_lo_k)[..., None]
    pruned = r_lo_m > R_up_k[..., None]
    return QueryResult(
        indices=indices, est_rank=est_rank, r_lo=r_lo_m, r_up=r_up_m,
        R_lo_k=R_lo_k, R_up_k=R_up_k, guaranteed=guaranteed,
        n_accepted=jnp.sum(accepted, axis=-1).astype(jnp.int32),
        n_pruned=jnp.sum(pruned, axis=-1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("k", "n", "block_size"))
def finish_compacted(r_lo_c: jax.Array, r_up_c: jax.Array,
                     est_c: jax.Array, block_ids: jax.Array,
                     blk_valid: jax.Array, keep_q: jax.Array, m_items,
                     k: int, c: float, n: int, block_size: int
                     ) -> QueryResult:
    """Jitted phase-B tail for backends that produce compacted (B, nk·bs)
    bounds OUTSIDE a jit (the fused Pallas kernel, generic inner
    backends)."""
    return _finish_impl(r_lo_c, r_up_c, est_c, block_ids, blk_valid,
                        keep_q, m_items, k, c, n, block_size)


def _gathered_bounds(rt: RankTable, users, qs: jax.Array,
                     block_ids: jax.Array, block_size: int,
                     corr: Optional[DeltaCorrection] = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compacted step 1 (+ optional delta correction): gather kept rows,
    one (n_kept, d) × (d, B) matmul, one streamed pass over the kept
    threshold/table rows — the correction's count pass also only touches
    kept rows. Row gathers go through the storage-aware `take_rows`
    helpers, so int8 scale vectors (and quantized-user slack rows) travel
    with their rows. Returns (B, nk·bs) arrays."""
    n = users.shape[0]
    ridx = row_indices(block_ids, block_size)
    g = jnp.minimum(ridx, n - 1)
    scores, slack = user_scores_batch(take_user_rows(users, g),
                                      qs)                   # (nk·bs, B)
    r_lo, r_up, est = lookup_bounds_batch(rt.take_rows(g), scores, slack)
    if corr is not None:
        r_lo, r_up, est = rt_mod.apply_delta_corrections(
            scores, r_lo, r_up, est, corr.take_rows(g), slack=slack)
    return r_lo.T, r_up.T, est.T


@functools.partial(jax.jit, static_argnames=("k", "block_size"))
def pruned_query_batch(rt: RankTable, users: jax.Array, qs: jax.Array,
                       block_ids: jax.Array, blk_valid: jax.Array,
                       keep_q: jax.Array, k: int, c: float,
                       block_size: int = DEFAULT_BLOCK) -> QueryResult:
    """Dense phase B: ONE jit region — compacted step 1 + compacted
    selection (gather/matmul/lookup/select all fuse)."""
    r_lo, r_up, est = _gathered_bounds(rt, users, qs, block_ids,
                                       block_size)
    return _finish_impl(r_lo, r_up, est, block_ids, blk_valid, keep_q,
                        rt.m, k, c, users.shape[0], block_size)


@functools.partial(jax.jit, static_argnames=("block_size",))
def _pruned_delta_bounds(rt: RankTable, users: jax.Array, qs: jax.Array,
                         corr: DeltaCorrection, block_ids: jax.Array,
                         block_size: int
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    return _gathered_bounds(rt, users, qs, block_ids, block_size,
                            corr=corr)


def pruned_query_batch_delta(rt: RankTable, users: jax.Array,
                             qs: jax.Array, corr: DeltaCorrection,
                             block_ids: jax.Array, blk_valid: jax.Array,
                             keep_q: jax.Array, k: int, c: float,
                             block_size: int = DEFAULT_BLOCK
                             ) -> QueryResult:
    """Dense phase B over a mutated index. TWO jit regions for the same
    reason as `query.query_batch_delta` (XLA CPU re-fuses the corrected
    bound chain into every selection consumer otherwise)."""
    r_lo, r_up, est = _pruned_delta_bounds(rt, users, qs, corr, block_ids,
                                           block_size)
    return finish_compacted(r_lo, r_up, est, block_ids, blk_valid, keep_q,
                            corr.selection_m(), k, c, users.shape[0],
                            block_size)


@functools.partial(jax.jit, static_argnames=("k", "n", "block_size"))
def delta_finish_compacted(users, qs: jax.Array,
                           corr: DeltaCorrection, r_lo_c: jax.Array,
                           r_up_c: jax.Array, est_c: jax.Array,
                           block_ids: jax.Array, blk_valid: jax.Array,
                           keep_q: jax.Array, k: int, c: float, n: int,
                           block_size: int) -> QueryResult:
    """Delta tail for compacted-bounds backends (the fused kernel path
    and generic inner backends): the shared correction needs the u·q
    scores of the kept rows — one gathered matmul, the same extra cost
    `QueryBackend._delta_query` pays — then correction + compacted
    selection."""
    ridx = row_indices(block_ids, block_size)
    g = jnp.minimum(ridx, n - 1)
    scores, slack = user_scores_batch(take_user_rows(users, g),
                                      qs)                   # (rows, B)
    r_lo, r_up, est = rt_mod.apply_delta_corrections(
        scores, r_lo_c.T, r_up_c.T, est_c.T, corr.take_rows(g),
        slack=slack)
    return _finish_impl(r_lo.T, r_up.T, est.T, block_ids, blk_valid,
                        keep_q, corr.selection_m(), k, c, n, block_size)
