"""Core data types for the c-approximate reverse k-ranks engine.

All types are JAX pytrees (NamedTuples of arrays) or static dataclass
configs, so they flow through jit / shard_map / checkpointing unchanged.

Precision-polymorphic storage tier (PR 5)
-----------------------------------------
`StorageSpec` governs how the user matrix, thresholds and rank table are
MATERIALIZED — f32 (exact), bf16, or int8 with per-user scales — and the
whole stack consumes it uniformly (`RankTable` carries optional per-row
affine parameters, `StoredUsers` the quantized user rows).

THE BOUND-WIDENING PROOF OBLIGATION. Every quantized read path must
certify, per user u and query q, an interval that CONTAINS the interval
the exact f32 storage would have produced:

    r↓_spec(u, q) ≤ r↓_f32(u, q)   and   r↑_spec(u, q) ≥ r↑_f32(u, q).

Concretely each error source is bracketed and folded in the certified
direction (r↓ rounds DOWN, r↑ rounds UP):

  * quantized user rows — the score error is bounded per row,
    |s_spec − s_f32| ≤ row_slack · ‖q‖₁ (`StoredUsers.row_slack`), and
    the bucketize compares against s ± slack two-sidedly;
  * quantized thresholds — a stored value brackets its f32 original
    (± half a step for int8 codes; bf16 via the monotone cast), so a
    two-sided bucketize yields idx_lo ≤ idx* ≤ idx_hi and the
    non-increasing table turns idx_hi into a sound r↓, idx_lo into a
    sound r↑;
  * quantized table entries — reads widen by the storage error
    (± (½+pad)·scale for int8, ×(1±EPS_BF16) for bf16);
  * quantized delta-score rows — exact counts become certified count
    RANGES (`rank_table._count_above_range`): r↓ shifts by the smallest
    possible net count, r↑ by the largest.

Given containment, §4.3 remains sound at every spec: R↑_k over widened
r↑ upper-bounds the f32 R↑_k, Lemma-1 pruning (r↓ > R↑_k) never discards
a user the exact engine could return, and the block envelopes of
`core.pruning` apply the same widening per tile — so the c-approximation
contract degrades only by the (bounded, measured) widening, never
unsoundly. The f32 spec bypasses every widening branch and traces the
identical XLA program as the pre-spec code: bit-identical results,
asserted against committed goldens in tests/test_storage.py.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def dot_precision():
    """Precision of every engine matmul: full f32 (HIGHEST) off the CPU.

    The certified bounds budget f32 rounding of each score (about d·2⁻²⁴
    of it); a TPU's default precision rounds f32 operands to bf16, which
    they do not cover. On the CPU the default precision is f32 already,
    and keeping it keeps the CPU programs bit for bit. Reads JAX's
    default backend when the caller is traced."""
    if jax.default_backend() == "cpu":
        return None
    return jax.lax.Precision.HIGHEST


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """`a @ b` at `dot_precision()`."""
    return jnp.matmul(a, b, precision=dot_precision())


# ---------------------------------------------------------------- storage
# bf16 keeps 8 mantissa bits; a round-to-nearest cast is within half an
# ulp, i.e. ~2^-9 relative. 2^-7 over-covers it (including the /(1-eps)
# reciprocal terms), trading a hair of bound tightness for an airtight
# widening at every magnitude.
EPS_BF16 = 2.0 ** -7


def round_bf16(x: jax.Array) -> jax.Array:
    """The bf16 value nearest to each f32 of `x` (ties to even), kept in
    f32. The bf16 certification needs the score interval's ends rounded
    exactly so, but XLA on a TPU may drop an f32 → bf16 → f32 round trip
    as excess precision; integer ops it cannot drop, and Mosaic lowers
    them in a kernel too."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = b + (0x7FFF + ((b >> 16) & 1))
    return jax.lax.bitcast_convert_type(b & -0x10000, jnp.float32)

# int8 quantized codes live in [-127, 127]; -128 is reserved as the
# "absent" sentinel (delta-score padding) so a clipped integer compare
# against -128 can never count a real entry.
_I8_MAX = 127.0

# Extra widening of int8 block envelopes / comparisons, in quantization
# steps: covers the f32 rounding of the (x - off) / scale transform
# (|s'| <= ~128, ulp ~1e-5) with a wide margin.
_I8_TRANSFORM_PAD = 1e-4


@dataclasses.dataclass(frozen=True)
class StorageSpec:
    """How the storage tier materializes the user matrix, thresholds and
    rank table (the precision-polymorphic storage spec, PR 5).

    kind:
      * "f32"  — exact float32 storage; the default. PROVABLY a no-op:
                 every query path traces the identical XLA program as the
                 pre-spec code, so selected indices are bit-identical.
      * "bf16" — bfloat16 rows everywhere; bounds are certified by
                 monotone-cast two-sided bucketize + EPS_BF16 widening of
                 the table values (see `repro.core.query`).
      * "int8" — int8 rows with PER-USER scales: symmetric per-row scale
                 for the user matrix, per-row affine (scale, offset) for
                 thresholds/table/delta-score rows; bounds are certified
                 by half-step widening in the quantized domain.

    The paper's contract is a c-approximation — it already tolerates
    bounded rank error — so precision is a tunable resource: the certified
    widening folds quantization error into (r↓, r↑) exactly the way
    `pruning.py` folds f32 rounding slack into block envelopes, and
    Lemma-1 selection stays sound at every spec.
    """

    kind: str = "f32"

    _ALIASES = {"f32": "f32", "float32": "f32",
                "bf16": "bf16", "bfloat16": "bf16",
                "int8": "int8"}

    def __post_init__(self):
        if self.kind not in ("f32", "bf16", "int8"):
            raise ValueError(f"unknown StorageSpec kind {self.kind!r}; "
                             "expected one of ('f32', 'bf16', 'int8')")

    @classmethod
    def parse(cls, spec) -> "StorageSpec":
        """Coerce a StorageSpec | name | legacy dtype name ("bfloat16")."""
        if isinstance(spec, StorageSpec):
            return spec
        kind = cls._ALIASES.get(str(spec))
        if kind is None:
            raise ValueError(f"unknown storage spec {spec!r}; expected "
                             f"one of {sorted(set(cls._ALIASES))}")
        return cls(kind=kind)

    @property
    def is_exact(self) -> bool:
        return self.kind == "f32"

    @property
    def table_dtype(self):
        return {"f32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": jnp.int8}[self.kind]

    # -------------------------------------------------- materialization
    # THE one code path that turns f32 build outputs into stored arrays —
    # the three pre-PR-5 ad-hoc `astype(storage_dtype)` casts (dense
    # build, sharded build, engine upsert) all collapse into these.
    def pack_table(self, thresholds: jax.Array, table: jax.Array,
                   m=None) -> "RankTable":
        """Materialize f32 (rows, τ) thresholds/table in spec space.

        Works on full matrices and on row blocks (upsert path): the int8
        affine parameters are strictly per-row, so packed rows can be
        scattered into a packed table field-by-field."""
        m = jnp.asarray(0, jnp.int32) if m is None else m
        thresholds = thresholds.astype(jnp.float32)
        table = table.astype(jnp.float32)
        if self.kind == "f32":
            return RankTable(thresholds=thresholds, table=table, m=m)
        if self.kind == "bf16":
            return RankTable(thresholds=thresholds.astype(jnp.bfloat16),
                             table=table.astype(jnp.bfloat16), m=m)
        thr_q, thr_sc, thr_off = _quant_affine_rows(thresholds)
        tab_q, tab_sc, tab_off = _quant_affine_rows(table)
        # Per-row deviation of the TRUE thresholds from the uniform
        # [−127, 127] code grid: Algorithm 1 builds thresholds with
        # `threshold_grid` (uniform), so dev is ~f32-rounding tiny and
        # the query-time bucketize becomes CLOSED FORM — zero gathers,
        # zero threshold-stream reads (`query._lookup_bounds_int8`).
        # Arbitrary (non-uniform) packed thresholds just get a larger
        # dev: the closed form stays certified, only less tight.
        tau = thresholds.shape[1]
        grid = jnp.linspace(-_I8_MAX, _I8_MAX, tau,
                            dtype=jnp.float32)[None, :]
        thr_dev = jnp.max(jnp.abs((thresholds - thr_off) / thr_sc - grid),
                          axis=1, keepdims=True)
        return RankTable(thresholds=thr_q, table=tab_q, m=m,
                         thr_scale=thr_sc, thr_off=thr_off,
                         tab_scale=tab_sc, tab_off=tab_off,
                         thr_dev=thr_dev)

    def pack_users(self, users: jax.Array) -> Optional["StoredUsers"]:
        """Materialize the (n, d) user matrix in spec space; None for the
        exact spec (the raw f32 array IS the storage — backends receive
        it unchanged, keeping the f32 path a bit-identical no-op).

        `row_slack` is the per-row certified score-error coefficient: for
        any query q, |stored-score − f32-score| ≤ row_slack · ‖q‖₁
        (per-coordinate error ≤ scale/2 for int8, ≤ EPS_BF16·‖row‖∞ for
        bf16)."""
        users = users.astype(jnp.float32)
        if self.kind == "f32":
            return None
        if self.kind == "bf16":
            rows = users.astype(jnp.bfloat16)
            slack = EPS_BF16 * jnp.max(
                jnp.abs(rows.astype(jnp.float32)), axis=1, keepdims=True)
            return StoredUsers(rows=rows, scale=None,
                               row_slack=slack + 1e-12)
        scale = jnp.maximum(jnp.max(jnp.abs(users), axis=1, keepdims=True),
                            1e-12) / _I8_MAX
        rows = jnp.clip(jnp.round(users / scale), -_I8_MAX, _I8_MAX
                        ).astype(jnp.int8)
        return StoredUsers(rows=rows, scale=scale, row_slack=0.5 * scale)

    def pack_scores(self, scores: jax.Array, pad: int
                    ) -> tuple[jax.Array, Optional[jax.Array],
                               Optional[jax.Array]]:
        """Materialize per-row ASCENDING delta score sets in spec space,
        left-padding `pad` absent-sentinel columns (−inf; −128 for int8).

        Returns (rows, scale, offset); scale/offset are None except for
        int8. Quantization is per-row monotone, so sortedness survives
        the pack and the query-time count stays one searchsorted."""
        scores = scores.astype(jnp.float32)
        if self.kind == "f32":
            out = scores
            if pad:
                out = jnp.pad(out, ((0, 0), (pad, 0)),
                              constant_values=-jnp.inf)
            return out, None, None
        if self.kind == "bf16":
            out = scores.astype(jnp.bfloat16)
            if pad:
                out = jnp.pad(out, ((0, 0), (pad, 0)),
                              constant_values=-jnp.inf)
            return out, None, None
        q, sc, off = _quant_affine_rows(scores)
        if pad:
            q = jnp.pad(q, ((0, 0), (pad, 0)), constant_values=-128)
        return q, sc, off


def _quant_affine_rows(x: jax.Array) -> tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """Per-row affine int8 quantization: codes in [-127, 127] with
    x ≈ code·scale + offset, |error| ≤ scale/2 (rounding; the range
    endpoints land exactly on ±127 before rounding, so the clip is a
    no-op on real data and only guards f32 edge rounding)."""
    lo = jnp.min(x, axis=1, keepdims=True)
    hi = jnp.max(x, axis=1, keepdims=True)
    off = 0.5 * (lo + hi)
    scale = jnp.maximum(hi - lo, 1e-12) / (2.0 * _I8_MAX)
    q = jnp.clip(jnp.round((x - off) / scale), -_I8_MAX, _I8_MAX
                 ).astype(jnp.int8)
    return q, scale, off


class StoredUsers(NamedTuple):
    """Spec-space user matrix (bf16/int8 specs; f32 passes the raw array).

    rows:      (n, d) bf16 or int8 stored rows.
    scale:     (n, 1) f32 per-user symmetric scale — int8 only.
    row_slack: (n, 1) f32 — certified per-row score-error coefficient:
               |score(stored) − score(f32)| ≤ row_slack · ‖q‖₁.
    """

    rows: jax.Array
    scale: Optional[jax.Array]
    row_slack: Optional[jax.Array]

    @property
    def shape(self):
        return self.rows.shape

    def take_rows(self, idx: jax.Array) -> "StoredUsers":
        return StoredUsers(
            rows=self.rows[idx],
            scale=None if self.scale is None else self.scale[idx],
            row_slack=(None if self.row_slack is None
                       else self.row_slack[idx]))


def stored_rows(users) -> jax.Array:
    """The raw row array of either a plain (n, d) array or StoredUsers."""
    return users.rows if isinstance(users, StoredUsers) else users


def take_user_rows(users, idx: jax.Array):
    """Row-gather either user representation (pruned phase-B compaction)."""
    if isinstance(users, StoredUsers):
        return users.take_rows(idx)
    return users[idx]


@dataclasses.dataclass(frozen=True)
class RankTableConfig:
    """Static configuration for Algorithm 1 (pre-processing).

    Attributes:
      tau:   number of inner-product thresholds per user (table columns).
             Paper default 500 (Table 1 tunes 100/500/1000).
      omega: number of norm-stratified partitions of P (Alg. 1 input).
      s:     number of random samples per partition (Alg. 1 input).
      threshold_mode: how f_min/f_max (threshold range per user) is obtained:
        * "sampled"    — min/max of u·p over the stratified sample, widened
                         by `range_pad` of the sampled range. O(ω·s·d)/user,
                         consistent with the paper's O(d) claim for
                         ω,s = O(1); the default.
        * "norm_bound" — ±‖u‖·max‖p‖ (the paper's footnote-1 "domain value"
                         O(1) variant).
        * "exact"      — true f_min/f_max via a full U·Pᵀ pass, O(nmd).
                         Only for small oracle tests.
      range_pad: fractional widening of the sampled threshold range.
      sample_with_replacement: stratified sampling mode; False matches the
        paper ("s random samples in P_l"), True is used when s > |P_l|.
    """

    tau: int = 500
    omega: int = 10
    s: int = 64
    threshold_mode: str = "sampled"
    range_pad: float = 0.05
    sample_with_replacement: bool = False
    # Storage spec for the user matrix + thresholds + table (§Perf H4 /
    # PR 5): "bfloat16"/"bf16" halves, "int8" quarters the dominant HBM
    # stream of the query; the quantization error is folded into the
    # certified (r↓, r↑) bounds (see `StorageSpec`), so the
    # c-approximation contract holds at every setting.
    storage_dtype: str = "float32"

    def __post_init__(self):
        if self.tau < 2:
            raise ValueError(f"tau must be >= 2, got {self.tau}")
        if self.omega < 1:
            raise ValueError(f"omega must be >= 1, got {self.omega}")
        if self.s < 1:
            raise ValueError(f"s must be >= 1, got {self.s}")
        if self.threshold_mode not in ("sampled", "norm_bound", "exact"):
            raise ValueError(f"unknown threshold_mode {self.threshold_mode!r}")
        StorageSpec.parse(self.storage_dtype)   # raises on unknown specs

    @property
    def storage(self) -> StorageSpec:
        """The parsed storage spec (the single source of truth for how
        users/thresholds/table are materialized)."""
        return StorageSpec.parse(self.storage_dtype)


class RankTable(NamedTuple):
    """The paper's rank table T (§4.1) plus its per-user thresholds.

    thresholds: (n, tau) storage dtype, ascending along axis 1 — t_{u_i,j}
                (f32 exact, bf16, or int8 codes under the per-row affine
                (thr_scale, thr_off)).
    table:      (n, tau) storage dtype, non-increasing along axis 1 —
                estimated rank of an item p for u_i when u_i·p = t_{u_i,j}
                (Eq. 1); int8 codes under (tab_scale, tab_off).
    m:          () int32 — |P|, needed for the out-of-range upper bound m+1.
    thr_scale/thr_off/tab_scale/tab_off: (n, 1) f32 per-row affine
                dequantization parameters; present iff the storage spec is
                int8 (None otherwise — the pytree stays shape-compatible
                with pre-spec tables). They row-shard exactly like the
                rows they describe (`core.distributed`).
    """

    thresholds: jax.Array
    table: jax.Array
    m: jax.Array
    thr_scale: Optional[jax.Array] = None
    thr_off: Optional[jax.Array] = None
    tab_scale: Optional[jax.Array] = None
    tab_off: Optional[jax.Array] = None
    # (n, 1) f32, int8 only: max per-row deviation of the true f32
    # thresholds from the uniform [−127, 127] code grid, in code units —
    # certifies the closed-form bucketize (see pack_table).
    thr_dev: Optional[jax.Array] = None

    @property
    def n(self) -> int:
        return self.thresholds.shape[0]

    @property
    def tau(self) -> int:
        return self.thresholds.shape[1]

    @property
    def spec_kind(self) -> str:
        """The storage kind this table is materialized in — derived from
        the arrays themselves so query code needs no side-channel."""
        if self.thr_scale is not None:
            return "int8"
        if self.thresholds.dtype == jnp.bfloat16:
            return "bf16"
        return "f32"

    _QUANT_FIELDS = ("thr_scale", "thr_off", "tab_scale", "tab_off",
                     "thr_dev")

    def take_rows(self, idx: jax.Array) -> "RankTable":
        """Row-gather every row-aligned field (pruned phase-B compaction,
        upsert row updates) — scale vectors travel with their rows."""
        g = lambda a: None if a is None else a[idx]
        return RankTable(thresholds=self.thresholds[idx],
                         table=self.table[idx], m=self.m,
                         **{f: g(getattr(self, f))
                            for f in self._QUANT_FIELDS})

    def set_rows(self, idx: jax.Array, rows: "RankTable") -> "RankTable":
        """Scatter packed row blocks (from `StorageSpec.pack_table`) into
        this table — the upsert path; per-row quantization parameters make
        the row update local."""
        s = lambda a, b: None if a is None else a.at[idx].set(b)
        return RankTable(
            thresholds=self.thresholds.at[idx].set(
                rows.thresholds.astype(self.thresholds.dtype)),
            table=self.table.at[idx].set(rows.table.astype(self.table.dtype)),
            m=self.m,
            **{f: s(getattr(self, f), getattr(rows, f))
               for f in self._QUANT_FIELDS})

    def append_rows(self, rows: "RankTable") -> "RankTable":
        """Concatenate packed row blocks (user-append upserts)."""
        c = lambda a, b: None if a is None else jnp.concatenate([a, b])
        return RankTable(
            thresholds=jnp.concatenate(
                [self.thresholds, rows.thresholds.astype(
                    self.thresholds.dtype)]),
            table=jnp.concatenate(
                [self.table, rows.table.astype(self.table.dtype)]),
            m=self.m,
            **{f: c(getattr(self, f), getattr(rows, f))
               for f in self._QUANT_FIELDS})


class DeltaCorrection(NamedTuple):
    """Query-time correction for a mutated index (see `repro.index`).

    The rank table is built over a frozen base item set P₀ and user set U₀;
    streaming mutations are absorbed by a delta buffer and FUSED into the
    estimated rank at query time as a bounded additive correction:

        r(q, u, P') = r(q, u, P₀) + #{a ∈ A : u·a > u·q}
                                  − #{p ∈ D : u·p > u·q}

    for P' = (P₀ \\ D) ∪ A. Both correction terms are computed EXACTLY
    from per-user scores against the (small) delta item sets, so the
    Eq. (1) estimator's error is untouched by the shift — the only delta
    degradation is the stale sampling noise of tombstoned sample
    positions, which the maintenance policy budgets (`repro.index.delta`).

    All fields are device arrays (the tuple is a pytree and flows through
    jit / shard_map); the per-row score sets are pre-sorted so the query-
    time count is one vmapped searchsorted — O(B·log|delta|) per user row
    on top of the static path.

    add_scores: (n, n_add) ascending per row — u_i·a for every live
                inserted item a ∈ A, stored in SPEC SPACE (f32 exact,
                bf16, or int8 codes under (add_scale, add_off); left-
                padded with the absent sentinel −inf / −128). Quantized
                sets yield certified COUNT RANGES instead of exact
                counts; `rank_table.apply_delta_corrections` widens
                (r↓, r↑) by them so the bounds stay certified.
    del_scores: (n, n_del) ascending per row — u_i·p for every
                tombstoned base item p ∈ D (same storage).
    user_live:  (n,) bool — False rows are deleted users; their bounds are
                forced past every admissible selection key.
    m_new:      () int32 — |P'| = |P₀| − |D| + |A|, the live item count
                (replaces `RankTable.m` in the selection).
    add_scale/add_off/del_scale/del_off: (n, 1) f32 per-row affine
                dequantization parameters, present iff the spec is int8.
    """

    add_scores: jax.Array
    del_scores: jax.Array
    user_live: jax.Array
    m_new: jax.Array
    add_scale: Optional[jax.Array] = None
    add_off: Optional[jax.Array] = None
    del_scale: Optional[jax.Array] = None
    del_off: Optional[jax.Array] = None

    @property
    def n_add(self) -> int:
        return self.add_scores.shape[1]

    @property
    def n_del(self) -> int:
        return self.del_scores.shape[1]

    def take_rows(self, idx: jax.Array) -> "DeltaCorrection":
        """Row-gather the per-user fields (pruned phase-B compaction,
        sharded per-shard sub-corrections)."""
        g = lambda a: None if a is None else a[idx]
        return DeltaCorrection(
            add_scores=self.add_scores[idx], del_scores=self.del_scores[idx],
            user_live=self.user_live[idx], m_new=self.m_new,
            add_scale=g(self.add_scale), add_off=g(self.add_off),
            del_scale=g(self.del_scale), del_off=g(self.del_off))

    def selection_m(self) -> jax.Array:
        """The `m_items` to pass into the §4.3 composite selection key on
        the delta path (see `query.lemma1_key`): the class-separation
        offset must dominate the SHIFTED estimate range
        [1 − n_del, m_base + 1 + n_add], whose width is
        m_new + 2·n_del ≥ width for the padded column counts — the plain
        live count m' is not enough once deletions widen the range
        downward. Every backend derives it from this one method, so the
        key stays identical across dense/fused/sharded."""
        return self.m_new + 2 * self.n_del


class QueryResult(NamedTuple):
    """Output of one c-approximate reverse k-ranks query (§4.3).

    indices:   (k,) int32 — selected user indices (U_c), best-first.
    est_rank:  (k,) float32 — interpolated rank estimates for the selection.
    r_lo:      (n,) float32 — per-user lower-bound rank r↓.
    r_up:      (n,) float32 — per-user upper-bound rank r↑.
    R_lo_k:    () float32 — k-th smallest lower bound (R↓_k).
    R_up_k:    () float32 — k-th smallest upper bound (R↑_k).
    guaranteed:() bool    — Lemma-1 case: c·R↓_k ≥ R↑_k (search closed in
                step 2; no interpolation fill needed).
    n_accepted:() int32   — #users with r↑ ≤ c·R↓_k (Lemma 1 (1)).
    n_pruned:  () int32   — #users with r↓ > R↑_k  (Lemma 1 (2)).
    """

    indices: jax.Array
    est_rank: jax.Array
    r_lo: jax.Array
    r_up: jax.Array
    R_lo_k: jax.Array
    R_up_k: jax.Array
    guaranteed: jax.Array
    n_accepted: jax.Array
    n_pruned: jax.Array


def kth_smallest(x: jax.Array, k: int) -> jax.Array:
    """k-th smallest value along the last axis (k is 1-indexed, static).

    Shape-polymorphic: (n,) → scalar, (B, n) → (B,) — the batched query
    path reduces every query's bound vector in one call.

    Implemented with jnp.partition rather than top_k on the negation: an
    order STATISTIC needs no indices, and XLA's CPU backend lowers a
    values-only top_k to a full O(n log n) sort (~100× slower at
    (16, 16k)). jnp.partition is top_k(-x, k) joined with top_k(x, n − k);
    only inside a jit does XLA drop the unused second half, so partition
    stays O(n) there and returns the identical value. Called eagerly it
    returns the whole joined array, which sorts the full last axis:
    callers run it compiled (`query._select_topk_jit`).
    """
    return jnp.partition(x, k - 1, axis=-1)[..., k - 1]


def partition_sizes(m: int, omega: int) -> tuple[int, ...]:
    """Sizes of the ω norm-descending partitions of P (Alg. 1 line 3).

    Equal sizes when ω | m; otherwise the first (m mod ω) buckets carry one
    extra item so every item is covered exactly once.
    """
    base = m // omega
    extra = m % omega
    return tuple(base + (1 if l < extra else 0) for l in range(omega))
