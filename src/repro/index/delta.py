"""Delta buffer: streaming index mutations absorbed without a rebuild.

Algorithm 1 freezes an item set P₀ (and a user set U₀) into the rank
table; real item-centric workloads churn both. The delta buffer holds the
difference between the frozen base and the LIVE sets, small enough
(|delta| / m ≤ ρ, enforced by the maintenance policy) that it can be
fused into every query as a bounded additive correction instead of
forcing a rebuild:

  * inserted items are scored EXACTLY against each user at query time —
    the step-1 pass gains one small (n, n_add)-vs-(n, B) counting pass
    over pre-sorted per-user scores (`DeltaCorrection.add_scores`);
  * deleted items get a TOMBSTONE over the base: their exact per-user
    score sets are subtracted the same way, and the sampled positions
    they occupied are tracked (`DeltaStats.stale_weight`) because those
    positions keep contributing Eq. (1) sampling noise for mass that no
    longer exists — the error-budget half of the rebuild policy;
  * user upserts re-estimate JUST the touched table rows against the
    retained build sample (`rank_table.recompute_user_rows` — bit-
    consistent with a from-scratch build), and user deletions are a live
    mask that forces the row past every admissible selection key.

Error accounting: both correction terms are exact counts, so the Eq. (1)
estimator's guarantee is SHIFTED, not degraded — E[est'] = r(q,u,P')
whenever E[est] = r(q,u,P₀). The only delta-induced slack is the stale
sampling noise of tombstoned positions, bounded by their stratum weight
Σ w_s (≤ |D|·max_l |P_l|/s); `DeltaStats.stale_fraction` surfaces it and
`MaintenancePolicy.max_stale_fraction` bounds it.

Everything here is immutable and functionally updated: a `DeltaState` is
owned by exactly one `IndexSnapshot` generation, so in-flight queries
against an older snapshot are never perturbed by new mutations.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rank_table as rt_mod
from repro.core.types import DeltaCorrection, RankTableConfig, StorageSpec, \
    matmul


@dataclasses.dataclass(frozen=True)
class BaseIndex:
    """The frozen substrate a rank table was built over, retained so the
    index can be mutated and rebuilt without the caller re-supplying it.

    items:        (m_base, d) base item vectors, ORIGINAL insertion order.
    item_ids:     (m_base,) ascending stable ids (survive rebuilds).
    samples:      (ω·s, d) the build's stratified sample vectors.
    weights:      (ω·s,) device stratum weights |P_l| / s.
    weights_host: host copy of `weights` for the (tiny) stats math.
    sample_ids:   (ω·s,) item id at each sampled position — the tombstone
                  join key for deletions.
    max_norm:     () float32 max ‖p‖ (threshold_mode="norm_bound").
    """

    items: jax.Array
    item_ids: np.ndarray
    samples: jax.Array
    weights: jax.Array
    weights_host: np.ndarray
    sample_ids: np.ndarray
    max_norm: jax.Array

    @classmethod
    def create(cls, items: jax.Array, item_ids: np.ndarray,
               cfg: RankTableConfig, key: jax.Array) -> "BaseIndex":
        """Re-derive the sampling state of `build_rank_table(items, …, key)`
        (deterministic in (items, cfg, key) — shared by the dense and the
        sharded build, see `rank_table.sampling_artifacts`).

        This repeats the build's O(m·d + m log m) norm/sort/sample pass
        — deliberately: it keeps `QueryBackend.build_index` a plain
        `(users, items, cfg, key) → RankTable` hook instead of threading
        artifacts through every backend, and the duplicate m-pass is
        noise next to the O(n·ω·s·d) table build (n ≫ m here)."""
        art = rt_mod.sampling_artifacts(items, cfg, key)
        order = np.asarray(art.order)
        positions = np.asarray(art.positions)
        return cls(items=items, item_ids=np.asarray(item_ids, np.int64),
                   samples=art.samples, weights=art.weights,
                   weights_host=np.asarray(art.weights),
                   sample_ids=np.asarray(item_ids,
                                         np.int64)[order[positions]],
                   max_norm=art.max_norm)

    @property
    def m_base(self) -> int:
        return int(self.item_ids.size)

    def positions_of(self, ids: np.ndarray) -> np.ndarray:
        """Base positions of `ids` (item_ids is ascending); -1 if absent."""
        ids = np.asarray(ids, np.int64)
        pos = np.searchsorted(self.item_ids, ids)
        pos = np.clip(pos, 0, self.item_ids.size - 1)
        return np.where(self.item_ids[pos] == ids, pos, -1)


@dataclasses.dataclass(frozen=True)
class DeltaStats:
    """Delta-buffer accounting driving the rebuild policy."""

    n_added: int            # live inserted items
    n_deleted: int          # tombstoned base items
    n_dead_users: int
    n_touched_users: int    # rows re-estimated in place since base epoch
    m_base: int
    m_live: int             # m_base − n_deleted + n_added
    delta_ratio: float      # (n_added + n_deleted) / m_base
    stale_weight: float     # Σ stratum weights of tombstoned sample slots
    stale_fraction: float   # stale_weight / m_base

    def __str__(self):
        return (f"+{self.n_added}/-{self.n_deleted} items "
                f"({self.delta_ratio:.3f} of m={self.m_base}), "
                f"{self.n_dead_users} dead users, "
                f"stale {self.stale_fraction:.4f}")


@dataclasses.dataclass(frozen=True)
class DeltaState:
    """Immutable mutation set relative to one `BaseIndex` generation.

    base_live:     (m_base,) bool — False marks tombstoned base items.
    added_ids:     (A,) int64 ids of LIVE inserted items (an item inserted
                   then deleted simply leaves the buffer).
    added_items:   (A, d) their vectors, or None when A == 0.
    user_live:     (n,) bool — False marks deleted users.
    touched_users: user indices whose table rows were re-estimated since
                   the base epoch (consumed by the rebuild re-base).
    """

    base_live: np.ndarray
    added_ids: np.ndarray
    added_items: Optional[jax.Array]
    user_live: np.ndarray
    touched_users: frozenset

    @classmethod
    def empty(cls, m_base: int, n_users: int) -> "DeltaState":
        return cls(base_live=np.ones(m_base, bool),
                   added_ids=np.empty(0, np.int64), added_items=None,
                   user_live=np.ones(n_users, bool),
                   touched_users=frozenset())

    # ------------------------------------------------------------ queries
    @property
    def n_added(self) -> int:
        return int(self.added_ids.size)

    @property
    def n_deleted(self) -> int:
        return int((~self.base_live).sum())

    @property
    def is_empty(self) -> bool:
        return (self.n_added == 0 and self.n_deleted == 0
                and bool(self.user_live.all()))

    def stats(self, base: Optional[BaseIndex]) -> DeltaStats:
        m_base = base.m_base if base is not None else int(self.base_live.size)
        stale = 0.0
        if base is not None and self.n_deleted:
            dead_ids = base.item_ids[~self.base_live]
            stale = float(base.weights_host[
                np.isin(base.sample_ids, dead_ids)].sum())
        return DeltaStats(
            n_added=self.n_added, n_deleted=self.n_deleted,
            n_dead_users=int((~self.user_live).sum()),
            n_touched_users=len(self.touched_users),
            m_base=m_base, m_live=m_base - self.n_deleted + self.n_added,
            delta_ratio=(self.n_added + self.n_deleted) / max(m_base, 1),
            stale_weight=stale, stale_fraction=stale / max(m_base, 1))

    # ------------------------------------------------- functional updates
    def with_inserted(self, ids: np.ndarray, vectors: jax.Array
                      ) -> "DeltaState":
        added = (vectors if self.added_items is None
                 else jnp.concatenate([self.added_items, vectors]))
        return dataclasses.replace(
            self, added_ids=np.concatenate([self.added_ids,
                                            np.asarray(ids, np.int64)]),
            added_items=added)

    def with_deleted(self, ids: np.ndarray, base: Optional[BaseIndex]
                     ) -> "DeltaState":
        """Tombstone base items / drop inserted items by id."""
        ids = np.unique(np.asarray(ids, np.int64))
        in_added = np.isin(ids, self.added_ids)
        base_live = self.base_live.copy()
        if base is not None:
            pos = base.positions_of(ids[~in_added])
        else:
            pos = np.full((~in_added).sum(), -1)
        unknown = ids[~in_added][pos < 0]
        if unknown.size:
            raise KeyError(f"unknown item ids {unknown.tolist()}")
        dead_already = ~base_live[pos]
        if dead_already.any():
            raise KeyError(f"item ids already deleted: "
                           f"{ids[~in_added][dead_already].tolist()}")
        base_live[pos] = False
        keep = ~np.isin(self.added_ids, ids)
        added_items = self.added_items
        if added_items is not None and not keep.all():
            added_items = (added_items[jnp.asarray(np.flatnonzero(keep))]
                           if keep.any() else None)
        return dataclasses.replace(self, base_live=base_live,
                                   added_ids=self.added_ids[keep],
                                   added_items=added_items)

    def with_users(self, *, touched: Tuple[int, ...] = (),
                   dead: Tuple[int, ...] = (), n_users: Optional[int] = None
                   ) -> "DeltaState":
        """Record upserted rows and/or user deletions; `n_users` grows the
        live mask when rows were appended."""
        user_live = self.user_live
        if n_users is not None and n_users > user_live.size:
            user_live = np.concatenate(
                [user_live, np.ones(n_users - user_live.size, bool)])
        else:
            user_live = user_live.copy()
        user_live[list(dead)] = False
        # an upsert resurrects nothing: dead rows stay dead unless the
        # caller re-appends; touched only drives the rebuild re-base
        return dataclasses.replace(
            self, user_live=user_live,
            touched_users=self.touched_users | frozenset(touched))


def _bucket(width: int) -> int:
    """Round a delta width up to a power-of-two bucket (min 8).

    Query programs are compiled per correction SHAPE; a streaming
    workload that grows the delta by a few items per batch would retrace
    on every mutation. Bucketing pads the sorted score sets LEFT with
    -inf — which counts as exactly zero in `_count_above` (strict >), so
    results are bit-identical — and caps recompiles at O(log |delta|)
    per epoch lineage.
    """
    if width == 0:
        return 0
    b = 8
    while b < width:
        b *= 2
    return b


def _sorted_padded(scores: jax.Array, width: int) -> jax.Array:
    """f32 sort + bucket-pad (the pre-spec correction rows; kept for
    tests building hand-rolled corrections)."""
    out, _, _ = StorageSpec().pack_scores(
        jnp.sort(scores.astype(jnp.float32), axis=1),
        _bucket(width) - width)
    return out


def _packed_scores(users: jax.Array, items: jax.Array, width: int,
                   spec: StorageSpec):
    """Score `items` against every user, sort per row, materialize in
    spec space, left-pad to the power-of-two bucket with the absent
    sentinel (−inf; −128 for int8 — `rank_table._count_above_range`
    guarantees the sentinel is never counted)."""
    raw = jnp.sort(matmul(users, items.T).astype(jnp.float32), axis=1)
    return spec.pack_scores(raw, _bucket(width) - width)


def build_correction(users: jax.Array, base: Optional[BaseIndex],
                     delta: DeltaState, m_base: int,
                     spec: Optional[StorageSpec] = None
                     ) -> Optional[DeltaCorrection]:
    """Materialize the query-time `DeltaCorrection` for one snapshot.

    O(n · |delta| · d) once per mutation batch (the per-user delta scores
    are sorted here so every query pays only a searchsorted) — None when
    the delta is empty, which keeps the static fast path untouched. Score
    sets are padded to power-of-two buckets (`_bucket`) so streaming
    mutations reuse compiled query programs instead of retracing per
    delta size.

    `spec` (PR 5): the engine's storage spec — correction rows are
    QUANTIZED ON INSERT (scored in f32 against the f32 system of record,
    then packed), so the whole delta path streams spec-space bytes; the
    query-time count becomes a certified range that
    `apply_delta_corrections` folds into the widened bounds. The f32 spec
    stores exactly the pre-spec f32 rows (bit-identity).
    """
    if delta.is_empty:
        return None
    spec = StorageSpec() if spec is None else spec
    n = users.shape[0]
    add_sc = add_off = del_sc = del_off = None
    if delta.n_added:
        add, add_sc, add_off = _packed_scores(users, delta.added_items,
                                              delta.n_added, spec)
    else:
        add = jnp.zeros((n, 0), jnp.float32)
    if delta.n_deleted:
        dead = base.items[jnp.asarray(np.flatnonzero(~delta.base_live))]
        dele, del_sc, del_off = _packed_scores(users, dead,
                                               delta.n_deleted, spec)
    else:
        dele = jnp.zeros((n, 0), jnp.float32)
    m_new = m_base - delta.n_deleted + delta.n_added
    return DeltaCorrection(add_scores=add, del_scores=dele,
                           user_live=jnp.asarray(delta.user_live),
                           m_new=jnp.asarray(m_new, jnp.int32),
                           add_scale=add_sc, add_off=add_off,
                           del_scale=del_sc, del_off=del_off)


def residual_after_rebuild(old_base: BaseIndex, delta_now: DeltaState,
                           new_ids: np.ndarray) -> DeltaState:
    """Re-base `delta_now` onto a rebuild that snapshotted an OLDER delta.

    The rebuild ran Algorithm 1 over the items live at capture time
    (`new_ids`); mutations that landed while it was building must survive
    the swap. Relative to the new base: an id in `new_ids` that is no
    longer live is a residual tombstone; a live inserted id not in
    `new_ids` is a residual insert. `touched_users` resets — the swap
    recomputes those rows against the new sample.
    """
    live_now = np.concatenate(
        [old_base.item_ids[delta_now.base_live], delta_now.added_ids])
    base_live = np.isin(np.asarray(new_ids, np.int64), live_now)
    keep = ~np.isin(delta_now.added_ids, new_ids)
    added_items = None
    if delta_now.added_items is not None and keep.any():
        added_items = delta_now.added_items[jnp.asarray(
            np.flatnonzero(keep))]
    return DeltaState(base_live=base_live,
                      added_ids=delta_now.added_ids[keep],
                      added_items=added_items,
                      user_live=delta_now.user_live.copy(),
                      touched_users=frozenset())
