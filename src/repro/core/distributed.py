"""Multi-pod sharded reverse k-ranks: the engine at 512-chip scale.

Layout (see DESIGN.md §3):
  * users + rank-table rows are ROW-SHARDED over a flat 1-D view of the
    mesh ("shard" = pod×data×model flattened) — n/512 users per chip;
  * items are sharded the same way for the build's norm pass and for exact
    refinement; stratified samples are small and replicated;
  * a query vector is replicated; step 1 (u·q + table lookup) is fully
    local; the global top-k runs as a TREE MERGE: per-shard top-k
    (k values) → gather of k·P candidates (not n) → re-top-k.

Collective budget per BATCH of B queries: one gather of O(B·k·P) floats
plus the final selection — O(B·k·P) bytes on the wire instead of O(B·n),
and the collective count is independent of B (single-query execution is
just B = 1). Per-chip compute is O(B·nd/P + BkP). The build's only
collective is the O(m)-scalar norm gather for the global sort (item
vectors never gather).

Functions take the production mesh; internally the engine re-views its
devices as a 1-D "shard" mesh, which is the natural layout for an index
that has no tensor dimension to model-parallelize.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import rank_table as rt_mod
from repro.core.query import lemma1_key, lemma1_select, \
    lookup_bounds_batch, user_scores_batch
from repro.core.types import DeltaCorrection, QueryResult, RankTable, \
    RankTableConfig, StoredUsers, kth_smallest, matmul, take_user_rows

AXIS = "shard"


def flat_mesh(mesh_or_devices) -> Mesh:
    """1-D engine view of a (possibly multi-axis) mesh's devices."""
    import numpy as np
    if isinstance(mesh_or_devices, Mesh):
        devs = mesh_or_devices.devices.reshape(-1)
    else:
        devs = np.asarray(mesh_or_devices).reshape(-1)
    return Mesh(devs, (AXIS,))


def user_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ------------------------------------------------- storage-spec sharding
# The storage tier is row-aligned by construction: every optional field
# (int8 affine scale/offset vectors, per-user score-slack coefficients)
# is (n, 1) and shards EXACTLY like the rows it describes. These helpers
# build the pytree in_specs for shard_map from the actual argument
# structure, so one query fn serves every StorageSpec.

def _rt_specs(rt: RankTable) -> RankTable:
    s = lambda a: None if a is None else P(AXIS, None)
    return RankTable(thresholds=P(AXIS, None), table=P(AXIS, None), m=P(),
                     **{f: s(getattr(rt, f))
                        for f in RankTable._QUANT_FIELDS})


def _user_specs(users):
    if not isinstance(users, StoredUsers):
        return P(AXIS, None)
    s = lambda a: None if a is None else P(AXIS, None)
    return StoredUsers(rows=P(AXIS, None), scale=s(users.scale),
                       row_slack=s(users.row_slack))


def _corr_specs(corr: DeltaCorrection) -> DeltaCorrection:
    s = lambda a: None if a is None else P(AXIS, None)
    return DeltaCorrection(
        add_scores=P(AXIS, None), del_scores=P(AXIS, None),
        user_live=P(AXIS), m_new=P(),
        add_scale=s(corr.add_scale), add_off=s(corr.add_off),
        del_scale=s(corr.del_scale), del_off=s(corr.del_off))


# ------------------------------------------------------------------- build
def build_sharded(users: jax.Array, items: jax.Array, cfg: RankTableConfig,
                  key: jax.Array, mesh: Mesh) -> RankTable:
    """Algorithm 1 on a flat mesh.

    Norm pass is item-sharded (O(md/P) per chip); the global norm-sort
    runs on the m gathered SCALARS; the per-user table build is
    embarrassingly row-parallel (zero collectives).

    threshold_mode="exact" is refused rather than silently degraded: the
    exact f_min/f_max needs every user row to see the FULL item set,
    which this row-parallel build never materializes (it is an O(nmd)
    oracle mode for small tests — build it dense).
    """
    if cfg.threshold_mode == "exact":
        raise ValueError(
            'build_sharded does not support threshold_mode="exact" (each '
            "user shard only sees its item shard); use the dense "
            "build_rank_table for the exact-threshold oracle mode")
    m = items.shape[0]

    norms_local = jax.shard_map(
        lambda it: jnp.linalg.norm(it.astype(jnp.float32), axis=1),
        mesh=mesh, in_specs=P(AXIS, None), out_specs=P(AXIS))
    norms = norms_local(items)
    order = jnp.argsort(-norms)                    # m scalars: cheap, global

    positions, weights = rt_mod.stratified_sample_indices(key, m, cfg)
    samples = items[order[positions]]              # (ω·s, d) — replicated
    max_norm = norms[order[0]]

    def local_build(u_shard, smp, w, mx):
        scores = matmul(u_shard, smp.T).astype(jnp.float32)
        if cfg.threshold_mode == "norm_bound":
            bound = jnp.linalg.norm(u_shard.astype(jnp.float32),
                                    axis=1) * mx
            smin, smax = -bound, bound
        else:
            smin = scores.min(axis=1)
            smax = scores.max(axis=1)
            pad = cfg.range_pad * jnp.maximum(smax - smin, 1e-6)
            smin, smax = smin - pad, smax + pad
        thr = rt_mod.threshold_grid(smin, smax, cfg.tau)
        table = rt_mod.estimate_table_rows(scores, w, thr)
        # the SAME pack path as the dense build — per-row quantization
        # parameters are shard-local, so packing commutes with sharding
        packed = cfg.storage.pack_table(thr, table)
        return tuple(f for f in
                     ((packed.thresholds, packed.table)
                      + tuple(getattr(packed, q)
                              for q in RankTable._QUANT_FIELDS))
                     if f is not None)

    n_out = 2 + len(RankTable._QUANT_FIELDS) \
        if cfg.storage.kind == "int8" else 2
    out = jax.shard_map(
        local_build, mesh=mesh,
        in_specs=(P(AXIS, None), P(None, None), P(None), P()),
        out_specs=tuple([P(AXIS, None)] * n_out))(
            users, samples, weights, max_norm)
    extra = dict(zip(RankTable._QUANT_FIELDS, out[2:]))
    return RankTable(thresholds=out[0], table=out[1],
                     m=jnp.asarray(m, jnp.int32), **extra)


# ------------------------------------------------------------------- query
def make_batch_query_fn(mesh: Mesh, k: int, n: int, c: float, *,
                        with_delta: bool = False):
    """Builds the jit'd batched sharded query:
    (rank_table, users, Q (B, d) [, delta]) → QueryResult, leading B axis.

    Stage 1 (shard_map): step 1 is ONE local U_shard @ Qᵀ MXU matmul plus
    a single streamed pass over the local threshold/table rows serving all
    B queries (`lookup_bounds_batch`) — the n·(d+2τ)/P byte stream per
    chip is read once per BATCH, not once per query. The per-shard
    k-smallest r↓/r↑ are then all-gathered ((B, k) scalars per shard —
    the kth of the union of per-shard k-smallest IS the global kth), so
    every shard computes the EXACT global R↓_k/R↑_k and selects its k
    candidates by the true §4.3 composite key (accepted ≺ U_temp ≺
    pruned, est within class). Ranking candidates by est alone would
    drop a Lemma-1-accepted user whose estimate is merely mediocre —
    dense and sharded would then legitimately disagree in the
    non-guaranteed regime (caught by tests/test_index.py parity).
    Stage 2: the out_specs stack every shard's candidates into a global
    (B, k·P) set in ONE gather (the tree merge) — not B per-query gathers;
    O(B·k·P) bytes on the wire instead of O(B·n). Global selection reuses
    the shared `lemma1_select` composite key (same R↓_k/R↑_k, same key),
    so the merge preserves the shards' exact ordering.

    With `with_delta=True` the returned fn takes a `DeltaCorrection` whose
    per-user score sets are ROW-SHARDED like the users/table, and the
    shared `apply_delta_corrections` runs inside the shard_map BEFORE the
    per-shard top-k (correcting after candidate selection would pick the
    wrong candidates) — so the mutated-index path keeps the O(B·k·P) wire
    budget: delta score rows never leave their shard.
    """
    nshards = mesh.devices.size
    shard_n = n // nshards

    def local_part(rt_loc, u_shard, qs, *delta):
        scores, slack = user_scores_batch(u_shard, qs)      # (n_loc, B) MXU
        r_lo, r_up, est = lookup_bounds_batch(rt_loc, scores,
                                              slack)        # (n_loc, B)
        if with_delta:
            corr, = delta
            r_lo, r_up, est = rt_mod.apply_delta_corrections(
                scores, r_lo, r_up, est, corr, slack=slack)
            m_eff = corr.selection_m()
        else:
            m_eff = rt_loc.m
        r_lo, r_up, est = r_lo.T, r_up.T, est.T             # (B, n_loc)
        neg_lo, _ = jax.lax.top_k(-r_lo, k)    # k smallest lower bounds / q
        neg_up, _ = jax.lax.top_k(-r_up, k)
        # exact global step-2 statistics: (P, B, k) of per-shard
        # k-smallest → the global kth smallest (order statistic of the
        # union) — O(B·k·P) scalars on the wire, independent of n
        gl = jnp.moveaxis(jax.lax.all_gather(-neg_lo, AXIS), 0, 1)
        gu = jnp.moveaxis(jax.lax.all_gather(-neg_up, AXIS), 0, 1)
        R_lo_k = kth_smallest(gl.reshape(gl.shape[0], -1), k)      # (B,)
        R_up_k = kth_smallest(gu.reshape(gu.shape[0], -1), k)
        # the SHARED composite key (query.lemma1_key) → the local top-k
        # ARE the global top-k's shard members; the merge re-derives the
        # identical key, so local and global ordering cannot drift
        key_val, _, _, _ = lemma1_key(r_lo, r_up, est, R_lo_k=R_lo_k,
                                      R_up_k=R_up_k, c=c, m_items=m_eff)
        _, cand = jax.lax.top_k(-key_val, k)                # k best / query
        shard_id = jax.lax.axis_index(AXIS)
        gidx = cand.astype(jnp.int32) + shard_id * shard_n
        payload = jnp.stack(
            [jnp.take_along_axis(est, cand, axis=-1),
             jnp.take_along_axis(r_lo, cand, axis=-1),
             jnp.take_along_axis(r_up, cand, axis=-1)], axis=-1)  # (B, k, 3)
        return -neg_lo, -neg_up, payload, gidx

    @jax.jit
    def batch_query_fn(rt: RankTable, users, qs: jax.Array,
                       corr: DeltaCorrection = None) -> QueryResult:
        # in_specs are built from the ARGUMENT structure at trace time:
        # int8 scale/offset vectors and quantized-user scale/slack rows
        # shard alongside the rows they describe; the f32 structure
        # lowers to exactly the pre-spec program (bit-identity)
        delta = (corr,) if with_delta else ()
        delta_specs = (_corr_specs(corr),) if with_delta else ()
        sharded = jax.shard_map(
            local_part, mesh=mesh,
            in_specs=(_rt_specs(rt), _user_specs(users),
                      P(None, None)) + delta_specs,
            out_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS, None),
                       P(None, AXIS)))
        all_lo, all_up, payload, gidx = sharded(
            rt, users, qs, *delta)                          # (B, k·P, …)
        est = payload[..., 0]
        r_lo = payload[..., 1]
        r_up = payload[..., 2]
        R_lo_k = kth_smallest(all_lo, k)                    # (B,)
        R_up_k = kth_smallest(all_up, k)
        sel, guaranteed, accepted, pruned = lemma1_select(
            r_lo, r_up, est, R_lo_k=R_lo_k, R_up_k=R_up_k, k=k, c=c,
            m_items=corr.selection_m() if with_delta else rt.m)
        return QueryResult(
            indices=jnp.take_along_axis(gidx, sel, axis=-1).astype(
                jnp.int32),
            est_rank=jnp.take_along_axis(est, sel, axis=-1),
            r_lo=r_lo, r_up=r_up,          # candidate-set bounds (B, k·P)
            R_lo_k=R_lo_k, R_up_k=R_up_k,
            guaranteed=guaranteed,
            n_accepted=jnp.sum(accepted, axis=-1).astype(jnp.int32),
            n_pruned=jnp.sum(pruned, axis=-1).astype(jnp.int32),
        )

    return batch_query_fn


def make_pruned_batch_query_fn(mesh: Mesh, k: int, n: int, c: float, *,
                               block_size: int, with_delta: bool = False):
    """Block-pruned twin of `make_batch_query_fn` (PR 4): each shard
    gathers only its SURVIVING user tiles before the per-shard top-k, so
    the local n·(d+2τ)/P stream shrinks to the kept fraction while the
    tree-merge wire budget stays O(B·k·P).

    The returned fn takes, after (rank_table, users, Q):
      ids   (P, W) int32 — per-shard LOCAL block ids to execute; the
            caller pads every shard to the same width W (SPMD needs
            uniform shapes) by repeating kept ids;
      valid (P, W) bool — False marks the repeated padding columns (and
            whole shards with nothing kept), whose rows are forced to
            +inf so duplicates can never become duplicate candidates;
      keep  (B, nb) bool, replicated — the PER-QUERY phase-A keep mask
            over GLOBAL block ids; rows executed only because another
            query (or the padding) needed them read as +inf for queries
            that pruned them, exactly like the single-process sentinel
            materialization.

    Correctness matches the single-process argument (`core.pruning`):
    every user that can influence R↓_k/R↑_k or the top-k lives in a kept
    tile of its own shard, +inf dominates every admissible key, and the
    per-shard k-smallest of {kept exact values ∪ +inf} reproduces the
    exact global order statistics through the unchanged all-gather
    merge. Requires n % (P · block_size) == 0 (tiles must not straddle
    shards — `PrunedBackend` falls back to the full scan otherwise).

    Reorder contract (PR 6): a build/rebuild-time cluster reorder is a
    GLOBAL row permutation applied to users/table BEFORE sharding, so
    each shard's local tiles are contiguous rows of the already-permuted
    matrix — shard-local block ids, the divisibility contract and the
    tree-merge are all unchanged (n is invariant under a permutation).
    The permuted snapshot answers in its own row coordinates, identical
    to every other backend on that snapshot; translation to pre-remap
    client ids happens once, host-side, via `IndexSnapshot.user_remap` —
    never inside the shard_map.
    """
    nshards = mesh.devices.size
    shard_n = n // nshards
    nb_loc = shard_n // block_size

    def local_part(rt_loc, u_shard, qs, ids, valid, keep, *delta):
        ids_loc = ids[0]                                    # (W,)
        valid_loc = valid[0]
        ridx = (ids_loc[:, None] * block_size
                + jnp.arange(block_size, dtype=jnp.int32)[None, :]
                ).reshape(-1)                               # (W·bs,) local
        scores, slack = user_scores_batch(
            take_user_rows(u_shard, ridx), qs)              # (W·bs, B)
        r_lo, r_up, est = lookup_bounds_batch(rt_loc.take_rows(ridx),
                                              scores, slack)
        if with_delta:
            corr, = delta
            r_lo, r_up, est = rt_mod.apply_delta_corrections(
                scores, r_lo, r_up, est, corr.take_rows(ridx), slack=slack)
            m_eff = corr.selection_m()
        else:
            m_eff = rt_loc.m
        shard_id = jax.lax.axis_index(AXIS)
        gblk = shard_id * nb_loc + ids_loc                  # global ids (W,)
        keep_rows = keep[:, gblk] & valid_loc[None, :]      # (B, W)
        alive = jnp.repeat(keep_rows, block_size, axis=1)   # (B, W·bs)
        inf = jnp.inf
        r_lo = jnp.where(alive, r_lo.T, inf)                # (B, W·bs)
        r_up = jnp.where(alive, r_up.T, inf)
        est = jnp.where(alive, est.T, inf)
        neg_lo, _ = jax.lax.top_k(-r_lo, k)
        neg_up, _ = jax.lax.top_k(-r_up, k)
        gl = jnp.moveaxis(jax.lax.all_gather(-neg_lo, AXIS), 0, 1)
        gu = jnp.moveaxis(jax.lax.all_gather(-neg_up, AXIS), 0, 1)
        R_lo_k = kth_smallest(gl.reshape(gl.shape[0], -1), k)      # (B,)
        R_up_k = kth_smallest(gu.reshape(gu.shape[0], -1), k)
        key_val, _, _, _ = lemma1_key(r_lo, r_up, est, R_lo_k=R_lo_k,
                                      R_up_k=R_up_k, c=c, m_items=m_eff)
        _, cand = jax.lax.top_k(-key_val, k)                # (B, k)
        gidx = (jnp.take(ridx, cand) + shard_id * shard_n).astype(jnp.int32)
        payload = jnp.stack(
            [jnp.take_along_axis(est, cand, axis=-1),
             jnp.take_along_axis(r_lo, cand, axis=-1),
             jnp.take_along_axis(r_up, cand, axis=-1)], axis=-1)  # (B, k, 3)
        return -neg_lo, -neg_up, payload, gidx

    @jax.jit
    def batch_query_fn(rt: RankTable, users, qs: jax.Array,
                       ids: jax.Array, valid: jax.Array, keep: jax.Array,
                       corr: DeltaCorrection = None) -> QueryResult:
        delta = (corr,) if with_delta else ()
        delta_specs = (_corr_specs(corr),) if with_delta else ()
        sharded = jax.shard_map(
            local_part, mesh=mesh,
            in_specs=(_rt_specs(rt), _user_specs(users),
                      P(None, None), P(AXIS, None), P(AXIS, None),
                      P(None, None)) + delta_specs,
            out_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS, None),
                       P(None, AXIS)))
        all_lo, all_up, payload, gidx = sharded(
            rt, users, qs, ids, valid, keep, *delta)        # (B, k·P, …)
        est = payload[..., 0]
        r_lo = payload[..., 1]
        r_up = payload[..., 2]
        R_lo_k = kth_smallest(all_lo, k)                    # (B,)
        R_up_k = kth_smallest(all_up, k)
        sel, guaranteed, accepted, pruned = lemma1_select(
            r_lo, r_up, est, R_lo_k=R_lo_k, R_up_k=R_up_k, k=k, c=c,
            m_items=corr.selection_m() if with_delta else rt.m)
        return QueryResult(
            indices=jnp.take_along_axis(gidx, sel, axis=-1).astype(
                jnp.int32),
            est_rank=jnp.take_along_axis(est, sel, axis=-1),
            r_lo=r_lo, r_up=r_up,          # candidate-set bounds (B, k·P)
            R_lo_k=R_lo_k, R_up_k=R_up_k,
            guaranteed=guaranteed,
            n_accepted=jnp.sum(accepted, axis=-1).astype(jnp.int32),
            n_pruned=jnp.sum(pruned, axis=-1).astype(jnp.int32),
        )

    return batch_query_fn


def make_query_fn(mesh: Mesh, k: int, n: int, c: float):
    """Single-query sharded execution: the B = 1 case of
    `make_batch_query_fn` (same shard_map, same merge; leading axis
    squeezed). Kept as the dry-run/roofline entry point."""
    batched = make_batch_query_fn(mesh, k=k, n=n, c=c)

    @jax.jit
    def query_fn(rt: RankTable, users: jax.Array, q: jax.Array
                 ) -> QueryResult:
        res = batched(rt, users, q[None, :])
        return jax.tree_util.tree_map(lambda x: x[0], res)

    return query_fn


# -------------------------------------------------------------- refinement
def ring_exact_ranks(users: jax.Array, items: jax.Array, q: jax.Array,
                     mesh: Mesh) -> jax.Array:
    """Exact Definition-1 ranks with BOTH users and items sharded: item
    shards rotate around a ring (collective_permute) while every user
    shard accumulates counts — compute/comm overlap with items never
    materializing unsharded. Used for boundary-user refinement and as the
    at-scale exact baseline."""
    nshards = mesh.devices.size
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]

    def local(u_shard, it_shard, qv):
        uq = matmul(u_shard, qv).astype(jnp.float32)

        def body(_, carry):
            counts, blk = carry
            scores = matmul(u_shard, blk.T).astype(jnp.float32)
            counts = counts + jnp.sum(scores > uq[:, None], axis=1)
            blk = jax.lax.ppermute(blk, AXIS, perm)
            return counts, blk

        counts, _ = jax.lax.fori_loop(
            0, nshards, body, (jnp.zeros_like(uq), it_shard))
        return 1.0 + counts

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P()),
        out_specs=P(AXIS))(users, items, q)
