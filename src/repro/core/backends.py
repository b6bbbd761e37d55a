"""Pluggable query-execution backends for the reverse k-ranks engine.

One `QueryBackend` protocol, three registered implementations:

  "dense"   — pure-jnp XLA path (`core.query`): one (n,d)×(d,B) matmul +
              one streamed table pass per batch. The default; runs
              anywhere.
  "fused"   — Pallas path (`kernels.ops.bound_ranks_batched`): the same
              math with step 1 fused into a single HBM pass per user tile
              (interpret=True on CPU, compiled on TPU).
  "sharded" — mesh path (`core.distributed`): row-sharded users/table,
              local batched step 1, tree-merge top-k gathering (B, k·P)
              candidates in one collective.

The protocol is batched-first: `bound_ranks` takes a (B, d) query block
and returns (B, n) bound arrays; `select` realizes §4.3 steps 2-3 with a
leading batch axis; `query_batch` composes the two (backends may override
it with a fully fused pipeline, as "sharded" does). Single-query
execution everywhere is the B = 1 case of the batched path — there is no
separate per-query code to drift out of sync.

Wrapper backends compose by NAME with a `<prefix>:<inner>` spec: the
prefix selects a registered wrapper factory, which resolves the inner
backend recursively. The serving cache (`repro.serve.cache`) registers
`"cached"`, so `backend="cached:fused"` builds a `CachingBackend` around
the Pallas path (within-tick dedupe + cross-tick per-query LRU) without
the engine knowing anything about caching.

Registering a new backend::

    from repro.core.backends import QueryBackend, register_backend

    @register_backend("mine")
    class MyBackend(QueryBackend):
        def bound_ranks(self, rt, users, qs): ...

    eng = ReverseKRanksEngine.build(..., backend="mine")
    eng = ReverseKRanksEngine.build(..., backend="cached:mine")  # wrapped
"""
from __future__ import annotations

import importlib
import logging
from collections import OrderedDict
from typing import Callable, Dict, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import query as query_mod
from repro.core.types import DeltaCorrection, QueryResult, RankTable, \
    RankTableConfig, StoredUsers, take_user_rows
from repro.obs import registry as obs
from repro.obs import trace


class QueryBackend:
    """Base class / protocol for batched query execution.

    Subclasses implement `bound_ranks` (step 1, returning (B, n) arrays)
    and optionally override `select` / `query_batch`. `mesh` is accepted
    by every backend for a uniform constructor; only "sharded" uses it.

    Two dynamic-index hooks (see `repro.index`) have working defaults:

    * `query_batch(..., delta=)` — when a `DeltaCorrection` is passed, the
      backend must fuse it between step 1 and selection via the SHARED
      `rank_table.apply_delta_corrections`, so dense/fused/sharded cannot
      drift on a mutated index. The base implementation handles any
      backend whose `bound_ranks` returns full (B, n) arrays.
    * `build_index` — Algorithm 1 on this backend's substrate; "sharded"
      overrides it to build row-sharded end-to-end, and the maintenance
      loop's rebuilds go through the same hook as `Engine.build`.
    """

    name: str = "abstract"

    def __init__(self, mesh=None):
        self.mesh = mesh
        self._degrade_level = 0

    def degrade(self, level: int) -> None:
        """Degrade-ladder hook (repro.serve.degrade): rung `level` stays
        in effect until the next call (0 = normal serving). The base
        backend has no cheaper mode, so the default just records the
        level; backends with a latency/quality knob override (e.g. the
        pruned backend's dense fallback) and wrappers delegate inward."""
        self._degrade_level = int(level)

    def bound_ranks(self, rt: RankTable, users: jax.Array, qs: jax.Array
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """§4.3 step 1 for a (B, d) query block → (r↓, r↑, est), each (B, n)."""
        raise NotImplementedError

    def select(self, rt: RankTable, r_lo: jax.Array, r_up: jax.Array,
               est: jax.Array, *, k: int, c: float) -> QueryResult:
        """§4.3 steps 2-3 on (B, n) bounds → QueryResult with leading B axis."""
        return query_mod._select_topk_jit(r_lo, r_up, est, rt.m, k, c)

    def build_index(self, users: jax.Array, items: jax.Array,
                    cfg: RankTableConfig, key: jax.Array) -> RankTable:
        """Algorithm 1 on this backend's execution substrate."""
        from repro.core import rank_table as rt_mod
        return rt_mod.build_rank_table(users, items, cfg, key)

    def check_users_shape(self, n: int) -> None:
        """Raise if this backend cannot query a (n, d) user matrix —
        called by the engine BEFORE a mutation grows the user set, so a
        bad append fails with a clear error instead of breaking every
        subsequent query."""

    def _delta_query(self, rt: RankTable, users, qs: jax.Array,
                     *, k: int, c: float, delta: DeltaCorrection
                     ) -> QueryResult:
        """Generic delta path for (B, n)-bounds backends: step-1 bounds,
        the shared correction (needs the u·q score matrix — one extra
        (n, d) × (d, B) matmul), then selection against the live m. The
        score slack of quantized user storage rides into the correction's
        certified count ranges (`apply_delta_corrections`)."""
        from repro.core import rank_table as rt_mod
        r_lo, r_up, est = self.bound_ranks(rt, users, qs)   # (B, n)
        scores, slack = query_mod.user_scores_batch(users, qs)  # (n, B)
        r_lo, r_up, est = rt_mod.apply_delta_corrections(
            scores, r_lo.T, r_up.T, est.T, delta, slack=slack)
        return query_mod._select_topk_jit(r_lo.T, r_up.T, est.T,
                                          delta.selection_m(), k, c)

    def query_batch(self, rt: RankTable, users: jax.Array, qs: jax.Array,
                    *, k: int, c: float,
                    delta: Optional[DeltaCorrection] = None) -> QueryResult:
        if delta is not None:
            return self._delta_query(rt, users, qs, k=k, c=c, delta=delta)
        r_lo, r_up, est = self.bound_ranks(rt, users, qs)
        return self.select(rt, r_lo, r_up, est, k=k, c=c)

    def dispatch_device(self, rt: RankTable, users, qs, *, k: int, c: float,
                        delta: Optional[DeltaCorrection] = None
                        ) -> QueryResult:
        """Serving-path dispatch entry (PR 10): take a HOST (numpy) query
        block, stage it to the device in ONE transfer, and return the
        tick's QueryResult as DEVICE HANDLES with no host sync — JAX
        async dispatch means the arrays are unmaterialized futures the
        caller materializes later (`jax.device_get` on a completion
        thread, never on the dispatch thread). The base implementation
        delegates to `query_batch`, which already returns unblocked
        device arrays; backends with a donation story override to route
        through a buffer-donating compiled entry (`ElasticBackend`), so
        the tick's input buffer is recycled instead of re-allocated.

        Contract: results are BIT-IDENTICAL to `query_batch` on the same
        block — this entry changes where buffers live, never values."""
        qs = jnp.asarray(qs)            # one H2D for the whole tick
        if delta is None:
            # no delta kwarg on the static path (same compatibility
            # contract as engine.query_batch_at)
            return self.query_batch(rt, users, qs, k=k, c=c)
        return self.query_batch(rt, users, qs, k=k, c=c, delta=delta)


_REGISTRY: Dict[str, Type[QueryBackend]] = {}


def register_backend(name: str):
    """Class decorator: register a QueryBackend under `name`."""
    def deco(cls: Type[QueryBackend]) -> Type[QueryBackend]:
        # Only stamp a name the class doesn't already own directly, so
        # registering an existing class under an alias doesn't rename
        # every live instance of its first registration.
        if "name" not in cls.__dict__:
            cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


_WRAPPERS: Dict[str, Callable[..., QueryBackend]] = {}

# Wrapper prefixes resolvable by lazy import, so `get_backend("cached:…")`
# and `get_backend("elastic:…")` work without the caller importing the
# wrapper's module first (and this module avoids hard import cycles with
# them). "elastic:<inner>" is the compile-once scan-over-tiles wrapper
# (repro.core.elastic) — note the prefix alone is not a backend name; the
# inner defaults to dense ("elastic:" ≡ "elastic:dense").
_LAZY_WRAPPERS = {"cached": "repro.serve.cache",
                  "elastic": "repro.core.elastic"}


def register_wrapper(prefix: str):
    """Register `factory(inner_name, *, mesh=None) -> QueryBackend` under
    `prefix`, making `"<prefix>:<inner>"` a resolvable backend spec."""
    def deco(factory):
        _WRAPPERS[prefix] = factory
        return factory
    return deco


def available_backends() -> list[str]:
    """Concrete registered names; any of them also composes as
    `"<wrapper>:<name>"` (e.g. "cached:dense")."""
    return sorted(_REGISTRY)


def get_backend(spec, *, mesh=None) -> QueryBackend:
    """Resolve `spec`: a registered name, a `"<wrapper>:<inner>"` spec, or
    an already-built instance."""
    if isinstance(spec, QueryBackend):
        if mesh is not None:
            raise ValueError(
                "mesh= only applies when the backend is given by NAME; "
                "construct the instance with its mesh instead")
        return spec
    if isinstance(spec, str) and ":" in spec:
        prefix, _, inner = spec.partition(":")
        factory = _WRAPPERS.get(prefix)
        if factory is None and prefix in _LAZY_WRAPPERS:
            importlib.import_module(_LAZY_WRAPPERS[prefix])
            factory = _WRAPPERS.get(prefix)
        if factory is not None:
            return factory(inner, mesh=mesh)
        # unknown prefix: fall through to the unknown-backend error below
    try:
        cls = _REGISTRY[spec]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown query backend {spec!r}; available: "
            f"{available_backends()}") from None
    obj = cls(mesh=mesh)
    obj.name = spec                 # requested (possibly aliased) name
    return obj


def _stock_pipeline(backend: QueryBackend, cls: Type["QueryBackend"]) -> bool:
    """True when the instance uses `cls`'s own bound_ranks and the base
    `select` — the end-to-end fast paths are only equivalent to
    bound_ranks+select in that case; a subclass overriding either hook
    must get the composed path so its logic actually runs."""
    t = type(backend)
    return (t.select is QueryBackend.select
            and t.bound_ranks is cls.bound_ranks)


@register_backend("dense")
class DenseBackend(QueryBackend):
    """Pure-jnp batched execution (the portable default)."""

    def bound_ranks(self, rt, users, qs):
        return query_mod.bound_ranks_batch(rt, users, qs)

    def query_batch(self, rt, users, qs, *, k, c, delta=None):
        if not _stock_pipeline(self, DenseBackend):
            return super().query_batch(rt, users, qs, k=k, c=c, delta=delta)
        if delta is not None:
            # one jit region: the correction reuses the step-1 score matrix
            return query_mod.query_batch_delta(rt, users, qs, delta, k, c)
        # one jit region end-to-end (matmul + lookup + select fuse)
        return query_mod.query_batch(rt, users, qs, k, c)


@register_backend("fused")
class FusedBackend(QueryBackend):
    """Pallas fused step 1 (interpret=True on CPU; compiled on TPU)."""

    def bound_ranks(self, rt, users, qs):
        from repro.kernels import ops as kops
        return kops.bound_ranks_batched_stored(users, qs, rt)

    def query_batch(self, rt, users, qs, *, k, c, delta=None):
        if not _stock_pipeline(self, FusedBackend):
            return super().query_batch(rt, users, qs, k=k, c=c, delta=delta)
        if delta is not None:
            # the inherited delta pipeline over this backend's
            # bound_ranks IS the fused delta path: kernel step 1, the
            # shared correction (one extra XLA matmul for u·q), shared
            # selection
            return self._delta_query(rt, users, qs, k=k, c=c, delta=delta)
        from repro.kernels import ops as kops
        return kops.query_fused_batch(rt, users, qs, k, c)


@register_backend("sharded")
class ShardedBackend(QueryBackend):
    """Row-sharded mesh execution with the tree-merge top-k.

    `query_batch` gathers only (B, k·P) candidates in ONE collective (its
    QueryResult carries candidate-set bounds of shape (B, k·P), not
    (B, n) — see `core.distributed`). The delta correction runs INSIDE the
    shard_map on row-sharded correction arrays, before the per-shard
    top-k, preserving the wire budget on mutated indexes. `bound_ranks`
    falls back to the dense path: materializing full (B, n) bounds defeats
    the O(k·P) wire budget and exists for debugging/parity checks only.

    `build_index` routes through `distributed.build_sharded`, so tables
    are row-sharded END-TO-END (never built on one device and re-sharded)
    — both for `Engine.build(backend="sharded")` and for the maintenance
    loop's rebuilds, which call the same hook.
    """

    def __init__(self, mesh=None):
        from repro.core import distributed as D
        super().__init__(mesh=D.flat_mesh(
            mesh if mesh is not None else jax.devices()))
        self._fns: dict = {}

    def bound_ranks(self, rt, users, qs):
        return query_mod.bound_ranks_batch(rt, users, qs)

    def build_index(self, users, items, cfg, key):
        from repro.core import distributed as D
        nshards = self.mesh.devices.size
        if cfg.threshold_mode == "exact":
            # oracle-only mode: exact f_min/f_max needs the full item set
            # per user row, which the row-parallel build never
            # materializes — build dense (small tests only) rather than
            # silently degrading to sampled thresholds
            return super().build_index(users, items, cfg, key)
        if users.shape[0] % nshards or items.shape[0] % nshards:
            # streaming churn drifts the live item count off the mesh
            # multiple; the row-parallel build's shard_map would raise an
            # opaque divisibility error (and a maintenance-loop rebuild
            # would then fail on every retry). Fall back to the dense
            # build — the resulting table queries fine on this backend as
            # long as n itself stays shard-divisible — and say so: the
            # counter lets a deployment check its index really is sharded
            obs.get_default().counter(
                "sharded_single_device_builds_total",
                "sharded-backend builds that ran on one device because n "
                "or m does not divide the mesh size").inc()
            logging.getLogger(__name__).warning(
                "sharded build of n=%d, m=%d runs on one device: both must "
                "be multiples of the %d shards", users.shape[0],
                items.shape[0], nshards)
            return super().build_index(users, items, cfg, key)
        return D.build_sharded(users, items, cfg, key, self.mesh)

    def check_users_shape(self, n):
        nshards = self.mesh.devices.size
        if n % nshards:
            raise ValueError(
                f"sharded backend row-shards {n} users over {nshards} "
                "devices; appends must keep n divisible by the mesh size "
                "(pad the append batch or rebuild on a resized mesh)")

    def query_batch(self, rt, users, qs, *, k, c, delta=None):
        from repro.core import distributed as D
        n = users.shape[0]
        shape = None if delta is None else (delta.n_add, delta.n_del)
        # storage structure rides in the key only for bookkeeping — the
        # built fn constructs its shard_map per argument structure at
        # trace time, so one fn serves every spec of the same (k, c, n)
        key = (k, float(c), n, shape, rt.spec_kind,
               isinstance(users, StoredUsers))
        fn = self._fns.get(key)
        if fn is None:
            fn = D.make_batch_query_fn(self.mesh, k=k, n=n, c=float(c),
                                       with_delta=delta is not None)
            self._fns[key] = fn
        if delta is None:
            return fn(rt, users, qs)
        return fn(rt, users, qs, delta)


@register_backend("pruned")
class PrunedBackend(QueryBackend):
    """Two-phase block-pruned execution (PR 4, `repro.core.pruning`).

    Wraps an inner backend: phase A scores per-block summaries against the
    whole query batch and certifies which user tiles can still hold
    non-Lemma-1-pruned users; phase B runs the inner backend's step-1 math
    over the surviving tiles only, with skipped users materialized at a
    dominated sentinel so the §4.3 selection returns BIT-IDENTICAL
    indices to the full scan (see the pruning module docstring for the
    invariants). Resolves as `"pruned"` (dense inner) or
    `"pruned:<inner>"`:

      pruned:dense    gathered-row phase B, one jit region;
      pruned:fused    masked-grid Pallas kernel — skipped tiles are never
                      DMA'd (`ops.bound_ranks_batched_pruned`);
      pruned:sharded  per-shard summaries; each shard gathers its own
                      surviving tiles before the unchanged tree-merge
                      (`distributed.make_pruned_batch_query_fn`);
      other inners    generic composition over `inner.bound_ranks` on the
                      compacted sub-problem.

    Summaries are cached per index GENERATION (array identity of
    users/thresholds/table, same contract as the serving cache), so
    mutations and rebuild hot-swaps regenerate them automatically;
    `build_index` pre-warms the cache so the first query after a build
    pays no summary pass. `use_cones=False` drops the PR 6 norm-band +
    angular-cone sketches and prunes on coordinate boxes alone (an A/B
    surface for the bench; the default keeps the intersected — strictly
    tighter — envelopes). A build-time cluster reorder
    (`Engine.build(cluster_reorder=True)` / rebuild) is invisible here:
    the reordered snapshot arrays key a fresh summary generation, and n
    is unchanged, so the sharded tile-alignment contract is unaffected.

    Fallbacks (always full-scan-correct, surfaced in `stats.fallback`):
      * `max_union_frac` — when phase A keeps more than this fraction of
        blocks, the gather would re-stream nearly everything; dispatch
        the inner backend directly (adversarial-case overhead is then
        phase A alone, the ≤ 1.1× acceptance bound);
      * `delta_guard` — past this |delta|/m ratio the widened envelopes
        stop pruning; skip phase A entirely;
      * sharded tile alignment — n must split into whole blocks per
        shard, else the sharded inner runs unpruned.
    """

    _SUMMARY_CACHE = 4          # index generations kept warm

    def __init__(self, inner="dense", *, mesh=None,
                 block_size: Optional[int] = None,
                 max_union_frac: float = 0.5, delta_guard: float = 0.25,
                 use_cones: bool = True):
        super().__init__(mesh=mesh)
        from repro.core import pruning
        self._pruning = pruning
        self.inner = get_backend(inner, mesh=mesh)
        self.name = f"pruned:{self.inner.name}"
        self.block_size = int(block_size or pruning.DEFAULT_BLOCK)
        self.max_union_frac = float(max_union_frac)
        self.delta_guard = float(delta_guard)
        self.use_cones = bool(use_cones)
        self._summaries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._sharded_fns: dict = {}
        self.stats = pruning.PruneStats()   # last query_batch's accounting

    # ----------------------------------------------------------- plumbing
    def bound_ranks(self, rt, users, qs):
        """Full (B, n) bounds are a debugging surface; pruning applies to
        the end-to-end query (sentinels would surprise bound callers)."""
        return self.inner.bound_ranks(rt, users, qs)

    def build_index(self, users, items, cfg, key):
        rt = self.inner.build_index(users, items, cfg, key)
        self.summary_for(rt, users)         # pre-warm this generation
        return rt

    def check_users_shape(self, n):
        return self.inner.check_users_shape(n)

    def degrade(self, level):
        """Rung ≥ 1 disables the `max_union_frac` dense fallback: an
        adversarially non-pruning query pays the certified two-phase
        gather over its kept blocks instead of a full-scan latency spike
        (the bimodal p99 that breaks deadline SLOs under load). Bounds
        and results are unchanged — this rung has no contract cost."""
        super().degrade(level)
        self.inner.degrade(level)

    def summary_for(self, rt: RankTable, users: jax.Array):
        """The `BlockSummary` for this index generation (identity-cached;
        a mutation or rebuild swaps the arrays and lazily regenerates)."""
        key = (id(users), id(rt.thresholds), id(rt.table), self.block_size,
               self.use_cones)
        hit = self._summaries.get(key)
        if hit is not None:
            self._summaries.move_to_end(key)
            return hit[1]
        summary = self._pruning.build_block_summary(
            users, rt, block_size=self.block_size,
            with_cones=self.use_cones)
        # the value keeps the keyed arrays alive, so their id()s cannot
        # be recycled while the entry exists (cf. serve.cache weakrefs)
        self._summaries[key] = ((users, rt.thresholds, rt.table), summary)
        while len(self._summaries) > self._SUMMARY_CACHE:
            self._summaries.popitem(last=False)
        return summary

    # -------------------------------------------------------------- query
    def _full_scan(self, rt, users, qs, *, k, c, delta, why: str,
                   n_blocks: int) -> QueryResult:
        self.stats = self._pruning.PruneStats(
            n_blocks=n_blocks, kept_union=n_blocks, kept_per_query=1.0,
            fallback=why)
        if delta is None:
            return self.inner.query_batch(rt, users, qs, k=k, c=c)
        return self.inner.query_batch(rt, users, qs, k=k, c=c, delta=delta)

    def query_batch(self, rt, users, qs, *, k, c, delta=None):
        # the scheduler's tick id, when called inside its `serve.tick`
        tick = trace.current_attr("tick")
        with trace.span("prune.query", tick=tick, batch=qs.shape[0], k=k):
            res = self._query_impl(rt, users, qs, k=k, c=c, delta=delta,
                                   tick=tick)
        # publish this batch's accounting (skip rate, block and fallback
        # counters) — the live half of the §6.3 prune columns
        self.stats.publish()
        return res

    def _query_impl(self, rt, users, qs, *, k, c, delta=None, tick=None):
        P = self._pruning
        n = users.shape[0]
        bs = self.block_size
        nb = -(-n // bs)
        sharded = isinstance(self.inner, ShardedBackend)
        if sharded:
            nshards = self.inner.mesh.devices.size
            if n % (nshards * bs):
                # tiles must not straddle shard boundaries
                return self._full_scan(rt, users, qs, k=k, c=c, delta=delta,
                                       why="align", n_blocks=nb)
        if delta is not None:
            m_base = max(int(rt.m), 1)
            if (delta.n_add + delta.n_del) / m_base > self.delta_guard:
                return self._full_scan(rt, users, qs, k=k, c=c, delta=delta,
                                       why="delta-guard", n_blocks=nb)
        with trace.span("prune.phase_a", tick=tick, n_blocks=nb) as sp_a:
            summary = self.summary_for(rt, users)
            if delta is None:
                keep, _ = P.phase_a(summary, qs, k=k, block_size=bs)
            else:
                keep, _ = P.phase_a(summary, qs, k=k, block_size=bs,
                                    n_add=float(delta.n_add),
                                    n_del=float(delta.n_del),
                                    user_live=delta.user_live,
                                    with_live=True)
            with trace.span("prune.keep_sync", tick=tick):
                keep_np = np.asarray(keep)                  # host sync
            union = np.flatnonzero(keep_np.any(axis=0))
            per_q = float(keep_np.mean())
            sp_a.set(kept_union=int(union.size))
        # degrade rung ≥ 1 lifts the union cap to 1.0 — the fallback is
        # unreachable (union ≤ nb) and every query stays on the bounded
        # pruned path (see degrade())
        union_cap = (1.0 if self._degrade_level >= 1
                     else self.max_union_frac)
        if union.size > union_cap * nb:
            res = self._full_scan(rt, users, qs, k=k, c=c, delta=delta,
                                  why="dense", n_blocks=nb)
            self.stats.kept_union = int(union.size)
            self.stats.kept_per_query = per_q
            return res
        self.stats = P.PruneStats(n_blocks=nb, kept_union=int(union.size),
                                  kept_per_query=per_q)
        min_blocks = -(-k // bs)
        with trace.span("prune.phase_b", tick=tick, kept=int(union.size),
                        n_blocks=nb):
            if sharded:
                return self._sharded_query(rt, users, qs, keep_np, k=k,
                                           c=c, delta=delta,
                                           min_blocks=min_blocks)
            ids_np = P.bucket_blocks(union, n_blocks=nb,
                                     min_blocks=min_blocks)
            ids = jnp.asarray(ids_np)
            # padding tiles repeat kept ids; mark them invalid so a user
            # is never a selection candidate twice
            blk_valid = jnp.asarray(
                np.arange(ids_np.size) < max(union.size, 1))
            stock_dense = (type(self.inner) is DenseBackend
                           and _stock_pipeline(self.inner, DenseBackend))
            if stock_dense and delta is None:
                return P.pruned_query_batch(rt, users, qs, ids, blk_valid,
                                            keep, k, c, block_size=bs)
            if stock_dense:
                return P.pruned_query_batch_delta(rt, users, qs, delta,
                                                  ids, blk_valid, keep, k,
                                                  c, block_size=bs)
            # compacted step 1 on the inner backend (masked-grid kernel
            # for the stock fused path, generic gather otherwise)
            if (type(self.inner) is FusedBackend
                    and type(self.inner).bound_ranks
                    is FusedBackend.bound_ranks):
                from repro.kernels import ops as kops
                r_lo, r_up, est = kops.bound_ranks_batched_pruned_stored(
                    users, qs, rt, ids, block_n=bs)
            else:
                ridx = P.row_indices(ids, bs)
                g = jnp.minimum(ridx, n - 1)
                sub_rt = rt.take_rows(g)
                r_lo, r_up, est = self.inner.bound_ranks(
                    sub_rt, take_user_rows(users, g), qs)
            if delta is None:
                return P.finish_compacted(r_lo, r_up, est, ids, blk_valid,
                                          keep, rt.m, k, c, n=n,
                                          block_size=bs)
            return P.delta_finish_compacted(users, qs, delta, r_lo, r_up,
                                            est, ids, blk_valid, keep, k,
                                            c, n=n, block_size=bs)

    def _sharded_query(self, rt, users, qs, keep_np, *, k, c, delta,
                       min_blocks):
        from repro.core import distributed as D
        P = self._pruning
        mesh = self.inner.mesh
        nshards = mesh.devices.size
        n = users.shape[0]
        bs = self.block_size
        nb = keep_np.shape[1]
        nb_loc = nb // nshards
        union = keep_np.any(axis=0)
        per_shard = union.reshape(nshards, nb_loc)
        width = P.bucket_width(int(per_shard.sum(axis=1).max()),
                               n_blocks=nb_loc, min_blocks=min_blocks)
        ids = np.zeros((nshards, width), np.int32)
        valid = np.zeros((nshards, width), bool)
        for s in range(nshards):
            kept = np.flatnonzero(per_shard[s])
            if kept.size == 0:
                continue                    # ids stay 0, valid stays False
            reps = -(-width // kept.size)
            ids[s] = np.tile(kept, reps)[:width]
            # the duplicate tail stays invalid so repeated rows cannot
            # produce duplicate candidates in the tree-merge
            valid[s, :kept.size] = True
        shape = None if delta is None else (delta.n_add, delta.n_del)
        fkey = (k, float(c), n, width, shape)
        fn = self._sharded_fns.get(fkey)
        if fn is None:
            fn = D.make_pruned_batch_query_fn(
                mesh, k=k, n=n, c=float(c), block_size=bs,
                with_delta=delta is not None)
            self._sharded_fns[fkey] = fn
        args = (rt, users, qs, jnp.asarray(ids), jnp.asarray(valid),
                jnp.asarray(keep_np))
        if delta is None:
            return fn(*args)
        return fn(*args, delta)


@register_wrapper("pruned")
def _make_pruned(inner: str, *, mesh=None) -> PrunedBackend:
    """Registry hook: `get_backend("pruned:<inner>")` lands here."""
    return PrunedBackend(inner, mesh=mesh)
