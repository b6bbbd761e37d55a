"""Compile-once elastic serving: a scan-over-tiles query program (PR 7).

Every other backend's query program is shaped by n, the user count — so
every insert-triggered rebuild, compaction, or tenant growth that changes
n retraces and recompiles a fresh XLA program per backend (a recompile
storm on every hot-swap, exactly what a live promotion-monitoring fleet
cannot tolerate). This module restructures the phase-B scan as a
`lax.fori_loop` over FIXED-SIZE user tiles against CAPACITY-PADDED
operands, so one compiled program serves any n:

  * operands (users / rank table / delta correction) are padded host-side
    (numpy — zero per-(n, cap) XLA pad programs) to a power-of-two tile
    capacity `capacity_for(n, tile)`; growing n re-pads inside the same
    bucket without touching the compiled program, and doubles the bucket
    O(log n) times over a fleet's lifetime;
  * the traced program takes the VALID ROW COUNT as a runtime scalar: a
    fori_loop with a data-dependent trip count ⌈n_valid/tile⌉ runs the
    §4.3 step-1 tile unit (`query.tile_bounds`, or the tile-shaped Pallas
    call `kernels.ops.bound_ranks_tile` for the fused inner) and writes
    each (tile, B) result into a (cap, B) buffer; rows ≥ n_valid are
    masked to a DOMINATED SENTINEL after the loop;
  * §4.3 steps 2-3 run unchanged over the (B, cap) bounds; the sentinel
    is constructed to be invisible to them (proof below), and the two
    Lemma-1 population counters are corrected for the pad rows.

This is the haliax-`Stacked` / torch_xla-`apply_layers` idiom applied to
the user axis: compile one tile's computation, reuse it across all
homogeneous tiles. The compile key of the one program is
(tile, d, B, τ, storage spec, k, capacity bucket) — never n.

Sentinel soundness (bit-identical selection, asserted in
tests/test_elastic.py):

  static path   S = m + 2 (f32). Real bounds and estimates all lie in
  [.., m+1], so for k ≤ n every order statistic R↓_k/R↑_k over the padded
  axis equals the unpadded one. Selection keys: in the guaranteed case
  the sentinel's key is its est = m+2 > any real est; in the
  non-guaranteed case the sentinel is accepted only when c·R↓_k ≥ m+2 —
  but then EVERY real user is accepted too (r↑ ≤ m+1) with key est ≤
  m+1 < m+2; otherwise S > R↑_k always holds (R↑_k ≤ m+1), the sentinel
  is pruned with key 2·big + S, strictly above every real key of any
  class. Pad rows therefore never enter the top-k for k ≤ n, and real
  rows keep their indices and tie-breaks.

  delta path    S = +inf — the one unconditionally dominated value under
  `apply_delta_corrections`' dead-user convention (deleted users are
  forced to +inf; at equal +inf keys top_k breaks ties toward the LOWER
  index, so real dead rows still win over pads). Pad correction rows
  carry user_live=False and absent-sentinel score sets, so the
  correction arithmetic never produces non-finite intermediates.

  The two population counters do see the pads: n_accepted over-counts by
  pad·[S ≤ c·R↓_k] and n_pruned by pad·[S > R↑_k]; both are subtracted
  inside the same program. (With S = +inf the two indicators also
  reproduce the dead-row accounting of the unpadded delta program —
  see tests.)

Usage — a wrapper backend, composed by name like the others::

    eng = ReverseKRanksEngine.build(..., backend="elastic:dense")
    eng = ReverseKRanksEngine.build(..., backend="elastic:fused")

(There is no bare "elastic" spec: the wrapper needs an inner backend to
name the tile unit. "elastic:" defaults the inner to dense.) Stock dense
and fused inners get the elastic program; any other inner — sharded
(collectives are built per n inside shard_map), pruned (host-side keep
lists), or a user subclass — delegates unchanged, documented rather than
silently reinterpreted.

The tile size is the `REPRO_ELASTIC_TILE` env knob (default 256, must be
a multiple of 32 so one tile satisfies every TPU min-tile: f32 (8, 128),
bf16 (16, 128), int8 (32, 128)). On CPU the fused inner runs the Pallas
tile in interpret mode (`repro.kernels.interpret_mode`); interpret kernels
trace into the fori_loop body like any jnp code, so the compile-once
property holds in both modes.

`compiled_program_count()` is the serving-side observability hook: a
monotone count of compiled programs across the query stack's jit entry
points, sampled by the scheduler around every tick
(`TickStats.compiles`) and asserted flat across an n-sweep in tier-1.
"""
from __future__ import annotations

import functools
import os
import sys
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import importlib

from repro.core import backends as BK
from repro.core.types import DeltaCorrection, QueryResult, RankTable, \
    StoredUsers, stored_rows
from repro.kernels import ops as kops
from repro.obs import registry as obs
from repro.obs import trace

# `repro.core.__init__` re-exports the `query` FUNCTION under the package
# attribute `query`, shadowing the submodule for late importers like this
# one — resolve the module through sys.modules instead.
query_mod = importlib.import_module("repro.core.query")

# Traces of the elastic program observed this process — the tentpole's
# acceptance counter. Incremented at TRACE time (the Python body runs
# once per compile, not per call), so an n-sweep that stays inside one
# capacity bucket must leave it unchanged.
_TRACE_EVENTS = 0


def default_tile() -> int:
    """The elastic tile size: `REPRO_ELASTIC_TILE` env (default 256).

    Must be a multiple of 32 (one tile then satisfies the TPU min-tile
    of every storage dtype — f32 (8, 128), bf16 (16, 128), int8
    (32, 128)). It is also the fused kernel's block_n, which
    tests/test_tpu_compile.py compiles for a v5e."""
    raw = os.environ.get("REPRO_ELASTIC_TILE", "").strip()
    tile = int(raw) if raw else 256
    if tile < 32 or tile % 32:
        raise ValueError(
            f"REPRO_ELASTIC_TILE must be a positive multiple of 32 "
            f"(TPU min-tile alignment for f32/bf16/int8); got {tile}")
    return tile


def capacity_for(n: int, tile: int) -> int:
    """Row capacity serving n users: tile · next_pow2(⌈n/tile⌉).

    Power-of-two bucketing bounds the lifetime compile count at O(log n)
    while wasting at most half the capacity; every n in (cap/2, cap]
    shares one padded shape and hence one compiled program."""
    n_tiles = max(1, -(-int(n) // tile))
    return tile * (1 << (n_tiles - 1).bit_length())


# ------------------------------------------------------- host-side padding
def _np_pad_rows(x, cap: int, value):
    """Pad axis 0 to `cap` rows with `value`, in HOST numpy: repadding on
    a hot-swap must compile ZERO XLA programs (a jnp.pad would lower one
    tiny program per (n, cap) pair — the storm in miniature)."""
    if x is None or x.shape[0] == cap:
        return x
    a = np.asarray(jax.device_get(x))
    out = np.full((cap,) + a.shape[1:], value, dtype=a.dtype)
    out[: a.shape[0]] = a
    return jnp.asarray(out)


def _pad_users(users, cap: int):
    """Capacity-pad either user representation. Pad rows are all-zero
    with identity scale and zero slack (the quantized kernels' junk-row
    soundness values, cf. `ops._pad_quant_operands`): their scores are
    exactly 0 and every lookup on them is finite."""
    if isinstance(users, StoredUsers):
        return StoredUsers(
            rows=_np_pad_rows(users.rows, cap, 0),
            scale=_np_pad_rows(users.scale, cap, 1.0),
            row_slack=_np_pad_rows(users.row_slack, cap, 0.0))
    return _np_pad_rows(users, cap, 0.0)


def _pad_table(rt: RankTable, cap: int) -> RankTable:
    """Capacity-pad every row-aligned rank-table field. Values follow the
    kernel-padding conventions: thresholds 0 (constant row — trivially
    ascending), table 1.0 (int8: code 0 under identity affine → 0.0),
    scales 1.0, offsets/dev 0.0. Pad-row lookups are finite junk,
    overwritten by the sentinel mask."""
    pad_vals = {"thr_scale": 1.0, "thr_off": 0.0, "tab_scale": 1.0,
                "tab_off": 0.0, "thr_dev": 0.0}
    tab_pad = 0 if rt.table.dtype == jnp.int8 else 1.0
    return RankTable(
        thresholds=_np_pad_rows(rt.thresholds, cap, 0),
        table=_np_pad_rows(rt.table, cap, tab_pad), m=rt.m,
        **{f: _np_pad_rows(getattr(rt, f), cap, pad_vals[f])
           for f in RankTable._QUANT_FIELDS})


def _pad_corr(corr: DeltaCorrection, cap: int) -> DeltaCorrection:
    """Capacity-pad the delta correction. Pad rows are DEAD USERS
    (user_live=False → `apply_delta_corrections` forces their bounds to
    the +inf sentinel) with absent-sentinel score sets (−inf; −128 for
    int8 codes), so the count/shift arithmetic sees zero delta items and
    stays finite on them."""
    absent = lambda a: -128 if a.dtype == jnp.int8 else -np.inf
    return DeltaCorrection(
        add_scores=_np_pad_rows(corr.add_scores, cap,
                                absent(corr.add_scores)),
        del_scores=_np_pad_rows(corr.del_scores, cap,
                                absent(corr.del_scores)),
        user_live=_np_pad_rows(corr.user_live, cap, False),
        m_new=corr.m_new,
        add_scale=_np_pad_rows(corr.add_scale, cap, 1.0),
        add_off=_np_pad_rows(corr.add_off, cap, 0.0),
        del_scale=_np_pad_rows(corr.del_scale, cap, 1.0),
        del_off=_np_pad_rows(corr.del_off, cap, 0.0))


# ------------------------------------------------------------ tile slicing
def _dyn_rows(a, start, size: int):
    return (None if a is None
            else jax.lax.dynamic_slice_in_dim(a, start, size, axis=0))


def _slice_users(users, start, size: int):
    if isinstance(users, StoredUsers):
        return StoredUsers(rows=_dyn_rows(users.rows, start, size),
                           scale=_dyn_rows(users.scale, start, size),
                           row_slack=_dyn_rows(users.row_slack, start, size))
    return _dyn_rows(users, start, size)


def _slice_table(rt: RankTable, start, size: int) -> RankTable:
    return RankTable(
        thresholds=_dyn_rows(rt.thresholds, start, size),
        table=_dyn_rows(rt.table, start, size), m=rt.m,
        **{f: _dyn_rows(getattr(rt, f), start, size)
           for f in RankTable._QUANT_FIELDS})


def _slice_corr(corr: DeltaCorrection, start, size: int) -> DeltaCorrection:
    return DeltaCorrection(
        add_scores=_dyn_rows(corr.add_scores, start, size),
        del_scores=_dyn_rows(corr.del_scores, start, size),
        user_live=_dyn_rows(corr.user_live, start, size),
        m_new=corr.m_new,
        add_scale=_dyn_rows(corr.add_scale, start, size),
        add_off=_dyn_rows(corr.add_off, start, size),
        del_scale=_dyn_rows(corr.del_scale, start, size),
        del_off=_dyn_rows(corr.del_off, start, size))


# ------------------------------------------------------- the ONE program
_STATIC_ARGS = ("tile", "use_kernel", "m_kernel", "k")


def _elastic_query_impl(rt: RankTable, users, qs: jax.Array,
                        n_valid: jax.Array,
                        corr: Optional[DeltaCorrection], c: jax.Array, *,
                        tile: int, use_kernel: bool, m_kernel: int, k: int
                        ) -> QueryResult:
    """The compile-once program: fori_loop over tiles → sentinel mask →
    shared §4.3 selection → pad-count correction. ONE jit region — unlike
    the delta path's deliberate two-region split (`query_batch_delta`),
    the fori_loop materializes its (cap, B) carry as a while-op output
    XLA cannot re-fuse into the selection's consumers, so the region
    break buys nothing here.

    Operands are capacity-padded; `n_valid` is the runtime valid-row
    count, the ONLY place n enters — never a shape. `m_kernel` is the
    static item count the Pallas tile call needs (the kernel wrappers
    take m statically, exactly like the existing fused path); the dense
    tile unit reads the traced `rt.m` instead, so pass −1 there and item
    churn cannot retrace it.
    """
    global _TRACE_EVENTS
    _TRACE_EVENTS += 1                  # trace-time: once per compile
    cap = stored_rows(users).shape[0]
    B = qs.shape[0]
    is_delta = corr is not None
    sentinel = (jnp.float32(jnp.inf) if is_delta
                else (rt.m + 2).astype(jnp.float32))
    init = tuple(jnp.full((cap, B), sentinel, jnp.float32)
                 for _ in range(3))
    n_tiles = (n_valid + tile - 1) // tile      # data-dependent trip count

    def body(t, bufs):
        start = t * tile
        users_t = _slice_users(users, start, tile)
        rt_t = _slice_table(rt, start, tile)
        corr_t = _slice_corr(corr, start, tile) if is_delta else None
        if use_kernel:
            r_lo, r_up, est = kops.bound_ranks_tile(users_t, qs, rt_t,
                                                    m=m_kernel,
                                                    block_n=tile)
            if is_delta:
                from repro.core import rank_table as rt_mod
                scores, slack = query_mod.user_scores_batch(users_t, qs)
                r_lo, r_up, est = rt_mod.apply_delta_corrections(
                    scores, r_lo, r_up, est, corr_t, slack=slack)
        else:
            r_lo, r_up, est = query_mod.tile_bounds(rt_t, users_t, qs,
                                                    corr_t)
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(jnp.float32), start, axis=0)
            for buf, val in zip(bufs, (r_lo, r_up, est)))

    r_lo, r_up, est = jax.lax.fori_loop(0, n_tiles, body, init)
    live = jnp.arange(cap, dtype=jnp.int32)[:, None] < n_valid
    r_lo = jnp.where(live, r_lo, sentinel)
    r_up = jnp.where(live, r_up, sentinel)
    est = jnp.where(live, est, sentinel)
    m_items = corr.selection_m() if is_delta else rt.m
    res = query_mod.select_topk(r_lo.T, r_up.T, est.T, k=k, c=c,
                                m_items=m_items)
    # the two Lemma-1 population counters are the only fields that SEE
    # the pad rows; subtract exactly the pads' contribution (module doc)
    pad = (cap - n_valid).astype(jnp.int32)
    over_acc = pad * (sentinel <= c * res.R_lo_k).astype(jnp.int32)
    over_prn = pad * (sentinel > res.R_up_k).astype(jnp.int32)
    return res._replace(n_accepted=res.n_accepted - over_acc,
                        n_pruned=res.n_pruned - over_prn)


_elastic_query = jax.jit(_elastic_query_impl, static_argnames=_STATIC_ARGS)


# The SERVING entry donates the query block: the scheduler stages each
# tick's block into a fresh device buffer that is never read after
# dispatch, so on an accelerator XLA may reuse its memory for outputs.
_elastic_query_serve = jax.jit(_elastic_query_impl,
                               static_argnames=_STATIC_ARGS,
                               donate_argnames=("qs",))


def _serve_program():
    """The jit entry a serving call dispatches to, chosen at the call:
    on the CPU donation is a no-op that warns per call, so the plain
    entry serves there (same computation, zero extra compiles)."""
    if jax.default_backend() == "cpu":
        return _elastic_query
    return _elastic_query_serve


# -------------------------------------------------------- observability
def elastic_trace_count() -> int:
    """Traces of the elastic program so far (monotone; one per
    (tile, B, k, spec, capacity-bucket) combination ever served)."""
    return _TRACE_EVENTS


# Modules whose jit entry points constitute the query stack; only
# already-imported ones are counted (sys.modules — counting must never
# import pieces of the stack the process isn't using).
_COUNTED_MODULES = ("repro.core.query", "repro.core.rank_table",
                    "repro.core.pruning", "repro.kernels.ops",
                    "repro.core.elastic")


# Memoized scan of the counted modules' jit entry points. The scheduler
# brackets EVERY tick with compiled_program_count(); rebuilding the
# callable list by walking vars() of five modules per call was measurable
# at small tick sizes. The key detects both late imports (a counted
# module appearing in sys.modules) and late jit definitions (a module
# growing attributes); jit objects themselves are stable across calls.
_JIT_SCAN_KEY: Optional[tuple] = None
_JIT_SCAN: tuple = ()


def _jit_entries() -> tuple:
    global _JIT_SCAN_KEY, _JIT_SCAN
    key = tuple((name, id(mod), len(vars(mod)))
                for name in _COUNTED_MODULES
                if (mod := sys.modules.get(name)) is not None)
    if key == _JIT_SCAN_KEY:
        return _JIT_SCAN
    seen: set = set()
    entries = []
    for name in _COUNTED_MODULES:
        mod = sys.modules.get(name)
        if mod is None:
            continue
        for obj in vars(mod).values():
            size_fn = getattr(obj, "_cache_size", None)
            if callable(size_fn) and id(obj) not in seen:
                seen.add(id(obj))
                entries.append(size_fn)
    _JIT_SCAN = tuple(entries)
    _JIT_SCAN_KEY = key
    return _JIT_SCAN


def compiled_program_count() -> int:
    """Total compiled-program count across the query stack's jit caches.

    Sums `_cache_size()` over every jit-wrapped callable in the counted
    modules (deduped by identity — re-exports must not double-count; the
    module scan itself is memoized, see `_jit_entries`). Monotone in
    practice (jit caches only grow), so a DELTA across a serving interval
    is "programs compiled during it": the scheduler samples it around
    each tick (`TickStats.compiles`) and the tier-1 n-sweep asserts the
    delta is zero after the elastic warm-up. Also exported as the
    callback gauge `query_compiled_programs` (read at scrape time)."""
    total = 0
    for size_fn in _jit_entries():
        try:
            total += int(size_fn())
        except Exception:
            pass
    return total


# scrape-time callback gauge: dashboards watch the derivative — a nonzero
# slope in steady state is the recompile-storm signature
obs.get_default().gauge(
    "query_compiled_programs",
    "compiled XLA programs across the query stack's jit caches"
).set_function(compiled_program_count)


# ------------------------------------------------------------ the backend
class ElasticBackend(BK.QueryBackend):
    """Wrapper backend: compile-once elastic serving over a stock dense
    or fused inner; any other inner delegates unchanged (module doc).

    The padded-operand cache is keyed on ARRAY IDENTITY per index
    generation (same contract as `PrunedBackend._summaries` /
    `serve.cache`): snapshot generations are immutable, so identity
    equality is epoch equality, and the cached value holds strong
    references to the keyed arrays so an id() can never be recycled
    while its entry lives. A hot-swap that changes any operand repads
    host-side (numpy) and re-dispatches the SAME compiled program.
    """

    _PAD_CACHE = 4              # index generations kept padded

    def __init__(self, inner="dense", *, mesh=None,
                 tile: Optional[int] = None):
        super().__init__(mesh=mesh)
        self.inner = BK.get_backend(inner or "dense", mesh=mesh)
        self.name = f"elastic:{self.inner.name}"
        self.tile = int(tile) if tile else default_tile()
        if self.tile < 32 or self.tile % 32:
            raise ValueError(f"elastic tile must be a positive multiple "
                             f"of 32; got {self.tile}")
        if (type(self.inner) is BK.DenseBackend
                and BK._stock_pipeline(self.inner, BK.DenseBackend)):
            self._mode = "dense"
        elif (type(self.inner) is BK.FusedBackend
                and BK._stock_pipeline(self.inner, BK.FusedBackend)):
            self._mode = "fused"
        else:
            # sharded (per-n shard_map programs), pruned (host-side keep
            # lists), or subclassed hooks: delegate rather than silently
            # reinterpret — their elasticization is tracked on the ROADMAP
            self._mode = None
        self._padded: "OrderedDict[tuple, tuple]" = OrderedDict()

    # ----------------------------------------------------------- plumbing
    def bound_ranks(self, rt, users, qs):
        """Full (B, n) bounds are a debugging surface (cf. pruned/cached
        wrappers); the elastic program applies to the end-to-end query."""
        return self.inner.bound_ranks(rt, users, qs)

    def build_index(self, users, items, cfg, key):
        return self.inner.build_index(users, items, cfg, key)

    def check_users_shape(self, n):
        return self.inner.check_users_shape(n)

    def degrade(self, level):
        """Ladder levels act on the wrapped execution backend."""
        super().degrade(level)
        self.inner.degrade(level)

    def _padded_operands(self, rt, users, corr):
        n = users.shape[0]
        cap = capacity_for(n, self.tile)
        key = (id(stored_rows(users)), id(rt.thresholds), id(rt.table),
               cap)
        if corr is not None:
            key += (id(corr.add_scores), id(corr.del_scores),
                    id(corr.user_live))
        hit = self._padded.get(key)
        if hit is not None:
            self._padded.move_to_end(key)
            return hit[1]
        with trace.span("elastic.repad", n=n, cap=cap):
            value = (_pad_table(rt, cap), _pad_users(users, cap),
                     None if corr is None else _pad_corr(corr, cap))
        obs.get_default().counter(
            "elastic_repads_total",
            "host-side capacity repads (one per new index generation)"
        ).inc()
        # pin the keyed arrays: their id()s must not be recycled while
        # this entry can be returned for them
        self._padded[key] = ((users, rt, corr), value)
        while len(self._padded) > self._PAD_CACHE:
            self._padded.popitem(last=False)
        return value

    # -------------------------------------------------------------- query
    def _query_via(self, program, rt, users, qs, *, k, c, delta):
        """Shared dispatch body for `query_batch` (plain jit entry) and
        `dispatch_device` (donating serve entry): padded operands → the
        compile-once program → eager slice epilogue."""
        n = users.shape[0]
        rt_p, users_p, corr_p = self._padded_operands(rt, users, delta)
        m_kernel = int(rt.m) if self._mode == "fused" else -1
        with trace.span("elastic.dispatch", n=n, batch=qs.shape[0], k=k):
            res = program(
                rt_p, users_p, qs, jnp.asarray(n, jnp.int32), corr_p,
                jnp.float32(c), tile=self.tile,
                use_kernel=self._mode == "fused", m_kernel=m_kernel,
                k=int(k))
        if res.r_lo.shape[1] == n:
            return res
        # Restore the documented (B, n) shape of the two per-user fields.
        # Deliberately OUTSIDE the jit: an eager op-by-op slice is a
        # trivial epilogue (XLA caches it per shape in microseconds), not
        # a retrace of the query program — folding it in would key the
        # one compiled program on n and undo the whole point.
        return res._replace(r_lo=res.r_lo[:, :n], r_up=res.r_up[:, :n])

    def query_batch(self, rt, users, qs, *, k, c, delta=None):
        n = users.shape[0]
        if self._mode is None or k > n:
            # k > n: the shared selection (partition at k−1) needs k ≤ n
            # of REAL rows for the sentinel proof; hand the degenerate
            # case to the inner backend for identical error behavior
            if delta is None:
                return self.inner.query_batch(rt, users, qs, k=k, c=c)
            return self.inner.query_batch(rt, users, qs, k=k, c=c,
                                          delta=delta)
        return self._query_via(_elastic_query, rt, users, qs,
                               k=k, c=c, delta=delta)

    def dispatch_device(self, rt, users, qs, *, k, c, delta=None):
        """Serving dispatch (PR 10): one H2D for the tick's host query
        block, then the DONATING jit entry — the block's device buffer is
        tick-private (freshly staged here, never reused by the caller),
        so on accelerators XLA reclaims it for outputs. Values are
        bit-identical to `query_batch`: same compiled computation, only
        buffer residency differs (on CPU it IS the same jit entry)."""
        qs = jnp.asarray(qs)            # the tick's single H2D
        n = users.shape[0]
        if self._mode is None or k > n:
            if delta is None:
                return self.inner.dispatch_device(rt, users, qs, k=k, c=c)
            return self.inner.dispatch_device(rt, users, qs, k=k, c=c,
                                              delta=delta)
        return self._query_via(_serve_program(), rt, users, qs,
                               k=k, c=c, delta=delta)


@BK.register_wrapper("elastic")
def _make_elastic(inner: str, *, mesh=None) -> ElasticBackend:
    """Registry hook: `get_backend("elastic:<inner>")` lands here."""
    return ElasticBackend(inner, mesh=mesh)
