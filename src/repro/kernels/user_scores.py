"""Fused bound-rank kernels — the query's O(nd) hot loop (§4.3 step 1).

One pass over the user matrix produces (r↓, r↑, est) for a whole batch
of B queries:

    HBM                          VMEM (per grid step i)
    U[i·Bn : (i+1)·Bn, :] ──►    (Bn, d) user tile        ─┐
    Qᵀ                    ──►    (d, B) query block        ├─ MXU matmul
    thresholds[i·Bn:…, :] ──►    (Bn, τ) ascending grid    │  (Bn, B) scores
    table[i·Bn:…, :]      ──►    (Bn, τ) rank estimates   ─┘
                                  VPU: count-bucketize + gather + lerp
    r_lo/r_up/est[i·Bn:…] ◄──    three (Bn, B) outputs

The (n, B) score matrix never round-trips to HBM, and the dominant
n·(d + 2τ) HBM stream is read once per BATCH instead of once per query —
the table-bandwidth amortization the batched engine API exists for. The
extra cost is VPU work (B× compares on data already in VMEM). Block
sizes: Bn = block_n users/step (multiple of 8 sublanes; τ lands on
128-lane tiles after padding by ops.py). A single query is the B = 1
case (ops.bound_ranks pads it to one sublane group).

The bucketize is branch-free: idx = Σ_j I[t_j ≤ s AND j < τ_valid], which
equals searchsorted(side='right') for ascending thresholds; padded τ
columns are masked via the `tau_valid` scalar so ops.py can pad τ to a
lane multiple without changing semantics. Each query column bucketizes
and reads the table as 2-D (Bn, τp) work (`_by_query`), and the table
reads are one-hot select-sums (`_take`) — the forms Mosaic lowers for
the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.query import _est_from_grid
from repro.core.types import EPS_BF16, _I8_TRANSFORM_PAD, dot_precision, \
    round_bf16
from repro.kernels import interpret_mode

# QUANTIZED-STORAGE VARIANTS (PR 5): the same grid and the same per-tile
# structure, but the HBM operands are the storage-tier arrays — bf16
# rows, or int8 codes plus (block_n, 1) per-row scale/offset vectors that
# ride the same tile index maps. The DMA moves the quantized bytes (the
# ~2×/4× bandwidth win); dequantization is VPU work on VMEM-resident
# tiles (the "int8-input / f32-accumulate" shape: the MXU matmul runs on
# in-register f32 casts of the int8 user tile). Quantization error is
# folded into the outputs — r↓ rounds down, r↑ rounds up, mirroring the
# dense `query._lookup_bounds_{bf16,int8}` certification — so Lemma-1
# selection over kernel outputs stays sound at every spec.


def _by_query(fn, *xs):
    """Apply `fn` to each query column of the (Bn, B) arrays `xs` and
    reassemble its (Bn, 1) results into (Bn, B) arrays.

    Every bucketize and table read is then 2-D (Bn, τp) work on one
    query's score column: Mosaic lowers it, and the VMEM working set stays
    one (Bn, τp) tile — a (Bn, B, τp) broadcast does not fit the scoped
    VMEM limit at B = 16, τp = 512. B is static and small (the scheduler's
    tick width), so the loop unrolls at trace time.
    """
    shape = xs[0].shape
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    outs = None
    for j in range(shape[1]):
        cols = fn(*(x[:, j:j + 1] for x in xs))
        if outs is None:
            outs = [jnp.broadcast_to(c, shape) for c in cols]
        else:
            outs = [jnp.where(lane == j, c, o) for c, o in zip(cols, outs)]
    return outs


def _take(a, at):
    """Row-wise gather a[r, c_r] as a one-hot select-sum: `at` is the
    (Bn, τp) mask col == c, with exactly one true entry per row, so the
    sum adds one value to zeros — bit-identical to take_along_axis (which
    Mosaic cannot lower for a (Bn, 1) index into a (Bn, τp) table)."""
    return jnp.sum(jnp.where(at, a, 0.0), axis=1, keepdims=True)


def _bound_rank_batched_kernel(u_ref, qt_ref, thr_ref, tab_ref, rlo_ref,
                               rup_ref, est_ref, *, m: int, tau_valid: int,
                               precision):
    """All B queries against one VMEM-resident user/threshold/table tile
    (see module docstring)."""
    u = u_ref[...].astype(jnp.float32)                    # (Bn, d)
    qt = qt_ref[...].astype(jnp.float32)                  # (d, B)
    thr = thr_ref[...]                                    # (Bn, τp)
    tab = tab_ref[...]                                    # (Bn, τp)
    taup = thr.shape[1]

    score = jax.lax.dot_general(
        u, qt, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)               # (Bn, B) one matmul

    col = jax.lax.broadcasted_iota(jnp.int32, thr.shape, 1)
    valid = col < tau_valid                               # (Bn, τp)

    def lookup(s):                                        # s: (Bn, 1)
        # every query column bucketizes against the SAME resident tile
        idx = jnp.sum(((thr <= s) & valid).astype(jnp.int32), axis=1,
                      keepdims=True)                      # ∈ [0, τ]
        at_up = col == jnp.clip(idx - 1, 0, taup - 1)
        at_lo = col == jnp.clip(idx, 0, tau_valid - 1)
        return (idx, _take(tab, at_up), _take(tab, at_lo),
                _take(thr, at_up), _take(thr, at_lo))

    idx, t_up, t_lo, lo_thr, hi_thr = _by_query(lookup, score)
    r_up = jnp.where(idx == 0, float(m + 1), t_up)
    r_lo = jnp.where(idx == tau_valid, 1.0, t_lo)

    span = jnp.maximum(hi_thr - lo_thr, 1e-12)
    frac = jnp.clip((score - lo_thr) / span, 0.0, 1.0)
    interior = (idx > 0) & (idx < tau_valid)
    est_in = r_up + (r_lo - r_up) * frac
    # margin-decayed out-of-range estimate (matches ref_bound_ranks)
    t_lo_edge = thr[:, :1]                                # (Bn, 1)
    t_hi_edge = thr[:, tau_valid - 1:tau_valid]
    rng = jnp.maximum(t_hi_edge - t_lo_edge, 1e-12)
    m_above = jnp.maximum(score - t_hi_edge, 0.0) / rng
    m_below = jnp.maximum(t_lo_edge - score, 0.0) / rng
    est_above = 1.0 + (r_up - 1.0) / (1.0 + tau_valid * m_above)
    est_below = float(m + 1) - (float(m + 1) - r_lo) * jnp.exp(
        -tau_valid * m_below)
    est = jnp.where(interior, est_in,
                    jnp.where(idx == tau_valid, est_above, est_below))

    rlo_ref[...] = r_lo
    rup_ref[...] = r_up
    # sub-unit margin tie-break (matches ref_bound_ranks)
    est_ref[...] = jnp.clip(est, r_lo, r_up) - 0.5 * m_above / (1.0 + m_above)


def bound_ranks_batched_masked_kernel_call(
        users: jax.Array, qt: jax.Array, thresholds: jax.Array,
        table: jax.Array, block_ids: jax.Array, *, m: int, tau_valid: int,
        block_n: int = 256
        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Masked-grid twin of `bound_ranks_batched_kernel_call` (PR 4): the
    grid runs over the KEPT block list instead of every user tile.

    `block_ids` (nk,) int32 selects which user/threshold/table tiles each
    grid step loads — the tile index maps read it as a SCALAR-PREFETCH
    operand (`pltpu.PrefetchScalarGridSpec`), so the DMA engine fetches
    exactly the surviving tiles and the n·(d + 2τ) HBM stream shrinks to
    the kept fraction. Outputs are COMPACTED: grid step i writes tile i
    of three (nk·block_n, B) arrays (the caller scatters them back to
    user coordinates — writing through the same index map would leave
    skipped tiles uninitialized).

    Per-tile math is `_bound_rank_batched_kernel` verbatim — a kept
    tile's (block_n, d) × (d, B) matmul sees the identical operand tile
    as the full scan, so compacted results are bit-identical to the
    corresponding rows of the unpruned kernel.
    """
    n, d = users.shape
    taup = thresholds.shape[1]
    B = qt.shape[1]
    nk = block_ids.shape[0]
    kern = functools.partial(_bound_rank_batched_kernel, m=m,
                             tau_valid=tau_valid,
                             precision=dot_precision())

    def tile(i, ids):
        return (ids[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((block_n, d), tile),               # U tile (gathered)
            pl.BlockSpec((d, B), lambda i, ids: (0, 0)),    # Qᵀ (replicated)
            pl.BlockSpec((block_n, taup), tile),
            pl.BlockSpec((block_n, taup), tile),
        ],
        out_specs=[pl.BlockSpec((block_n, B), lambda i, ids: (i, 0))] * 3,
    )

    def wrapped(ids_ref, u_ref, qt_ref, thr_ref, tab_ref, rlo_ref, rup_ref,
                est_ref):
        # the prefetched id array steers the index maps only; the tile
        # body is the stock batched kernel
        kern(u_ref, qt_ref, thr_ref, tab_ref, rlo_ref, rup_ref, est_ref)

    out_shape = [jax.ShapeDtypeStruct((nk * block_n, B), jnp.float32)] * 3
    return pl.pallas_call(
        wrapped,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(block_ids, users, qt, thresholds, table)


def bound_ranks_batched_kernel_call(users: jax.Array, qt: jax.Array,
                                    thresholds: jax.Array, table: jax.Array,
                                    *, m: int, tau_valid: int,
                                    block_n: int = 256
                                    ) -> tuple[jax.Array, jax.Array,
                                               jax.Array]:
    """Raw batched pallas_call; inputs pre-padded (see ops.bound_ranks_batched).

    users (n, d) [n % block_n == 0], qt (d, B) [B a sublane multiple],
    thresholds/table (n, τp) f32. Returns three (n, B) float32 arrays.
    """
    n, d = users.shape
    taup = thresholds.shape[1]
    B = qt.shape[1]
    nb = n // block_n
    kern = functools.partial(_bound_rank_batched_kernel, m=m,
                             tau_valid=tau_valid,
                             precision=dot_precision())
    out_shape = [jax.ShapeDtypeStruct((n, B), jnp.float32)] * 3
    out_spec = pl.BlockSpec((block_n, B), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # U tile
            pl.BlockSpec((d, B), lambda i: (0, 0)),         # Qᵀ (replicated)
            pl.BlockSpec((block_n, taup), lambda i: (i, 0)),
            pl.BlockSpec((block_n, taup), lambda i: (i, 0)),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(users, qt, thresholds, table)


def _est_tail(score, idx_hi, thr_up, thr_lo, edge_lo, edge_hi, r_lo, r_up,
              tau_valid: int, m: int):
    """§4.3-step-3 estimate on dequantized f32 grid values — THE shared
    implementation (`query._est_from_grid`); kernels call it on
    VMEM-resident tiles so the dense and fused quantized paths cannot
    drift on the interpolation/margin-decay/tie-break math."""
    return _est_from_grid(score, idx_hi, thr_up, thr_lo, edge_lo, edge_hi,
                          r_lo, r_up, tau_valid, float(m + 1))


def _bound_rank_batched_bf16_kernel(u_ref, uslack_ref, qt_ref, thr_ref,
                                    tab_ref, rlo_ref, rup_ref, est_ref, *,
                                    m: int, tau_valid: int, precision):
    """bf16-storage twin of `_bound_rank_batched_kernel`.

    Certification mirrors `query._lookup_bounds_bf16`: the score interval
    [s−δ, s+δ] (δ = per-row slack · ‖q‖₁, covering the bf16 user rows) is
    cast to bf16 — the cast is monotone, so a two-sided count brackets the
    true bucketize index — and table reads widen by EPS_BF16 in the
    certified direction. All compares are VPU work on the VMEM-resident
    bf16 tile; HBM moved only bf16 bytes.
    """
    u = u_ref[...].astype(jnp.float32)                    # (Bn, d) ← bf16
    qt = qt_ref[...].astype(jnp.float32)                  # (d, B)
    thr = thr_ref[...].astype(jnp.float32)                # (Bn, τp) ← bf16
    tab = tab_ref[...].astype(jnp.float32)                # (Bn, τp) ← bf16
    taup = thr.shape[1]
    score = jax.lax.dot_general(
        u, qt, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)               # (Bn, B)
    slack = uslack_ref[...] * jnp.sum(jnp.abs(qt), axis=0)[None, :]
    # the interval ends round to bf16 and compare in f32: bf16 → f32 is
    # exact, so this is the bf16 compare bit for bit
    s_hi = round_bf16(score + slack)
    s_lo = round_bf16(score - slack)

    col = jax.lax.broadcasted_iota(jnp.int32, thr.shape, 1)
    valid = col < tau_valid

    def lookup(hi, lo):                                   # (Bn, 1) each
        count = lambda hit: jnp.sum((hit & valid).astype(jnp.int32), axis=1,
                                    keepdims=True)
        idx_hi = count(thr <= hi)                         # ≥ idx*
        idx_lo = count(thr < lo)                          # ≤ idx*
        at_lo = col == jnp.clip(idx_hi, 0, tau_valid - 1)
        return (idx_hi, idx_lo,
                _take(tab, col == jnp.clip(idx_lo - 1, 0, taup - 1)),
                _take(tab, at_lo),
                _take(thr, col == jnp.clip(idx_hi - 1, 0, taup - 1)),
                _take(thr, at_lo))

    idx_hi, idx_lo, t_up, t_lo, thr_up, thr_lo = _by_query(lookup, s_hi,
                                                           s_lo)
    r_up = jnp.where(idx_lo == 0, float(m + 1), t_up * (1.0 + EPS_BF16))
    r_lo = jnp.where(idx_hi == tau_valid, 1.0, t_lo * (1.0 - EPS_BF16))
    edge_lo = thr[:, :1]
    edge_hi = thr[:, tau_valid - 1:tau_valid]
    rlo_ref[...] = r_lo
    rup_ref[...] = r_up
    est_ref[...] = _est_tail(score, idx_hi, thr_up, thr_lo, edge_lo,
                             edge_hi, r_lo, r_up, tau_valid, m)


def _bound_rank_batched_int8_kernel(u_ref, uscale_ref, uslack_ref, qt_ref,
                                    thr_sc_ref, thr_off_ref, thr_dev_ref,
                                    tab_ref, tab_sc_ref, tab_off_ref,
                                    rlo_ref, rup_ref, est_ref, *, m: int,
                                    tau_valid: int, precision):
    """int8-storage twin of `_bound_rank_batched_kernel` — int8 inputs,
    f32 accumulate, CLOSED-FORM bucketize.

    The user tile is cast in-register and scaled per row; the bucketize
    is the uniform-grid closed form of `query._lookup_bounds_int8`
    (thresholds are an affine grid in code units within the certified
    per-row `thr_dev`), so the threshold matrix is NEVER DMA'd — the HBM
    stream per tile is the int8 user rows + int8 table codes + five
    (block_n, 1) f32 vectors, the ~4× bandwidth cut on the scan. Table
    codes dequantize per row and widen by (½ + pad)·scale in the
    certified direction.
    """
    u = u_ref[...].astype(jnp.float32)                    # (Bn, d) ← int8
    qt = qt_ref[...].astype(jnp.float32)                  # (d, B)
    score = jax.lax.dot_general(
        u, qt, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32) * uscale_ref[...]
    slack = uslack_ref[...] * jnp.sum(jnp.abs(qt), axis=0)[None, :]

    sc_t = thr_sc_ref[...]                                # (Bn, 1)
    off_t = thr_off_ref[...]
    s_n = (score - off_t) / sc_t                          # (Bn, B) in codes
    d_n = slack / sc_t
    dev = thr_dev_ref[...] + 20.0 * _I8_TRANSFORM_PAD
    delta = 254.0 / (tau_valid - 1)
    count = lambda v: jnp.clip(
        jnp.floor((v + 127.0) / delta), -1.0, float(tau_valid)
    ).astype(jnp.int32) + 1
    idx_hi = jnp.clip(count(s_n + d_n + dev), 0, tau_valid)   # ≥ idx*
    idx_lo = jnp.clip(count(s_n - d_n - dev), 0, tau_valid)   # ≤ idx*

    tab_f = tab_ref[...].astype(jnp.float32)
    taup = tab_f.shape[1]
    sc_b = tab_sc_ref[...]
    off_b = tab_off_ref[...]
    widen = (0.5 + _I8_TRANSFORM_PAD) * sc_b
    up_col = jnp.clip(idx_lo - 1, 0, taup - 1)
    lo_col = jnp.clip(idx_hi, 0, tau_valid - 1)
    col = jax.lax.broadcasted_iota(jnp.int32, tab_f.shape, 1)
    c_up, c_lo = _by_query(
        lambda up, lo: (_take(tab_f, col == up), _take(tab_f, col == lo)),
        up_col, lo_col)
    r_up = jnp.where(idx_lo == 0, float(m + 1), c_up * sc_b + off_b + widen)
    r_lo = jnp.where(idx_hi == tau_valid, 1.0, c_lo * sc_b + off_b - widen)

    grid_at = lambda c: ((c.astype(jnp.float32) * delta - 127.0) * sc_t
                         + off_t)
    thr_up = grid_at(jnp.clip(idx_hi - 1, 0, taup - 1))
    thr_lo = grid_at(lo_col)
    edge_lo = -127.0 * sc_t + off_t
    edge_hi = 127.0 * sc_t + off_t
    rlo_ref[...] = r_lo
    rup_ref[...] = r_up
    est_ref[...] = _est_tail(score, idx_hi, thr_up, thr_lo, edge_lo,
                             edge_hi, r_lo, r_up, tau_valid, m)


def _quant_kernel_and_operands(kind: str, users, uscale, uslack, qt,
                               thresholds, table, thr_sc, thr_off,
                               thr_dev, tab_sc, tab_off, *, m: int,
                               tau_valid: int):
    """(kernel, operands, per-operand block factories) for a storage kind.

    Each factory maps (block_n, d, taup, B) → the operand's block shape;
    vector operands are (block_n, 1) tiles riding the same row index map.
    Shared by the full-grid and the masked-grid (pruned) callers. The
    int8 kernel takes NO threshold operand (closed-form bucketize).
    """
    precision = dot_precision()
    if kind == "bf16":
        kern = functools.partial(_bound_rank_batched_bf16_kernel, m=m,
                                 tau_valid=tau_valid, precision=precision)
        ops = (users, uslack, qt, thresholds, table)
        shapes = (lambda b, d, t, B: (b, d), lambda b, d, t, B: (b, 1),
                  "q", lambda b, d, t, B: (b, t), lambda b, d, t, B: (b, t))
        return kern, ops, shapes
    kern = functools.partial(_bound_rank_batched_int8_kernel, m=m,
                             tau_valid=tau_valid, precision=precision)
    ops = (users, uscale, uslack, qt, thr_sc, thr_off, thr_dev, table,
           tab_sc, tab_off)
    vec = lambda b, d, t, B: (b, 1)
    shapes = (lambda b, d, t, B: (b, d), vec, vec, "q", vec, vec, vec,
              lambda b, d, t, B: (b, t), vec, vec)
    return kern, ops, shapes


def bound_ranks_batched_quant_kernel_call(
        kind: str, users, uscale, uslack, qt, thresholds, table, thr_sc,
        thr_off, thr_dev, tab_sc, tab_off, *, m: int, tau_valid: int,
        block_n: int = 256
        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw quantized-storage batched pallas_call (inputs pre-padded, see
    ops._bound_ranks_batched_stored_impl). Returns three (n, B) f32."""
    n, d = users.shape
    taup = table.shape[1]
    B = qt.shape[1]
    nb = n // block_n
    kern, ops, shapes = _quant_kernel_and_operands(
        kind, users, uscale, uslack, qt, thresholds, table, thr_sc,
        thr_off, thr_dev, tab_sc, tab_off, m=m, tau_valid=tau_valid)
    in_specs = [
        pl.BlockSpec((d, B), lambda i: (0, 0)) if s == "q"
        else pl.BlockSpec(s(block_n, d, taup, B), lambda i: (i, 0))
        for s in shapes]
    out_spec = pl.BlockSpec((block_n, B), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((n, B), jnp.float32)] * 3
    return pl.pallas_call(
        kern, grid=(nb,), in_specs=in_specs,
        out_specs=[out_spec] * 3, out_shape=out_shape,
        interpret=interpret_mode())(*ops)


def bound_ranks_batched_quant_masked_kernel_call(
        kind: str, users, uscale, uslack, qt, thresholds, table, thr_sc,
        thr_off, thr_dev, tab_sc, tab_off, block_ids: jax.Array, *, m: int,
        tau_valid: int, block_n: int = 256
        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Masked-grid (pruned) twin of the quantized batched call: the grid
    runs only over the kept tiles named by the scalar-prefetch
    `block_ids`, exactly like `bound_ranks_batched_masked_kernel_call` —
    the (block_n, 1) scale/offset/slack vectors ride the same gathered
    tile index map as the rows they describe. Outputs are COMPACTED
    (nk·block_n, B) arrays in block-list order."""
    n, d = users.shape
    taup = table.shape[1]
    B = qt.shape[1]
    nk = block_ids.shape[0]
    kern, ops, shapes = _quant_kernel_and_operands(
        kind, users, uscale, uslack, qt, thresholds, table, thr_sc,
        thr_off, thr_dev, tab_sc, tab_off, m=m, tau_valid=tau_valid)

    def tile(i, ids):
        return (ids[i], 0)

    in_specs = [
        pl.BlockSpec((d, B), lambda i, ids: (0, 0)) if s == "q"
        else pl.BlockSpec(s(block_n, d, taup, B), tile)
        for s in shapes]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_n, B), lambda i, ids: (i, 0))] * 3,
    )

    def wrapped(ids_ref, *refs):
        kern(*refs)

    out_shape = [jax.ShapeDtypeStruct((nk * block_n, B), jnp.float32)] * 3
    return pl.pallas_call(
        wrapped, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret_mode())(block_ids, *ops)
