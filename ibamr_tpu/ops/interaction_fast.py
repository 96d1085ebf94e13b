"""MXU-formulated spread/interpolate: bucketed one-hot matmul kernels.

Reference parity: same operations as :mod:`ibamr_tpu.ops.interaction`
(``LEInteractor::spread/interpolate``, T2 — the north-star hot path) —
bitwise-equivalent math, radically different schedule.

The problem with the direct formulation: XLA lowers the 64-point-per-
marker scatter-add serially on TPU (~230 ms for 1e5 markers at 256^3).
TPU-first redesign (SURVEY.md §7.3 hard-part #1): turn the scatter into
DENSE MATMULS so the MXU does it:

1. **Bucket** markers by the (x, y) tile containing their stencil origin
   (one argsort + one scatter of N elements — cheap); fixed capacity
   ``cap`` per tile (static shapes), overflow handled exactly by a
   masked fallback to the scatter path under ``lax.cond``.
2. **Dense per-axis weights.** For each marker evaluate the delta
   kernel at ALL 13 = T+5 x-offsets of its tile (and 13 y-offsets) —
   compact support makes everything outside the true 4-point stencil
   exactly zero — and at all Nz wrapped z-offsets. No index arithmetic
   survives into the hot loop.
3. **Tensor-product accumulation as matmul.** Per tile b:
       spread:  T[b, xy, z] = sum_m (Wx (x) Wy * F)[b, m, xy] Wz[b, m, z]
       interp:  U[b, m] = sum_xy A[b, m, xy] sum_z T[b, xy, z] Wz[b, m, z]
   — batched (169, cap) x (cap, Nz) contractions that run on the MXU at
   TFLOP rates instead of serialized scatter updates.
4. **Overlap-add** the (13, 13, Nz) tiles into the periodic grid with
   core/spill reshapes + rolls (pure data movement).

The weights are the same ``delta.get_kernel`` functions, so spread and
interp remain exact adjoints of each other and agree with the reference
formulation to floating-point roundoff (enforced by tests).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ibamr_tpu import obs
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.delta import Kernel, get_kernel
from ibamr_tpu.ops.interaction import _centering_offsets

Vel = Tuple[jnp.ndarray, ...]

# Debug-mode enforcement of the compact_overflow pad convention
# (ADVICE r5 item 4): pad slots of the compact overflow list carry the
# REAL marker index order[N-1] with weight 0, so correctness requires
# every consumer to weight contributions by ``o_w`` — a 0 weight makes
# the pad entry inert unless the aliased marker's value is non-finite
# (0 * inf = nan) or a future engine family forgets the weighting.
# With the flag on (env IBAMR_TPU_DEBUG_OVERFLOW=1, or set
# ``debug_overflow_pad(True)``), both consumers re-derive their compact
# contribution with pad entries hard-masked and assert bitwise
# agreement at runtime via jax.debug.callback.
import os as _os

_DEBUG_OVERFLOW_PAD = bool(int(_os.environ.get(
    "IBAMR_TPU_DEBUG_OVERFLOW", "0")))


def debug_overflow_pad(enabled: bool) -> bool:
    """Toggle the pad-inertness debug check; returns the previous
    value. Takes effect at TRACE time — flip it before jitting."""
    global _DEBUG_OVERFLOW_PAD
    prev, _DEBUG_OVERFLOW_PAD = _DEBUG_OVERFLOW_PAD, bool(enabled)
    return prev


def _check_pad_inert(tag: str, with_pads: jnp.ndarray,
                     pads_masked: jnp.ndarray) -> None:
    def _host_check(a, b):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise FloatingPointError(
                f"compact_overflow pad convention violated in {tag}: "
                f"o_w == 0 pad entries contributed to the result "
                f"(non-finite aliased marker value, or a consumer "
                f"not weighting by o_w)")
    jax.debug.callback(_host_check, with_pads, pads_masked)


class BucketGeometry(NamedTuple):
    """Static bucketing configuration (python ints -> one compilation)."""
    tile: Tuple[int, ...]     # tile extent per blocked axis (all but last)
    nblk: Tuple[int, ...]     # number of tiles per blocked axis
    cap: int                  # marker capacity per tile
    support: int              # delta support s
    width: Tuple[int, ...]    # tile + s + 1 per blocked axis


def make_geometry(grid: StaggeredGrid, kernel: Kernel = "IB_4",
                  tile: int = 8, cap: int = 256) -> BucketGeometry:
    support, _ = get_kernel(kernel)
    blocked = grid.n[:-1]
    if tile < support + 1:
        # the spill segment (support+1 wide) must fit inside one tile,
        # or _overlap_add would silently drop it
        raise ValueError(
            f"tile {tile} must be >= support+1 = {support + 1}")
    for n in blocked:
        if n % tile != 0:
            raise ValueError(f"grid extent {n} not divisible by tile {tile}")
        if n < tile + support + 1:
            # footprint wider than the axis: the wrapped footprint would
            # overlap itself and double-count
            raise ValueError(
                f"grid extent {n} too small for tile {tile} + "
                f"support {support} + 1")
    return BucketGeometry(
        tile=tuple(tile for _ in blocked),
        nblk=tuple(n // tile for n in blocked),
        cap=int(cap),
        support=int(support),
        width=tuple(tile + support + 1 for _ in blocked))


def suggest_cap(grid: StaggeredGrid, X, kernel: Kernel = "IB_4",
                tile: int = 8, slack: float = 1.5) -> int:
    """Host-side capacity heuristic from a concrete marker distribution:
    1.5x the max tile occupancy, rounded up to a multiple of 8."""
    Xn = np.asarray(X)
    support, _ = get_kernel(kernel)
    bids = _block_ids_np(grid, Xn, support, tile)
    counts = np.bincount(bids, minlength=int(np.prod(
        [n // tile for n in grid.n[:-1]])))
    cap = int(math.ceil(max(1, counts.max()) * slack / 8.0) * 8)
    return cap


def _block_ids_np(grid, Xn, support, tile):
    dim = grid.dim
    bid = np.zeros(len(Xn), dtype=np.int64)
    for d in range(dim - 1):
        xi = (Xn[:, d] - grid.x_lo[d]) / grid.dx[d] - 0.5
        j0 = np.floor(xi - 0.5 * support).astype(np.int64) + 1
        b = np.mod(j0, grid.n[d]) // tile
        bid = bid * (grid.n[d] // tile) + b
    return bid


class Buckets(NamedTuple):
    """Per-call bucketed marker layout (all shapes static)."""
    Xb: jnp.ndarray         # (B, cap, dim) positions (junk in empty slots)
    wb: jnp.ndarray         # (B, cap) weights incl. 0 padding
    slot_of_marker: jnp.ndarray   # (N,) flat slot index or B*cap (dropped)
    w_overflow: jnp.ndarray       # (N,) weights of dropped markers
    o_idx: jnp.ndarray      # (ocap,) original indices of overflow markers
    o_w: jnp.ndarray        # (ocap,) their weights (0 in pad slots)
    any_overflow: jnp.ndarray     # () bool
    exceeded: jnp.ndarray   # () bool: overflow count > ocap (rare)
    x0: Tuple[jnp.ndarray, ...]   # per blocked axis: (B,) tile origin cell


def compact_overflow(order: jnp.ndarray, keep: jnp.ndarray,
                     slot_sorted: jnp.ndarray, weights: jnp.ndarray,
                     N: int, overflow_cap: int):
    """Shared overflow machinery for every bucketed/packed layout (one
    definition so the pad-slot conventions the downstream fallbacks
    rely on cannot diverge between engine families): the per-ORIGINAL-
    marker slot / overflow-weight write-back (``order`` is a
    permutation -> unique-indices scatters) and the compact overflow
    list via sized nonzero (positions come out in the same increasing
    order a stable argsort produced; pad entries carry weight 0).
    Returns (slot_of_marker, w_overflow, o_idx, o_w, n_over,
    exceeded)."""
    slot_of_marker = jnp.zeros((N,), dtype=jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32), unique_indices=True)
    w_overflow = jnp.zeros((N,), dtype=weights.dtype).at[order].set(
        jnp.where(keep, 0.0, weights[order]), unique_indices=True)
    o_pos = jnp.nonzero(~keep, size=overflow_cap, fill_value=N)[0]
    o_valid = o_pos < N
    o_pos_c = jnp.minimum(o_pos, N - 1)
    o_idx = order[o_pos_c].astype(jnp.int32)
    o_w = jnp.where(o_valid, weights[order[o_pos_c]], 0.0)
    n_over = N - jnp.sum(keep)
    return (slot_of_marker, w_overflow, o_idx, o_w, n_over,
            n_over > overflow_cap)


def bucket_markers(geom: BucketGeometry, grid: StaggeredGrid,
                   X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   overflow_cap: Optional[int] = None) -> Buckets:
    N, dim = X.shape
    if weights is None:
        weights = jnp.ones((N,), dtype=X.dtype)
    if overflow_cap is None:
        overflow_cap = min(N, max(2048, 1 << int(math.ceil(
            math.log2(max(N // 8, 1))))))
    s = geom.support
    # block id per marker from the cell-centered stencil origin
    bid = jnp.zeros((N,), dtype=jnp.int32)
    for d in range(dim - 1):
        xi = (X[:, d] - grid.x_lo[d]) / grid.dx[d] - 0.5
        j0 = jnp.floor(xi - 0.5 * s).astype(jnp.int32) + 1
        b = jnp.mod(j0, grid.n[d]) // geom.tile[d]
        bid = bid * geom.nblk[d] + b
    B = int(np.prod(geom.nblk))
    cap = geom.cap

    order = jnp.argsort(bid)
    bid_s = bid[order]
    edges = jnp.searchsorted(bid_s,
                             jnp.arange(B + 1, dtype=bid_s.dtype))
    start, counts = edges[:-1], jnp.diff(edges).astype(jnp.int32)
    rank = jnp.arange(N, dtype=jnp.int32) - start[bid_s].astype(jnp.int32)
    keep = rank < cap
    slot_sorted = jnp.where(keep, bid_s * cap + rank, B * cap)

    # slot -> sorted-marker position as pure GATHERS (TPU scatter over
    # 1e5 indices serializes; gather of the same layout does not —
    # bitwise-identical pool to the old scatter construction)
    slot_b = jnp.arange(B * cap, dtype=jnp.int32) // cap
    slot_r = jnp.arange(B * cap, dtype=jnp.int32) % cap
    src = jnp.where(slot_r < counts[slot_b],
                    start[slot_b].astype(jnp.int32) + slot_r, N)
    Xb = jnp.take(X[order], src, axis=0, mode="fill",
                  fill_value=0).reshape(B, cap, dim)
    wb = jnp.take(weights[order], src, mode="fill",
                  fill_value=0).reshape(B, cap)

    (slot_of_marker, w_overflow, o_idx, o_w, n_over,
     exceeded) = compact_overflow(order, keep, slot_sorted, weights, N,
                                  overflow_cap)

    # tile origins per blocked axis, broadcast over the flat block index
    x0 = []
    for d in range(dim - 1):
        ids = jnp.arange(B, dtype=jnp.int32)
        for a in range(dim - 1 - 1, d, -1):
            ids = ids // geom.nblk[a]
        x0.append((ids % geom.nblk[d]) * geom.tile[d])
    return Buckets(Xb=Xb, wb=wb, slot_of_marker=slot_of_marker,
                   w_overflow=w_overflow, o_idx=o_idx, o_w=o_w,
                   any_overflow=n_over > 0, exceeded=exceeded,
                   x0=tuple(x0))


# -- dense per-axis weights --------------------------------------------------

def _phi_safe(phi, support):
    half = 0.5 * support

    def f(t):
        inside = jnp.abs(t) < half
        return jnp.where(inside, phi(jnp.clip(t, -half, half)), 0.0)
    return f


def _blocked_axis_weights(geom, grid, b: Buckets, d: int, off: float, phi):
    """(B, cap, width) weights over the tile footprint of blocked axis d
    (footprint starts one cell below the tile origin)."""
    n = grid.n[d]
    xi = (b.Xb[..., d] - grid.x_lo[d]) / grid.dx[d] - off   # (B, cap)
    l = jnp.arange(geom.width[d], dtype=xi.dtype)
    base = b.x0[d].astype(xi.dtype)[:, None, None] - 1.0
    t = xi[..., None] - (base + l)
    # markers whose wrapped stencil landed them in an edge tile sit a
    # full period away from the footprint coordinates
    t = jnp.mod(t + 0.5 * n, float(n)) - 0.5 * n
    return phi(t)


def _full_axis_weights(grid, b: Buckets, d: int, off: float, phi):
    """(B, cap, n_d) wrapped weights over the full (periodic) last axis."""
    n = grid.n[d]
    xi = (b.Xb[..., d] - grid.x_lo[d]) / grid.dx[d] - off
    k = jnp.arange(n, dtype=xi.dtype)
    t = xi[..., None] - k
    t = jnp.mod(t + 0.5 * n, float(n)) - 0.5 * n
    return phi(t)


def _tile_weights(geom, grid, b: Buckets, centering, kernel):
    support, phi0 = get_kernel(kernel)
    phi = _phi_safe(phi0, support)
    offs = _centering_offsets(grid, centering)
    dim = grid.dim
    Ws = [_blocked_axis_weights(geom, grid, b, d, offs[d], phi)
          for d in range(dim - 1)]
    Wlast = _full_axis_weights(grid, b, dim - 1, offs[dim - 1], phi)
    # combine blocked axes into one footprint axis p
    A = Ws[0]
    for W in Ws[1:]:
        A = A[..., :, None] * W[..., None, :]
        A = A.reshape(A.shape[0], A.shape[1], -1)
    return A, Wlast       # (B, cap, P), (B, cap, n_last)


# -- overlap-add / tile extraction -------------------------------------------

def _overlap_add(geom, grid, T: jnp.ndarray) -> jnp.ndarray:
    """Accumulate tiles T (B, w0[, w1], n_last) into the periodic grid:
    split each blocked axis into core [0, tile) and spill [tile, width)
    segments, reshape each combination onto the grid, roll into place."""
    dim = grid.dim
    nb = geom.nblk
    tl = geom.tile
    wd = geom.width
    n_last = grid.n[dim - 1]
    B = T.shape[0]
    T = T.reshape(tuple(nb) + tuple(wd) + (n_last,))
    nblocked = dim - 1
    out = jnp.zeros(grid.n, dtype=T.dtype)
    for mask in range(2 ** nblocked):
        seg = T
        shift = []
        ok = True
        for d in range(nblocked):
            spill = (mask >> d) & 1
            lo, hi = (0, tl[d]) if not spill else (tl[d], wd[d])
            sl = [slice(None)] * seg.ndim
            sl[nblocked + d] = slice(lo, hi)
            seg = seg[tuple(sl)]
            # pad segment length up to tile (spill is s+1 <= tile)
            pad = tl[d] - (hi - lo)
            if pad < 0:
                ok = False
                break
            if pad:
                pw = [(0, 0)] * seg.ndim
                pw[nblocked + d] = (0, pad)
                seg = jnp.pad(seg, pw)
            # core starts at x0 - 1; spill starts at x0 + tile - 1
            shift.append(-1 if not spill else tl[d] - 1)
        if not ok:
            continue
        # interleave (nb, tile) axis pairs -> grid layout
        perm = []
        for d in range(nblocked):
            perm += [d, nblocked + d]
        perm += [2 * nblocked]
        seg = seg.transpose(perm).reshape(grid.n)
        for d in range(nblocked):
            seg = jnp.roll(seg, shift[d], axis=d)
        out = out + seg
    return out


def _extract_tiles(geom, grid, f: jnp.ndarray) -> jnp.ndarray:
    """Gather the (width..., n_last) tile of every block -> (B, P, n_last)."""
    dim = grid.dim
    nblocked = dim - 1
    arr = f
    # take along each blocked axis: axis d of arr is the grid axis d
    for d in range(nblocked):
        idx = (np.arange(geom.nblk[d])[:, None] * geom.tile[d] - 1
               + np.arange(geom.width[d])[None, :]) % grid.n[d]
        arr = jnp.take(arr, jnp.asarray(idx.reshape(-1)), axis=2 * d)
        arr = arr.reshape(arr.shape[:2 * d]
                          + (geom.nblk[d], geom.width[d])
                          + arr.shape[2 * d + 1:])
    # arr: (nb0, w0[, nb1, w1], n_last) -> (B, P, n_last)
    if nblocked == 1:
        B = geom.nblk[0]
        return arr.reshape(B, geom.width[0], grid.n[dim - 1])
    perm = (0, 2, 1, 3, 4)
    arr = arr.transpose(perm)
    B = geom.nblk[0] * geom.nblk[1]
    return arr.reshape(B, geom.width[0] * geom.width[1], grid.n[dim - 1])


# -- public ops --------------------------------------------------------------

# how a trace moved per-marker values between marker order and slot
# order (bumped once per traced gather / scatter-add over
# ``slot_of_marker``: three per velocity transfer moved a component at
# a time, one moved as rows)
_MARKER_GATHERS = obs.counter("transfer_marker_gathers_total")
_MARKER_SCATTERS = obs.counter("transfer_marker_scatters_total")
obs.describe("transfer_marker_gathers_total",
             "traced gathers of per-slot values into marker order "
             "(one index per marker, whatever the row under it holds)")
obs.describe("transfer_marker_scatters_total",
             "traced scatter-adds of per-marker values into slot order")


def _marshalled(rows: bool) -> None:
    """Tell the ``driver/chunk`` span whose call traced it in which
    form the values crossed: ``rows`` (a trailing channel axis, one
    index per marker for all components) or ``scalars``."""
    obs.annotate("driver/chunk",
                 transfer_marshal="rows" if rows else "scalars")


def bucketed_channel(b: Buckets, F: jnp.ndarray) -> jnp.ndarray:
    """Scatter a per-marker channel (N,) into the bucket-slot layout
    (B, cap) of ``b`` (shared by the MXU and Pallas spread engines and
    the packed engine's per-component spread); the dump row takes the
    overflowed markers."""
    _MARKER_SCATTERS.inc()
    _marshalled(False)
    Ff = jnp.zeros(b.wb.size + 1, dtype=F.dtype)
    return Ff.at[b.slot_of_marker].add(F)[:-1].reshape(b.wb.shape)


def spread_overflow_fallbacks(out: jnp.ndarray, b: Buckets,
                              F: jnp.ndarray, X: jnp.ndarray,
                              grid: StaggeredGrid, centering,
                              kernel: Kernel) -> jnp.ndarray:
    """Accumulate the overflow markers' contribution into ``out``:
    compact scatter for the buffered overflow, exact full-scatter when
    the buffer itself overflowed (shared by both bucketed engines)."""
    def compact(o):
        # pad slots rely on o_w == 0 making them inert (the index
        # aliases a real marker — compact_overflow's convention)
        res = interaction.spread(F[b.o_idx], grid, X[b.o_idx],
                                 centering=centering, kernel=kernel,
                                 weights=b.o_w, out=o)
        if _DEBUG_OVERFLOW_PAD:
            live = b.o_w != 0
            masked = interaction.spread(
                jnp.where(live, F[b.o_idx], 0.0), grid, X[b.o_idx],
                centering=centering, kernel=kernel, weights=b.o_w,
                out=o)
            _check_pad_inert("spread_overflow_fallbacks", res, masked)
        return res

    def full(o):
        return interaction.spread(F, grid, X, centering=centering,
                                  kernel=kernel, weights=b.w_overflow,
                                  out=o)

    return jax.lax.cond(
        b.exceeded, full,
        lambda o: jax.lax.cond(b.any_overflow, compact,
                               lambda oo: oo, o), out)


def contract_compressed(spec: str, a, b, compute_dtype,
                        precision=jax.lax.Precision.HIGHEST):
    """The packed engines' contraction point: exact f32 einsum, or
    bf16-compressed operands with f32 accumulation when
    ``compute_dtype`` is set (the weight operands are the dominant HBM
    traffic of the whole IB step; compression costs ~3 decimal digits
    of delta-weight precision, pinned by tests). One definition for
    both directions so the scheme cannot diverge between them."""
    if compute_dtype is not None:
        return jnp.einsum(spec, a.astype(compute_dtype),
                          b.astype(compute_dtype),
                          preferred_element_type=jnp.float32
                          ).astype(a.dtype)
    return jnp.einsum(spec, a, b, precision=precision)


def spread_bucketed(geom: BucketGeometry, grid: StaggeredGrid,
                    b: Buckets, F: jnp.ndarray, X: jnp.ndarray,
                    centering, kernel: Kernel) -> jnp.ndarray:
    """Spread marker values F (N,) -> grid field; exact up to roundoff
    vs interaction.spread (overflow markers go through that path).

    Marker weights are the ones baked into ``b`` at bucket-build time
    (``b.wb``/``b.o_w``/``b.w_overflow``) — there is deliberately no
    per-call weights argument here, so stale-weights misuse is
    impossible (ADVICE round 1)."""
    inv_vol = 1.0 / math.prod(grid.dx)
    Ff = bucketed_channel(b, F)
    A, Wlast = _tile_weights(geom, grid, b, centering, kernel)
    A = A * (Ff * b.wb * inv_vol)[..., None]
    T = jnp.einsum("bmp,bmz->bpz", A, Wlast,
                   precision=jax.lax.Precision.HIGHEST)
    out = _overlap_add(geom, grid, T.reshape(
        (T.shape[0],) + tuple(geom.width) + (grid.n[grid.dim - 1],)))
    return spread_overflow_fallbacks(out, b, F, X, grid, centering,
                                     kernel)


def slots_to_markers(Ub: jnp.ndarray, b: Buckets) -> jnp.ndarray:
    """Per-slot values Ub (B, cap) or rows (B, cap, C) in marker order,
    (N,) or (N, C): ONE gather over ``slot_of_marker``, zero where the
    marker overflowed and has no slot."""
    _MARKER_GATHERS.inc()
    rows = Ub.ndim > b.wb.ndim
    _marshalled(rows)
    S = b.wb.size
    U = jnp.take(Ub.reshape((S,) + Ub.shape[b.wb.ndim:]),
                 jnp.minimum(b.slot_of_marker, S - 1), axis=0)
    live = b.slot_of_marker < S
    return jnp.where(live[:, None] if rows else live, U, 0.0)


def unbucket_with_overflow(Ub: jnp.ndarray, b: Buckets, f, X: jnp.ndarray,
                           grid: StaggeredGrid, centering, kernel: Kernel,
                           merge=None) -> jnp.ndarray:
    """Scatter per-slot interpolants Ub (B, cap) back to marker order
    and add the overflow markers' contribution (compact gather for the
    buffered overflow, exact full gather when the buffer itself
    overflowed) — the interp twin of spread_overflow_fallbacks, shared
    by the MXU and Pallas engines.

    Rows: where Ub carries a trailing channel axis, (B, cap, C), ``f``
    and ``centering`` are C-sequences (a field and its centering per
    channel) and the result is (N, C), brought to marker order by the
    one gather; the overflow branches still evaluate channel by
    channel. ``merge(U, o_idx, vals)`` accumulates the compact list
    in place of ``U.at[o_idx].add(vals)`` (the packed reverse mode
    passes a scatter-free one)."""
    rows = Ub.ndim > b.wb.ndim
    channels = tuple(zip(f, centering)) if rows else ((f, centering),)
    U = slots_to_markers(Ub, b)

    def overflow(Xo, weights):
        cols = [interaction.interpolate(fc, grid, Xo, centering=cc,
                                        kernel=kernel, weights=weights)
                for fc, cc in channels]
        return jnp.stack(cols, axis=-1) if rows else cols[0]

    def compact(U):
        # pad slots rely on o_w == 0 making them inert (the index
        # aliases a real marker — compact_overflow's convention)
        Uo = overflow(X[b.o_idx], b.o_w)
        if _DEBUG_OVERFLOW_PAD:
            live = b.o_w != 0
            _check_pad_inert(
                "unbucket_with_overflow",
                jnp.where(live[:, None] if rows else live, 0.0, Uo),
                jnp.zeros_like(Uo))
        if merge is not None:
            return merge(U, b.o_idx, Uo)
        return U.at[b.o_idx].add(Uo)

    def full(U):
        return U + overflow(X, b.w_overflow)

    return jax.lax.cond(
        b.exceeded, full,
        lambda u: jax.lax.cond(b.any_overflow, compact,
                               lambda uu: uu, u), U)


def interpolate_bucketed(geom: BucketGeometry, grid: StaggeredGrid,
                         b: Buckets, f: jnp.ndarray, X: jnp.ndarray,
                         centering, kernel: Kernel) -> jnp.ndarray:
    """Interpolate grid field at markers -> (N,) (adjoint of spread).
    Marker weights come from ``b`` only — see spread_bucketed."""
    T = _extract_tiles(geom, grid, f)                 # (B, P, n_last)
    A, Wlast = _tile_weights(geom, grid, b, centering, kernel)
    D = jnp.einsum("bpz,bmz->bmp", T, Wlast,
                   precision=jax.lax.Precision.HIGHEST)
    # wb already carries the caller's marker weights (bucket_markers)
    Ub = jnp.sum(A * D, axis=-1) * b.wb               # (B, cap)
    return unbucket_with_overflow(Ub, b, f, X, grid, centering, kernel)


class FastInteraction:
    """Drop-in spread/interp engine: bucket once per X, reuse for all
    components and both directions within a timestep.

    Marker ``weights`` are baked into the Buckets at build time; when a
    prebuilt ``b`` is passed to spread/interp, the ``weights`` argument
    is used only as the build input for ``b is None`` and MUST match
    what the buckets were built with.
    """

    def __init__(self, grid: StaggeredGrid, kernel: Kernel = "IB_4",
                 tile: int = 8, cap: int = 256,
                 overflow_cap: Optional[int] = None):
        self.grid = grid
        self.kernel: Kernel = kernel
        self.geom = make_geometry(grid, kernel, tile=tile, cap=cap)
        self.overflow_cap = overflow_cap

    def buckets(self, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None) -> Buckets:
        return bucket_markers(self.geom, self.grid, X, weights,
                              overflow_cap=self.overflow_cap)

    def interpolate_vel(self, u: Vel, X: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None,
                        b: Optional[Buckets] = None) -> jnp.ndarray:
        if b is None:
            b = self.buckets(X, weights)
        cols = [interpolate_bucketed(self.geom, self.grid, b, u[d], X,
                                     d, self.kernel)
                for d in range(self.grid.dim)]
        return jnp.stack(cols, axis=-1)

    def spread_vel(self, F: jnp.ndarray, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   b: Optional[Buckets] = None) -> Vel:
        if b is None:
            b = self.buckets(X, weights)
        return tuple(spread_bucketed(self.geom, self.grid, b, F[:, d], X,
                                     d, self.kernel)
                     for d in range(self.grid.dim))
