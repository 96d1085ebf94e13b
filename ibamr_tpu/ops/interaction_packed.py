"""Occupancy-packed MXU spread/interpolate: chunked bucket matmuls.

Reference parity: same operations as :mod:`ibamr_tpu.ops.interaction`
(``LEInteractor::spread/interpolate``, T2 — the north-star hot path);
same math as :mod:`ibamr_tpu.ops.interaction_fast` (the bucketed MXU
formulation), different *layout*.

Why: the fixed ``(B_tiles, cap)`` slot pool of ``interaction_fast``
sizes ``cap`` by the MAXIMUM tile occupancy. For surface structures
(the flagship shell) the marker density is silhouette-clustered, so at
256^3 the pool runs at ~10% utilization — and the dominant HBM arrays
(the ``(B, cap, P)`` / ``(B, cap, nz)`` weight operands) are ~90%
padding. Round-3 on-chip profiling attributes most of the 167 ms of
transfer time per step to exactly that traffic.

TPU-first redesign: keep the tile/footprint geometry, but allocate
**chunks** of ``c`` marker slots per tile in proportion to occupancy:

  chunks_needed(tile) = ceil(count(tile) / c)
  chunk q in [0, Q): holds <= c markers of ONE tile, tile_of_chunk[q]

Total slots become ``Q*c ~ N + c*active_tiles`` instead of
``B*cap_max`` — utilization goes from ~10% to >40% on the flagship,
shrinking every weight/einsum operand by the same factor. The einsum
runs per chunk; per-tile partial tiles are reduced with a sorted
``segment_sum`` (chunk ids are assigned in tile order, so the segment
reduction is contiguous); the overlap-add is unchanged. Markers beyond
the global chunk capacity ``Q`` (not per-tile — a hot tile can take
arbitrarily many chunks) flow through the exact compact-scatter
fallback shared with interaction_fast.

Spread/interp reuse the same ``delta.get_kernel`` weights and remain
exact adjoints; tests pin equality against the scatter oracle.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ibamr_tpu import obs
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.delta import Kernel, get_kernel
from ibamr_tpu.ops.interaction_fast import (
    BucketGeometry, _block_ids_np, _extract_tiles, _overlap_add,
    _tile_weights, bucketed_channel, contract_compressed, make_geometry,
    spread_overflow_fallbacks, unbucket_with_overflow)

Vel = Tuple[jnp.ndarray, ...]

# Reverse-mode policy for the packed transfers (PR 19). The default
# custom VJP reuses spread/interp adjointness: d(spread) wrt F is an
# interp of the grid cotangent through the SAME ``PackedBuckets`` (pure
# gathers — the overflow merge is rewritten scatter-free), d(interp)
# wrt f is a spread through the same buckets, and position cotangents
# flow through the oracle stencil weights (gather-only graphs). The
# bucket layout itself is treated as a non-differentiated constant:
# pack-time integers are piecewise constant in X, and the position
# gradient is returned in full through the explicit ``X`` argument
# (callers always pass the same X the buckets were built from — the
# engine API bakes that in). Set False to fall back to plain autodiff
# through the packed implementation (saves nothing, emits transposed
# scatters, and is NOT covered by the ``grad_spread``/``grad_interp``
# graph budgets).
GRAD_TRANSFERS = True


@contextlib.contextmanager
def plain_autodiff_transfers():
    """Trace-scoped opt-out of the custom-VJP transfer wrappers.

    ``jax.custom_vjp`` functions refuse forward-mode autodiff
    (jvp/linearize), so any graph that takes exact JVPs through a
    spread/interp — the implicit Newton-Krylov coupling linearizes its
    whole spread -> solve -> interp residual — must trace inside this
    context: transfers route through the raw packed implementations,
    which JAX differentiates natively in both modes (reverse mode there
    emits transposed scatters and is NOT covered by the
    ``grad_spread``/``grad_interp`` budgets)."""
    global GRAD_TRANSFERS
    prev = GRAD_TRANSFERS
    GRAD_TRANSFERS = False
    try:
        yield
    finally:
        GRAD_TRANSFERS = prev


class PackedBuckets(NamedTuple):
    """Chunk-packed marker layout (duck-types interaction_fast.Buckets
    for the shared helpers: same field names + ``tile_of_chunk``)."""
    Xb: jnp.ndarray               # (Q, c, dim)
    wb: jnp.ndarray               # (Q, c) marker weights (0 = empty slot)
    slot_of_marker: jnp.ndarray   # (N,) flat slot or Q*c (overflowed)
    marker_of_slot: jnp.ndarray   # (Q*c,) its inverse; N = empty slot
    w_overflow: jnp.ndarray       # (N,)
    o_idx: jnp.ndarray            # (ocap,)
    o_w: jnp.ndarray              # (ocap,)
    any_overflow: jnp.ndarray     # () bool
    exceeded: jnp.ndarray         # () bool
    x0: Tuple[jnp.ndarray, ...]   # per blocked axis: (Q,) tile origin
    tile_of_chunk: jnp.ndarray    # (Q,) int32, nondecreasing


def suggest_chunks(grid: StaggeredGrid, X, kernel: Kernel = "IB_4",
                   tile: int = 8, chunk: int = 128,
                   slack: float = 1.3) -> int:
    """Host-side chunk-capacity heuristic from a concrete marker
    distribution: slack x the exact chunk demand sum(ceil(count/c))."""
    Xn = np.asarray(X)
    support, _ = get_kernel(kernel)
    bids = _block_ids_np(grid, Xn, support, tile)
    B = int(np.prod([n // tile for n in grid.n[:-1]]))
    counts = np.bincount(bids, minlength=B)
    need = int(np.sum(-(-counts // chunk)))
    return max(8, int(math.ceil(need * slack)))


def chunk_pack_core(bid: jnp.ndarray, X: jnp.ndarray,
                    weights: jnp.ndarray, Q: int, c: int, B: int,
                    overflow_cap: int):
    """The occupancy-packing core: given per-marker tile ids ``bid`` in
    [0, B), pack markers into ``Q`` chunks of ``c`` slots allocated
    compactly in tile order. Returns
    (Xb, wb, slot_of_marker, w_overflow, o_idx, o_w, n_over,
    exceeded, tile_of_chunk, marker_of_slot)."""
    N, dim = X.shape
    order = jnp.argsort(bid)
    bid_s = bid[order]
    # per-tile marker ranges from the sorted ids (no scatter: TPU
    # scatter-adds over 1e5 indices serialize — measured 14.6 ms of
    # bucket prep at the flagship shape before this rewrite)
    edges = jnp.searchsorted(bid_s,
                             jnp.arange(B + 1, dtype=bid_s.dtype))
    start, counts = edges[:-1], jnp.diff(edges).astype(jnp.int32)
    nchunk_tile = -((-counts) // c)                     # ceil(counts/c)
    base = jnp.cumsum(nchunk_tile) - nchunk_tile        # exclusive scan
    rank = jnp.arange(N, dtype=jnp.int32) - start[bid_s].astype(jnp.int32)
    chunk_s = base[bid_s] + rank // c                   # global chunk id
    keep = chunk_s < Q
    slot_sorted = jnp.where(keep, chunk_s * c + rank % c, Q * c)

    # tile of every chunk, directly from the chunk allocation (base is
    # nondecreasing): chunk j belongs to the last tile whose first
    # chunk is <= j; trailing never-allocated chunks pin to B-1 so the
    # id sequence stays nondecreasing for the sorted segment_sum
    tid = (jnp.searchsorted(base, jnp.arange(Q, dtype=base.dtype),
                            side="right").astype(jnp.int32) - 1)
    tid = jnp.clip(tid, 0, B - 1)

    # slot -> marker (pure gathers; every slot of an allocated chunk
    # maps to sorted position start[tile] + offset-in-tile, an empty
    # slot to N and so to a zero fill). Position-independent, so it is
    # part of the layout: a refresh re-gathers X through it.
    q_c = jnp.arange(Q * c, dtype=jnp.int32) // c       # chunk of slot
    r = jnp.arange(Q * c, dtype=jnp.int32) % c          # rank in chunk
    t_of_slot = tid[q_c]
    off_in_tile = (q_c - base[t_of_slot]) * c + r
    valid = (off_in_tile >= 0) & (off_in_tile < counts[t_of_slot])
    src = jnp.where(valid, start[t_of_slot] + off_in_tile, N)
    marker_of_slot = jnp.take(order.astype(jnp.int32), src, mode="fill",
                              fill_value=N)
    Xb = jnp.take(X, marker_of_slot, axis=0, mode="fill",
                  fill_value=0).reshape(Q, c, dim)
    wb = jnp.take(weights, marker_of_slot, mode="fill",
                  fill_value=0).reshape(Q, c)

    from ibamr_tpu.ops.interaction_fast import compact_overflow
    (slot_of_marker, w_overflow, o_idx, o_w, n_over,
     exceeded) = compact_overflow(order, keep, slot_sorted, weights, N,
                                  overflow_cap)

    return (Xb, wb, slot_of_marker, w_overflow, o_idx, o_w, n_over,
            exceeded, tid, marker_of_slot)


def default_overflow_cap(N: int) -> int:
    """Shared overflow-buffer sizing heuristic."""
    return min(N, max(2048, 1 << int(math.ceil(
        math.log2(max(N // 8, 1))))))


@jax.named_scope("pack")
def pack_markers(geom: BucketGeometry, grid: StaggeredGrid,
                 X: jnp.ndarray, weights: Optional[jnp.ndarray] = None,
                 nchunks: int = 1024,
                 overflow_cap: Optional[int] = None) -> PackedBuckets:
    """Bucket markers by tile, then pack tiles' markers into ``Q``
    chunks of ``geom.cap`` slots, allocated compactly in tile order."""
    N, dim = X.shape
    if weights is None:
        weights = jnp.ones((N,), dtype=X.dtype)
    if overflow_cap is None:
        overflow_cap = default_overflow_cap(N)
    s = geom.support
    Q = int(nchunks)
    bid = jnp.zeros((N,), dtype=jnp.int32)
    for d in range(dim - 1):
        xi = (X[:, d] - grid.x_lo[d]) / grid.dx[d] - 0.5
        j0 = jnp.floor(xi - 0.5 * s).astype(jnp.int32) + 1
        b = jnp.mod(j0, grid.n[d]) // geom.tile[d]
        bid = bid * geom.nblk[d] + b
    B = int(np.prod(geom.nblk))

    (Xb, wb, slot_of_marker, w_overflow, o_idx, o_w, n_over,
     exceeded, tid, marker_of_slot) = chunk_pack_core(
         bid, X, weights, Q, geom.cap, B, overflow_cap)
    x0 = []
    for d in range(dim - 1):
        ids = tid
        for a in range(dim - 1 - 1, d, -1):
            ids = ids // geom.nblk[a]
        x0.append((ids % geom.nblk[d]) * geom.tile[d])
    return PackedBuckets(Xb=Xb, wb=wb, slot_of_marker=slot_of_marker,
                         marker_of_slot=marker_of_slot,
                         w_overflow=w_overflow, o_idx=o_idx, o_w=o_w,
                         any_overflow=n_over > 0, exceeded=exceeded,
                         x0=tuple(x0), tile_of_chunk=tid)


def refresh_packed(geom: BucketGeometry, grid: StaggeredGrid,
                   b: PackedBuckets, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None
                   ) -> Tuple[PackedBuckets, jnp.ndarray]:
    """Slot-preserving half-step refresh: re-gather the NEW positions
    ``X`` into the existing pack-time chunk layout of ``b`` instead of
    re-running the full sort/bucket/pack.

    Exactness: a chunk's footprint covers cells ``[x0-1, x0+tile+s-1]``
    (``_blocked_axis_weights`` starts one cell below the tile origin).
    On a staggered grid axis ``d`` sees TWO stencil origins per marker
    — the cell-centered one (offset 0.5; components != d) and the
    face-centered one (offset 0.0; component d, up to one cell higher)
    — and the transfer stays EXACT for any drifted position whose new
    origins BOTH satisfy ``mod(j0 - (x0-1), n) <= tile+1`` on every
    blocked axis (then every stencil cell of every component still
    lands in the footprint, and the mod-centered distances evaluate
    the same periodic weights the scatter oracle uses). In continuous
    terms that gives every marker at least half a cell of forward
    slack and a full cell backward, so CFL-bounded substep drift
    always passes. Overflow markers stay exact regardless: the
    compact-scatter fallbacks evaluate at call-time ``X``.

    The drift bound is checked jittably; when ANY live packed marker
    violates it the whole layout falls back to a full re-pack under
    ``lax.cond`` (identical static shapes), so the result is exact
    either way. Returns ``(buckets, hit)`` with ``hit`` True when the
    cheap re-gather was sufficient."""
    N, dim = X.shape
    if weights is None:
        weights = jnp.ones((N,), dtype=X.dtype)
    # both lax.cond branches must carry identical pytrees: the re-pack
    # branch derives its weight fields from ``weights``, the refresh
    # branch keeps ``b``'s
    weights = jnp.asarray(weights, dtype=b.wb.dtype)
    Q, c = b.Xb.shape[0], b.Xb.shape[1]
    ocap = b.o_idx.shape[0]
    s = geom.support

    # one gather of the new positions into the pack-time slots, through
    # the slot -> marker map the pack kept. Everything else in the
    # layout (weights, overflow lists, chunk->tile map) is
    # position-independent and carries over.
    Xb = jnp.take(X, b.marker_of_slot, axis=0, mode="fill",
                  fill_value=0).reshape(Q, c, dim)

    # drift-bound check per blocked axis, slot by slot against the
    # chunk's pack-time tile origin (elementwise: no per-marker lookup).
    # Empty slots and inactive markers carry weight 0 and are exempt, as
    # overflowed markers are by having no slot: their transfers never
    # read the packed layout.
    ok = jnp.ones((Q, c), dtype=bool)
    for d in range(dim - 1):
        x0 = b.x0[d][:, None]
        for off in (0.5, 0.0):      # cell- and face-centered origins
            xi = (Xb[..., d] - grid.x_lo[d]) / grid.dx[d] - off
            j0 = jnp.floor(xi - 0.5 * s).astype(jnp.int32) + 1
            r = jnp.mod(j0 - (x0 - 1), grid.n[d])
            ok &= r <= geom.tile[d] + 1
    hit = jnp.all(ok | (b.wb == 0))

    def repack():
        # scope inside the FALSE branch only: device time under
        # ``repack`` is time spent falling back (0 = always hit)
        with jax.named_scope("repack"):
            return pack_markers(geom, grid, X, weights, nchunks=Q,
                                overflow_cap=ocap)

    return jax.lax.cond(hit, lambda: b._replace(Xb=Xb), repack), hit


# bumped once per traced gather of per-marker rows into slot order
# through ``marker_of_slot`` (the packed spread_vel's way in)
_SLOT_GATHERS = obs.counter("transfer_slot_gathers_total")
obs.describe("transfer_slot_gathers_total",
             "traced gathers of per-marker values into slot order "
             "through marker_of_slot (one index per slot)")


def slot_channel(b: PackedBuckets, F: jnp.ndarray) -> jnp.ndarray:
    """Per-marker rows F (N, C) in slot order, (Q, c, C): ONE gather
    through ``marker_of_slot``, an index per slot, an empty slot reading
    0. It equals ``bucketed_channel``'s scatter-add onto zeros to every
    bit but the sign of a zero (slots are unique per marker, and an
    overflowed marker has no slot to be read from), and its result fuses
    into the ops that read it, as a scatter's does not."""
    _SLOT_GATHERS.inc()
    obs.annotate("driver/chunk", transfer_marshal="rows")
    return jnp.take(F, b.marker_of_slot, axis=0, mode="fill",
                    fill_value=0).reshape(b.wb.shape + F.shape[1:])


def _spread_slots(geom: BucketGeometry, grid: StaggeredGrid,
                  b: PackedBuckets, Ff: jnp.ndarray, centering,
                  kernel: Kernel, precision, compute_dtype) -> jnp.ndarray:
    """Grid field of ONE component from its channel in slot order,
    ``Ff`` (Q, c): the packed markers' part of a spread."""
    inv_vol = 1.0 / math.prod(grid.dx)
    A, Wlast = _tile_weights(geom, grid, b, centering, kernel)
    A = A * (Ff * b.wb * inv_vol)[..., None]
    Tq = contract_compressed("qmp,qmz->qpz", A, Wlast, compute_dtype,
                             precision=precision)
    B = int(np.prod(geom.nblk))
    T = jax.ops.segment_sum(Tq, b.tile_of_chunk, num_segments=B,
                            indices_are_sorted=True)
    with jax.named_scope("overlap_add"):
        return _overlap_add(geom, grid, T.reshape(
            (B,) + tuple(geom.width) + (grid.n[grid.dim - 1],)))


def _interp_slots(geom: BucketGeometry, grid: StaggeredGrid,
                  b: PackedBuckets, f: jnp.ndarray, centering,
                  kernel: Kernel, precision, compute_dtype) -> jnp.ndarray:
    """Interpolants of ONE component in slot order, (Q, c): the packed
    markers' part of an interpolation (pure gathers and a contraction)."""
    T = _extract_tiles(geom, grid, f)                 # (B, P, nz)
    Tq = jnp.take(T, b.tile_of_chunk, axis=0)         # (Q, P, nz)
    A, Wlast = _tile_weights(geom, grid, b, centering, kernel)
    D = contract_compressed("qpz,qmz->qmp", Tq, Wlast, compute_dtype,
                            precision=precision)
    return jnp.sum(A * D, axis=-1) * b.wb


def _spread_raw(geom: BucketGeometry, grid: StaggeredGrid,
                b: PackedBuckets, F: jnp.ndarray, X: jnp.ndarray,
                centering, kernel: Kernel,
                precision=jax.lax.Precision.HIGHEST,
                compute_dtype=None) -> jnp.ndarray:
    out = _spread_slots(geom, grid, b, bucketed_channel(b, F), centering,
                        kernel, precision, compute_dtype)
    return spread_overflow_fallbacks(out, b, F, X, grid, centering,
                                     kernel)


def _interp_raw(geom: BucketGeometry, grid: StaggeredGrid,
                b: PackedBuckets, f: jnp.ndarray, X: jnp.ndarray,
                centering, kernel: Kernel,
                precision=jax.lax.Precision.HIGHEST,
                compute_dtype=None, merge=None) -> jnp.ndarray:
    Ub = _interp_slots(geom, grid, b, f, centering, kernel, precision,
                       compute_dtype)
    return unbucket_with_overflow(Ub, b, f, X, grid, centering, kernel,
                                  merge=merge)


def _spread_vel_raw(geom: BucketGeometry, grid: StaggeredGrid,
                    kernel: Kernel, precision, compute_dtype,
                    b: PackedBuckets, F: jnp.ndarray,
                    X: jnp.ndarray) -> Vel:
    """All components of a velocity-like spread, F (N, dim): the
    markers' values go to slot order as ROWS, by one gather through
    ``marker_of_slot``; each component then spreads from its own slice
    as ``_spread_raw`` would from its own scatter-add, to every bit."""
    Ff = slot_channel(b, F)                           # (Q, c, dim)
    return tuple(
        spread_overflow_fallbacks(
            _spread_slots(geom, grid, b, Ff[..., d], d, kernel,
                          precision, compute_dtype),
            b, F[:, d], X, grid, d, kernel)
        for d in range(grid.dim))


def _interp_vel_raw(geom: BucketGeometry, grid: StaggeredGrid,
                    kernel: Kernel, precision, compute_dtype,
                    b: PackedBuckets, u: Vel, X: jnp.ndarray,
                    merge=None) -> jnp.ndarray:
    """All components of a velocity interpolation -> (N, dim): the
    per-slot interpolants come to marker order as ROWS, by one
    gather (``_interp_raw``'s columns, to every bit)."""
    dims = tuple(range(grid.dim))
    Ub = jnp.stack([_interp_slots(geom, grid, b, u[d], d, kernel,
                                  precision, compute_dtype)
                    for d in dims], axis=-1)          # (Q, c, dim)
    return unbucket_with_overflow(Ub, b, tuple(u), X, grid, dims, kernel,
                                  merge=merge)


# -- packed-transfer reverse mode (PR 19) ------------------------------------

def _marker_weights(b: PackedBuckets) -> jnp.ndarray:
    """Recover the per-ORIGINAL-marker weight vector from the packed
    layout: the pack-time weight for packed markers (their slot is
    unique) plus ``w_overflow`` for dropped ones — pure gathers."""
    wb_flat = b.wb.reshape(-1)
    packed = jnp.take(wb_flat, jnp.minimum(b.slot_of_marker,
                                           wb_flat.size - 1))
    packed = jnp.where(b.slot_of_marker < wb_flat.size, packed, 0.0)
    return packed + b.w_overflow


def _merge_overflow_gather(U: jnp.ndarray, o_idx: jnp.ndarray,
                           vals: jnp.ndarray) -> jnp.ndarray:
    """``U.at[o_idx].add(vals)`` rewritten scatter-free: sort the
    compact overflow list by marker id, prefix-sum the sorted values,
    and gather each marker's run sum via two searchsorted probes
    (sort + cumsum + gathers only — pad entries alias real markers
    with value 0, and duplicate ids sum exactly as the scatter-add
    would). Scalars (N,) or rows (N, C) alike."""
    perm = jnp.argsort(o_idx)
    so = o_idx[perm]
    cs = jnp.concatenate([jnp.zeros((1,) + vals.shape[1:], vals.dtype),
                          jnp.cumsum(vals[perm], axis=0)])
    ar = jnp.arange(U.shape[0], dtype=so.dtype)
    lo = jnp.searchsorted(so, ar, side="left")
    hi = jnp.searchsorted(so, ar, side="right")
    return U + (cs[hi] - cs[lo])


def _position_cotangent(grid: StaggeredGrid, field: jnp.ndarray,
                        X: jnp.ndarray, centering, kernel: Kernel,
                        scale: jnp.ndarray) -> jnp.ndarray:
    """Marker-position cotangent of a transfer: pull ``scale`` (the
    per-marker chain factor) back through the oracle stencil evaluation
    ``X -> sum_cells field * delta_h(cells - X)``. The stencil indices
    are floor-derived (zero derivative); only the kernel weights
    differentiate, so the pulled-back graph is gathers + elementwise —
    no scatters."""
    y, pull = jax.vjp(
        lambda Xp: interaction.interpolate(field, grid, Xp,
                                           centering=centering,
                                           kernel=kernel), X)
    (X_ct,) = pull(scale.astype(y.dtype))
    return X_ct


def _zeros_ct(x):
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _spread_vjp(geom, grid, centering, kernel, precision, compute_dtype,
                b: PackedBuckets, F: jnp.ndarray,
                X: jnp.ndarray) -> jnp.ndarray:
    return _spread_raw(geom, grid, b, F, X, centering, kernel,
                       precision=precision, compute_dtype=compute_dtype)


def _spread_fwd(geom, grid, centering, kernel, precision, compute_dtype,
                b, F, X):
    out = _spread_raw(geom, grid, b, F, X, centering, kernel,
                      precision=precision, compute_dtype=compute_dtype)
    return out, (b, F, X)


def _spread_bwd(geom, grid, centering, kernel, precision, compute_dtype,
                res, ct):
    b, F, X = res
    inv_vol = 1.0 / math.prod(grid.dx)
    # d/dF: interp of the grid cotangent through the SAME buckets
    # (weights included), scaled by the spread's 1/h^dim — zero
    # scatters, zero bucket preps
    F_ct = inv_vol * _interp_raw(geom, grid, b, ct, X, centering, kernel,
                                 precision=precision,
                                 compute_dtype=compute_dtype,
                                 merge=_merge_overflow_gather)
    # d/dX: the kernel-weight derivative, pulled back through the
    # oracle stencil evaluation of the SAME cotangent field
    w_full = _marker_weights(b)
    X_ct = _position_cotangent(grid, ct, X, centering, kernel,
                               F * w_full * inv_vol)
    return (jax.tree_util.tree_map(_zeros_ct, b), F_ct, X_ct)


_spread_vjp.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _interp_vjp(geom, grid, centering, kernel, precision, compute_dtype,
                b: PackedBuckets, f: jnp.ndarray,
                X: jnp.ndarray) -> jnp.ndarray:
    return _interp_raw(geom, grid, b, f, X, centering, kernel,
                       precision=precision, compute_dtype=compute_dtype)


def _interp_fwd(geom, grid, centering, kernel, precision, compute_dtype,
                b, f, X):
    out = _interp_raw(geom, grid, b, f, X, centering, kernel,
                      precision=precision, compute_dtype=compute_dtype)
    return out, (b, f, X)


def _interp_bwd(geom, grid, centering, kernel, precision, compute_dtype,
                res, ct):
    b, f, X = res
    vol = math.prod(grid.dx)
    # d/df: spread of the marker cotangents through the SAME buckets;
    # interp carries no 1/h^dim, so undo the spread's factor. The
    # grid-side adjoint of a gather IS a scatter — this path reuses
    # the primal spread's scatter set verbatim (grad_interp budgets
    # it; no NEW scatter shapes are introduced)
    f_ct = vol * _spread_raw(geom, grid, b, ct, X, centering, kernel,
                             precision=precision,
                             compute_dtype=compute_dtype)
    w_full = _marker_weights(b)
    X_ct = _position_cotangent(grid, f, X, centering, kernel,
                               ct * w_full)
    return (jax.tree_util.tree_map(_zeros_ct, b), f_ct, X_ct)


_interp_vjp.defvjp(_interp_fwd, _interp_bwd)


# The velocity transfers' reverse mode: the two rules above on ROWS.
# The cotangent passes are the batched transfers themselves, so they
# marshal once as the primal does: d(spread_vel) wrt F one row gather
# (still scatter-free), d(interp_vel) wrt u the primal spread_vel's one
# row gather through ``marker_of_slot``; no bucket prep in either.

def _position_cotangent_vel(grid, fields, X, kernel, scale):
    """The components' position cotangents, summed: ``scale`` (N, dim)
    holds each component's per-marker chain factor in its column."""
    X_ct = _position_cotangent(grid, fields[0], X, 0, kernel, scale[:, 0])
    for d in range(1, grid.dim):
        X_ct = X_ct + _position_cotangent(grid, fields[d], X, d, kernel,
                                          scale[:, d])
    return X_ct


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _spread_vel_vjp(*args) -> Vel:
    return _spread_vel_raw(*args)


def _spread_vel_fwd(*args):
    return _spread_vel_raw(*args), args[-3:]


def _spread_vel_bwd(geom, grid, kernel, precision, compute_dtype, res, ct):
    b, F, X = res
    inv_vol = 1.0 / math.prod(grid.dx)
    F_ct = inv_vol * _interp_vel_raw(geom, grid, kernel, precision,
                                     compute_dtype, b, ct, X,
                                     merge=_merge_overflow_gather)
    X_ct = _position_cotangent_vel(
        grid, ct, X, kernel, F * _marker_weights(b)[:, None] * inv_vol)
    return (jax.tree_util.tree_map(_zeros_ct, b), F_ct, X_ct)


_spread_vel_vjp.defvjp(_spread_vel_fwd, _spread_vel_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _interp_vel_vjp(*args) -> jnp.ndarray:
    return _interp_vel_raw(*args)


def _interp_vel_fwd(*args):
    return _interp_vel_raw(*args), args[-3:]


def _interp_vel_bwd(geom, grid, kernel, precision, compute_dtype, res, ct):
    b, u, X = res
    vol = math.prod(grid.dx)
    u_ct = tuple(vol * g for g in _spread_vel_raw(
        geom, grid, kernel, precision, compute_dtype, b, ct, X))
    X_ct = _position_cotangent_vel(grid, u, X, kernel,
                                   ct * _marker_weights(b)[:, None])
    return (jax.tree_util.tree_map(_zeros_ct, b), u_ct, X_ct)


_interp_vel_vjp.defvjp(_interp_vel_fwd, _interp_vel_bwd)


def spread_packed(geom: BucketGeometry, grid: StaggeredGrid,
                  b: PackedBuckets, F: jnp.ndarray, X: jnp.ndarray,
                  centering, kernel: Kernel,
                  precision=jax.lax.Precision.HIGHEST,
                  compute_dtype=None) -> jnp.ndarray:
    """Spread marker values F (N,) -> grid field; exact up to roundoff
    vs interaction.spread (overflow flows through that path).
    ``compute_dtype=jnp.bfloat16`` compresses the chunk operands (the
    dominant HBM traffic; ~3 decimal digits of weight precision).

    Reverse mode: a custom VJP (see ``GRAD_TRANSFERS``) whose cotangent
    pass is an interp through the SAME buckets — zero scatter
    primitives, zero extra bucket preps (the ``grad_spread`` graph
    budget pins both)."""
    if not GRAD_TRANSFERS:
        return _spread_raw(geom, grid, b, F, X, centering, kernel,
                           precision=precision,
                           compute_dtype=compute_dtype)
    return _spread_vjp(geom, grid, centering, kernel, precision,
                       compute_dtype, b, F, X)


def interpolate_packed(geom: BucketGeometry, grid: StaggeredGrid,
                       b: PackedBuckets, f: jnp.ndarray, X: jnp.ndarray,
                       centering, kernel: Kernel,
                       precision=jax.lax.Precision.HIGHEST,
                       compute_dtype=None) -> jnp.ndarray:
    """Interpolate grid field at markers -> (N,) (adjoint of spread).

    Reverse mode: custom VJP — d/df is a spread through the SAME
    buckets (scaled by h^dim), d/dX the oracle weight-derivative
    pullback (``grad_interp`` budgets the pass)."""
    if not GRAD_TRANSFERS:
        return _interp_raw(geom, grid, b, f, X, centering, kernel,
                           precision=precision,
                           compute_dtype=compute_dtype)
    return _interp_vjp(geom, grid, centering, kernel, precision,
                       compute_dtype, b, f, X)


class PackedInteraction:
    """Drop-in FastInteraction-shaped engine with occupancy-packed
    chunks: bucket+pack once per X, reuse for all components and both
    directions within a timestep. ``chunk`` is the per-chunk slot count
    (the MXU contraction depth — keep it a multiple of 128);
    ``nchunks`` the static global chunk capacity — size it from a
    concrete marker distribution with :func:`suggest_chunks` (the
    flagship model does this at build time); markers beyond it flow
    through the exact scatter fallback."""

    def __init__(self, grid: StaggeredGrid, kernel: Kernel = "IB_4",
                 tile: int = 8, chunk: int = 128, nchunks: int = 1024,
                 overflow_cap: Optional[int] = None,
                 compute_dtype=None):
        self.grid = grid
        self.kernel: Kernel = kernel
        self.geom = make_geometry(grid, kernel, tile=tile, cap=chunk)
        self.nchunks = int(nchunks)
        self.overflow_cap = overflow_cap
        self.compute_dtype = compute_dtype

    def buckets(self, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None) -> PackedBuckets:
        return pack_markers(self.geom, self.grid, X, weights,
                            nchunks=self.nchunks,
                            overflow_cap=self.overflow_cap)

    def refresh(self, b: PackedBuckets, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None
                ) -> Tuple[PackedBuckets, jnp.ndarray]:
        """Slot-preserving re-gather of new positions into ``b``'s
        chunk layout (full re-pack fallback under the drift bound);
        returns ``(buckets, hit)`` — see :func:`refresh_packed`."""
        return refresh_packed(self.geom, self.grid, b, X, weights)

    def interpolate_vel(self, u: Vel, X: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None,
                        b: Optional[PackedBuckets] = None) -> jnp.ndarray:
        """(N, dim): the columns are ``interpolate_packed`` of each
        component, brought to marker order together, as rows (one
        gather over ``slot_of_marker`` and not one per component)."""
        if b is None:
            b = self.buckets(X, weights)
        transfer = _interp_vel_vjp if GRAD_TRANSFERS else _interp_vel_raw
        return transfer(self.geom, self.grid, self.kernel,
                        jax.lax.Precision.HIGHEST, self.compute_dtype,
                        b, tuple(u), X)

    def spread_vel(self, F: jnp.ndarray, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   b: Optional[PackedBuckets] = None) -> Vel:
        """``spread_packed`` of each column of F (N, dim), the columns
        taken to slot order together, as rows (one gather through
        ``marker_of_slot``)."""
        if b is None:
            b = self.buckets(X, weights)
        transfer = _spread_vel_vjp if GRAD_TRANSFERS else _spread_vel_raw
        return transfer(self.geom, self.grid, self.kernel,
                        jax.lax.Precision.HIGHEST, self.compute_dtype,
                        b, F, X)
