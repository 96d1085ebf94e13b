"""Fully-blocked occupancy-packed spread/interpolate: z-blocked chunks
+ spill-folding overlap-add.

Reference parity: the same T2 operations as every other engine
(``LEInteractor::spread/interpolate``,
``ibtk/src/lagrangian/fortran/lagrangian_interaction3d.f.m4`` [U] —
SURVEY.md T2, the north-star hot path); exact adjoint pair; overflow
through the shared scatter fallback.

Why a third layout (round 5, VERDICT item 2 — attack the roofline gap
structurally): the HLO audit (`PERF_HLO.md`) measured the xy-packed
engine's remaining waste —

1. **Full-z contraction.** The packed engine carries the entire last
   axis (n_z = 256 at the flagship) through the contraction while a
   marker's delta support touches only ``s = 4`` z-cells: 14.2 of its
   14.2 GFLOP/component are ~64x against the useful work, and the
   per-tile partials ``T`` materialize at (B, P, n_z) grid scale
   (177 MB/component).
2. **Masked overlap-add.** Accumulating width-13 tiles into the grid
   as 4 core/spill mask combinations costs 4 grid-size materializations
   + rolls (1.6 GB/component — the single largest traffic block of the
   whole coupled step after packing).

This module blocks ALL axes (z tiles of 16 by default): chunks hold
markers of one (x,y,z)-tile, the contraction output is (chunk,
w_z, P) with w_z = 21 instead of (chunk, P, 256) — ~12x less partial
traffic and ~6-10x less MXU work — and the overlap-add is restructured
as **spill folding**: because the spill width (s+1) never exceeds the
tile, each block's spill lands entirely in its successor's core, so
the periodic accumulation happens ON THE SMALL TILE TENSOR (roll by
one block + add, per axis), leaving a pure partition that reshapes to
the grid in ONE pass (plus one multi-axis roll) — no masked grid-size
passes at all. The same measured at 256^3/1e5 markers (HLO audit,
re-run with this engine): spread bytes-accessed 11.25 -> ~3 GB,
transfer dot-FLOPs 38 -> ~3 GFLOP against identical results.

Layout notes (TPU): contraction outputs put the xy-footprint P = 169
on the minor (lane) axis and w_z on the sublane axis — w_z = 21 on
lanes would pad 6x. Chunk capacity defaults to 64 (finer occupancy
granularity than the xy-packed 128: z-blocking multiplies active
tiles, so per-tile counts shrink).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops.delta import Kernel, get_kernel
from ibamr_tpu.ops.interaction import _centering_offsets
from ibamr_tpu.ops.interaction_fast import (
    _phi_safe, bucketed_channel, contract_compressed,
    spread_overflow_fallbacks, unbucket_with_overflow)

Vel = Tuple[jnp.ndarray, ...]


class BucketGeometry3(NamedTuple):
    """Static fully-blocked configuration (python ints -> one
    compilation). All ``dim`` axes carry a (tile, nblk, width) triple;
    ``width_d = tile_d + support + 1`` (the +-1 margin absorbs the
    per-centering j0 shift, same convention as interaction_fast)."""
    tile: Tuple[int, ...]
    nblk: Tuple[int, ...]
    cap: int                  # marker slots per chunk
    support: int
    width: Tuple[int, ...]


def make_geometry3(grid: StaggeredGrid, kernel: Kernel = "IB_4",
                   tile: int = 8, tile_last: int = 16,
                   cap: int = 64) -> BucketGeometry3:
    support, _ = get_kernel(kernel)
    tiles = tuple([tile] * (grid.dim - 1) + [tile_last])
    for d, (n, t) in enumerate(zip(grid.n, tiles)):
        if t < support + 1:
            raise ValueError(
                f"tile {t} (axis {d}) must be >= support+1 = "
                f"{support + 1} (spill must fit one tile)")
        if n % t != 0:
            raise ValueError(
                f"grid extent {n} not divisible by tile {t} (axis {d})")
        if n < t + support + 1:
            raise ValueError(
                f"grid extent {n} too small for tile {t} + support "
                f"{support} + 1 (axis {d})")
    return BucketGeometry3(
        tile=tiles,
        nblk=tuple(n // t for n, t in zip(grid.n, tiles)),
        cap=int(cap),
        support=int(support),
        width=tuple(t + support + 1 for t in tiles))


class PackedBuckets3(NamedTuple):
    """Chunk-packed marker layout over (x, y, z)-tiles. Duck-types the
    shared-fallback fields of interaction_fast.Buckets."""
    Xb: jnp.ndarray               # (Q, c, dim)
    wb: jnp.ndarray               # (Q, c)
    slot_of_marker: jnp.ndarray   # (N,)
    w_overflow: jnp.ndarray       # (N,)
    o_idx: jnp.ndarray            # (ocap,)
    o_w: jnp.ndarray              # (ocap,)
    any_overflow: jnp.ndarray     # () bool
    exceeded: jnp.ndarray         # () bool
    x0: Tuple[jnp.ndarray, ...]   # per axis: (Q,) tile origin cell
    tile_of_chunk: jnp.ndarray    # (Q,) int32 nondecreasing


def _block_ids3_np(grid, Xn, support, tiles):
    bid = np.zeros(len(Xn), dtype=np.int64)
    for d in range(grid.dim):
        xi = (Xn[:, d] - grid.x_lo[d]) / grid.dx[d] - 0.5
        j0 = np.floor(xi - 0.5 * support).astype(np.int64) + 1
        bid = bid * (grid.n[d] // tiles[d]) \
            + np.mod(j0, grid.n[d]) // tiles[d]
    return bid


def suggest_chunks3(grid: StaggeredGrid, X, kernel: Kernel = "IB_4",
                    tile: int = 8, tile_last: int = 16,
                    chunk: int = 64, slack: float = 1.3) -> int:
    """Host-side chunk-capacity heuristic from a concrete marker
    distribution (slack x the exact demand sum(ceil(count/c)))."""
    Xn = np.asarray(X)
    support, _ = get_kernel(kernel)
    tiles = tuple([tile] * (grid.dim - 1) + [tile_last])
    bids = _block_ids3_np(grid, Xn, support, tiles)
    B = int(np.prod([n // t for n, t in zip(grid.n, tiles)]))
    counts = np.bincount(bids, minlength=B)
    need = int(np.sum(-(-counts // chunk)))
    return max(8, int(math.ceil(need * slack)))


def pack_markers3(geom: BucketGeometry3, grid: StaggeredGrid,
                  X: jnp.ndarray,
                  weights: Optional[jnp.ndarray] = None,
                  nchunks: int = 1024,
                  overflow_cap: Optional[int] = None) -> PackedBuckets3:
    """Bucket markers by (x,y,z)-tile, pack into ``Q`` chunks of
    ``geom.cap`` slots in tile order. The sort/assign/scatter/overflow
    machinery is interaction_packed.chunk_pack_core — shared with the
    xy-packed engine so the two layouts cannot diverge; only the tile
    id (all dim axes here) and the x0 decode differ."""
    from ibamr_tpu.ops.interaction_packed import (chunk_pack_core,
                                                  default_overflow_cap)

    N, dim = X.shape
    if weights is None:
        weights = jnp.ones((N,), dtype=X.dtype)
    if overflow_cap is None:
        overflow_cap = default_overflow_cap(N)
    s = geom.support
    Q = int(nchunks)
    bid = jnp.zeros((N,), dtype=jnp.int32)
    for d in range(dim):
        xi = (X[:, d] - grid.x_lo[d]) / grid.dx[d] - 0.5
        j0 = jnp.floor(xi - 0.5 * s).astype(jnp.int32) + 1
        b = jnp.mod(j0, grid.n[d]) // geom.tile[d]
        bid = bid * geom.nblk[d] + b
    B = int(np.prod(geom.nblk))

    (Xb, wb, slot_of_marker, w_overflow, o_idx, o_w, n_over,
     exceeded, tid, _) = chunk_pack_core(bid, X, weights, Q, geom.cap,
                                         B, overflow_cap)
    x0 = []
    for d in range(dim):
        ids = tid
        for a in range(dim - 1, d, -1):
            ids = ids // geom.nblk[a]
        x0.append((ids % geom.nblk[d]) * geom.tile[d])
    return PackedBuckets3(Xb=Xb, wb=wb, slot_of_marker=slot_of_marker,
                          w_overflow=w_overflow, o_idx=o_idx, o_w=o_w,
                          any_overflow=n_over > 0, exceeded=exceeded,
                          x0=tuple(x0), tile_of_chunk=tid)


def _axis_weights3(geom, grid, b: PackedBuckets3, d: int, off: float,
                   phi):
    """(Q, c, width_d) delta weights over the footprint of axis d
    (footprint starts one cell below the tile origin)."""
    n = grid.n[d]
    xi = (b.Xb[..., d] - grid.x_lo[d]) / grid.dx[d] - off
    l = jnp.arange(geom.width[d], dtype=xi.dtype)
    base = b.x0[d].astype(xi.dtype)[:, None, None] - 1.0
    t = xi[..., None] - (base + l)
    t = jnp.mod(t + 0.5 * n, float(n)) - 0.5 * n
    return phi(t)


def _tile_weights3(geom, grid, b: PackedBuckets3, centering,
                   kernel: Kernel):
    """A (Q, c, P) over the first dim-1 axes + Wz (Q, c, w_last)."""
    support, phi0 = get_kernel(kernel)
    phi = _phi_safe(phi0, support)
    offs = _centering_offsets(grid, centering)
    dim = grid.dim
    Ws = [_axis_weights3(geom, grid, b, d, offs[d], phi)
          for d in range(dim)]
    A = Ws[0]
    for W in Ws[1:-1]:
        A = A[..., :, None] * W[..., None, :]
        A = A.reshape(A.shape[0], A.shape[1], -1)
    return A, Ws[-1]


def _fold_spills_to_grid(geom, grid, T: jnp.ndarray) -> jnp.ndarray:
    """Spill-folding overlap-add: T in the CONTRACTION-OUTPUT layout
    (nb0[, nb1], nb2, w_last, w0[, w1]) -> grid.

    Per axis, the spill segment [tile, width) of block b lies entirely
    inside block b+1's core [0, s+1) (guaranteed by tile >= s+1), so a
    roll-by-one-block + add on the SMALL tile tensor replaces the
    masked grid-size accumulation of interaction_fast._overlap_add.
    Folding happens IN the contraction layout (largest axis first, so
    every later pass touches a smaller tensor and no pre-transpose of
    the widths tensor is ever materialized); only the folded core —
    exactly grid-sized — pays the interleave transpose. Footprint base
    = tile origin - 1 -> one final multi-axis roll(-1)."""
    dim = grid.dim
    nb, tl, wd = geom.nblk, geom.tile, geom.width
    # width-axis position for block axis d in the contraction layout
    w_ax = {dim - 1: dim}
    for d in range(dim - 1):
        w_ax[d] = dim + 1 + d
    # fold the largest-relative-shrink axes first, so every later
    # pass touches the smallest possible tensor
    for d in sorted(range(dim), key=lambda a: tl[a] / wd[a]):
        ax_b, ax_w = d, w_ax[d]
        core = jax.lax.slice_in_dim(T, 0, tl[d], axis=ax_w)
        spill = jax.lax.slice_in_dim(T, tl[d], wd[d], axis=ax_w)
        spill = jnp.roll(spill, 1, axis=ax_b)    # periodic successor
        pad = [(0, 0)] * core.ndim
        pad[ax_w] = (0, tl[d] - (wd[d] - tl[d]))
        T = core + jnp.pad(spill, pad)
    perm = []
    for d in range(dim):
        perm += [d, w_ax[d]]
    out = T.transpose(perm).reshape(grid.n)
    return jnp.roll(out, (-1,) * dim, tuple(range(dim)))


def _extract_tiles3(geom, grid, f: jnp.ndarray) -> jnp.ndarray:
    """Gather every block's (width...) footprint -> (B, w_last, P)
    with the xy-footprint combined on the MINOR axis (P on lanes)."""
    dim = grid.dim
    arr = f
    for d in range(dim):
        idx = (np.arange(geom.nblk[d])[:, None] * geom.tile[d] - 1
               + np.arange(geom.width[d])[None, :]) % grid.n[d]
        arr = jnp.take(arr, jnp.asarray(idx.reshape(-1)), axis=2 * d)
        arr = arr.reshape(arr.shape[:2 * d]
                          + (geom.nblk[d], geom.width[d])
                          + arr.shape[2 * d + 1:])
    # arr: (nb0, w0[, nb1, w1], nb2, w2) -> (B, w_last, P)
    B = int(np.prod(geom.nblk))
    if dim == 2:
        arr = arr.transpose(0, 2, 3, 1)          # nb0 nb1 w1 w0
        return arr.reshape(B, geom.width[1], geom.width[0])
    arr = arr.transpose(0, 2, 4, 5, 1, 3)        # nb0 nb1 nb2 w2 w0 w1
    return arr.reshape(B, geom.width[dim - 1],
                       int(np.prod(geom.width[:dim - 1])))


def spread_packed3(geom: BucketGeometry3, grid: StaggeredGrid,
                   b: PackedBuckets3, F: jnp.ndarray, X: jnp.ndarray,
                   centering, kernel: Kernel,
                   precision=jax.lax.Precision.HIGHEST,
                   compute_dtype=None) -> jnp.ndarray:
    """Spread marker values F (N,) -> grid field (exact vs the scatter
    oracle up to roundoff; overflow through the shared fallback)."""
    inv_vol = 1.0 / math.prod(grid.dx)
    Ff = bucketed_channel(b, F)
    A, Wz = _tile_weights3(geom, grid, b, centering, kernel)
    A = A * (Ff * b.wb * inv_vol)[..., None]
    # (Q, w_last, P): footprint P on the minor (lane) axis
    Tq = contract_compressed("qmp,qmw->qwp", A, Wz, compute_dtype,
                             precision=precision)
    B = int(np.prod(geom.nblk))
    T = jax.ops.segment_sum(Tq, b.tile_of_chunk, num_segments=B,
                            indices_are_sorted=True)
    dim = grid.dim
    # stay in the contraction layout — the fold shrinks the tensor
    # BEFORE any transpose materializes
    T = T.reshape(tuple(geom.nblk) + (geom.width[dim - 1],)
                  + tuple(geom.width[:dim - 1]))
    out = _fold_spills_to_grid(geom, grid, T)
    return spread_overflow_fallbacks(out, b, F, X, grid, centering,
                                     kernel)


def interpolate_packed3(geom: BucketGeometry3, grid: StaggeredGrid,
                        b: PackedBuckets3, f: jnp.ndarray,
                        X: jnp.ndarray, centering, kernel: Kernel,
                        precision=jax.lax.Precision.HIGHEST,
                        compute_dtype=None) -> jnp.ndarray:
    """Interpolate grid field at markers -> (N,) (adjoint of spread)."""
    T = _extract_tiles3(geom, grid, f)               # (B, w_last, P)
    Tq = jnp.take(T, b.tile_of_chunk, axis=0)        # (Q, w_last, P)
    A, Wz = _tile_weights3(geom, grid, b, centering, kernel)
    D = contract_compressed("qwp,qmw->qmp", Tq, Wz, compute_dtype,
                            precision=precision)
    Ub = jnp.sum(A * D, axis=-1) * b.wb              # (Q, c)
    return unbucket_with_overflow(Ub, b, f, X, grid, centering, kernel)


class PackedInteraction3:
    """Drop-in FastInteraction-shaped engine: fully-blocked
    occupancy-packed chunks + spill-folding overlap-add. Bucket+pack
    once per X (``buckets``), reuse for all components and both
    directions within a step (the ctx protocol)."""

    def __init__(self, grid: StaggeredGrid, kernel: Kernel = "IB_4",
                 tile: int = 8, tile_last: int = 16, chunk: int = 64,
                 nchunks: int = 2048,
                 overflow_cap: Optional[int] = None,
                 compute_dtype=None):
        self.grid = grid
        self.kernel: Kernel = kernel
        self.geom = make_geometry3(grid, kernel, tile=tile,
                                   tile_last=tile_last, cap=chunk)
        self.nchunks = int(nchunks)
        self.overflow_cap = overflow_cap
        self.compute_dtype = compute_dtype

    def buckets(self, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None
                ) -> PackedBuckets3:
        return pack_markers3(self.geom, self.grid, X, weights,
                             nchunks=self.nchunks,
                             overflow_cap=self.overflow_cap)

    def interpolate_vel(self, u: Vel, X: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None,
                        b: Optional[PackedBuckets3] = None
                        ) -> jnp.ndarray:
        if b is None:
            b = self.buckets(X, weights)
        cols = [interpolate_packed3(self.geom, self.grid, b, u[d], X,
                                    d, self.kernel,
                                    compute_dtype=self.compute_dtype)
                for d in range(self.grid.dim)]
        return jnp.stack(cols, axis=-1)

    def spread_vel(self, F: jnp.ndarray, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   b: Optional[PackedBuckets3] = None) -> Vel:
        if b is None:
            b = self.buckets(X, weights)
        return tuple(spread_packed3(self.geom, self.grid, b, F[:, d],
                                    X, d, self.kernel,
                                    compute_dtype=self.compute_dtype)
                     for d in range(self.grid.dim))
