"""Pallas TPU programs for the occupancy-packed spread and interpolate.

Reference parity: the Fortran ``lagrangian_ib4_spread_3d`` /
``lagrangian_ib4_interp_3d`` inner loops (T2/P23), the north-star
scatter and its adjoint. Beside the XLA scatter-add (ops.interaction),
the MXU one-hot matmul (ops.interaction_fast) and its chunk-packed
layout (ops.interaction_packed), this module drives that SAME packed
layout with hand-written programs: ONE Pallas program per chunk
accumulating its tile's (W*W, NZ) dense block in VMEM (same-tile chunks
are consecutive grid steps, so the block stays resident), with no
weight intermediates in HBM and no scatter at all. The periodic
overlap-add of the finished tiles reuses interaction_fast._overlap_add
(pure data movement).

Weights evaluate the SAME delta.get_kernel functions at ALL W tile
offsets — compact support zeroes everything outside the true stencil,
so no dynamic slicing (and none of its TPU layout constraints) is
needed inside the kernel.

Correctness oracle: agreement with ops.interaction.spread /
interpolate to f32 roundoff (tested in interpret mode on the CPU;
tests/test_tpu_compile.py compiles the programs for a described v5e).

Two engines: :class:`PallasPackedInteraction` (both directions) and
:class:`HybridPackedInteraction` (this spread beside the XLA packed
interpolation with bf16 operands). Neither is what ``auto`` resolves
to anywhere; PERF.md §6 (PR 30) has their chip readings beside the
selected engines'.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops.delta import Kernel, get_kernel
from ibamr_tpu.ops.interaction import _centering_offsets
from ibamr_tpu.ops.interaction_fast import BucketGeometry, _phi_safe


def _marker_weight_preamble(geom: BucketGeometry, grid: StaggeredGrid,
                            offs, phi):
    """Shared per-tile weight computation for BOTH Pallas programs
    (spread and its interp adjoint must evaluate bit-identical weights):
    markers on the LANE axis, per-axis kernel-weight matrices
    ``wx (W0, cap), wy (W1, cap), wz (nz, cap)`` with periodic wrap.

    Mosaic-friendly by construction (round 3: the per-marker rank-1
    fori_loop form died in infer-vector-layout on a reshape): iota +
    broadcast arithmetic only — no reshape or transpose in-kernel.
    """
    W0, W1 = geom.width
    nz = grid.n[2]
    t0, t1 = geom.tile
    lo = grid.x_lo
    dx = grid.dx

    def weights(Xt, bx, by):
        x0 = bx * t0 - 1          # tile footprint origin (cells)
        y0 = by * t1 - 1
        ox = jax.lax.broadcasted_iota(jnp.int32, (W0, 1), 0).astype(
            Xt.dtype)
        oy = jax.lax.broadcasted_iota(jnp.int32, (W1, 1), 0).astype(
            Xt.dtype)
        kz = jax.lax.broadcasted_iota(jnp.int32, (nz, 1), 0).astype(
            Xt.dtype)

        xi = (Xt[0:1, :] - lo[0]) / dx[0] - offs[0]    # (1, cap)
        yi = (Xt[1:2, :] - lo[1]) / dx[1] - offs[1]
        zi = (Xt[2:3, :] - lo[2]) / dx[2] - offs[2]
        # wrapped distances (periodic) at every tile/axis offset
        tx = xi - (x0 + ox)                            # (W0, cap)
        tx = tx - jnp.round(tx / grid.n[0]) * grid.n[0]
        ty = yi - (y0 + oy)                            # (W1, cap)
        ty = ty - jnp.round(ty / grid.n[1]) * grid.n[1]
        tz = zi - kz                                   # (nz, cap)
        tz = tz - jnp.round(tz / nz) * nz
        return phi(tx), phi(ty), phi(tz)

    return weights


def _packed_spread_kernel_3d(geom: BucketGeometry, grid: StaggeredGrid,
                             offs, phi, interpret: bool):
    """Packed-chunk spread program: grid over CHUNKS (not tiles), the
    output block chosen by the scalar-prefetched ``tile_of_chunk`` map.
    Chunk ids are assigned in tile order (interaction_packed), so all
    chunks of one tile are consecutive grid steps and Pallas keeps the
    output block resident in VMEM — the revisit-accumulation pattern.
    Blocks no chunk visits are zeroed outside (visited-tile mask)."""
    from jax.experimental.pallas import tpu as pltpu

    W0, W1 = geom.width
    nz = grid.n[2]
    nb1 = geom.nblk[1]
    cap = geom.cap
    weights = _marker_weight_preamble(geom, grid, offs, phi)

    def kernel(tid_ref, XbT_ref, coef_ref, out_ref):
        q = pl.program_id(0)
        tid = tid_ref[q]
        prev = tid_ref[jnp.maximum(q - 1, 0)]
        first = (q == 0) | (tid != prev)
        bx = tid // nb1
        by = tid % nb1
        Xt = XbT_ref[0]                                # (3, cap)
        c = coef_ref[0]                                # (1, cap)
        wx, wy, wz = weights(Xt, bx, by)
        wzc = wz * c                                   # (nz, cap)

        @pl.when(first)
        def _():
            out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

        for a in range(W0):                            # static unroll
            rows = jax.lax.dot_general(
                wy * wx[a:a + 1, :], wzc,
                (((1,), (1,)), ((), ())),
                preferred_element_type=out_ref.dtype,
                precision=jax.lax.Precision.HIGHEST)   # (W1, nz)
            out_ref[0, a * W1:(a + 1) * W1, :] += rows

    def call(tid, Xb, coef, B):
        Q = Xb.shape[0]
        XbT = jnp.swapaxes(Xb, 1, 2)                   # (Q, 3, cap)
        coefT = coef[:, None, :]                       # (Q, 1, cap)
        gspec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Q,),
            in_specs=[
                pl.BlockSpec((1, 3, cap), lambda q, t: (q, 0, 0)),
                pl.BlockSpec((1, 1, cap), lambda q, t: (q, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, W0 * W1, nz),
                                   lambda q, t: (t[q], 0, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=gspec,
            out_shape=jax.ShapeDtypeStruct((B, W0 * W1, nz), Xb.dtype),
            interpret=interpret,
        )(tid, XbT, coefT)

    return call


def _packed_interp_kernel_3d(geom: BucketGeometry, grid: StaggeredGrid,
                             offs, phi, interpret: bool):
    """Packed-chunk interp program: per chunk, DMA the (P, nz) tile of
    ``tile_of_chunk[q]`` and contract against the chunk's marker
    weights (consecutive same-tile reads reuse the resident block)."""
    from jax.experimental.pallas import tpu as pltpu

    W0, W1 = geom.width
    nz = grid.n[2]
    nb1 = geom.nblk[1]
    cap = geom.cap
    weights = _marker_weight_preamble(geom, grid, offs, phi)

    def kernel(tid_ref, XbT_ref, T_ref, out_ref):
        q = pl.program_id(0)
        tid = tid_ref[q]
        bx = tid // nb1
        by = tid % nb1
        Xt = XbT_ref[0]                                # (3, cap)
        wx, wy, wz = weights(Xt, bx, by)               # (nz, cap) wz

        T = T_ref[0]                                   # (P, nz)
        tmp = jnp.dot(T, wz.astype(T.dtype),
                      preferred_element_type=T.dtype,
                      precision=jax.lax.Precision.HIGHEST)  # (P, cap)
        out = jnp.zeros((1, cap), dtype=T.dtype)
        for a in range(W0):                            # static unroll
            blk = tmp[a * W1:(a + 1) * W1, :]          # (W1, cap)
            inner = jnp.sum(wy.astype(T.dtype) * blk, axis=0,
                            keepdims=True)             # (1, cap)
            out = out + wx[a:a + 1, :].astype(T.dtype) * inner
        out_ref[0] = out

    def call(tid, Xb, T):
        Q = Xb.shape[0]
        XbT = jnp.swapaxes(Xb, 1, 2)                   # (Q, 3, cap)
        gspec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Q,),
            in_specs=[
                pl.BlockSpec((1, 3, cap), lambda q, t: (q, 0, 0)),
                pl.BlockSpec((1, W0 * W1, nz), lambda q, t: (t[q], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, cap), lambda q, t: (q, 0, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=gspec,
            out_shape=jax.ShapeDtypeStruct((Q, 1, cap), Xb.dtype),
            interpret=interpret,
        )(tid, XbT, T)

    return call


class PallasPackedInteraction:
    """Occupancy-packed chunks (ops.interaction_packed layout) driven by
    Pallas tile programs: the best of both round-3 engines. Work scales
    with ``Q*c ~ N`` instead of ``B*cap_max`` (packing), and the weight
    tensors never exist in HBM (Pallas) — the only large HBM arrays are
    the per-tile partial grids. Spread accumulates same-tile chunks in
    VMEM via the consecutive-revisit pattern; unvisited tiles are
    zeroed by a visited-tile mask outside the kernel."""

    def __init__(self, grid: StaggeredGrid, kernel: Kernel = "IB_4",
                 tile: int = 8, chunk: int = 128, nchunks: int = 1024,
                 overflow_cap: Optional[int] = None,
                 interpret: Optional[bool] = None):
        from ibamr_tpu.ops.interaction_fast import make_geometry

        if grid.dim != 3:
            raise ValueError("PallasPackedInteraction is 3D-only")
        self.grid = grid
        self.kernel: Kernel = kernel
        self.geom = make_geometry(grid, kernel, tile=tile, cap=chunk)
        self.nchunks = int(nchunks)
        self.overflow_cap = overflow_cap
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        self.interpret = bool(interpret)
        support, phi0 = get_kernel(kernel)
        self._phi = _phi_safe(phi0, support)

    def buckets(self, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None):
        from ibamr_tpu.ops.interaction_packed import pack_markers

        return pack_markers(self.geom, self.grid, X, weights=weights,
                            nchunks=self.nchunks,
                            overflow_cap=self.overflow_cap)

    def refresh(self, b, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None):
        """Slot-preserving half-step refresh (pallas twin): the chunk
        layout is the shared interaction_packed one, so the re-gather
        and drift-bound fallback are identical — the Pallas programs
        only ever see the resulting PackedBuckets."""
        from ibamr_tpu.ops.interaction_packed import refresh_packed

        return refresh_packed(self.geom, self.grid, b, X, weights)

    def _visited_mask(self, b) -> jnp.ndarray:
        import numpy as np

        B = int(np.prod(self.geom.nblk))
        occupied = jnp.sum(b.wb != 0, axis=1) > 0          # (Q,)
        return jnp.zeros((B,), dtype=bool).at[b.tile_of_chunk].max(
            occupied)

    def spread(self, F: jnp.ndarray, X: jnp.ndarray, centering,
               b) -> jnp.ndarray:
        import math as _math

        from ibamr_tpu.ops.interaction_fast import (
            _overlap_add, bucketed_channel, spread_overflow_fallbacks)
        import numpy as np

        geom = self.geom
        grid = self.grid
        B = int(np.prod(geom.nblk))
        inv_vol = 1.0 / _math.prod(grid.dx)
        offs = _centering_offsets(grid, centering)
        coef = bucketed_channel(b, F) * b.wb * inv_vol
        call = _packed_spread_kernel_3d(geom, grid, offs, self._phi,
                                        self.interpret)
        T = call(b.tile_of_chunk, b.Xb.astype(coef.dtype), coef, B)
        T = jnp.where(self._visited_mask(b)[:, None, None], T, 0.0)
        T = T.reshape((B,) + tuple(geom.width) + (grid.n[2],))
        out = _overlap_add(geom, grid, T.astype(F.dtype))
        return spread_overflow_fallbacks(out, b, F, X, grid, centering,
                                         self.kernel)

    def spread_vel(self, F: jnp.ndarray, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   b=None) -> tuple:
        if b is None:
            b = self.buckets(X, weights=weights)
        return tuple(self.spread(F[:, d], X, d, b)
                     for d in range(self.grid.dim))

    def interpolate(self, f: jnp.ndarray, X: jnp.ndarray, centering,
                    b) -> jnp.ndarray:
        from ibamr_tpu.ops.interaction_fast import (
            _extract_tiles, unbucket_with_overflow)

        geom = self.geom
        grid = self.grid
        offs = _centering_offsets(grid, centering)
        T = _extract_tiles(geom, grid, f)             # (B, P, nz)
        call = _packed_interp_kernel_3d(geom, grid, offs, self._phi,
                                        self.interpret)
        Ub = call(b.tile_of_chunk, b.Xb.astype(f.dtype),
                  T.astype(f.dtype))[:, 0, :]
        Ub = Ub * b.wb                                # (Q, cap)
        return unbucket_with_overflow(Ub, b, f, X, grid, centering,
                                      self.kernel)

    def interpolate_vel(self, u, X: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None,
                        b=None) -> jnp.ndarray:
        if b is None:
            b = self.buckets(X, weights=weights)
        return jnp.stack([self.interpolate(u[d], X, d, b)
                          for d in range(self.grid.dim)], axis=-1)


class HybridPackedInteraction:
    """Pallas-packed SPREAD + XLA packed (bf16-compressible) INTERP
    over ONE shared PackedBuckets context. Motivated by the round-5
    on-chip phases table: within the packed engine spread costs 28.8 ms
    to interp's 13.7 for identical dot work — the spread overlap-add's
    materialized per-tile partials are the waste, and the Pallas spread
    program accumulates them in VMEM instead; interp has no such
    asymmetry, and the XLA interp with bf16-compressed operands is the
    measured-cheapest interp. This engine composes the best measured
    direction of each backend. Same exactness contract as both parents
    (scatter-oracle equality, overflow fallback)."""

    def __init__(self, grid: StaggeredGrid, kernel: Kernel = "IB_4",
                 tile: int = 8, chunk: int = 128, nchunks: int = 1024,
                 overflow_cap: Optional[int] = None,
                 compute_dtype=None, interpret: Optional[bool] = None):
        from ibamr_tpu.ops.interaction_packed import PackedInteraction

        self._pal = PallasPackedInteraction(
            grid, kernel=kernel, tile=tile, chunk=chunk,
            nchunks=nchunks, overflow_cap=overflow_cap,
            interpret=interpret)
        self._xla = PackedInteraction(
            grid, kernel=kernel, tile=tile, chunk=chunk,
            nchunks=nchunks, overflow_cap=overflow_cap,
            compute_dtype=compute_dtype)
        self.grid = grid
        self.kernel: Kernel = kernel
        self.geom = self._xla.geom
        self.nchunks = int(nchunks)
        self.overflow_cap = overflow_cap

    def buckets(self, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None):
        return self._xla.buckets(X, weights)

    def refresh(self, b, X: jnp.ndarray,
                weights: Optional[jnp.ndarray] = None):
        """Both backends read the ONE shared PackedBuckets, so one
        slot-preserving refresh serves spread and interp alike."""
        return self._xla.refresh(b, X, weights)

    def spread_vel(self, F: jnp.ndarray, X: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   b=None) -> tuple:
        if b is None:
            b = self.buckets(X, weights=weights)
        return self._pal.spread_vel(F, X, weights=weights, b=b)

    def interpolate_vel(self, u, X: jnp.ndarray,
                        weights: Optional[jnp.ndarray] = None,
                        b=None) -> jnp.ndarray:
        if b is None:
            b = self.buckets(X, weights=weights)
        return self._xla.interpolate_vel(u, X, weights=weights, b=b)

