"""Slab-fused PPM convective operator (Pallas TPU kernel).

The same arithmetic as ``ops/convection.convective_rate_bc(u, dx,
"ppm", wall_axes, wall_tangential)`` on a 3D float32 grid with any
combination of periodic and walled axes, evaluated slab by slab
along axis 0 with every intermediate (slopes, face interpolants,
monotonised edge states, upwinded face values, fluxes) in VMEM: HBM
sees the three velocity fields read (plus a 3-plane halo per slab) and
the three rates written. The ghost-padded path writes each of those
intermediates to HBM and reads it back through offset slices (18 GB by
XLA's own count at 256^3 against 0.4 GB the operator needs).

Layout: axes 1 and 2 are held whole in a block (their extents are
multiples of the (8, 128) float32 tile), so a shift along them is a
rotation (``pltpu.roll``) and the periodic wrap is exact without a
ghost cell; axis 0 has no tile constraint, so a shift along it is
another plane, and its wrap is the halo planes' index maps (mod n).
One grid step owns ``bz`` planes ``k in [0, bz)`` and sees planes
``[-3, bz + 3)``; all loops run plane by plane. The limiter is the
padded path's own functions (``_mc_slope``, ``_ppm_face``,
``_ppm_monotonize``, ``_upwind_face``), the slopes computed once per
pass and read at their shifts.

Walls (``wall_axes``, static; storage convention of
``integrators/ins_walls``: component d pins slot 0 along a walled
axis d, which the fields handed in must honour, as every state the
integrator's solves return does). The oracle's two ghost rules are
``_pad_face_pinned_wall`` (component d along its own axis d: odd
about the wall nodes, a[-k] = -a[k], a[n] = 0, a[n+k] = -a[n-k]) and
``_pad_cell_wall`` (the other two axes: odd about the wall plane
through the tangential value V, ghost = 2 V - interior).

- Axis 0: a field has one rule along it, so its halo planes' index
  maps REFLECT instead of wrapping, and the first and the last grid
  step copy them into the window through the rule's affine map
  (sign -1, offset 2 V or 0; plane n of component 0 written as 0).
  The plane loops then compute the hi wall node's cell from real
  ghost planes, unchanged.
- Axes 1 and 2: the row or lane a rotation wraps in is replaced by the
  ghost value under an iota mask: ``a[i-+1]`` by the rule; the slope
  beyond the wall by symmetry (data odd about the wall, so slopes
  even: s[-1] = s[0] cell-centred, s[-1] = s[1] and s[n] = -a[n-1]
  pinned); both face interpolants from the ghosted operands; and, for
  the pinned pass, the lower edge state of the hi wall node's cell,
  which is its lower face interpolant (the cell's parabola is odd
  about the node, where it is 0, so the monotonisation leaves it).
- What needs no ghost: a flux ON a wall (its advecting velocity is
  the pinned slot's 0, so the rotations of the flux differences
  stay), and the advecting velocity's ghost along a walled axis d
  (it feeds only rate d's pinned slot, which is written 0).

With no walled axis every such branch is a Python ``if`` on a static
value: the periodic kernel is traced and lowered as before.

Selection (shape, dtype, scheme; no option) lives in
``ops/convection.convective_rate_select``; ``convective_rate_bc``
stays the oracle, and the backward pass is its VJP.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ibamr_tpu.ops.convection import (_mc_slope, _ppm_face,
                                      _ppm_monotonize, _upwind_face,
                                      convective_rate_bc)

Vel = Tuple[jnp.ndarray, ...]

# planes of halo on each side of a slab: a PPM face value reaches
# a[i-2 .. i+2] through its slopes, the flux difference one more
HALO = 3
# planes a grid step owns: the largest divisor of n0 up to this (at
# 256^3 16 planes read 3.11 ms against 3.33 on the chip, for 92 MiB of
# VMEM against 54: not taken)
SLAB = 8
# scoped-VMEM request (the default, 16 MiB, holds less than one slab
# of three fields with its halo), and what the kernel's own buffers
# may take of it: Mosaic's spills need the rest
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
VMEM_BUFFER_BYTES = 64 * 1024 * 1024


def _slab(shape) -> int:
    return math.gcd(shape[0], SLAB)


def _buffer_bytes(shape) -> int:
    """VMEM the kernel's buffers take: the window of planes, slopes,
    two edge states and fluxes in scratch, and the pipeline's two
    copies of every input and output block."""
    bz = _slab(shape)
    scratch = 3 * (bz + 2 * HALO) + (bz + 4) + 2 * (bz + 2) + (bz + 1)
    pipeline = 2 * 3 * (bz + 2 * HALO) + 2 * 3 * bz
    return 4 * shape[1] * shape[2] * (scratch + pipeline)


def fused_ppm_supported(u: Sequence,
                        wall_axes: Sequence[bool] = (False,) * 3) -> bool:
    """Whether the fused kernel can take these fields: three float32
    components of one rank-3 shape whose last two extents fill whole
    (8, 128) tiles, a slab of whose planes fits VMEM (and, where axis 0
    is walled, whose planes 1..3 exist to be reflected)."""
    if len(u) != 3:
        return False
    shape = tuple(u[0].shape)
    if len(shape) != 3 or shape[0] < HALO + bool(wall_axes[0]):
        return False
    if shape[1] % 8 or shape[2] % 128:
        return False
    if _buffer_bytes(shape) > VMEM_BUFFER_BYTES:
        return False
    return all(tuple(c.shape) == shape and c.dtype == jnp.float32
               for c in u)


def _roll(x, shift, axis):
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _each(lo, hi, body):
    lax.fori_loop(lo, hi, lambda k, c: (body(k), c)[1], 0)


def _kernel(*refs, n0, bz, dx, walls, tang):
    per = 1 + 2 * HALO                 # a field's block and its halo planes
    ins, refs = refs[:3 * per], refs[3 * per:]
    outs, (buf, slope, edge_l, edge_r, flux) = refs[:3], refs[3:]
    plane_shape = buf.shape[2:]

    # where a rotation along a walled block axis wraps: its first and
    # last row or lane, built once per grid step
    at_lo, at_hi = {}, {}
    for ax in range(2):
        if walls[ax + 1]:
            i = lax.broadcasted_iota(jnp.int32, plane_shape, ax)
            at_lo[ax], at_hi[ax] = i == 0, i == plane_shape[ax] - 1

    def twice_wall_value(c, e, side):
        return 2.0 * tang.get((c, e, side), 0.0)

    def halo_plane(c, j, src):
        """Window plane j of field c from its halo block: as it is
        inside the grid; beyond a wall of axis 0 (on the first and the
        last grid step only, unless a slab is thinner than the halo)
        its image under the field's ghost rule along that axis."""
        if not walls[0]:
            buf[c, j] = src[...]
            return
        g = pl.program_id(0) * bz + j - HALO     # the plane's own index
        inside = (g >= 0) & (g < n0)
        offset = 0.0 if c == 0 else twice_wall_value(c, 0, int(j >= HALO))

        @pl.when(inside)
        def _():
            buf[c, j] = src[...]

        @pl.when(jnp.logical_not(inside))
        def _():
            buf[c, j] = offset - src[...]

        if c == 0 and j >= HALO:
            @pl.when(g == n0)          # the pinned component's hi node
            def _():
                buf[c, j] = jnp.zeros(plane_shape, jnp.float32)

    # one contiguous window of planes [-3, bz + 3) per field (index
    # k + HALO), so a plane is one dynamic index on the untiled axis
    for c in range(3):
        main, halo = ins[per * c], ins[per * c + 1:per * (c + 1)]
        for j in range(HALO):
            halo_plane(c, j, halo[j])
            halo_plane(c, HALO + bz + j, halo[HALO + j])

        def copy(k, c=c, main=main):
            buf[c, HALO + k] = main[k]
        _each(0, bz, copy)

    def plane(c, k):
        return buf[c, k + HALO]

    def in_plane(d, e, k):
        """(flux difference)/dx of component d along the in-plane
        direction e at plane k: rotations along block axis e - 1,
        ghosted where that axis is walled."""
        ax = e - 1
        a = plane(d, k)
        am, ap = _roll(a, 1, ax), _roll(a, -1, ax)
        if walls[e]:
            lo, hi = at_lo[ax], at_hi[ax]
            if e == d:
                ap = jnp.where(hi, 0.0, ap)
                am = jnp.where(lo, -ap, am)
            else:
                am = jnp.where(lo, twice_wall_value(d, e, 0) - a, am)
                ap = jnp.where(hi, twice_wall_value(d, e, 1) - a, ap)
        s = _mc_slope(a, am, ap)
        if walls[e]:
            sm, sp = _roll(s, 1, ax), _roll(s, -1, ax)
            if e == d:
                sm, sp = jnp.where(lo, sp, sm), jnp.where(hi, -a, sp)
            else:
                sm, sp = jnp.where(lo, s, sm), jnp.where(hi, s, sp)
            f, f_up = _ppm_face(am, a, sm, s), _ppm_face(a, ap, s, sp)
        else:
            f = _ppm_face(am, a, _roll(s, 1, ax), s)
            f_up = _roll(f, -1, ax)
        aL, aR = _ppm_monotonize(a, f, f_up)
        if e == d:
            adv = 0.5 * (a + ap)
            aL_up = _roll(aL, -1, ax)
            if walls[e]:
                aL_up = jnp.where(hi, f_up, aL_up)
            fx = adv * _upwind_face(adv, aR, aL_up)
            return (fx - _roll(fx, 1, ax)) / dx[e]
        ue = plane(e, k)
        below = plane(e, k - 1) if d == 0 else _roll(ue, 1, d - 1)
        adv = 0.5 * (below + ue)
        fx = adv * _upwind_face(adv, _roll(aR, 1, ax), aL)
        return (_roll(fx, -1, ax) - fx) / dx[e]

    for d in range(3):
        # direction 0: the 1D cells are planes. slopes on [-2, bz + 2)
        # (index k + 2), edge states on [-1, bz + 1) (index k + 1)
        def slopes(k, d=d):
            slope[k + 2] = _mc_slope(plane(d, k), plane(d, k - 1),
                                     plane(d, k + 1))
        _each(-2, bz + 2, slopes)

        def edges(k, d=d):
            am, a, ap = plane(d, k - 1), plane(d, k), plane(d, k + 1)
            sm, s, sp = slope[k + 1], slope[k + 2], slope[k + 3]
            aL, aR = _ppm_monotonize(a, _ppm_face(am, a, sm, s),
                                     _ppm_face(a, ap, s, sp))
            edge_l[k + 1] = aL
            edge_r[k + 1] = aR
        _each(-1, bz + 1, edges)

        if d == 0:
            # fluxes at the cell centres k + 1/2, k in [-1, bz)
            def fluxes(k):
                adv = 0.5 * (plane(0, k) + plane(0, k + 1))
                flux[k + 1] = adv * _upwind_face(adv, edge_r[k + 1],
                                            edge_l[k + 2])
            _each(-1, bz, fluxes)
        else:
            # fluxes at the lower 0-faces k - 1/2, k in [0, bz]
            def fluxes(k, d=d):
                u0 = plane(0, k)
                adv = 0.5 * (_roll(u0, 1, d - 1) + u0)
                flux[k] = adv * _upwind_face(adv, edge_r[k], edge_l[k + 1])
            _each(0, bz + 1, fluxes)

        def rate(k, d=d):
            acc = (flux[k + 1] - flux[k]) / dx[0]
            acc = acc + in_plane(d, 1, k)
            acc = acc + in_plane(d, 2, k)
            if d > 0 and walls[d]:
                acc = jnp.where(at_lo[d - 1], 0.0, acc)
            outs[d][k] = acc
        _each(0, bz, rate)

        if d == 0 and walls[0]:
            # rate 0's pinned slot: the lo wall's plane
            @pl.when(pl.program_id(0) == 0)
            def _():
                outs[0][0] = jnp.zeros(plane_shape, jnp.float32)


def _call(u: Vel, dx: Tuple[float, ...], walls: Tuple[bool, ...],
          tang: Dict[Tuple[int, int, int], float]) -> Vel:
    n0, n1, n2 = u[0].shape
    bz = _slab(u[0].shape)
    main = pl.BlockSpec((bz, n1, n2), lambda i: (i, 0, 0))

    def halo(off, pinned):
        """The plane ``off`` planes from a slab's first: wrapped, or
        where axis 0 is walled the plane whose image the ghost is
        (pinned: -k -> k, n + k -> n - k, n itself is written;
        cell-centred: -1 - k -> k, n + k -> n - 1 - k)."""
        centred = 0 if pinned else 1

        def wrapped(i):
            return ((i * bz + n0 + off) % n0, 0, 0)

        def reflected(i):
            g = i * bz + off
            if off < 0:
                image = jnp.where(g < 0, -g - centred, g)
            else:
                image = jnp.where(g >= n0, 2 * n0 - g - centred, g)
            return (jnp.minimum(image, n0 - 1), 0, 0)

        return pl.BlockSpec((None, n1, n2),
                            reflected if walls[0] else wrapped)

    def per_field(pinned):
        return ([main] + [halo(j - HALO, pinned) for j in range(HALO)]
                + [halo(bz + j, pinned) for j in range(HALO)])

    in_specs = per_field(True) + per_field(False) * 2
    planes = functools.partial(pltpu.VMEM, dtype=jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, n0=n0, bz=bz, dx=dx, walls=walls,
                          tang=tang),
        out_shape=tuple(jax.ShapeDtypeStruct(c.shape, c.dtype)
                        for c in u),
        grid=(n0 // bz,),
        in_specs=in_specs,
        out_specs=tuple(main for _ in u),
        scratch_shapes=[
            planes((3, bz + 2 * HALO, n1, n2)),
            planes((bz + 4, n1, n2)),
            planes((bz + 2, n1, n2)),
            planes((bz + 2, n1, n2)),
            planes((bz + 1, n1, n2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() == "cpu",
        name="ppm_convect_fused",
    )(*[c for c in u for _ in range(1 + 2 * HALO)])
    return tuple(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def convective_rate_ppm_fused(
        u: Vel, dx: Tuple[float, ...],
        wall_axes: Tuple[bool, ...] = (False,) * 3,
        wall_tangential: Tuple[Tuple[Tuple[int, int, int], float], ...] = (),
) -> Vel:
    """N(u)_d = sum_e d/dx_e(u_e u_d), limited PPM, 3D float32 (see
    :func:`fused_ppm_supported`), no-slip walls on both sides of the
    axes ``wall_axes`` marks and periodic along the others; ``dx`` a
    tuple of Python floats, ``wall_tangential`` the items of
    ``convective_rate_bc``'s dict of Python floats (hashable: the
    walls are static). Differentiates as the ghost-padded operator
    does."""
    return _call(tuple(u), tuple(float(h) for h in dx),
                 tuple(bool(w) for w in wall_axes),
                 {key: float(v) for key, v in wall_tangential})


def _fwd(u, dx, wall_axes, wall_tangential):
    return convective_rate_ppm_fused(u, dx, wall_axes, wall_tangential), u


def _bwd(dx, wall_axes, wall_tangential, u, g):
    _, vjp = jax.vjp(
        lambda v: convective_rate_bc(v, dx, "ppm", wall_axes,
                                     dict(wall_tangential)), tuple(u))
    return vjp(tuple(g))


convective_rate_ppm_fused.defvjp(_fwd, _bwd)
