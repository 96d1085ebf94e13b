"""Convective (advection) operators for the staggered INS equations.

Reference parity: the INSStaggered*ConvectiveOperator family (P4, SURVEY.md
§2.2) — PPM/upwind/centered Godunov-type operators with Fortran flux loops.
TPU-first redesign: the fluxes are whole-array fused stencils (jnp.roll or
ghost-padded slices), conservative (divergence) form on the MAC grid, so
XLA fuses the entire N(u) evaluation into a few HBM passes; no per-cell
Riemann logic.

Conventions as in ibamr_tpu.ops.stencils: u_d[i] at the lower d-face of
cell i. The operator returns N(u)_d at u_d's own faces, where
N(u)_d = sum_e d/dx_e (u_e u_d) (conservative form; equals u.grad u for
discretely divergence-free u).

Schemes:
- "centered": 2nd-order centered flux averages (energy-stable at moderate
  CFL with CN diffusion; the default for smooth acceptance configs).
- "upwind": 1st-order donor-cell upwinding of the advected component
  (robust, diffusive; the stabilized fallback).
- "ppm": piecewise-parabolic (Colella–Woodward 1984) limited
  reconstruction, upwinded at faces — the reference's default operator
  (``INSStaggeredPPMConvectiveOperator``), implemented as whole-array
  limited interpolants instead of Fortran predictor loops.

Three code paths:
- :func:`convective_rate` — the original fully-periodic roll formulation
  (centered/upwind only; kept as the minimal-HBM fast path).
- :func:`convective_rate_bc` — ghost-padded formulation supporting all
  schemes AND no-slip walls on any subset of axes (the wall-bounded
  Navier–Stokes path, VERDICT round 1 item 4), including inhomogeneous
  tangential wall velocities (moving lids). Wall storage follows
  ibamr_tpu.integrators.ins_walls: the wall-NORMAL component pins slot 0
  along its own axis to the lo wall face (and the hi wall face is the
  wrap image of slot 0), so its beyond-wall ghosts are odd reflections
  about the wall NODE; tangential components are cell-centered along the
  wall axis, so their ghosts reflect about the wall PLANE
  (ghost = 2*V_wall - interior).
- ``ops/pallas_convection.convective_rate_ppm_fused`` — the same PPM
  arithmetic as :func:`convective_rate_bc` evaluated slab by slab in a
  Pallas kernel whose intermediates never leave VMEM (the padded path
  moves 45 times the bytes the operator needs at 256^3), walls
  included: reflecting halo planes along axis 0, ghosted rotations in
  a plane. :func:`convective_rate_select` takes it where the code can
  see that it applies — scheme ``ppm``, three float32 components of
  one rank-3 shape whose last two extents are multiples of (8, 128),
  ``dx`` and the walls' tangential values Python numbers — and
  :func:`convective_rate_bc` everywhere else (``cui``, 2D, the
  16^3-64^3 test grids, float64, a traced wall value, the sharded
  wrapper); no option chooses. The padded path stays the oracle and
  supplies the fused path's VJP.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp

from ibamr_tpu import obs
from ibamr_tpu.ops import stencils

Vel = Tuple[jnp.ndarray, ...]

# ghost depth of the padded path: PPM face states reach 3 cells out
_G = 3


def _avg_m(f: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Backward 2-point average: value at i-1/2 from i-1, i."""
    return 0.5 * (f + jnp.roll(f, 1, axis))


def _avg_p(f: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Forward 2-point average: value at i+1/2 from i, i+1."""
    return 0.5 * (f + jnp.roll(f, -1, axis))


def _upwind_m(f: jnp.ndarray, vel: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Donor-cell value at i-1/2 given advecting velocity there."""
    return jnp.where(vel >= 0, jnp.roll(f, 1, axis), f)


def _upwind_p(f: jnp.ndarray, vel: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Donor-cell value at i+1/2 given advecting velocity there."""
    return jnp.where(vel >= 0, f, jnp.roll(f, -1, axis))


def _cui_face(U: jnp.ndarray, C: jnp.ndarray,
              D: jnp.ndarray) -> jnp.ndarray:
    """CUI face value from (far-upwind, upwind, downwind) cell states:
    cubic upwind interpolation limited by the convective-boundedness
    criterion in normalized-variable form (Waterson & Deconinck, JCP
    224 (2007); the reference's AdvDiffCUIConvectiveOperator /
    INSVCStaggeredConservative CUI menu entry, SURVEY.md P4/P19 [U]).

    NVD: phi_hat = (C-U)/(D-U); the limited face value is
      3*phi_hat           on (0, 1/6]
      3/4*phi_hat + 3/8   on (1/6, 5/6)   (the cubic-upwind segment)
      1                   on [5/6, 1)
      phi_hat (upwind)    outside (0, 1)  (non-smooth: donor cell)
    """
    den = D - U
    # guard the normalized variable where D == U (uniform data: face
    # value reduces to C regardless of the branch taken)
    safe = jnp.where(jnp.abs(den) > 0.0, den, 1.0)
    ph = (C - U) / safe
    f_hat = jnp.where(
        ph < 1.0 / 6.0, 3.0 * ph,
        jnp.where(ph <= 5.0 / 6.0, 0.75 * ph + 0.375,
                  jnp.ones_like(ph)))
    f_hat = jnp.where((ph > 0.0) & (ph < 1.0), f_hat, ph)
    return jnp.where(jnp.abs(den) > 0.0, U + f_hat * den, C)


def advective_face_value(Qm: jnp.ndarray, Qp: jnp.ndarray,
                         vel: jnp.ndarray, scheme: str,
                         Qmm: Optional[jnp.ndarray] = None,
                         Qpp: Optional[jnp.ndarray] = None
                         ) -> jnp.ndarray:
    """Face value of an advected scalar from its two neighbor cells
    (Qm below the face, Qp above) and the face-normal velocity — the one
    shared scheme-selection point for the cell-centered transport paths
    (adv_diff and the two-level AMR fluxes). ``"cui"`` additionally
    needs the far neighbors Qmm (below Qm) and Qpp (above Qp)."""
    if scheme == "centered":
        return 0.5 * (Qm + Qp)
    if scheme == "upwind":
        return jnp.where(vel > 0, Qm, Qp)
    if scheme == "cui":
        if Qmm is None or Qpp is None:
            raise ValueError("cui needs the far-neighbor states "
                             "Qmm/Qpp")
        up = _cui_face(Qmm, Qm, Qp)    # vel >= 0: C = Qm, U = Qmm
        dn = _cui_face(Qpp, Qp, Qm)    # vel <  0: C = Qp, U = Qpp
        return jnp.where(vel > 0, up,
                         jnp.where(vel < 0, dn, 0.5 * (up + dn)))
    raise ValueError(f"unknown convective scheme {scheme!r}")


def convective_rate(u: Vel, dx: Sequence[float], scheme: str = "centered") -> Vel:
    """N(u)_d = sum_e d/dx_e(u_e u_d), each component at its own faces."""
    if scheme not in ("centered", "upwind"):
        raise ValueError(f"unknown convective scheme {scheme!r}")
    dim = len(u)
    out = []
    for d in range(dim):
        acc = jnp.zeros_like(u[d])
        for e in range(dim):
            if e == d:
                # flux at cell centers along d: (avg u_d)^2 or upwind
                adv = _avg_p(u[d], d)           # advecting velocity at centers
                if scheme == "upwind":
                    q = _upwind_p(u[d], adv, d)
                else:
                    q = adv
                flux = adv * q                   # at cell centers
                acc = acc + (flux - jnp.roll(flux, 1, d)) / dx[d]
            else:
                # flux at d-e edges (corner i-1/2 in d, j-1/2 in e):
                # u_e averaged along d, u_d averaged (or upwinded) along e
                adv = _avg_m(u[e], d)            # u_e at the edge
                if scheme == "upwind":
                    q = _upwind_m(u[d], adv, e)
                else:
                    q = _avg_m(u[d], e)
                flux = adv * q                   # at edges (lower in e)
                acc = acc + (jnp.roll(flux, -1, e) - flux) / dx[e]
        out.append(acc)
    return tuple(out)


# ---------------------------------------------------------------------------
# Ghost-padded path: walls + PPM (convective_rate_bc)
# ---------------------------------------------------------------------------

def _take(a: jnp.ndarray, axis: int, lo: int, hi: int) -> jnp.ndarray:
    return stencils.axis_slice(a, axis, lo, hi)


def _pad_wrap(a: jnp.ndarray, axis: int, g: int) -> jnp.ndarray:
    n = a.shape[axis]
    return jnp.concatenate(
        [_take(a, axis, n - g, n), a, _take(a, axis, 0, g)], axis)


def _pad_cell_wall(a: jnp.ndarray, axis: int, g: int,
                   v_lo: float = 0.0, v_hi: float = 0.0) -> jnp.ndarray:
    """Ghosts for data at CELL CENTERS along a wall axis: odd reflection
    about the wall plane through the Dirichlet value
    (ghost[-1-k] = 2 v_lo - a[k]); v != 0 is a moving tangential wall."""
    n = a.shape[axis]
    lo = 2.0 * v_lo - jnp.flip(_take(a, axis, 0, g), axis)
    hi = 2.0 * v_hi - jnp.flip(_take(a, axis, n - g, n), axis)
    return jnp.concatenate([lo, a, hi], axis)


def _pad_face_pinned_wall(a: jnp.ndarray, axis: int, g: int) -> jnp.ndarray:
    """Ghosts for data at FACES along its own wall axis (pinned storage:
    slot 0 == lo wall face == 0; hi wall face == wrap image). Odd
    reflection about the wall nodes: a[-k] = -a[k]; a[n] = 0 (hi wall),
    a[n+k] = -a[n-k]. No-penetration is homogeneous by construction."""
    n = a.shape[axis]
    lo = -jnp.flip(_take(a, axis, 1, g + 1), axis)
    zero = jnp.zeros_like(_take(a, axis, 0, 1))
    hi = jnp.concatenate(
        [zero, -jnp.flip(_take(a, axis, n - (g - 1), n), axis)], axis)
    return jnp.concatenate([lo, a, hi], axis)


def _sh(ap: jnp.ndarray, axis: int, s: int, n: int, g: int) -> jnp.ndarray:
    """Shifted view of a g-padded array: value at index i+s, i in [0, n)."""
    return _take(ap, axis, g + s, g + s + n)


def _mc_slope(c, m, p):
    """Monotonized-central slope of cell ``c`` from its neighbours."""
    d = 0.5 * (p - m)
    mono = (p - c) * (c - m) > 0.0
    lim = jnp.minimum(jnp.abs(d),
                      2.0 * jnp.minimum(jnp.abs(p - c), jnp.abs(c - m)))
    return jnp.where(mono, jnp.sign(d) * lim, 0.0)


def _ppm_face(am, a, sm, s):
    """4th-order interpolant at the face between cells ``am | a`` with
    the limited-slope correction (CW84 1.6)."""
    return am + 0.5 * (a - am) - (1.0 / 6.0) * (s - sm)


def _ppm_monotonize(a, fL, fR):
    """Monotonize the cell's parabola (CW84 1.10): its limited
    lower/upper edge states from the face interpolants."""
    local_ext = (fR - a) * (a - fL) <= 0.0
    aL = jnp.where(local_ext, a, fL)
    aR = jnp.where(local_ext, a, fR)
    diff = aR - aL
    q6 = diff * (a - 0.5 * (aL + aR))
    d2 = diff * diff / 6.0
    aL = jnp.where(q6 > d2, 3.0 * a - 2.0 * aR, aL)
    aR = jnp.where(q6 < -d2, 3.0 * a - 2.0 * aL, aR)
    return aL, aR


def _upwind_face(adv, up, dn):
    """The upwind state by the sign of the advecting velocity, the
    centred value where it is exactly zero."""
    return jnp.where(adv > 0.0, up,
                     jnp.where(adv < 0.0, dn, 0.5 * (up + dn)))


def _ppm_states(ap: jnp.ndarray, axis: int, n: int, g: int):
    """CW84 limited parabola edge states over the EXTENDED cell range
    [-1, n] (length n+2 along ``axis``): returns (aL, aR) with aL/aR the
    monotonized lower/upper-face states of each 1D cell."""
    def ext(s):
        return _take(ap, axis, g - 1 + s, g + 1 + s + n)

    a, am, ap1 = ext(0), ext(-1), ext(1)
    am2, ap2 = ext(-2), ext(2)
    s0 = _mc_slope(a, am, ap1)
    sm = _mc_slope(am, am2, a)
    sp = _mc_slope(ap1, a, ap2)
    return _ppm_monotonize(a, _ppm_face(am, a, sm, s0),
                           _ppm_face(a, ap1, s0, sp))


def _face_value_padded(ap: jnp.ndarray, adv: jnp.ndarray, axis: int,
                       n: int, g: int, scheme: str,
                       shift: int) -> jnp.ndarray:
    """Advected value at the 1D faces ``i + shift - 1/2`` (shift=0: lower
    face of cell i; shift=1: upper face) from the g-padded cell data
    ``ap`` and the face-normal advecting velocity ``adv`` there."""
    qm = _sh(ap, axis, shift - 1, n, g)
    qp = _sh(ap, axis, shift, n, g)
    if scheme == "centered":
        return 0.5 * (qm + qp)
    if scheme == "upwind":
        return jnp.where(adv >= 0.0, qm, qp)
    if scheme == "ppm":
        aL, aR = _ppm_states(ap, axis, n, g)
        up = _take(aR, axis, shift, shift + n)        # aR of cell i+shift-1
        dn = _take(aL, axis, shift + 1, shift + 1 + n)  # aL of cell i+shift
        return _upwind_face(adv, up, dn)
    if scheme == "cui":
        qmm = _sh(ap, axis, shift - 2, n, g)
        qpp = _sh(ap, axis, shift + 1, n, g)
        up = _cui_face(qmm, qm, qp)
        dn = _cui_face(qpp, qp, qm)
        return _upwind_face(adv, up, dn)
    raise ValueError(f"unknown convective scheme {scheme!r}")


def _pin_wall_faces(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    idx = [slice(None)] * a.ndim
    idx[axis] = slice(0, 1)
    return a.at[tuple(idx)].set(0.0)


def convective_rate_bc(
        u: Vel, dx: Sequence[float], scheme: str = "ppm",
        wall_axes: Optional[Sequence[bool]] = None,
        wall_tangential: Optional[Dict[Tuple[int, int, int], float]] = None,
) -> Vel:
    """N(u)_d = sum_e d/dx_e(u_e u_d) with BC-aware ghost fills.

    ``wall_axes[e]`` puts no-slip walls on both sides of axis e (storage
    convention of ibamr_tpu.integrators.ins_walls); axes without walls
    are periodic. ``wall_tangential[(d, e, side)]`` prescribes the
    tangential velocity of component d on the side(0=lo,1=hi) wall of
    axis e (a moving lid); unset entries are 0 (stationary no-slip).

    Wall-edge momentum fluxes vanish identically (the advecting normal
    velocity is 0 at walls), so the flux-divergence rolls stay exact;
    the wall-normal output faces (pinned slots) are zeroed.
    """
    dim = len(u)
    if wall_axes is None:
        wall_axes = (False,) * dim
    tang = wall_tangential or {}
    g = _G
    out = []
    for d in range(dim):
        acc = jnp.zeros_like(u[d])
        n_d = u[d].shape
        for e in range(dim):
            n_e = n_d[e]
            if e == d:
                # 1D cells = the faces of u_d along d; fluxes at cell
                # centers (the 1D upper faces, shift=1)
                if wall_axes[d]:
                    ud_p = _pad_face_pinned_wall(u[d], d, g)
                else:
                    ud_p = _pad_wrap(u[d], d, g)
                adv = 0.5 * (_sh(ud_p, d, 0, n_e, g)
                             + _sh(ud_p, d, 1, n_e, g))
                q = _face_value_padded(ud_p, adv, d, n_e, g, scheme,
                                       shift=1)
                flux = adv * q
                acc = acc + (flux - jnp.roll(flux, 1, d)) / dx[d]
            else:
                # fluxes at d-e edges (lower d-face x lower e-face).
                # advecting u_e averaged along d (u_e is cell-centered
                # along d; its wall value on axis d is its tangential
                # Dirichlet datum there)
                if wall_axes[d]:
                    ue_p = _pad_cell_wall(
                        u[e], d, 1,
                        v_lo=tang.get((e, d, 0), 0.0),
                        v_hi=tang.get((e, d, 1), 0.0))
                else:
                    ue_p = _pad_wrap(u[e], d, 1)
                adv = 0.5 * (_sh(ue_p, d, -1, n_d[d], 1)
                             + _sh(ue_p, d, 0, n_d[d], 1))
                # advected u_d along e (cell-centered along e)
                if wall_axes[e]:
                    ud_p = _pad_cell_wall(
                        u[d], e, g,
                        v_lo=tang.get((d, e, 0), 0.0),
                        v_hi=tang.get((d, e, 1), 0.0))
                else:
                    ud_p = _pad_wrap(u[d], e, g)
                q = _face_value_padded(ud_p, adv, e, n_e, g, scheme,
                                       shift=0)
                flux = adv * q
                acc = acc + (jnp.roll(flux, -1, e) - flux) / dx[e]
        if wall_axes[d]:
            acc = _pin_wall_faces(acc, d)
        out.append(acc)
    return tuple(out)


# which evaluation a trace chose (bumped once per traced selection)
_FUSED_TOTAL = obs.counter("fluid_convect_fused_total")
_PADDED_TOTAL = obs.counter("fluid_convect_padded_total")
obs.describe("fluid_convect_fused_total",
             "traces of the ghost-padded-menu convective operator that "
             "took the slab-fused PPM kernel (periodic or walled)")
obs.describe("fluid_convect_padded_total",
             "traces of it that took convective_rate_bc")


def convective_rate_select(
        u: Vel, dx: Sequence[float], scheme: str = "ppm",
        wall_axes: Optional[Sequence[bool]] = None,
        wall_tangential: Optional[Dict[Tuple[int, int, int], float]] = None,
        partitioned: bool = False,
) -> Vel:
    """:func:`convective_rate_bc`'s operator by whichever evaluation
    fits what is observed at trace time: the slab-fused kernel for 3D
    float32 ``ppm`` on tile-aligned extents, walled or periodic along
    each axis, with ``dx`` and every wall's tangential value Python
    numbers (the kernel's statics); the ghost-padded path otherwise,
    and always where the caller's program is ``partitioned`` over a
    mesh (the sharded wrapper says so: a pallas_call does not
    partition). The choice is counted
    (``fluid_convect_{fused,padded}_total``) and told to the
    ``driver/chunk`` span whose call traced it (``convect_path``, and
    ``convect_walls``: the walled axes, ``"xyz"`` to ``""``)."""
    from ibamr_tpu.ops import pallas_convection

    walls = tuple(bool(w) for w in wall_axes or (False,) * len(u))
    tang = wall_tangential or {}
    fused = (scheme == "ppm" and not partitioned
             and all(isinstance(v, (int, float))
                     for v in (*dx, *tang.values()))
             and pallas_convection.fused_ppm_supported(u, walls))
    (_FUSED_TOTAL if fused else _PADDED_TOTAL).inc()
    obs.annotate("driver/chunk",
                 convect_path="fused" if fused else "padded",
                 convect_walls="".join(
                     name for name, w in zip("xyz", walls) if w))
    if fused:
        return pallas_convection.convective_rate_ppm_fused(
            tuple(u), tuple(dx), walls, tuple(sorted(tang.items())))
    return convective_rate_bc(u, dx, scheme, wall_axes, wall_tangential)
