"""Navier–Stokes integrator for inflow/outflow (open-boundary) domains.

Reference parity: the ``INSStaggeredHierarchyIntegrator`` configuration
that every non-periodic, non-enclosed acceptance scenario uses — channel
and jet flows with prescribed-velocity inflows and traction-free open
outflows (P2/P3 + ``INSProjectionBcCoef``/``INSIntermediateVelocityBcCoef``
boundary plumbing, SURVEY.md §2.2). The enclosed/no-slip configurations
are served by :mod:`ibamr_tpu.integrators.ins_walls`; the periodic ones
by :mod:`ibamr_tpu.integrators.ins`. This module completes the boundary
menu with the open/traction case, driven by the coupled saddle solver of
:mod:`ibamr_tpu.solvers.stokes`.

Scheme: explicit first-order-upwind convection + backward-Euler viscous
step, coupled velocity–pressure solve each step (the reference's
"stokes solve per timestep" path, not the split projection):

    (1/dt) u^{n+1} - mu lap u^{n+1} + grad p = (1/dt) u^n - N(u^n) + f
    div u^{n+1} = 0

Everything is jit-traceable; the FGMRES saddle solve compiles into the
step function.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ibamr_tpu.bc import pad_boundary_data
from ibamr_tpu.solvers.stokes import StaggeredStokesSolver, StokesBC

Array = jnp.ndarray
Vel = Tuple[Array, ...]


class OpenINSState(NamedTuple):
    u: Vel
    p: Array
    t: Array


class INSOpenIntegrator:
    """Incompressible NS on a box domain with inflow/wall/open sides.

    ``bdry`` is the boundary-data dict of
    :meth:`StaggeredStokesSolver.make_rhs` — {(d, e, side): value}
    (inflow profiles, moving-wall tangential values), fixed at
    construction so the compiled step is data-free.
    """

    def __init__(self, n, dx, bc: StokesBC, mu: float, dt: float,
                 bdry: Optional[Dict] = None, rho: float = 1.0,
                 tol: float = 1e-8, dtype=jnp.float64,
                 convective_op_type: str = "upwind",
                 stab_band: int = 4):
        self.mu = float(mu)
        self.rho = float(rho)
        self.dt = float(dt)
        self.alpha = self.rho / self.dt
        convective_op_type = convective_op_type.lower()
        if convective_op_type not in ("upwind", "stabilized_ppm"):
            raise ValueError(
                f"unknown convective_op_type {convective_op_type!r}")
        self.convective_op_type = convective_op_type
        self.stab_band = int(stab_band)
        # ``tol`` is relative to the right-hand side and has to be
        # reachable in ``dtype``: an f32 solve asked for the default 1e-8
        # runs every restart
        self.solver = StaggeredStokesSolver(
            n, dx, bc, alpha=self.alpha, mu=self.mu, tol=tol,
            dtype=dtype)
        self.bdry = dict(bdry or {})
        self.n = self.solver.n
        self.dx = self.solver.dx

    # ------------------------------------------------------------------
    def initialize(self, u: Optional[Vel] = None) -> OpenINSState:
        s = self.solver
        if u is None:
            u = tuple(jnp.zeros(sh, dtype=s.dtype) for sh in s.shapes)
        p = jnp.zeros(s.n, dtype=s.dtype)
        return OpenINSState(u=tuple(u), p=p,
                            t=jnp.asarray(0.0, dtype=s.dtype))

    # -- advection helpers ---------------------------------------------
    def _ghost_with_data(self, c: Array, d: int) -> Array:
        """One ghost layer on EVERY axis honoring the actual boundary
        data (unlike the solver's homogeneous pad) — sequential
        applications of :meth:`_ghost_axis`, the one reflection
        implementation both advection paths share."""
        out = c
        for e in range(c.ndim):
            out = self._ghost_axis(out, d, e, width=1)
        return out

    def _to_cells(self, u: Vel) -> Vel:
        """Average every MAC component to cell centers (shape n)."""
        s = self.solver
        out = []
        for e, c in enumerate(u):
            if s.bc.periodic(e):
                out.append(0.5 * (c + jnp.roll(c, -1, axis=e)))
            else:
                lo = [slice(None)] * c.ndim
                hi = [slice(None)] * c.ndim
                lo[e] = slice(0, -1)
                hi[e] = slice(1, None)
                out.append(0.5 * (c[tuple(lo)] + c[tuple(hi)]))
        return tuple(out)

    def _advect(self, u: Vel) -> Vel:
        """First-order upwind N(u)_d = sum_e a_e * d(u_d)/dx_e with
        BC-data ghosts; advecting velocities interpolated through cell
        centers (compact, layout-uniform)."""
        s = self.solver
        uc = self._to_cells(u)                   # all at cells, shape n
        out = []
        for d, c in enumerate(u):
            G = self._ghost_with_data(c, d)
            center = tuple(slice(1, -1) for _ in range(c.ndim))
            N = jnp.zeros_like(c)
            for e in range(c.ndim):
                lo = list(center)
                hi = list(center)
                lo[e] = slice(0, -2)
                hi[e] = slice(2, None)
                dm = (c - G[tuple(lo)]) / s.dx[e]
                dp = (G[tuple(hi)] - c) / s.dx[e]
                a = self._advecting(uc, u, d, e)
                N = N + jnp.where(a > 0, a * dm, a * dp)
            out.append(N)
        return tuple(out)

    def _ghost_axis(self, c: Array, d: int, e: int, width: int) -> Array:
        """``width`` ghost layers along axis ``e`` only, honoring the
        boundary data (the wide-stencil fill the stabilized-PPM path
        needs; the one-layer all-axes fill above serves upwind)."""
        s = self.solver

        def take(lo, hi):
            sl = [slice(None)] * c.ndim
            sl[e] = slice(lo, hi)
            return c[tuple(sl)]

        n_e = c.shape[e]
        if s.bc.periodic(e):
            return jnp.concatenate(
                [take(n_e - width, n_e), c, take(0, width)], axis=e)
        if e != d:
            # cell-centered along e: odd reflection about prescribed
            # data, constant extrapolation past open sides
            lo_int = jnp.flip(take(0, width), axis=e)
            hi_int = jnp.flip(take(n_e - width, n_e), axis=e)
            if s.bc.side(e, 0).prescribed:
                v = pad_boundary_data(jnp.asarray(
                    self.bdry.get((d, e, 0), 0.0), c.dtype), c, e)
                lo_g = 2.0 * v - lo_int
            else:
                lo_g = jnp.repeat(take(0, 1), width, axis=e)
            if s.bc.side(e, 1).prescribed:
                v = pad_boundary_data(jnp.asarray(
                    self.bdry.get((d, e, 1), 0.0), c.dtype), c, e)
                hi_g = 2.0 * v - hi_int
            else:
                hi_g = jnp.repeat(take(n_e - 1, n_e), width, axis=e)
        else:
            # face-centered along its own axis: the boundary faces ARE
            # slots 0 / -1 (the saddle solve keeps them exact); odd
            # reflection through the boundary node for prescribed
            # sides, constant extrapolation for open ones
            if s.bc.side(e, 0).prescribed:
                lo_g = 2.0 * take(0, 1) - jnp.flip(take(1, width + 1),
                                                   axis=e)
            else:
                lo_g = jnp.repeat(take(0, 1), width, axis=e)
            if s.bc.side(e, 1).prescribed:
                hi_g = 2.0 * take(n_e - 1, n_e) - jnp.flip(
                    take(n_e - 1 - width, n_e - 1), axis=e)
            else:
                hi_g = jnp.repeat(take(n_e - 1, n_e), width, axis=e)
        return jnp.concatenate([lo_g, c, hi_g], axis=e)

    def _stab_mask(self, shape, e: int) -> Array:
        """Upwind-blend weight along flux axis ``e``: 1 at a
        non-periodic boundary, linear ramp to 0 over ``stab_band``
        cells (the reference's stabilized-PPM boundary band)."""
        s = self.solver
        n_e = shape[e]
        # the solver's working dtype, NOT a hard-coded f64: the ramp
        # values are exact in f32, and requesting f64 with x64 disabled
        # warns and silently truncates (graph-audit first-wave finding)
        idx = jnp.arange(n_e, dtype=s.dtype)
        chi = jnp.zeros((n_e,), dtype=s.dtype)
        band = float(max(self.stab_band, 1))
        if not s.bc.periodic(e):
            chi = jnp.maximum(chi, jnp.clip(1.0 - idx / band, 0.0, 1.0))
            chi = jnp.maximum(chi, jnp.clip(
                1.0 - (n_e - 1 - idx) / band, 0.0, 1.0))
        sh = [1] * len(shape)
        sh[e] = n_e
        return chi.reshape(sh)

    def _advect_stabilized(self, u: Vel) -> Vel:
        """PPM-reconstructed advective derivatives in the interior,
        blended to first-order upwind within ``stab_band`` cells of the
        physical boundaries — the
        ``INSStaggeredStabilizedPPMConvectiveOperator`` contract
        (SURVEY.md P4 [U]): high-order transport where the flow is
        smooth, damping at open/inflow boundaries where PPM's wide
        stencil would ring against the boundary model."""
        from ibamr_tpu.ops.convection import _face_value_padded, _sh

        s = self.solver
        g = 3
        uc = self._to_cells(u)
        out = []
        for d, c in enumerate(u):
            N = jnp.zeros_like(c)
            for e in range(c.ndim):
                a = self._advecting(uc, u, d, e)
                # midpoint advecting values between c's sample points
                # (wrap on periodic axes: an edge pad would pick the
                # wrong upwind donor at the seam)
                pad = [(0, 0)] * c.ndim
                pad[e] = (1, 1)
                ap = jnp.pad(a, pad,
                             mode="wrap" if s.bc.periodic(e) else "edge")
                lo_sl = [slice(None)] * c.ndim
                hi_sl = [slice(None)] * c.ndim
                lo_sl[e] = slice(0, -2)
                hi_sl[e] = slice(2, None)
                a_lo = 0.5 * (a + ap[tuple(lo_sl)])
                a_hi = 0.5 * (a + ap[tuple(hi_sl)])

                G = self._ghost_axis(c, d, e, width=g)
                n_e = c.shape[e]
                q_lo = _face_value_padded(G, a_lo, e, n_e, g, "ppm",
                                          shift=0)
                q_hi = _face_value_padded(G, a_hi, e, n_e, g, "ppm",
                                          shift=1)
                ppm_term = a * (q_hi - q_lo) / s.dx[e]

                c_m = _sh(G, e, -1, n_e, g)
                c_p = _sh(G, e, 1, n_e, g)
                up_term = jnp.where(
                    a > 0, a * (c - c_m) / s.dx[e],
                    a * (c_p - c) / s.dx[e])
                chi = self._stab_mask(c.shape, e).astype(c.dtype)
                N = N + chi * up_term + (1.0 - chi) * ppm_term
            out.append(N)
        return tuple(out)

    def _advecting(self, uc: Vel, u: Vel, d: int, e: int) -> Array:
        """Velocity component e evaluated at component d's faces."""
        s = self.solver
        if e == d:
            return u[d]
        ce = uc[e]                      # cell-centered, shape n
        if s.bc.periodic(d):
            return 0.5 * (ce + jnp.roll(ce, 1, axis=d))
        # interior faces: mean of adjacent cells; boundary faces: edge
        pad = [(0, 0)] * ce.ndim
        pad[d] = (1, 1)
        Gp = jnp.pad(ce, pad, mode="edge")
        lo = [slice(None)] * ce.ndim
        hi = [slice(None)] * ce.ndim
        lo[d] = slice(0, -1)
        hi[d] = slice(1, None)
        return 0.5 * (Gp[tuple(lo)] + Gp[tuple(hi)])

    # ------------------------------------------------------------------
    def step(self, state: OpenINSState, dt=None,
             f: Optional[Vel] = None) -> OpenINSState:
        """One step. ``dt`` may be omitted (construction dt — the
        original compiled-in behavior), a Python float, or a TRACED
        scalar: alpha = rho/dt is threaded through the saddle solve
        dynamically, so the CFL-adaptive ``hierarchy_driver`` loop
        drives this integrator without recompilation (VERDICT round 4
        item 6 — dt is no longer baked into the factorization)."""
        return self.step_with_stats(state, dt, f)[0]

    def step_with_stats(self, state: OpenINSState, dt=None,
                        f: Optional[Vel] = None):
        """:meth:`step` and the saddle solve's int32 counts:
        ``restarts`` (FGMRES restart cycles), ``iters`` (Arnoldi
        iterations: ``m`` a restart) and ``unconverged`` (1 where the
        solve ended above its tolerance). The phases (``jax.named_scope``)
        are ``fluid``, and under it ``convect`` and the solve's
        ``krylov``."""
        with jax.named_scope("fluid"):
            return self._step(state, dt, f)

    def _step(self, state, dt, f):
        s = self.solver
        if dt is None:
            dt, alpha = self.dt, None
            a_expl = self.alpha
        else:
            alpha = self.rho / dt
            a_expl = alpha
        with jax.named_scope("convect"):
            if self.convective_op_type == "stabilized_ppm":
                N = self._advect_stabilized(state.u)
            else:
                N = self._advect(state.u)
        f_u = []
        for d in range(len(s.n)):
            r = a_expl * state.u[d] - self.rho * N[d]
            if f is not None:
                r = r + f[d]
            f_u.append(r)
        rhs = s.make_rhs(f_u=tuple(f_u), bdry=self.bdry)
        sol = s.solve(rhs, x0=(state.u, state.p), alpha=alpha)
        restarts = jnp.asarray(sol.iters, jnp.int32)
        stats = {"restarts": restarts, "iters": restarts * s.m,
                 "unconverged": jnp.logical_not(sol.converged).astype(
                     jnp.int32)}
        return OpenINSState(u=sol.u, p=sol.p, t=state.t + dt), stats

    def cfl_dt(self, state: OpenINSState, cfl: float = 0.5) -> float:
        """Largest stable dt by the advective CFL condition (host-side
        global-min reduction, the hierarchy_driver contract)."""
        import math

        umax = max(float(jnp.max(jnp.abs(c))) for c in state.u)
        if umax == 0.0:
            return math.inf
        return cfl * min(self.dx) / umax

    def max_divergence(self, state: OpenINSState) -> Array:
        return jnp.max(jnp.abs(self.solver.divergence(state.u)))


def advance(integ: INSOpenIntegrator, state: OpenINSState,
            nsteps: int, f: Optional[Vel] = None) -> OpenINSState:
    """jit/scan-rolled advance of ``nsteps`` steps."""
    def body(st, _):
        return integ.step(st, f=f), None

    out, _ = jax.lax.scan(body, state, None, length=nsteps)
    return out
