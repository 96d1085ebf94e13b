"""Incompressible Navier-Stokes integrator on the staggered (MAC) grid.

Reference parity: ``INSStaggeredHierarchyIntegrator`` (P2) with its
convective-operator menu (P4) and the staggered Stokes solve (P3) —
SURVEY.md §3.3. On the periodic uniform level the reference's Krylov
saddle-point solve with projection preconditioner collapses to an exact
projection method (the preconditioner IS the exact solver when FFTs invert
the sub-blocks), which is what we implement:

per step (pressure-increment projection, AB2 convection, CN diffusion):
  1. N* = 3/2 N(u^n) - 1/2 N(u^{n-1})          (forward Euler on step 0)
  2. (rho/dt - mu/2 lap) u* = (rho/dt + mu/2 lap) u^n - rho N* + f - grad p^{n-1/2}
  3. lap(phi) = (rho/dt) div(u*)
  4. u^{n+1} = u* - (dt/rho) grad(phi)          (div u^{n+1} == 0 exactly)
  5. p^{n+1/2} = p^{n-1/2} + phi - (mu dt / (2 rho)) lap(phi)

TPU-first design: the state is a NamedTuple pytree; ``step`` is a pure
function of (state, dt, body_force) built once per integrator config and
meant to live inside jit / lax.scan. All solves are FFT (exact, no inner
iteration), so one timestep is a fixed dataflow graph — no data-dependent
control flow anywhere.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import stencils
from ibamr_tpu.ops.convection import convective_rate
from ibamr_tpu.solvers import fft

Vel = Tuple[jnp.ndarray, ...]


class INSState(NamedTuple):
    """Functional INS state pytree."""
    u: Vel                  # MAC velocity components
    p: jnp.ndarray          # cell-centered pressure (at t^{n-1/2})
    n_prev: Vel             # N(u^{n-1}) for AB2 extrapolation
    t: jnp.ndarray          # scalar time
    k: jnp.ndarray          # step counter (AB2 bootstrap)


class INSStaggeredIntegrator:
    """Projection-method INS integrator on a periodic uniform MAC grid.

    Parameters mirror the reference's input-file vocabulary where sensible:
    ``rho`` (mass density), ``mu`` (dynamic viscosity), and
    ``convective_op_type`` in {"centered", "upwind", "ppm", "cui",
    "none"} (case-insensitive; "ppm" is the reference's default
    operator, "cui" the CBC-limited cubic upwind of the newer menu).
    ``wall_axes`` puts homogeneous no-slip walls on both sides of the
    marked axes; ``wall_tangential[(d, e, side)]`` prescribes component
    d's tangential velocity on the side(0=lo,1=hi) wall of axis e (a
    moving lid).
    """

    def __init__(self, grid: StaggeredGrid, rho: float = 1.0,
                 mu: float = 0.01, convective_op_type: str = "centered",
                 dtype=jnp.float32,
                 wall_axes: Optional[Tuple[bool, ...]] = None,
                 wall_tangential=None,
                 spectral_dtype=None):
        # reference input files spell these uppercase ("PPM", "CENTERED")
        convective_op_type = convective_op_type.lower()
        if convective_op_type not in ("centered", "upwind", "ppm", "cui",
                                      "none"):
            raise ValueError(f"unknown convective_op_type {convective_op_type!r}")
        self.grid = grid
        self.rho = float(rho)
        self.mu = float(mu)
        self.convective_op_type = convective_op_type
        self.dtype = dtype
        self.wall_axes = (tuple(bool(w) for w in wall_axes)
                          if wall_axes is not None
                          else (False,) * grid.dim)
        if len(self.wall_axes) != grid.dim:
            raise ValueError(
                f"wall_axes has {len(self.wall_axes)} entries for a "
                f"{grid.dim}D grid")
        # opt-in mixed-precision spectral transforms (bf16/split-real
        # operands, f32 twiddle/accumulation); only the fused periodic
        # path honors it — walls use fastdiag, where it has no meaning
        from ibamr_tpu.solvers import spectral_plan
        self.spectral_dtype = spectral_plan.canonical_spectral_dtype(
            spectral_dtype)
        if self.spectral_dtype is not None and any(self.wall_axes):
            raise ValueError(
                "spectral_dtype requires the fully-periodic fused "
                f"spectral path; wall_axes={self.wall_axes}")
        self.wall_tangential = dict(wall_tangential or {})
        for key, val in self.wall_tangential.items():
            ok = (isinstance(key, tuple) and len(key) == 3
                  and 0 <= key[0] < grid.dim and 0 <= key[1] < grid.dim
                  and key[0] != key[1] and key[2] in (0, 1)
                  and self.wall_axes[key[1]])
            if not ok:
                raise ValueError(
                    f"wall_tangential key {key!r} must be (component d, "
                    f"wall axis e != d, side in {{0, 1}}) with "
                    f"wall_axes[e] set; wall_axes={self.wall_axes}")
        # Overridable solver seams (the StaggeredStokesSolver plugin
        # interface of the north star): the sharded path swaps these for
        # pencil-decomposed distributed FFT solves (parallel.fftpar); the
        # wall-bounded path (no-slip walls on ``wall_axes``) swaps them
        # for fast-diagonalization solves (solvers.fastdiag).
        self.fused_stokes = None     # set on the periodic path below
        if any(self.wall_axes):
            from ibamr_tpu.integrators import ins_walls

            ops = ins_walls.WallOps(grid, self.wall_axes,
                                    tangential=self.wall_tangential)
            self.helmholtz_vel_solve = ops.helmholtz_vel
            self.project = ops.project
            self.laplacian_vel = ops.laplacian_vel
            self.pressure_gradient = ops.pressure_gradient
            self.laplacian_cc = ops.laplacian_cc
        else:
            # (non-empty wall_tangential with no wall axes is already
            # rejected by the per-key validation above)
            self.helmholtz_vel_solve = fft.solve_helmholtz_periodic_vel
            self.project = fft.project_divergence_free
            self.laplacian_vel = stencils.laplacian_vel
            self.pressure_gradient = stencils.gradient
            self.laplacian_cc = stencils.laplacian
            # fused spectral Stokes substep (Helmholtz + projection +
            # pressure increment in one spectral pass — 7 transforms
            # instead of 8 + three stencil passes). Disabled by the
            # sharded wrapper, which swaps in pencil-FFT seams.
            self.fused_stokes = fft.helmholtz_project_periodic
        # convective operator (P4 menu). Walls or PPM need the
        # ghost-padded path; fully-periodic centered/upwind keep the
        # original roll formulation. ``_convective`` evaluates the
        # padded path's operator with the slab-fused kernel where
        # shape, dtype and scheme allow, walls included (chosen at
        # trace time);
        # ``_convective_padded`` never does (the sharded wrapper puts
        # it in ``_convective``'s place: a pallas_call does not
        # partition).
        from ibamr_tpu.ops.convection import convective_rate_select
        self._convective_padded = None
        if convective_op_type == "none":
            self._convective = None
        elif any(self.wall_axes) or convective_op_type in ("ppm", "cui"):
            menu = dict(scheme=convective_op_type,
                        wall_axes=self.wall_axes,
                        wall_tangential=self.wall_tangential)
            self._convective_padded = partial(
                convective_rate_select, partitioned=True, **menu)
            self._convective = partial(convective_rate_select, **menu)
        else:
            self._convective = partial(convective_rate,
                                       scheme=convective_op_type)

    # -- state construction -------------------------------------------------
    def initialize(self, u0=None, u0_arrays: Optional[Vel] = None) -> INSState:
        """Build the initial state.

        ``u0`` may be either a sequence of per-component callables
        ``u0[d](coords_tuple, t) -> array`` (e.g. CartGridFunction per
        component), or a single vector-valued callable
        ``u0(coords_tuple, t) -> [array, ...]`` (what ``function_from_db``
        returns); each component is evaluated at its own face centers.
        (A vector callable is invoked once per component — dim calls —
        because each MAC component lives at different coordinates; pass
        per-component callables or arrays to avoid the redundant work.)
        ``u0_arrays`` passes raw MAC arrays directly."""
        g = self.grid
        if u0_arrays is not None:
            u = tuple(jnp.asarray(c, dtype=self.dtype) for c in u0_arrays)
        elif u0 is not None:
            def eval_comp(d):
                coords = g.face_centers(d, self.dtype)
                if callable(u0):
                    val = u0(coords, 0.0)[d]
                else:
                    val = u0[d](coords, 0.0)
                return jnp.broadcast_to(
                    jnp.asarray(val, dtype=self.dtype), g.n)

            u = tuple(eval_comp(d) for d in range(g.dim))
        else:
            u = tuple(jnp.zeros(g.n, dtype=self.dtype) for _ in range(g.dim))
        zero_cc = jnp.zeros(g.n, dtype=self.dtype)
        zeros_vel = tuple(jnp.zeros(g.n, dtype=self.dtype)
                          for _ in range(g.dim))
        return INSState(u=u, p=zero_cc, n_prev=zeros_vel,
                        t=jnp.asarray(0.0, dtype=self.dtype),
                        k=jnp.asarray(0, dtype=jnp.int32))

    # -- single step (pure, jittable) ---------------------------------------
    def step(self, state: INSState, dt: float,
             f: Optional[Vel] = None,
             q: Optional[jnp.ndarray] = None) -> INSState:
        """Advance one timestep. ``f`` is an optional MAC body force
        (e.g. the spread IB force) held fixed over the step; ``q`` is an
        optional cell-centered divergence source (internal fluid
        sources/sinks — the IBStandardSourceGen analog, P14), imposed as
        div u^{n+1} = q by the projection."""
        # Phase names (jax.named_scope: metadata only): the fluid solve
        # names its own phases, so that a program without an IB wrapper
        # carries them too. ``transforms`` is opened by the fused substep
        # directly under ``fluid``; no named phase may sit between them
        # (obs/deviceprof.phase_of takes the longest sequence).
        with jax.named_scope("fluid"):
            return self._step(state, dt, f, q)

    def _step(self, state, dt, f, q):
        """:meth:`step` under its ``fluid`` scope."""
        scope = jax.named_scope
        g = self.grid
        rho, mu = self.rho, self.mu
        dx = g.dx
        u, p = state.u, state.p

        # 1. convective extrapolation (AB2; Euler on the first step)
        with scope("convect"):
            if self._convective is None:
                n_star = tuple(jnp.zeros_like(c) for c in u)
                n_curr = n_star
            else:
                n_curr = self._convective(u, dx)
                c1 = jnp.where(state.k == 0, 1.0, 1.5).astype(self.dtype)
                c2 = jnp.where(state.k == 0, 0.0, -0.5).astype(self.dtype)
                n_star = tuple(c1 * a + c2 * b
                               for a, b in zip(n_curr, state.n_prev))

        # 2. semi-implicit viscous solve for u*
        with scope("rhs"):
            lap_u = self.laplacian_vel(u, dx)
            gp = self.pressure_gradient(p, dx)
            rhs = []
            for d in range(g.dim):
                r = (rho / dt) * u[d] + 0.5 * mu * lap_u[d] \
                    - rho * n_star[d] - gp[d]
                if f is not None:
                    r = r + f[d]
                rhs.append(r)
        # the fused path is only valid while the solver seams are the
        # stock periodic-FFT ones — a custom helmholtz_vel_solve /
        # project override (pencil solvers, user plugins) must win
        use_fused = (
            self.fused_stokes is not None and q is None
            and self.helmholtz_vel_solve is fft.solve_helmholtz_periodic_vel
            and self.project is fft.project_divergence_free)
        if use_fused:
            # fused spectral path: Helmholtz solve + projection +
            # pressure increment in one spectral round trip.
            # p_inc = (rho/dt) phi0 - (0.5 mu) lap(phi0)
            # spectral_dtype is forwarded only when set, so swapped-in
            # fused_stokes seams keep their plain signature
            extra = ({"spectral_dtype": self.spectral_dtype}
                     if self.spectral_dtype is not None else {})
            u_new, p_inc = self.fused_stokes(
                tuple(rhs), dx, alpha=rho / dt, beta=-0.5 * mu,
                pinc_coeffs=(rho / dt, -0.5 * mu), **extra)
            p_new = p + p_inc
        else:
            u_star = self.helmholtz_vel_solve(
                tuple(rhs), dx, alpha=rho / dt, beta=-0.5 * mu)

            # 3-4. exact projection (phi0 = lap^{-1} div u*;
            # phi = (rho/dt) phi0)
            u_new, phi0 = self.project(u_star, dx, q=q)
            phi = (rho / dt) * phi0

            # 5. pressure update (pressure-increment form w/ viscous
            # correction)
            p_new = p + phi \
                - (0.5 * mu * dt / rho) * self.laplacian_cc(phi, dx)

        return INSState(u=u_new, p=p_new, n_prev=n_curr,
                        t=state.t + dt, k=state.k + 1)

    # -- diagnostics --------------------------------------------------------
    def cfl_dt(self, state: INSState, cfl: float = 0.5) -> float:
        """Largest stable dt by the advective CFL condition (host-side;
        the analog of the reference's global-min dt reduction)."""
        g = self.grid
        umax = max(float(jnp.max(jnp.abs(c))) for c in state.u)
        if umax == 0.0:
            return math.inf
        return cfl * min(g.dx) / umax

    def kinetic_energy(self, state: INSState) -> jnp.ndarray:
        ke = sum(jnp.sum(jnp.square(c)) for c in state.u)
        return 0.5 * self.rho * ke * self.grid.cell_volume

    def enstrophy(self, state: INSState) -> jnp.ndarray:
        """0.5 * integral of |curl u|^2 (3D), with the compact MAC curl:
        each vorticity component at its own edges, from face differences
        one cell apart. For a discretely divergence-free periodic u this
        is the discrete viscous dissipation over mu: d/dt kinetic_energy
        = -2 mu enstrophy is what the MAC Laplacian itself balances."""
        u, dx = state.u, self.grid.dx

        def dm(f, axis):
            return (f - jnp.roll(f, 1, axis)) / dx[axis]

        w2 = sum(jnp.sum(jnp.square(dm(u[b], a) - dm(u[a], b)))
                 for a, b in ((1, 2), (2, 0), (0, 1)))
        return 0.5 * w2 * self.grid.cell_volume

    def max_divergence(self, state: INSState) -> jnp.ndarray:
        return jnp.max(jnp.abs(stencils.divergence(state.u, self.grid.dx)))


def advance(integrator: INSStaggeredIntegrator, state: INSState, dt: float,
            num_steps: int, f: Optional[Vel] = None,
            q: Optional[jnp.ndarray] = None) -> INSState:
    """Advance ``num_steps`` fixed-dt steps under one jitted lax.scan."""
    def body(s, _):
        return integrator.step(s, dt, f, q=q), None

    out, _ = jax.lax.scan(body, state, None, length=num_steps)
    return out
