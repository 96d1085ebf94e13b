"""Wall-bounded (no-slip) operators for the staggered INS integrator.

Reference parity: the non-periodic half of the staggered Stokes machinery
(P3: StaggeredStokesPhysicalBoundaryHelper, INSProjectionBcCoef,
INSIntermediateVelocityBcCoef; T8's non-periodic solvers; T9 wall fills —
SURVEY.md §2.1/§2.2) for homogeneous no-slip walls, collapsed onto the
fast-diagonalization solver (solvers.fastdiag).

Storage convention for a wall axis (see fastdiag "fc_pinned"): every MAC
component keeps shape ``n`` per axis; for the wall-NORMAL component the
slot at index 0 along that axis is the lo wall face, pinned to 0, and
the hi wall face is the periodic-wrap image of slot 0 — so for
HOMOGENEOUS no-slip both wall faces carry 0 and the periodic roll
stencils for divergence and the normal-axis Laplacian remain EXACT; only
tangential components need explicit odd-reflection ghosts, and the
pressure gradient is masked at pinned faces.

Projection note: with u.n = 0 enforced at walls the pressure Poisson
problem gets homogeneous Neumann BCs; the masked discrete gradient
composed with the roll divergence reproduces the Neumann matrix rows
exactly, so the projection is discretely exact (div u = 0 to roundoff).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ibamr_tpu.bc import AxisBC, DomainBC, SideBC, dirichlet_axis, neumann_axis
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.ops import stencils
from ibamr_tpu.solvers.fastdiag import FastDiagSolver

Vel = Tuple[jnp.ndarray, ...]


def _axis_bc(wall: bool, kind_builder) -> AxisBC:
    return kind_builder() if wall else AxisBC()


def pin_normal(c: jnp.ndarray, d: int, wall_axes) -> jnp.ndarray:
    """Zero the pinned wall-face slot of MAC component d (the storage
    convention of this module: slot 0 along a wall axis is the lo wall
    face; the hi wall face is its periodic-wrap image). Shared by every
    wall-bounded integrator so the convention is single-sourced."""
    if not wall_axes[d]:
        return c
    idx = [slice(None)] * c.ndim
    idx[d] = slice(0, 1)
    return c.at[tuple(idx)].set(0.0)


class WallOps:
    """Per-grid wall-aware operators + solvers, built once per config.

    ``tangential[(d, e, side)]`` prescribes component d's tangential
    velocity on the side(0=lo,1=hi) wall of axis e != d (a moving lid,
    e.g. the driven cavity). Inhomogeneous values enter the explicit
    Laplacian through the Dirichlet ghost fill and the implicit
    Helmholtz solve through RHS lifting (the ghost correction is a
    state-independent constant, so the homogeneous fast-diagonalization
    solver stays exact)."""

    def __init__(self, grid: StaggeredGrid, wall_axes: Sequence[bool],
                 tangential=None):
        self.grid = grid
        self.wall_axes = tuple(bool(w) for w in wall_axes)
        self.tangential = dict(tangential or {})
        dim = grid.dim

        # velocity Helmholtz solvers: component d -> per-axis centering
        self.vel_solvers = []
        for d in range(dim):
            axes, cents = [], []
            for e in range(dim):
                if not self.wall_axes[e]:
                    axes.append(AxisBC())
                    cents.append("cc")
                elif e == d:
                    axes.append(dirichlet_axis())
                    cents.append("fc_pinned")
                else:
                    axes.append(dirichlet_axis())
                    cents.append("cc")
            self.vel_solvers.append(
                FastDiagSolver(grid, DomainBC(axes=tuple(axes)),
                               tuple(cents)))

        # pressure Poisson: cc, Neumann at walls
        p_axes = tuple(_axis_bc(w, neumann_axis) for w in self.wall_axes)
        self.p_solver = FastDiagSolver(grid, DomainBC(axes=p_axes),
                                       ("cc",) * dim)

        # ghost-fill BC descriptors for the explicit stencils (shared
        # with bc.laplacian_cc so the ghost arithmetic lives in ONE
        # place). Component d treats its own wall axis as periodic: the
        # pinned-face storage wraps exactly for homogeneous walls.
        self._p_lap_bc = DomainBC(axes=p_axes)
        self._vel_lap_bc = [
            DomainBC(axes=tuple(
                dirichlet_axis(self.tangential.get((d, e, 0), 0.0),
                               self.tangential.get((d, e, 1), 0.0))
                if (self.wall_axes[e] and e != d)
                else AxisBC()
                for e in range(dim)))
            for d in range(dim)]

        # RHS lifting for the implicit solve: L_inhom u = L_hom u + lift,
        # lift = 2*V/dx_e^2 in the cell rows adjacent to a moving wall.
        # Kept as one vector along each moving wall's axis, shaped to
        # broadcast (a whole-grid array here would be a constant of the
        # jitted chunk: 67 MB at 256^3)
        self._lift = []
        for d in range(dim):
            lift = None
            for e in range(dim):
                if not self.wall_axes[e] or e == d:
                    continue
                rows = np.zeros(grid.n[e])
                for side in (0, 1):
                    v = self.tangential.get((d, e, side), 0.0)
                    rows[0 if side == 0 else -1] += 2.0 * v / grid.dx[e] ** 2
                if not rows.any():
                    continue
                vec = jnp.asarray(rows).reshape(
                    [-1 if a == e else 1 for a in range(dim)])
                lift = vec if lift is None else lift + vec
            self._lift.append(lift)

    # -- masks ---------------------------------------------------------------
    def _pin_normal(self, c: jnp.ndarray, d: int) -> jnp.ndarray:
        """Zero the pinned wall-face slot of component d (wall axes only)."""
        return pin_normal(c, d, self.wall_axes)

    # -- operators -----------------------------------------------------------
    def laplacian_vel(self, u: Sequence[jnp.ndarray],
                      dx: Sequence[float]) -> Vel:
        """Component Laplacians with homogeneous no-slip ghosts.

        Per component d, axis e:
        - e periodic, or e == d on a wall axis (pinned storage): the
          periodic wrap is exact (wall nodes carry 0).
        - e != d on a wall axis: tangential no-slip -> homogeneous
          Dirichlet ghosts (odd reflection).
        Ghost arithmetic delegates to bc.laplacian_cc.
        """
        from ibamr_tpu import bc as bc_mod

        return tuple(
            self._pin_normal(bc_mod.laplacian_cc(c, self._vel_lap_bc[d], dx),
                             d)
            for d, c in enumerate(u))

    def pressure_gradient(self, p: jnp.ndarray,
                          dx: Sequence[float]) -> Vel:
        """grad p at faces; zero at pinned wall faces (no normal update —
        the discrete homogeneous-Neumann condition)."""
        g = stencils.gradient(p, dx)
        return tuple(self._pin_normal(c, d) for d, c in enumerate(g))

    def laplacian_cc(self, f: jnp.ndarray, dx: Sequence[float]) -> jnp.ndarray:
        """Cell-centered Laplacian with homogeneous-Neumann wall ghosts
        (for the pressure-increment update); delegates to bc.laplacian_cc."""
        from ibamr_tpu import bc as bc_mod

        return bc_mod.laplacian_cc(f, self._p_lap_bc, dx)

    # -- solver seams (signatures match the periodic fft module) -------------
    def helmholtz_vel(self, rhs: Vel, dx, alpha, beta) -> Vel:
        out = []
        for d, c in enumerate(rhs):
            if self._lift[d] is not None:
                # (alpha + beta L_inhom) u = rhs
                #   <=> (alpha + beta L_hom) u = rhs - beta*lift
                c = c - beta * self._lift[d].astype(c.dtype)
            out.append(self.vel_solvers[d].solve(c, alpha, beta))
        return tuple(out)

    def project(self, u: Vel, dx, q=None) -> Tuple[Vel, jnp.ndarray]:
        """Leray projection with wall BCs: div uses the roll stencil
        (exact — wall faces carry 0), phi solves the Neumann Poisson
        problem, and the correction is masked at pinned faces. ``q`` is
        an optional cell-centered divergence source (P14); the Neumann
        solve's nullspace projection handles any net component."""
        div = stencils.divergence(u, dx)
        if q is not None:
            div = div - q
        phi = self.p_solver.solve(div, 0.0, 1.0, zero_nullspace=True)
        g = self.pressure_gradient(phi, dx)
        u_new = tuple(self._pin_normal(c - gc, d)
                      for d, (c, gc) in enumerate(zip(u, g)))
        return u_new, phi
