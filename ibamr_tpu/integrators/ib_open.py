"""IB coupling on inflow/outflow (open-boundary) domains — the
flow-past-an-immersed-structure configuration.

Reference parity: the reference's most-run IB scenarios are external
flows past structures in channels with prescribed inflow and open
outflow (``IBExplicitHierarchyIntegrator`` over the
inflow/outflow-configured ``INSStaggeredHierarchyIntegrator``, SURVEY.md
P2/P8 — flow past a cylinder, flapping filaments, valve leaflets). The
periodic and enclosed IB couplings exist (`integrators.ib`,
`amr_ins`); this module completes the boundary menu by coupling the
marker-cloud IBStrategy seam to
:class:`~ibamr_tpu.integrators.ins_open.INSOpenIntegrator`'s coupled
velocity-pressure solve.

Layout bridge: the open solver stores velocities FACE-COMPLETE (+1 on
the component's own axis); the transfer ops use the periodic lower-face
layout. The structure must keep delta-support clearance from every
domain boundary (markers at a boundary would wrap their stencil), which
makes the conversion exact: interpolation reads the lower faces,
spreading appends a zero upper-boundary face — the same clearance
contract as the fine-window composite path
(`amr_ins._box_mac_from_periodic`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins_open import INSOpenIntegrator, OpenINSState
from ibamr_tpu.ops.stencils import (mac_complete_from_periodic,
                                    mac_periodic_from_complete)

Vel = Tuple[jnp.ndarray, ...]


class IBOpenState(NamedTuple):
    fluid: OpenINSState
    X: jnp.ndarray
    U: jnp.ndarray
    mask: jnp.ndarray
    # net Lagrangian force actually spread during the last step (at the
    # midpoint configuration X_half, U_n, t+dt/2) — carried so drag/lift
    # diagnostics report the applied force, not a half-step-lagged
    # recomputation; zeros before the first step
    F_net: jnp.ndarray


class IBOpenIntegrator:
    """Explicit midpoint IB coupling over the open-boundary INS step.
    The construction dt on the INS integrator is the default; ``step``
    also takes an explicit (possibly traced) dt — alpha = rho/dt is
    threaded through the saddle solve dynamically, so the CFL-adaptive
    driver loop works on this family.

    ``ib`` is any marker-cloud IBStrategy (IBMethod, IBFEMethod, ...);
    ``x_lo`` places the solver's index box in physical space (default
    origin)."""

    def __init__(self, ins: INSOpenIntegrator, ib,
                 x_lo: Optional[Sequence[float]] = None):
        self.ins = ins
        self.ib = ib
        dim = len(ins.n)
        x_lo = tuple(float(v) for v in (x_lo or (0.0,) * dim))
        x_up = tuple(x_lo[d] + ins.n[d] * ins.dx[d] for d in range(dim))
        self.grid = StaggeredGrid(n=tuple(ins.n), x_lo=x_lo, x_up=x_up)

    # -- layout bridge (shared with the fine-window composite path) ----------
    def _to_lower(self, u: Vel) -> Vel:
        """Face-complete -> periodic lower-face layout (drop the upper
        boundary face; exact under the clearance contract)."""
        return mac_periodic_from_complete(u, self.grid.n)

    def _to_complete(self, f: Vel) -> Vel:
        """Periodic lower-face layout -> face-complete (the duplicated
        wrap face carries zero under the clearance contract — no
        spread force lands on any boundary face)."""
        return mac_complete_from_periodic(f)

    # -- state ---------------------------------------------------------------
    def initialize(self, X0, fluid: Optional[OpenINSState] = None,
                   mask=None) -> IBOpenState:
        if fluid is None:
            fluid = self.ins.initialize()
        # cast markers to the FLUID dtype (same contract as
        # IBExplicitIntegrator.initialize): a mixed-precision carry
        # would either break the scan (f32 markers + f64 fluid) or
        # silently promote the production-f32 step to f64
        dtype = self.ins.solver.dtype
        X = jnp.asarray(X0, dtype=dtype)
        if mask is None:
            mask = jnp.ones(X.shape[0], dtype=dtype)
        return IBOpenState(fluid=fluid, X=X, U=jnp.zeros_like(X),
                           mask=jnp.asarray(mask, dtype=dtype),
                           F_net=jnp.zeros(X.shape[1], dtype=dtype))

    # -- single step (pure, jittable) ----------------------------------------
    def step(self, state: IBOpenState, dt=None) -> IBOpenState:
        """``dt`` may be None (construction dt), a float, or a traced
        scalar — the saddle solve takes alpha = rho/dt dynamically, so
        the CFL-adaptive hierarchy_driver loop works on this family
        (VERDICT round 4 item 6)."""
        return self.step_with_stats(state, dt)[0]

    def step_with_stats(self, state: IBOpenState, dt=None):
        """:meth:`step` and the saddle solve's int32 counts
        (:meth:`INSOpenIntegrator.step_with_stats`). Phases
        (``jax.named_scope``): ``ib/prep``, ``ib/interp``, ``ib/force``,
        ``ib/spread``, then the fluid step's own."""
        dt_arg = dt
        if dt is None:
            dt = self.ins.dt
        scope = jax.named_scope
        grid = self.grid
        ib = self.ib
        fluid = state.fluid
        X_n = state.X
        u_low = self._to_lower(fluid.u)
        with scope("ib/interp"):
            U_n = ib.interpolate_velocity(u_low, grid, X_n, state.mask)
        X_half = X_n + 0.5 * dt * U_n
        with scope("ib/force"):
            F = ib.compute_force(X_half, U_n, fluid.t + 0.5 * dt)
        with scope("ib/prep"):
            ctx = ib.prepare(X_half, state.mask) \
                if hasattr(ib, "prepare") else None
        with scope("ib/spread"):
            f_per = ib.spread_force(F, grid, X_half, state.mask, ctx=ctx)
        fluid_new, stats = self.ins.step_with_stats(
            fluid, dt=dt_arg, f=self._to_complete(f_per))
        u_mid = tuple(0.5 * (a + b)
                      for a, b in zip(u_low,
                                      self._to_lower(fluid_new.u)))
        with scope("ib/interp"):
            U_half = ib.interpolate_velocity(u_mid, grid, X_half,
                                             state.mask, ctx=ctx)
        X_new = X_n + dt * U_half
        return IBOpenState(fluid=fluid_new, X=X_new, U=U_half,
                           mask=state.mask,
                           F_net=jnp.sum(F * state.mask[:, None],
                                         axis=0)), stats

    # -- the carried form: the solve's counts leave the chunk ----------------
    # (``utils/hierarchy_driver.scan_steps``): nothing is carried between
    # steps, and the chunk's tally is the saddle solves' counts, reported
    # after the chunk's one sync on a ``driver/chunk/krylov`` span
    chunk_tally = ("krylov", ("iters", "restarts", "unconverged"))

    def init_carry(self, state: IBOpenState):
        return None

    def step_carried(self, state: IBOpenState, ctx, dt):
        new, stats = self.step_with_stats(state, dt)
        return new, None, stats

    # -- diagnostics ---------------------------------------------------------
    def body_force_on_fluid(self, state: IBOpenState) -> jnp.ndarray:
        """Net structural force applied to the fluid during the LAST
        step (the NEGATIVE of the hydrodynamic force on the body):
        sum of the Lagrangian forces at the spread configuration
        (X_half, U_n, t+dt/2) — e.g. drag = -F_net[flow_axis] for a
        target-point-held body. Before the first step, zero."""
        return state.F_net


    def cfl_dt(self, state: IBOpenState, cfl: float = 0.5) -> float:
        """Advective CFL bound from the fluid field (hierarchy_driver
        contract; the marker velocities ride the same field)."""
        return self.ins.cfl_dt(state.fluid, cfl)


def advance_ib_open(integ: IBOpenIntegrator, state: IBOpenState,
                    num_steps: int) -> IBOpenState:
    def body(s, _):
        return integ.step(s), None

    out, _ = jax.lax.scan(body, state, None, length=num_steps)
    return out
