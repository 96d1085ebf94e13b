"""ConstraintIB: rigid / prescribed-kinematics bodies by momentum projection.

Reference parity: ``ConstraintIBMethod`` + ``ConstraintIBKinematics``
(P16, SURVEY.md §2.2; Bhalla, Bale, Griffith, Patankar, JCP 250 (2013)
446-476 — the fictitious-domain momentum-projection formulation). Unlike
CIB (P15), no constraint SOLVE happens: after an unconstrained fluid
step, the velocity inside each body is PROJECTED onto rigid modes (plus
any prescribed deformational kinematics) and imposed back on the grid,
followed by a divergence-free projection.

One step:
  1. unconstrained INS step                         -> u*
  2. interpolate u* at body markers                 -> U_i
  3. least-squares rigid projection per body        -> (V_b, W_b)
     (free DOFs keep the projected momentum — that IS momentum
     conservation; prescribed DOFs are overwritten from the kinematics)
  4. constrained marker velocity U_b = K(V,W) + U_def
     (U_def = prescribed deformation velocity with its rigid component
     projected out, so it carries no net momentum)
  5. grid correction u <- u* + S_norm (U_b - U_i), where S_norm is
     delta-spreading NORMALIZED by the spread indicator (a partition of
     unity inside the body) — velocity replacement, not force addition
  6. re-project to the divergence-free space; move the markers by the
     rigid motion itself (``rigid_move``: the centroid by dt V, the
     rotation by Rodrigues' formula, so a body keeps its pairwise marker
     distances to rounding) plus dt U_def.

TPU-first: all of 1-6 is one fused jittable function; per-body
reductions are ``segment_sum`` over the static ``body_id`` array and the
3x3 (or scalar) inertia solves run batched on the MXU.

Transfers: X is the same for every transfer of a step, so one marker
context serves them all. With a transfer engine (``fast``: a row of
``models/engine_resolver.ENGINES`` built for this grid and marker
cloud) a step makes ONE context (a pack, or a refresh of the layout the
chunk carries: ``init_carry`` / ``step_carried``, as
``integrators/ib.IBExplicitIntegrator``), ONE ``interpolate_vel`` and
TWO ``spread_vel`` (the correction and the indicator, ``dim`` scalar
spreads each). Without one (``fast=None``) the same calls go to the
scatter/gather oracle of ``ops/interaction``.

Phases (``jax.named_scope``, ``obs/deviceprof.PHASES``): ``ib/prep`` /
``ib/refresh``, ``ib/interp``, ``constraint/rigid`` (steps 3-4),
``ib/spread``, ``constraint/impose`` (the normalisation on the grid),
``fluid/reproject`` (step 6's projection; its axis transforms stay
``fluid/transforms``); the marker update is under none.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ibamr_tpu import obs
from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.cib import (RigidBodies, body_centroids,
                                       n_rigid_modes, rigid_velocity)
from ibamr_tpu.integrators.ib import IBMethod
from ibamr_tpu.integrators.ins import INSState, INSStaggeredIntegrator
from ibamr_tpu.ops.delta import Kernel

Vel = Tuple[jnp.ndarray, ...]

# what a traced step constrains (bumped at trace time, once per traced
# step, as the fluid solve's own trace-time counters are)
_BODIES = obs.counter("constraint_bodies")
_MARKERS = obs.counter("constraint_markers")
obs.describe("constraint_bodies",
             "rigid bodies of traced ConstraintIB steps, one count per "
             "body and traced step")
obs.describe("constraint_markers",
             "volumetric markers of traced ConstraintIB steps, one "
             "count per marker and traced step")


class ConstraintIBState(NamedTuple):
    ins: INSState
    X: jnp.ndarray          # (N, dim) marker positions
    U_body: jnp.ndarray     # (B, modes) last rigid motion (diagnostic)


def project_rigid(X: jnp.ndarray, bodies: RigidBodies,
                  U: jnp.ndarray) -> jnp.ndarray:
    """Least-squares projection of marker velocities onto rigid modes
    per body -> (B, n_rigid_modes) = (V, W) about each centroid.

    Equal marker weights (the reference weights by material volume; for
    uniformly seeded bodies these coincide)."""
    N, dim = X.shape
    nb = bodies.n_bodies
    bid = bodies.body_id
    ones = jnp.ones((N, 1), X.dtype)
    cnt = jnp.maximum(jax.ops.segment_sum(ones, bid, num_segments=nb), 1.0)
    V = jax.ops.segment_sum(U, bid, num_segments=nb) / cnt

    cent = body_centroids(X, bodies)
    r = X - cent[bid]
    u_rel = U - V[bid]
    if dim == 2:
        # scalar angular momentum / moment of inertia
        L = jax.ops.segment_sum(r[:, 0] * u_rel[:, 1]
                                - r[:, 1] * u_rel[:, 0],
                                bid, num_segments=nb)
        I = jax.ops.segment_sum(jnp.sum(r * r, axis=1), bid,
                                num_segments=nb)
        W = (L / jnp.maximum(I, 1e-30))[:, None]
        return jnp.concatenate([V, W], axis=1)
    # 3D: solve I W = L with the batched inertia tensor
    L = jax.ops.segment_sum(jnp.cross(r, u_rel), bid, num_segments=nb)
    # an outer product by broadcasting: no contraction, so nothing here
    # can land on the matrix unit at its default (bfloat16) precision
    rr = jax.ops.segment_sum(r[:, :, None] * r[:, None, :], bid,
                             num_segments=nb)
    tr = jnp.trace(rr, axis1=-2, axis2=-1)
    I = tr[:, None, None] * jnp.eye(dim, dtype=X.dtype) - rr
    I = I + 1e-30 * jnp.eye(dim, dtype=X.dtype)
    W = jnp.linalg.solve(I, L[..., None])[..., 0]
    return jnp.concatenate([V, W], axis=1)


def rigid_move(X: jnp.ndarray, bodies: RigidBodies, U: jnp.ndarray,
               dt) -> jnp.ndarray:
    """The markers after the rigid motions ``U`` (B, n_rigid_modes) have
    acted for ``dt``: each body's centroid translated by dt V and its
    markers rotated about it by exp(dt [W]x) (Rodrigues' formula, in
    displacement form so that nothing large is subtracted). Unlike
    ``X + dt * rigid_velocity(X, bodies, U)``, whose rotation stretches
    every distance by (dt |W|)^2 / 2 a step, this preserves a body's
    pairwise marker distances to rounding."""
    dim = X.shape[1]
    bid = bodies.body_id
    r = X - body_centroids(X, bodies)[bid]
    V = U[:, :dim][bid]
    if dim == 2:
        half = 0.5 * dt * U[:, 2][bid]
        sn, cm1 = jnp.sin(2.0 * half), -2.0 * jnp.sin(half) ** 2
        turn = jnp.stack([cm1 * r[:, 0] - sn * r[:, 1],
                          sn * r[:, 0] + cm1 * r[:, 1]], axis=-1)
        return X + dt * V + turn
    W = U[:, 3:6]
    th2 = dt * dt * jnp.sum(W * W, axis=1)
    # sin(t)/t and (sin(t/2)/(t/2))^2 / 2, by their series under the
    # angle at which float32 no longer tells them apart (and so that no
    # square root is taken at W = 0)
    small = th2 < 1e-8
    th = jnp.sqrt(jnp.where(small, 1.0, th2))
    s1 = jnp.where(small, 1.0 - th2 / 6.0, jnp.sin(th) / th)
    s2 = jnp.where(small, 0.5 - th2 / 24.0,
                   0.5 * (jnp.sin(0.5 * th) / (0.5 * th)) ** 2)
    Wm = W[bid]
    wxr = jnp.cross(Wm, r)
    return (X + dt * V + (dt * s1)[bid, None] * wxr
            + (dt * dt * s2)[bid, None] * jnp.cross(Wm, wxr))


class ConstraintIBMethod:
    """Momentum-projection constraint IB coupling (P16).

    ``free``: (B, n_rigid_modes) 0/1 — 1 keeps the momentum-projected
    value (freely moving DOF), 0 takes the prescribed value from
    ``prescribed_fn(t) -> (B, n_rigid_modes)``.
    ``deformation_fn(t, X) -> (N, dim)``: optional prescribed
    deformational velocity (swimming gaits etc.); its rigid component is
    projected out automatically.
    ``fast``: a transfer engine of ``models/engine_resolver`` built for
    ``ins.grid`` and the body's marker cloud (``buckets`` /
    ``interpolate_vel`` / ``spread_vel``, and ``refresh`` where its
    layout can be carried through a chunk); None is the scatter/gather
    oracle. Per step the method makes one marker context, one velocity
    interpolation and two velocity spreads at the one position X_n
    (the correction and the indicator), whichever serves them: the
    engine-or-oracle choice is ``integrators/ib.IBMethod``'s, which this
    method holds (with no force specs) for its transfers.
    ``engine_name``: the engine's row name in the resolver's table, from
    the builder that resolved it (what the ``driver/chunk`` span is
    told); by default ``scatter`` or the engine's type.
    """

    def __init__(self, ins: INSStaggeredIntegrator, bodies: RigidBodies,
                 free=None,
                 prescribed_fn: Optional[Callable] = None,
                 deformation_fn: Optional[Callable] = None,
                 kernel: Kernel = "IB_4",
                 indicator_floor: float = 1e-4,
                 density_ratio=None, gravity=None,
                 virtual_mass: float = 1.0, fast=None,
                 engine_name: Optional[str] = None):
        self.ins = ins
        self.bodies = bodies
        self.transfers = IBMethod(None, kernel=kernel, fast=fast)
        self.engine_name = engine_name or (
            "scatter" if fast is None else type(fast).__name__)
        dim = ins.grid.dim
        modes = n_rigid_modes(dim)
        if free is None:
            free = jnp.ones((bodies.n_bodies, modes), dtype=ins.dtype)
        self.free = jnp.asarray(free, dtype=ins.dtype)
        self.prescribed_fn = prescribed_fn
        self.deformation_fn = deformation_fn
        # spread-indicator threshold below which a cell is treated as
        # outside every body (no correction applied)
        self.indicator_floor = float(indicator_floor)
        # inertial (time-dependent) rigid-body dynamics: per-body
        # density ratio rho_body/rho_fluid (the reference's free-moving
        # ConstraintIB bodies with excess inertia — Bhalla et al. 2013
        # §2.4). ratio == 1 (or None) is the neutrally-buoyant limit
        # where the momentum projection alone IS the dynamics.
        self.density_ratio = None if density_ratio is None else \
            jnp.asarray(density_ratio, dtype=ins.dtype).reshape(-1, 1)
        # virtual-mass stabilization weight (0 = raw explicit
        # Newton-Euler update; 1 = interior-fluid added mass)
        self.virtual_mass = float(virtual_mass)
        if gravity is None:
            self._g_modes = None
        else:
            if self.density_ratio is None:
                raise ValueError(
                    "gravity without density_ratio has no effect: a "
                    "neutrally-buoyant body feels no net gravity; pass "
                    "density_ratio to enable the excess-mass dynamics")
            g = jnp.asarray(gravity, dtype=ins.dtype)
            self._g_modes = jnp.concatenate(
                [g, jnp.zeros(modes - dim, dtype=ins.dtype)])[None, :]

    @property
    def fast(self):
        return self.transfers.fast

    @property
    def kernel(self) -> Kernel:
        return self.transfers.kernel

    # -- normalized velocity imposition --------------------------------------
    def _impose(self, u: Vel, X: jnp.ndarray, dU: jnp.ndarray,
                ctx=None) -> Vel:
        """u + S_norm(dU): delta-spread the velocity correction and
        normalize by the spread indicator so the correction is a
        velocity (partition-of-unity) rather than a force density. Two
        ``spread_vel`` calls a step: the correction, and the indicator
        once for all components."""
        spread, grid = self.transfers.spread_force, self.ins.grid
        with jax.named_scope("ib/spread"):
            num = spread(dU, grid, X, None, ctx)
            den = spread(jnp.ones_like(dU), grid, X, None, ctx)
        floor = self.indicator_floor
        with jax.named_scope("constraint/impose"):
            return tuple(
                c + jnp.where(d > floor, n / jnp.maximum(d, floor), 0.0)
                for c, n, d in zip(u, num, den))

    # -- one coupled step -----------------------------------------------------
    def step(self, state: ConstraintIBState,
             dt: float) -> ConstraintIBState:
        with jax.named_scope("ib/prep"):
            ctx = self.transfers.prepare(state.X, None)
        return self._advance(state, dt, ctx)

    # -- the carried form: the marker context outlives the step --------------
    def init_carry(self, state: ConstraintIBState):
        """The marker context a chunk of steps carries through its scan
        (``utils/hierarchy_driver.scan_steps``): one pack at
        ``state.X``, or None where there is nothing to carry (no
        engine, or one whose layout has no ``refresh``)."""
        if getattr(self.fast, "refresh", None) is None:
            return None
        with jax.named_scope("ib/prep"):
            return self.transfers.prepare(state.X, None)

    def step_carried(self, state: ConstraintIBState, ctx, dt: float):
        """``step`` with the marker context carried in and out:
        ``(state, ctx, stats)``. The context at X_n is a refresh of
        ``ctx`` (exact: it falls back to a full re-pack under its
        drift bound, and the re-packed layout is what is carried on);
        ``stats`` counts the step's one refresh and whether it fell.
        With ``ctx=None`` this is ``step``."""
        if ctx is None:
            return self.step(state, dt), None, {"refreshes": 0, "falls": 0}
        with jax.named_scope("ib/refresh"):
            ctx, hit = self.transfers.refresh(ctx, state.X, None)
        return (self._advance(state, dt, ctx), ctx,
                {"refreshes": 1,
                 "falls": jnp.logical_not(hit).astype(jnp.int32)})

    def _advance(self, state: ConstraintIBState, dt: float,
                 ctx) -> ConstraintIBState:
        """One step given the marker context at X_n."""
        scope = jax.named_scope
        ins, grid = self.ins, self.ins.grid
        bodies = self.bodies
        X = state.X
        _BODIES.inc(bodies.n_bodies)
        _MARKERS.inc(X.shape[0])
        obs.annotate("driver/chunk", transfer_engine=self.engine_name,
                     constraint_bodies=bodies.n_bodies,
                     constraint_markers=X.shape[0])

        # 1. unconstrained fluid step (it opens ``fluid`` itself)
        ins_star = ins.step(state.ins, dt)
        u_star = ins_star.u
        t_new = ins_star.t

        # 2. interpolate at markers
        with scope("ib/interp"):
            U_i = self.transfers.interpolate_velocity(u_star, grid, X,
                                                      None, ctx)

        with scope("constraint/rigid"):
            U_body, U_b, U_def = self._rigid_motion(state, X, U_i, dt,
                                                    t_new)

        # 5. impose on the grid, 6. restore incompressibility
        u_corr = self._impose(u_star, X, U_b - U_i, ctx)
        with scope("fluid"), scope("reproject"):
            u_new, _ = ins.project(u_corr, grid.dx)
        ins_new = ins_star._replace(u=u_new)

        X_new = rigid_move(X, bodies, U_body, dt)
        if U_def is not None:
            X_new = X_new + dt * U_def
        return ConstraintIBState(ins=ins_new, X=X_new, U_body=U_body)

    def _rigid_motion(self, state, X, U_i, dt, t_new):
        """Steps 3-4: ``(U_body, U_b, U_def)``: the bodies' rigid
        motion, the constrained marker velocity and its deformational
        part (None without a ``deformation_fn``), from the interpolated
        velocity."""
        bodies = self.bodies
        # 3. rigid projection; free DOFs keep it, others prescribed
        U_proj = project_rigid(X, bodies, U_i)
        # 3b. excess-inertia update for density-mismatched free bodies:
        #   V = V_fluid + a * (V_prev + dt g - V_fluid),
        #   a = (s-1)/(s+vm),  s = rho_b/rho_f.
        # The per-step gravity kick a*dt*g is the ADDED-MASS-corrected
        # buoyant acceleration (s-1)g/(s+vm) — for vm = 1 (default)
        # exactly the classical early-time free fall of a 2D cylinder
        # (added mass = displaced mass; use vm = 0.5 for a 3D sphere).
        # |a| < 1 for every s > 0 when vm >= 1, which is the
        # stabilization the raw explicit vm = 0 form (a = (s-1)/s,
        # added-mass unstable for light bodies) lacks. NOTE the map's
        # fixed-point slip vs the projected fluid velocity,
        # D = a/(1-a) dt g = (s-1)/(1+vm) dt g, is an O(dt)
        # operator-splitting artifact, NOT the terminal velocity: the
        # terminal state is wake-drag-limited through the fluid solve
        # (the slip here is ~1e-3 of the resolved velocities).
        # test_constraint_ib_dynamics pins the early-time added-mass
        # trajectory quantitatively (ADVICE round 2).
        if self.density_ratio is not None:
            s = self.density_ratio
            U_prev = state.U_body
            if self._g_modes is not None:
                U_prev = U_prev + dt * self._g_modes
            U_proj = U_proj + (s - 1.0) / (s + self.virtual_mass) \
                * (U_prev - U_proj)
        if self.prescribed_fn is not None:
            U_pres = jnp.asarray(self.prescribed_fn(t_new),
                                 dtype=U_proj.dtype)
            U_body = self.free * U_proj + (1.0 - self.free) * U_pres
        else:
            U_body = U_proj

        # 4. constrained marker velocity
        U_b = rigid_velocity(X, bodies, U_body)
        U_def = None
        if self.deformation_fn is not None:
            U_def = self.deformation_fn(t_new, X)
            U_def = U_def - rigid_velocity(
                X, bodies, project_rigid(X, bodies, U_def))
            U_b = U_b + U_def
        return U_body, U_b, U_def

    # -- setup ----------------------------------------------------------------
    def initialize(self, X0, ins_state: Optional[INSState] = None
                   ) -> ConstraintIBState:
        X = jnp.asarray(X0, dtype=self.ins.dtype)
        if ins_state is None:
            ins_state = self.ins.initialize()
        modes = n_rigid_modes(self.ins.grid.dim)
        return ConstraintIBState(
            ins=ins_state, X=X,
            U_body=jnp.zeros((self.bodies.n_bodies, modes),
                             dtype=self.ins.dtype))


def advance_constraint_ib(method: ConstraintIBMethod,
                          state: ConstraintIBState, dt: float,
                          num_steps: int) -> ConstraintIBState:
    """Advance ``num_steps`` under one jitted lax.scan."""
    def body(s, _):
        return method.step(s, dt), None

    out, _ = jax.lax.scan(body, state, None, length=num_steps)
    return out


def _fill_ball(center, radius: float, spacing: float, dtype):
    """The points of a lattice of at most ``spacing`` (an exact divisor
    of the diameter, so the lattice is symmetric about the centre)
    within ``radius`` of ``center``, in ``len(center)`` dimensions."""
    import numpy as np
    # the tolerances keep a diameter that is a whole number of spacings,
    # and lattice points on the sphere itself, on their side of rounding
    n = int(np.ceil(2 * radius / spacing - 1e-9)) + 1
    ax = np.linspace(-radius, radius, n)
    mesh = np.meshgrid(*[ax] * len(center), indexing="ij")
    keep = sum(m ** 2 for m in mesh) <= radius ** 2 * (1 + 1e-12)
    pts = np.stack([m[keep] + c for m, c in zip(mesh, center)], axis=1)
    return jnp.asarray(pts, dtype=dtype or jnp.float32)


def fill_disc(center, radius: float, spacing: float,
              dtype=None) -> jnp.ndarray:
    """Uniformly seeded solid disc of markers (the volumetric body
    sampling ConstraintIB needs, vs CIB's surface-only blobs)."""
    return _fill_ball(center[:2], radius, spacing, dtype)


def fill_sphere(center, radius: float, spacing: float,
                dtype=None) -> jnp.ndarray:
    """Uniformly seeded solid sphere of markers: :func:`fill_disc` in
    three dimensions (d = 24 h at spacing h/2: 57,777 markers)."""
    return _fill_ball(center[:3], radius, spacing, dtype)
