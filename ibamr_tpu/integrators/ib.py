"""Immersed-boundary coupling integrator (explicit schemes).

Reference parity (SURVEY.md §3.2): ``IBExplicitHierarchyIntegrator`` (P8)
driving the ``IBStrategy`` contract (P7) implemented by ``IBMethod`` (P9)
with ``LDataManager`` marker data (T1) and ``IBStandardForceGen`` forces
(P11). One midpoint timestep:

  U^n      = J(X^n) u^n                       (interpolateVelocity)
  X^{n+1/2} = X^n + dt/2 U^n                  (forwardEulerStep half)
  F^{n+1/2} = Force(X^{n+1/2}, U^n)           (computeLagrangianForce)
  f         = S(X^{n+1/2}) F^{n+1/2}          (spreadForce)
  u^{n+1}   = INS step with body force f      (fluid solve, §3.3)
  U^{n+1/2} = J(X^{n+1/2}) (u^n + u^{n+1})/2  (interpolateVelocity)
  X^{n+1}   = X^n + dt U^{n+1/2}              (midpointStep)

TPU-first design: the marker set is a fixed-capacity ``(N, dim)`` array
plus an active mask (SURVEY.md §7.1); the entire step — force SoA
evaluation, spread scatter, FFT fluid solve, interp gather — is one pure
jittable function, so ``lax.scan`` runs whole simulations on-device.

The ``IBMethod`` plugin seam survives as a small Python protocol: anything
with ``compute_force(X, U, t)`` can replace the standard force generator
(the analog of registering a custom IBLagrangianForceStrategy).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ibamr_tpu.grid import StaggeredGrid
from ibamr_tpu.integrators.ins import INSState, INSStaggeredIntegrator
from ibamr_tpu.ops import forces as force_mod
from ibamr_tpu.ops import interaction
from ibamr_tpu.ops.delta import Kernel

Vel = Tuple[jnp.ndarray, ...]


class IBState(NamedTuple):
    """Coupled fluid + structure state pytree."""
    ins: INSState
    X: jnp.ndarray       # (N, dim) marker positions
    U: jnp.ndarray       # (N, dim) marker velocities (diagnostic / damping)
    mask: jnp.ndarray    # (N,) 0/1 active-slot mask (fixed-capacity pool)


def check_fast_grid(fast, grid: StaggeredGrid) -> None:
    """A fast transfer engine bakes in its grid at construction;
    calling it against a different grid (a regrid, or the FINE grid of
    a composite hierarchy while the engine was built for the coarse
    one) must fail loudly — a shape-compatible mismatch would transfer
    with the wrong dx/origin silently. Shared by every IBStrategy."""
    eg = getattr(fast, "grid", None)
    if eg is not None and (tuple(eg.n) != tuple(grid.n)
                           or eg.x_lo != grid.x_lo
                           or eg.x_up != grid.x_up):
        # print the full geometry: in the composite-hierarchy mismatch
        # (coarse engine vs fine window) the SHAPES can be identical
        # and only the extents differ
        raise ValueError(
            f"fast engine grid (n={tuple(eg.n)}, x_lo={eg.x_lo}, "
            f"x_up={eg.x_up}) != call grid (n={tuple(grid.n)}, "
            f"x_lo={grid.x_lo}, x_up={grid.x_up}); rebuild the "
            "engine for this grid")


class IBMethod:
    """Classic marker-IB structure container (P9 parity).

    Holds the force specs and the delta kernel choice; provides the
    spread / interpolate / force operations the coupling integrator calls
    through the IBStrategy-shaped interface.
    """

    def __init__(self, specs: force_mod.ForceSpecs,
                 kernel: Kernel = "IB_4",
                 force_fn: Optional[Callable] = None,
                 fast=None):
        self.specs = specs
        self.kernel = kernel
        self.force_fn = force_fn  # optional custom force strategy
        # optional FastInteraction engine (ops.interaction_fast): the
        # bucketed-MXU formulation of spread/interp; None = scatter path
        self.fast = fast
        # RESOLVED engine name (set by factory builders after auto
        # resolution / fallback) — fingerprint and cache-key material;
        # None = derive a label from the engine object's type
        self.engine_name = None

    def compute_force(self, X: jnp.ndarray, U: jnp.ndarray,
                      t) -> jnp.ndarray:
        if self.force_fn is not None:
            return self.force_fn(X, U, t)
        return force_mod.compute_lagrangian_force(X, U, self.specs)

    def prepare(self, X: jnp.ndarray, mask: jnp.ndarray):
        """Per-position transfer context (marker buckets), shared by all
        spread/interp calls at the same X within a step."""
        if self.fast is None:
            return None
        return self.fast.buckets(X, mask)

    def refresh(self, ctx, X: jnp.ndarray, mask: jnp.ndarray):
        """Slot-preserving context refresh at a drifted position (the
        half-step of the midpoint scheme): re-gather the new positions
        into the pack-time layout instead of re-bucketing from scratch
        (exact — engines fall back to a full re-pack under a drift
        bound). Returns ``(ctx, hit)``, or ``(None, None)`` when the
        engine has no refresh path and the caller must re-prepare."""
        if ctx is None or self.fast is None:
            return None, None
        r = getattr(self.fast, "refresh", None)
        if r is None:
            return None, None
        return r(ctx, X, weights=mask)

    def interpolate_velocity(self, u: Vel, grid: StaggeredGrid,
                             X: jnp.ndarray, mask: jnp.ndarray,
                             ctx=None) -> jnp.ndarray:
        if self.fast is not None:
            check_fast_grid(self.fast, grid)
            return self.fast.interpolate_vel(u, X, weights=mask, b=ctx)
        return interaction.interpolate_vel(u, grid, X, kernel=self.kernel,
                                           weights=mask)

    def spread_force(self, F: jnp.ndarray, grid: StaggeredGrid,
                     X: jnp.ndarray, mask: jnp.ndarray,
                     ctx=None) -> Vel:
        if self.fast is not None:
            check_fast_grid(self.fast, grid)
            return self.fast.spread_vel(F, X, weights=mask, b=ctx)
        return interaction.spread_vel(F, grid, X, kernel=self.kernel,
                                      weights=mask)


class IBExplicitIntegrator:
    """Explicit IB coupling of an INS integrator and an IBMethod (P8).

    ``ins`` is any fluid integrator exposing ``grid``, ``dtype``,
    ``initialize()`` and ``step(state, dt, f=...)`` with a state
    carrying ``u`` and ``t`` — the periodic staggered integrator, the
    wall-bounded one, and the MULTIPHASE VC forms all satisfy the seam,
    so capsule-style structures in two-phase flow are the same
    composition (pass ``ins_state=vc.initialize(phi0)`` to
    ``initialize``; pinned by tests/test_vc_ib.py)."""

    def __init__(self, ins: INSStaggeredIntegrator, ib: IBMethod,
                 scheme: str = "midpoint"):
        if scheme not in ("midpoint", "forward_euler"):
            raise ValueError(f"unknown IB time stepping scheme {scheme!r}")
        self.ins = ins
        self.ib = ib
        self.scheme = scheme
        self._jitted_steps = {}

    def jitted_step(self, donate: bool = True, with_stats: bool = False):
        """Compiled step with whole-step buffer donation: the input
        IBState's buffers (velocity, pressure, markers) are reused for
        the output — fields update in place instead of allocating fresh
        full-field HBM buffers each step. Cached per (donate,
        with_stats), so repeated calls share one compiled executable.

        Donation contract: after ``new = f(state, dt)`` the caller's
        ``state`` buffers are DELETED — anyone retaining pre-step state
        (rollback templates, trajectory recorders keeping live arrays)
        must pass ``donate=False``. That includes reverse-mode autodiff:
        a cotangent pass replays the step from saved primals, so a
        donated input under an outer ``grad``/``vjp`` trace is a
        use-after-free the donated executable would hide. The returned
        callable therefore REFUSES (raises, does not silently ignore)
        donation when any input leaf is a tracer — mirroring
        ResilientDriver's forced-off donation, but loudly: the caller
        asked for an optimization the gradient makes unsound, and must
        choose (``donate=False``, or ``RunConfig(remat=...)`` chunks
        which force donation off under grad)."""
        key = (bool(donate), bool(with_stats))
        fn = self._jitted_steps.get(key)
        if fn is None:
            base = self.step_with_stats if with_stats else self.step
            if donate:
                jitted = jax.jit(base, donate_argnums=(0,))

                @functools.wraps(base)
                def fn(state, dt):
                    if any(isinstance(l, jax.core.Tracer)
                           for l in jax.tree_util.tree_leaves(
                               (state, dt))):
                        raise ValueError(
                            "jitted_step(donate=True) called under an "
                            "active trace (grad/vjp/jit): buffer "
                            "donation invalidates the primal values "
                            "the cotangent pass replays from. Use "
                            "jitted_step(donate=False) when "
                            "differentiating (the design loop and "
                            "RunConfig(remat=...) chunks do this "
                            "automatically).")
                    return jitted(state, dt)
                # keep the RAW python step reachable for the graph-
                # contract harness (contracts._unwrap lowers it with
                # its own donate_argnums)
                fn.__wrapped__ = base
            else:
                fn = jax.jit(base)
            self._jitted_steps[key] = fn
        return fn

    # -- state ---------------------------------------------------------------
    def initialize(self, X0, ins_state: Optional[INSState] = None,
                   mask=None) -> IBState:
        dtype = self.ins.dtype
        X = jnp.asarray(X0, dtype=dtype)
        if ins_state is None:
            ins_state = self.ins.initialize()
        if mask is None:
            mask = jnp.ones(X.shape[0], dtype=dtype)
        return IBState(ins=ins_state, X=X,
                       U=jnp.zeros_like(X),
                       mask=jnp.asarray(mask, dtype=dtype))

    # -- single step (pure, jittable) ----------------------------------------
    def step(self, state: IBState, dt: float) -> IBState:
        new_state, _ = self.step_with_stats(state, dt)
        return new_state

    def step_with_stats(self, state: IBState, dt: float):
        """``step`` plus a per-step stats dict: ``refresh_hit`` is a
        traced bool when the transfer engine took the slot-preserving
        half-step refresh path (False = the drift bound forced a full
        re-pack), or None when the engine has no refresh. The stats are
        a second return value — the IBState pytree is unchanged, so
        checkpoints, sharding specs and lax.scan carriers are
        untouched. One bucket prep per step: the context at X_n is
        built from scratch (``step_carried`` is the form that keeps
        it)."""
        # strategies may expose a per-position transfer context (marker
        # buckets for the MXU path) shared across calls at the same X
        with jax.named_scope("ib/prep"):
            ctx_n = self._prepare(state.X, state.mask)
        new_state, _, refresh_hit = self._advance(state, dt, ctx_n)
        return new_state, {"refresh_hit": refresh_hit}

    # -- the carried form: the transfer context outlives the step ------------
    def init_carry(self, state: IBState):
        """The transfer context a chunk of steps carries through its
        scan: one bucket prep at ``state.X`` (the ``ib/prep`` scope),
        or None where nothing can be carried — the strategy has no
        ``prepare``/``refresh``, or its engine answers a refresh with
        no context (``scatter``, ``mxu``)."""
        refresh = getattr(self.ib, "refresh", None)
        if refresh is None:
            return None
        # asked abstractly, so an engine without a refresh path leaves
        # no dead bucket prep in the traced program
        ctx, _ = jax.eval_shape(
            lambda X, m: refresh(self._prepare(X, m), X, m),
            state.X, state.mask)
        if ctx is None:
            return None
        with jax.named_scope("ib/prep"):
            return self._prepare(state.X, state.mask)

    def step_carried(self, state: IBState, ctx, dt: float):
        """``step`` with the transfer context carried in and out:
        ``(state, ctx, stats)``. The context at X_n is a refresh of
        ``ctx`` (whatever positions it was last gathered at) instead of
        a bucket prep; the context returned is the one the step ended
        with, re-packed where a drift bound fell, so a fall is paid
        once and not by every later step. ``stats`` counts the
        ``refreshes`` of the step and how many of them fell back to a
        full re-pack (``falls``). With ``ctx=None`` (see
        :meth:`init_carry`) this is ``step``. A caller threads ``ctx``
        through ``lax.scan`` beside the state
        (``HierarchyDriver._chunk``); ``vmap``, ``grad`` and the sharded
        step keep ``step``, whose one prep a step needs no ``cond``."""
        if ctx is None:
            return self.step(state, dt), None, {"refreshes": 0, "falls": 0}
        with jax.named_scope("ib/refresh"):
            ctx_n, hit_n = self.ib.refresh(ctx, state.X, state.mask)
        new_state, ctx_out, hit_h = self._advance(state, dt, ctx_n)
        hits = [h for h in (hit_n, hit_h) if h is not None]
        falls = sum(jnp.logical_not(h).astype(jnp.int32) for h in hits)
        return new_state, ctx_out, {"refreshes": len(hits), "falls": falls}

    def _prepare(self, X, mask):
        prep = getattr(self.ib, "prepare", None)
        return prep(X, mask) if prep is not None else None

    def _advance(self, state: IBState, dt: float, ctx_n):
        """One step given the transfer context at X_n. Returns the new
        state, the context the step ended with (at X_half for the
        midpoint scheme) and the half-step ``refresh_hit`` (None where
        no refresh ran)."""
        grid = self.ins.grid
        ib = self.ib
        u_n = state.ins.u
        X_n = state.X

        # Phase names (jax.named_scope: metadata only, no op added): the
        # compiled step says which phase owns each instruction, and
        # obs/deviceprof reads device time back by these names. No
        # component may match an op class of perfbench/tracereduce.py.
        scope = jax.named_scope

        # structure prediction to the half step
        with scope("ib/interp"):
            U_n = ib.interpolate_velocity(u_n, grid, X_n, state.mask,
                                          ctx=ctx_n)
        refresh_hit = None
        if self.scheme == "midpoint":
            X_half = X_n + 0.5 * dt * U_n
            # half-step context: slot-preserving refresh of ctx_n when
            # the strategy supports it (the round-5 measured 14.6 ms
            # bucket_prep tax is not paid twice), full re-prepare
            # otherwise
            refresh = getattr(ib, "refresh", None)
            ctx_h = None
            if refresh is not None and ctx_n is not None:
                with scope("ib/refresh"):
                    ctx_h, refresh_hit = refresh(ctx_n, X_half,
                                                 state.mask)
            if ctx_h is None:
                with scope("ib/prep"):
                    ctx_h = self._prepare(X_half, state.mask)
        else:
            X_half = X_n
            ctx_h = ctx_n

        # Lagrangian force at the half step, spread to the grid
        t_half = state.ins.t + 0.5 * dt
        with scope("ib/force"):
            F_half = ib.compute_force(X_half, U_n, t_half)
        with scope("ib/spread"):
            f_eul = ib.spread_force(F_half, grid, X_half, state.mask,
                                    ctx=ctx_h)

        # fluid solve with the IB body force (it opens ``fluid`` itself)
        ins_new = self.ins.step(state.ins, dt, f=f_eul)

        # corrector: move markers with the midpoint velocity
        if self.scheme == "midpoint":
            u_half = tuple(0.5 * (a + b) for a, b in zip(u_n, ins_new.u))
            with scope("ib/interp"):
                U_half = ib.interpolate_velocity(u_half, grid, X_half,
                                                 state.mask, ctx=ctx_h)
            X_new = X_n + dt * U_half
            U_out = U_half
        else:
            X_new = X_n + dt * U_n
            U_out = U_n

        return (IBState(ins=ins_new, X=X_new, U=U_out, mask=state.mask),
                ctx_h, refresh_hit)

    # -- diagnostics ---------------------------------------------------------
    def total_marker_force(self, state: IBState) -> jnp.ndarray:
        F = self.ib.compute_force(state.X, state.U, state.ins.t)
        return jnp.sum(F * state.mask[:, None], axis=0)


def advance_ib(integrator: IBExplicitIntegrator, state: IBState, dt: float,
               num_steps: int) -> IBState:
    """Advance ``num_steps`` under one jitted lax.scan."""
    def body(s, _):
        return integrator.step(s, dt), None

    out, _ = jax.lax.scan(body, state, None, length=num_steps)
    return out


def polygon_area(X: jnp.ndarray) -> jnp.ndarray:
    """Shoelace area of a closed 2D marker loop (volume-conservation
    diagnostic for the membrane acceptance configs)."""
    x, y = X[:, 0], X[:, 1]
    xn, yn = jnp.roll(x, -1), jnp.roll(y, -1)
    return 0.5 * jnp.abs(jnp.sum(x * yn - xn * y))
