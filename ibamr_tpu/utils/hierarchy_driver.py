"""Unified simulation run loop (the HierarchyIntegrator skeleton, T13).

Reference parity: ``IBTK::HierarchyIntegrator::advanceHierarchy`` plus
the driver boilerplate every reference ``main.cpp`` repeats — dt
management, regrid cadence, viz dumps, restart writing, per-step
diagnostics (SURVEY.md §2.1 T13, §3.1). Round 1 hand-rolled this loop
in every example and integrator (VERDICT round 1 item 8); this module
is the one shared skeleton, so examples shrink to config + callbacks.

TPU-first structure: the inner loop is a jitted ``lax.scan`` over
``chunk`` steps with a fused finite-state reduction, so health checking
costs one extra scalar per chunk instead of a host sync per step
(SURVEY.md §5.2's checkify/guard promise). ``dt`` is a traced argument
— CFL-driven dt changes between chunks do NOT retrigger compilation.

On divergence the driver raises :class:`SimulationDiverged` naming the
offending state leaves BEFORE any checkpoint of the broken state is
written — a blown-up run halts with a diagnostic instead of poisoning
the restart chain.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ibamr_tpu import obs as _obs

# module-cached handles: inc()/observe() on the instance is the
# lock-free path
_CHUNKS_TOTAL = _obs.counter("driver_chunks_total")
_STEPS_TOTAL = _obs.counter("driver_steps_total")
_CHUNK_WALL = _obs.histogram("driver_chunk_wall_seconds")
_obs.describe("driver_chunk_wall_seconds",
              "Per-chunk wall time including the post-chunk sync.")
# due viz_fn / checkpoint_fn calls by where they ran: after the next
# chunk's dispatch (deferred: True) or before it
_CALLBACKS = {
    (name, deferred): _obs.counter(
        "driver_callbacks_deferred_total" if deferred
        else "driver_callbacks_inline_total", callback=name)
    for name in ("viz_fn", "checkpoint_fn") for deferred in (True, False)}
_obs.describe("driver_callbacks_deferred_total",
              "Due viz/checkpoint callbacks run after the next chunk's "
              "dispatch, while the device steps.")
_obs.describe("driver_callbacks_inline_total",
              "Due viz/checkpoint callbacks run before the next dispatch "
              "(a regrid due, donated buffers, the last chunk).")
_REFRESHES_TOTAL = _obs.counter("transfer_refreshes_total")
_FALLS_TOTAL = _obs.counter("transfer_repack_falls_total")
_obs.describe("transfer_repack_falls_total",
              "Refreshes of a carried marker layout that fell back to a "
              "full re-pack (a drift bound was broken).")
_KRYLOV_ITERS_TOTAL = _obs.counter("stokes_krylov_iterations_total")
_obs.describe("stokes_krylov_iterations_total",
              "Arnoldi iterations of the coupled saddle solves (FGMRES) "
              "of the chunks run.")
_UNCONVERGED_TOTAL = _obs.counter("stokes_unconverged_solves_total")
_obs.describe("stokes_unconverged_solves_total",
              "Saddle solves that ended above their tolerance.")
# the counter each count of a carried chunk's tally lands on
_TALLY_COUNTERS = {"refreshes": _REFRESHES_TOTAL, "falls": _FALLS_TOTAL,
                   "iters": _KRYLOV_ITERS_TOTAL,
                   "unconverged": _UNCONVERGED_TOTAL}
# a carried chunk's tally: the span that reports it after the chunk's sync
# and its int32 counts, in order. An integrator names its own with a
# ``chunk_tally`` attribute; the default is the carried marker layout's
REFRESH_TALLY = ("refresh", ("refreshes", "falls"))


def chunk_tally(integ):
    """``(span name, count keys)`` of ``integ``'s carried chunk."""
    return getattr(integ, "chunk_tally", REFRESH_TALLY)


class SimulationDiverged(RuntimeError):
    """Raised when the state stops being finite; carries diagnostics.

    ``kind`` tags the incident-schema-v2 record the supervisor writes
    (subclasses: ``health_degraded`` precursor in utils/health.py,
    ``solver_breakdown`` in solvers/escalation.py);
    ``incident_payload()`` contributes subclass-specific fields."""

    kind = "divergence"

    def __init__(self, step: int, bad_leaves):
        self.step = step
        self.bad_leaves = bad_leaves
        names = ", ".join(bad_leaves) or "<unknown>"
        super().__init__(
            f"simulation diverged by step {step}: non-finite values in "
            f"state leaves [{names}] — no checkpoint written for the "
            f"broken state")

    def incident_payload(self) -> dict:
        return {}


class LaneFault(SimulationDiverged):
    """One or more lanes of a fleet chunk went bad; the REST of the
    fleet advanced normally and that progress must not be thrown away.

    Carries the post-chunk lane-stacked state (healthy lanes' progress)
    so the supervisor can patch only the failing lanes' slices and
    resume from ``step`` — rolling back B-1 healthy lanes for one bad
    lane is exactly the failure mode fleet execution exists to avoid.
    """

    kind = "lane_fault"

    def __init__(self, step: int, lanes, lane_reasons: dict,
                 vitals, fleet_size: int, state=None,
                 bad_leaves: Optional[dict] = None):
        self.lanes = list(lanes)
        self.lane_reasons = dict(lane_reasons)
        self.vitals = vitals
        self.fleet_size = int(fleet_size)
        self.state = state                 # post-chunk stacked state
        self.lane_bad_leaves = dict(bad_leaves or {})
        # SimulationDiverged's bad_leaves carries the union for callers
        # that only know the base class
        union = sorted({leaf for ls in self.lane_bad_leaves.values()
                        for leaf in ls})
        RuntimeError.__init__(
            self,
            f"lane fault at step {step}: lanes {self.lanes} of "
            f"{self.fleet_size} failed "
            f"({ {k: v for k, v in self.lane_reasons.items()} })")
        self.step = step
        self.bad_leaves = union

    def incident_payload(self) -> dict:
        vit = self.vitals
        return {
            "lanes": self.lanes,
            "lane_reasons": self.lane_reasons,
            "fleet_size": self.fleet_size,
            "lane_bad_leaves": self.lane_bad_leaves,
            "vitals": (np.asarray(vit).tolist()
                       if vit is not None else None),
        }


def _finite_flag(state) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(state)
    flags = [jnp.all(jnp.isfinite(l)) for l in leaves
             if hasattr(l, "dtype") and jnp.issubdtype(l.dtype,
                                                       jnp.floating)]
    out = jnp.asarray(True)
    for f in flags:
        out = jnp.logical_and(out, f)
    return out


def _finite_flag_lanes(state) -> jnp.ndarray:
    """Per-lane finite flags for a lane-stacked state: (B,) float
    vector, 1.0 where every floating leaf of that lane is finite."""
    leaves = jax.tree_util.tree_leaves(state)
    out = None
    for l in leaves:
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating):
            axes = tuple(range(1, l.ndim))
            f = jnp.all(jnp.isfinite(l), axis=axes)
            out = f if out is None else jnp.logical_and(out, f)
    if out is None:
        raise ValueError("state has no floating leaves")
    return out.astype(jnp.float32)


def _bad_leaf_names(state) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    bad = []
    for path, leaf in flat:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                bad.append(jax.tree_util.keystr(path))
    return bad


# Rematerialization policies for differentiable chunks (PR 19): what
# reverse-mode may SAVE inside each step of a scan chunk. "full" saves
# nothing (recompute everything from the per-step carry — minimal
# memory, one extra primal pass); "dots" saves matmul/contraction
# results (the MXU transfer einsums — recompute only the cheap
# elementwise chains). Names, not callables, so RunConfig stays a
# plain-data input file.
REMAT_POLICIES = {
    "full": None,
    "dots": "checkpoint_dots",
    "dots_no_batch": "checkpoint_dots_with_no_batch_dims",
}


def checkpointed_step(step, remat: str):
    """Wrap ``step(state, dt)`` in ``jax.checkpoint`` under the named
    policy — the building block for gradient-ready scan chunks."""
    policy_name = REMAT_POLICIES[remat]
    if policy_name is None:
        return jax.checkpoint(step)
    return jax.checkpoint(
        step, policy=getattr(jax.checkpoint_policies, policy_name))


def offers_carry(integ) -> bool:
    """Does ``integ`` have the carried form of its step
    (``init_carry`` / ``step_carried``, see :func:`scan_steps`)?"""
    return hasattr(integ, "init_carry") and hasattr(integ, "step_carried")


def scan_steps(step, state, dt, n: int, carried=None):
    """``n`` steps under one ``lax.scan``: ``(state, tally)``.

    Plain form (``carried=None``): the scan of ``step(state, dt)``;
    ``tally`` is None. Carried form: ``carried`` is an integrator with
    ``init_carry``/``step_carried``; its context (a packed marker
    layout, or None where its engine keeps none) is built once, before
    the scan, and rides the scan beside the state, and ``tally`` is the
    int32 ``[refreshes, falls]`` of the ``n`` steps. The driver's chunk
    and the replay of a recorded chunk (``tools/replay.py``) both come
    through here, so a replay lowers the program the run compiled."""
    if carried is None:
        def body(s, _):
            return step(s, dt), None

        out, _ = jax.lax.scan(body, state, None, length=n)
        return out, None

    keys = chunk_tally(carried)[1]

    def body(c, _):
        s, ctx, tally = c
        s, ctx, stats = carried.step_carried(s, ctx, dt)
        return (s, ctx, tally + jnp.stack(
            [jnp.asarray(stats[k], jnp.int32) for k in keys])), None

    (out, _, tally), _ = jax.lax.scan(
        body, (state, carried.init_carry(state),
               jnp.zeros((len(keys),), jnp.int32)), None, length=n)
    return out, tally


@dataclasses.dataclass
class RunConfig:
    """Cadences mirror the reference input-file vocabulary."""
    dt: float
    num_steps: int
    viz_dump_interval: int = 0        # 0 = off
    restart_interval: int = 0
    regrid_interval: int = 0
    health_interval: int = 10         # steps per jitted chunk (>= 1;
    #                                   the health check is not optional)
    cfl: Optional[float] = None       # recompute dt each chunk if set
    donate: bool = False              # donate the chunk's input state
    #   buffers (whole-step in-place update: no fresh HBM allocation
    #   per chunk). OPT-IN because donation invalidates the caller's
    #   pre-chunk state references — anything retaining the state it
    #   passed to run() (rollback templates, resume copies) must leave
    #   this off; ResilientDriver forces it off for exactly that reason.
    remat: Optional[str] = None       # checkpoint policy for the scan
    #   chunk (PR 19): None = primal-only chunks (unchanged); a policy
    #   name from REMAT_POLICIES wraps the per-step body in
    #   ``jax.checkpoint`` so reverse-mode through a chunk stores ONE
    #   state per step instead of every intermediate field. Setting it
    #   also forces chunk-input donation OFF (a donated input is a
    #   use-after-free for the cotangent replay) — the design loop
    #   differentiates these chunks via ibamr_tpu.design.

    def __post_init__(self):
        if self.remat is not None and self.remat not in REMAT_POLICIES:
            raise ValueError(
                f"RunConfig.remat must be one of "
                f"{sorted(REMAT_POLICIES)} or None, got {self.remat!r}")
        # Fail-fast input validation: a bad input file must die HERE
        # with the offending field named, not produce a zero-length
        # scan or a silent no-op run hours later.
        if not (self.dt > 0):            # also rejects NaN dt
            raise ValueError(
                f"RunConfig.dt must be > 0, got {self.dt!r} (a non-"
                f"positive or NaN timestep silently freezes the run)")
        if self.num_steps < 0:
            raise ValueError(
                f"RunConfig.num_steps must be >= 0, got "
                f"{self.num_steps!r}")
        for name in ("viz_dump_interval", "restart_interval",
                     "regrid_interval"):
            val = getattr(self, name)
            if val < 0:
                raise ValueError(
                    f"RunConfig.{name} must be >= 0 (0 = off), got "
                    f"{val!r} — a negative cadence is a typo'd input "
                    f"file, not a request")
        if self.health_interval < 1:
            raise ValueError(
                "health_interval is the steps-per-chunk granularity and "
                "must be >= 1 (the divergence guard cannot be disabled)")
        if self.cfl is not None and not (self.cfl > 0):
            raise ValueError(
                f"RunConfig.cfl must be > 0 when set, got {self.cfl!r}")


class HierarchyDriver:
    """Shared advance/regrid/viz/restart/health loop.

    ``integ`` needs ``step(state, dt) -> state`` (every integrator in
    the framework); optionally ``cfl_dt(state, cfl)`` when
    ``cfg.cfl`` is set. An integrator that also offers the carried
    form (``init_carry(state) -> ctx`` and ``step_carried(state, ctx,
    dt) -> (state, ctx, stats)``: ``IBExplicitIntegrator``) has its
    context threaded through the chunk's scan — one marker-layout pack
    per chunk, not one per step — unless the caller chose the step
    itself (``step_fn``), lanes or remat: those chunks scan ``step``.
    The carried chunk's counts leave with the health value in the one
    sync per chunk and close a span under ``driver/chunk`` that carries
    them as attributes (:func:`chunk_tally`): ``refresh`` with
    ``refreshes`` and ``falls`` (on ``transfer_refreshes_total`` /
    ``transfer_repack_falls_total``) for a carried marker layout,
    ``krylov`` with ``iters``, ``restarts`` and ``unconverged`` (on
    ``stokes_krylov_iterations_total`` /
    ``stokes_unconverged_solves_total``) for the open-boundary IB
    step's saddle solves. Callbacks (all optional), in the
    order they run after chunk k's sync and health check:

    - ``metrics_fn(state, step) -> dict`` after every chunk, always
      BEFORE the next dispatch (logged by the caller — returned dicts
      are aggregated into ``self.history``);
    - ``viz_fn(state, step)`` at the viz cadence, then
      ``checkpoint_fn(state, step)`` at the restart cadence. Both only
      read the post-chunk state, so where nothing forbids it the driver
      dispatches chunk k+1 FIRST and runs them beside it, on the calling
      thread, to completion before it syncs chunk k+1: the device steps
      while files are written, and a checkpoint of step k is on disk
      before chunk k+1's health is looked at. They run before the next
      dispatch, as ``metrics_fn`` does, where the loop sees that it must:
      ``regrid_fn`` is due at this step (chunk k+1 starts from another
      state), ``cfg.donate`` (chunk k+1 invalidates the buffers they
      read), or this was the last chunk. A due call of a healthy chunk is
      never dropped: if the next dispatch raises, it still runs before
      the exception leaves ``run``. Spans ``driver/viz_fn`` /
      ``driver/checkpoint_fn`` carry ``deferred``; counters
      ``driver_callbacks_deferred_total`` / ``_inline_total``. (Device
      work a deferred callback launches queues behind chunk k+1.)
    - ``regrid_fn(state, step) -> state`` at the regrid cadence, last
      (host-side retagging — may rebuild sharded placement).

    ``health_probe`` (a :class:`ibamr_tpu.utils.health.HealthProbe`)
    upgrades the per-chunk finite bool to the fused vitals vector at
    the SAME one-transfer-per-chunk cost: the probe's ``measure`` runs
    inside the jitted chunk, its ``check`` triages on the host and
    raises ``HealthDegraded`` (a ``SimulationDiverged`` precursor)
    before any cadence callback sees the degraded state.

    ``recorder`` (a :class:`ibamr_tpu.utils.flight_recorder
    .FlightRecorder`) snapshots the pre-chunk state to HOST memory
    before every chunk. The snapshot happens BEFORE the jitted chunk
    consumes the state, which is what makes recording compatible with
    ``cfg.donate=True``: the donated chunk invalidates the device
    buffers, but the ring holds independent host copies.

    ``shadow_audit`` (a :class:`ibamr_tpu.solvers.escalation
    .ShadowAuditor`) re-runs one fluid substep at f64 every N chunks
    and raises ``PrecisionDrift`` when the configured
    ``spectral_dtype`` path drifts past its pinned bound — BEFORE the
    checkpoint cadence can persist a silently-drifted state.
    """

    def __init__(self, integ, cfg: RunConfig,
                 viz_fn: Optional[Callable] = None,
                 metrics_fn: Optional[Callable] = None,
                 regrid_fn: Optional[Callable] = None,
                 checkpoint_fn: Optional[Callable] = None,
                 step_fn: Optional[Callable] = None,
                 timer=None,
                 timer_name: str = "HierarchyIntegrator::advanceHierarchy",
                 health_probe=None,
                 recorder=None,
                 shadow_audit=None,
                 lanes: Optional[int] = None,
                 fleet_step_wrap: Optional[Callable] = None,
                 lane_mesh=None):
        self.integ = integ
        self.cfg = cfg
        self.viz_fn = viz_fn
        self.metrics_fn = metrics_fn
        self.regrid_fn = regrid_fn
        self.checkpoint_fn = checkpoint_fn
        self.timer = timer                 # TimerManager: one entry per
        self.timer_name = timer_name       # chunk (its wall), see run()
        self.health_probe = health_probe
        self.recorder = recorder
        self.shadow_audit = shadow_audit
        self.last_vitals = None            # host dict of the last chunk
        self.last_chunk_wall_s = None      # wall seconds incl. the sync
        self.history = []
        self._base_step = (step_fn if step_fn is not None
                           else integ.step)
        # thread the integrator's carried context through the scan:
        # only where the step is the integrator's own and runs as is
        # (vmap would run both branches of the refresh's cond, remat
        # and a caller's step_fn wrap ``step``)
        self._carried = (step_fn is None and lanes is None
                         and cfg.remat is None and offers_carry(integ))
        # one compiled chunk per distinct length (a handful at most:
        # cadence-aligned lengths repeat) — no masked-tail waste
        self._chunks = {}
        # DISTINCT INPUT SIGNATURES observed per chunk length: the
        # retrace observable the no-retrace contract is tested against.
        # jit's _cache_size() cannot serve here — the process-global
        # pjit LRU can evict a live entry in a long session, reading as
        # 0 even though no retrace happened (and a later call would
        # silently recompile). Counting raw trace events is also too
        # coupled: a re-trace after jax.clear_caches() (the per-module
        # conftest fixture) or an AOT .lower() re-enters the closure
        # without any NEW signature (ADVICE r5 item 3) — so the dict
        # counts distinct (shape, dtype) signatures instead, which a
        # benign re-trace of a known signature leaves unchanged.
        self.trace_counts = {}
        self._trace_sigs = {}
        self._called = set()        # chunk lengths called so far
        # ---- fleet (lane-batched) mode -------------------------------
        # lanes=B runs B independent scenarios through ONE vmapped
        # chunk: state leaves carry a leading lane axis, dt becomes a
        # (B,) vector and a (B,) lane-alive mask freezes quarantined
        # lanes in-graph. Both are TRACED arguments — per-lane dt
        # backoff and quarantine never retrace.
        if lanes is not None and lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes!r}")
        if lanes is not None and cfg.cfl is not None:
            raise ValueError(
                "cfg.cfl adaptive dt is not supported in fleet mode — "
                "lanes carry independent per-lane dt (driver.lane_dt)")
        self.lanes = lanes
        self.fleet_step_wrap = fleet_step_wrap
        # lane_mesh: shard the LANE axis over devices (GSPMD over whole
        # lanes — parallel.mesh.make_lane_mesh). Orthogonal to the
        # per-lane machinery: quarantine/dt stay (B,) traced vectors.
        if lane_mesh is not None and lanes is None:
            raise ValueError("lane_mesh requires fleet mode (lanes=B)")
        if lane_mesh is not None:
            d = int(lane_mesh.devices.size)
            if lanes % d != 0:
                raise ValueError(
                    f"lanes={lanes} not divisible by the {d}-device "
                    f"lane mesh (each device must own whole lanes)")
        self.lane_mesh = lane_mesh
        if lanes is not None:
            # host mirrors of the traced per-lane knobs; the supervisor
            # mutates these between chunks (rollback backoff,
            # quarantine) without triggering a retrace
            self.lane_dt = np.full(lanes, float(cfg.dt), dtype=float)
            self.lane_alive = np.ones(lanes, dtype=bool)
        else:
            self.lane_dt = None
            self.lane_alive = None

    def _chunk(self, n: int):
        if n not in self._chunks:
            base_step = self._base_step
            if self.cfg.remat is not None:
                # gradient-ready chunk: per-step checkpoint policy; the
                # scan below then exposes the standard scan-of-remat
                # structure reverse-mode differentiates at one saved
                # carry per step
                base_step = checkpointed_step(base_step, self.cfg.remat)
            # local aliases: the closure must not capture self, or the
            # global pjit cache would pin the whole driver (integrator,
            # history, callbacks) for the cache entry's lifetime
            counts = self.trace_counts
            sigs = self._trace_sigs
            probe = self.health_probe
            lanes = self.lanes
            integ = self.integ if self._carried else None
            if lanes is not None:
                self._chunks[n] = self._build_fleet_chunk(n)
                return self._chunks[n]

            def chunk(state, dt):
                # runs at TRACE time only: record the input signature;
                # the count is the number of DISTINCT signatures, so a
                # benign re-trace (cache cleared, AOT lower) of a
                # known signature does not read as a retrace
                sig = (
                    tuple((tuple(l.shape), str(l.dtype))
                          for l in jax.tree_util.tree_leaves(state)
                          if hasattr(l, "shape")),
                    (tuple(getattr(dt, "shape", ())),
                     str(getattr(dt, "dtype", type(dt).__name__))))
                sigs.setdefault(n, set()).add(sig)
                counts[n] = len(sigs[n])

                out, tally = scan_steps(base_step, state, dt, n,
                                        carried=integ)
                # the vitals vector replaces the single finite bool at
                # the SAME one-transfer-per-chunk cost: both fuse into
                # the scan's output and cross to the host once
                health = (probe.measure(out, dt) if probe is not None
                          else _finite_flag(out))
                if tally is not None:
                    # ... and a carried chunk's counts ride behind it:
                    # health[0] stays the finite flag either way
                    health = jnp.concatenate(
                        [jnp.ravel(health).astype(jnp.float32),
                         tally.astype(jnp.float32)])
                return out, health

            # whole-chunk buffer donation: the input state's buffers are
            # reused for the output (velocity/pressure update in place
            # instead of allocating fresh full-field buffers per chunk).
            # Safe inside run(): callbacks only ever see the POST-chunk
            # state, and the loop immediately rebinds ``state``.
            # FORCED OFF under remat: a gradient-bound chunk's input is
            # replayed by the cotangent pass — donating it is a
            # use-after-free (same hazard jitted_step(donate=True)
            # refuses under an active trace).
            if self.cfg.donate and self.cfg.remat is None:
                self._chunks[n] = jax.jit(chunk, donate_argnums=(0,))
            else:
                self._chunks[n] = jax.jit(chunk)
        return self._chunks[n]

    def _build_fleet_chunk(self, n: int):
        """The lane-batched chunk: ``chunk(state, dt_vec, alive)``.

        One ``lax.scan`` over a vmapped step; quarantined lanes are
        frozen in-graph by selecting their PRE-step rows after every
        step (``jnp.where`` on the lane-alive mask — no retrace, no
        host round-trip). The bitwise contract: this chunk is
        batch-size invariant (lane k of B lanes == the same lane run at
        B=1; pinned by tests/test_fleet.py), which is what makes B=1
        runs the solo reference and single-lane capsules replayable."""
        base_step = self._base_step
        counts = self.trace_counts
        sigs = self._trace_sigs
        probe = self.health_probe
        lanes = self.lanes
        wrap = self.fleet_step_wrap
        # lane-mesh shardings built OUTSIDE the closure (no self capture)
        if self.lane_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            _lane_sh = NamedSharding(
                self.lane_mesh, PartitionSpec(self.lane_mesh.axis_names[0]))

            def _pin_lanes(t):
                # constraint-pin the lane axis at the chunk boundary so
                # GSPMD keeps whole lanes on their devices through the
                # scan; the comm scope labels any resulting resharding
                # for obs/deviceprof comm_s attribution
                with jax.named_scope("comm"):
                    return jax.tree_util.tree_map(
                        lambda a: (jax.lax.with_sharding_constraint(
                            a, _lane_sh)
                            if getattr(a, "ndim", 0) >= 1 else a), t)
        else:
            _pin_lanes = None

        stacked_step = jax.vmap(base_step, in_axes=(0, 0))
        if wrap is not None:
            # lane-targeted fault injection wraps the STACKED step: a
            # per-lane injector needs the lane axis in view
            stacked_step = wrap(stacked_step)
        if probe is not None:
            measure_lanes = jax.vmap(probe.measure, in_axes=(0, 0))

        def chunk(state, dt, alive):
            # trace-time signature record; the lane count is an
            # explicit element so the no-retrace contract is testable
            # per (B, chunk length)
            sig = (
                int(lanes),
                tuple((tuple(l.shape), str(l.dtype))
                      for l in jax.tree_util.tree_leaves(state)
                      if hasattr(l, "shape")),
                (tuple(dt.shape), str(dt.dtype)),
                (tuple(alive.shape), str(alive.dtype)))
            sigs.setdefault(n, set()).add(sig)
            counts[n] = len(sigs[n])

            if _pin_lanes is not None:
                state = _pin_lanes(state)
                (dt, alive) = _pin_lanes((dt, alive))

            def body(s, _):
                new = stacked_step(s, dt)
                # freeze dead lanes at their pre-step rows; healthy
                # lanes pass through bitwise (select, not arithmetic)
                frozen = jax.tree_util.tree_map(
                    lambda nl, ol: jnp.where(
                        alive.reshape((lanes,) + (1,) * (nl.ndim - 1)),
                        nl, ol),
                    new, s)
                return frozen, None

            out, _ = jax.lax.scan(body, state, None, length=n)
            if _pin_lanes is not None:
                out = _pin_lanes(out)
            if probe is not None:
                # (B, 7) per-lane vitals -> (7, B); still ONE host
                # transfer per chunk
                return out, jnp.transpose(measure_lanes(out, dt))
            return out, _finite_flag_lanes(out)

        if self.cfg.donate:
            return jax.jit(chunk, donate_argnums=(0,))
        return jax.jit(chunk)

    def _triage_fleet(self, state, health, step: int):
        """Host-side per-lane triage of a fleet chunk's vitals.

        ``health`` is the (7, B) vitals matrix (probe) or the (B,)
        finite vector. Dead (quarantined) lanes are skipped — their
        frozen rows are the last good state, not a new fault. Any LIVE
        lane that went non-finite or triaged FATAL raises
        :class:`LaneFault` carrying the post-chunk state; the
        supervisor patches only the failing lanes and resumes."""
        probe = self.health_probe
        alive = self.lane_alive
        B = self.lanes
        finite = (health[0] >= 1.0) if probe is not None \
            else (health >= 1.0)
        bad = [i for i in range(B) if alive[i] and not bool(finite[i])]
        reasons = {i: ["non_finite"] for i in bad}
        if probe is not None:
            verdicts = probe.check_lanes(health, step=step,
                                         dt=self.lane_dt, alive=alive)
            self.last_vitals = verdicts
            for i, v in enumerate(verdicts):
                if i in reasons or not alive[i]:
                    continue
                if v.get("fire"):
                    bad.append(i)
                    reasons[i] = list(v.get("reasons") or [])
        if bad:
            from ibamr_tpu.utils.lanes import lane_slice
            bad_leaves = {}
            for i in bad:
                if not bool(finite[i]):
                    bad_leaves[i] = _bad_leaf_names(lane_slice(state, i))
            raise LaneFault(step, sorted(bad), reasons, health, B,
                            state=state, bad_leaves=bad_leaves)

    def run(self, state, start_step: int = 0):
        """Advance to ``cfg.num_steps``; returns the final state."""
        cfg = self.cfg
        step = start_step
        dt = cfg.dt
        if (start_step and cfg.regrid_interval
                and self.regrid_fn is not None
                and start_step % cfg.regrid_interval == 0):
            # resume landing ON a regrid boundary: the checkpoint the
            # caller restored was written BEFORE that step's regrid ran
            # (cadence order below is checkpoint, then regrid), so the
            # pending regrid — or an assimilation analysis riding the
            # regrid hook — must fire exactly once here, else a
            # supervisor rollback silently drops it
            state = self.regrid_fn(state, start_step)
        cadences = [i for i in (cfg.viz_dump_interval,
                                cfg.restart_interval,
                                cfg.regrid_interval) if i]
        ordinal = 0                     # chunk ordinal within this run
        # the due viz_fn / checkpoint_fn calls of the chunk that just
        # ended: (name, fn, state, step, chunk, deferred)
        pending = []

        def flush():
            due = pending[:]
            del pending[:]      # a callback that raises drops the rest
            for name, cb, s, k, chunk, deferred in due:
                _CALLBACKS[name, deferred].inc()
                # a root span, as where it runs before the dispatch
                with _obs.detached(), _obs.span(
                        "driver/" + name, step=k, chunk=chunk,
                        deferred=deferred):
                    cb(s, k)

        try:
            while step < cfg.num_steps:
                if cfg.cfl is not None:
                    # float() keeps dt a weak-typed Python scalar whichever
                    # branch wins (a device-scalar cfl_dt would otherwise
                    # flip the aval and retrace)
                    dt = float(min(cfg.dt,
                                   self.integ.cfl_dt(state, cfg.cfl)))
                n = min(cfg.health_interval, cfg.num_steps - step)
                for i in cadences:               # land exactly on cadences
                    n = min(n, i - step % i)
                probe = self.health_probe
                fleet = self.lanes is not None
                if fleet:
                    snap_dt = self.lane_dt.copy()
                    snap_alive = self.lane_alive.copy()
                    chunk_args = (jnp.asarray(self.lane_dt),
                                  jnp.asarray(self.lane_alive))
                else:
                    snap_dt, snap_alive = dt, None
                    chunk_args = (dt,)
                if self.recorder is not None:
                    # host copy of the PRE-chunk state, taken before the
                    # (possibly donated) chunk invalidates its buffers
                    self.recorder.snapshot(state, step=step, dt=snap_dt,
                                           length=n, integ=self.integ,
                                           cfg=cfg, alive=snap_alive)
                t0 = time.perf_counter()
                # the chunk span brackets dispatch AND the one-per-chunk
                # host sync; its children split the two. Every span closes
                # into obs's ring (and the run ledger when one is attached)
                # and sits on a profiler capture's timeline. Telemetry never
                # reaches inside the jitted chunk — the *_telemetry graph
                # contracts pin zero in-scan host transfers with the bus
                # armed. Callbacks a boundary deferred run inside this
                # interval (beside the chunk, on this thread), so it and
                # last_chunk_wall_s hold them.
                first_call = n not in self._called
                with _obs.span("driver/chunk", step=step, length=n,
                               chunk=ordinal):
                    fn = self._chunk(n)
                    if first_call:
                        # what obs/deviceprof needs to read this program's
                        # compiled text after the run: shapes, no buffers
                        # (taken before a donating call deletes them)
                        self._called.add(n)
                        _obs.register_program(f"driver/chunk[{n}]",
                                              self._chunks[n],
                                              (state,) + chunk_args, steps=n)
                    with _obs.span("dispatch", step=step, chunk=ordinal,
                                   first_call=first_call):
                        state, health = fn(state, *chunk_args)
                    # the boundary before deferred its file writes to
                    # here: they run while the device steps, and are
                    # done before this chunk's health is looked at
                    flush()
                    with _obs.span("sync", step=step, chunk=ordinal):
                        # one device sync per chunk: the finite bool or the
                        # fused vitals vector
                        health = np.asarray(health)
                    if self._carried:
                        # the carried chunk's tally, off the tail of what
                        # the sync brought: counters and an event span
                        name, keys = chunk_tally(self.integ)
                        counts = dict(zip(keys, (
                            int(v) for v in health[-len(keys):])))
                        health = health[:-len(keys)]
                        for key, v in counts.items():
                            if key in _TALLY_COUNTERS:
                                _TALLY_COUNTERS[key].inc(v)
                        with _obs.span(name, step=step, chunk=ordinal,
                                       **counts):
                            pass
                self.last_chunk_wall_s = time.perf_counter() - t0
                if self.timer is not None:
                    # the report's entry per chunk, from the chunk span's
                    # own interval: no second span around it
                    self.timer.get(self.timer_name).add(self.last_chunk_wall_s)
                _CHUNKS_TOTAL.inc()
                _STEPS_TOTAL.inc(n)
                _CHUNK_WALL.observe(self.last_chunk_wall_s)
                # per-chunk counters snapshot + device-memory watermarks,
                # riding the sync that just happened (no-op when no ledger
                # is attached)
                _obs.chunk_boundary(step=step + n,
                                    chunk_wall_s=self.last_chunk_wall_s)
                if fleet:
                    # per-lane triage; raises LaneFault (carrying the
                    # post-chunk state so healthy-lane progress survives)
                    # BEFORE any cadence callback sees a poisoned lane
                    self._triage_fleet(state, health, step + n)
                else:
                    finite = bool(health.reshape(-1)[0] >= 1.0)
                    if not finite:
                        raise SimulationDiverged(step + n,
                                                 _bad_leaf_names(state))
                    if probe is not None:
                        # host-side triage; raises HealthDegraded (the
                        # SimulationDiverged precursor) BEFORE any cadence
                        # callback can checkpoint the degraded state
                        self.last_vitals = probe.check(health, step=step + n,
                                                       dt=dt)
                if self.shadow_audit is not None and not fleet:
                    # strided f64 shadow audit; raises PrecisionDrift
                    # BEFORE the checkpoint cadence can persist a
                    # silently-drifted state
                    self.shadow_audit.maybe_audit(self.integ, state, dt,
                                                  step=step + n)
                step += n

                if self.metrics_fn is not None:
                    with _obs.span("driver/metrics_fn", step=step,
                                   chunk=ordinal):
                        rec = self.metrics_fn(state, step)
                    if rec:
                        self.history.append(rec)
                regrid = (cfg.regrid_interval and self.regrid_fn is not None
                          and step % cfg.regrid_interval == 0)
                # the next dispatch may go ahead of this boundary's file
                # writes unless it starts from another state (a regrid),
                # invalidates the one they read (donation) or never
                # comes (the last chunk)
                defer = not (regrid or cfg.donate or step == cfg.num_steps)
                for name, cb, every in (
                        ("viz_fn", self.viz_fn, cfg.viz_dump_interval),
                        ("checkpoint_fn", self.checkpoint_fn,
                         cfg.restart_interval)):
                    if every and cb is not None and step % every == 0:
                        pending.append((name, cb, state, step, ordinal,
                                        defer))
                if not defer:
                    flush()
                if regrid:
                    with _obs.span("driver/regrid_fn", step=step,
                                   chunk=ordinal):
                        state = self.regrid_fn(state, step)
                ordinal += 1
        finally:
            # a due callback of a chunk that passed its health check is
            # never dropped: whatever stopped the next chunk from being
            # dispatched, its files are written before that leaves run()
            flush()
        # always visualize the final configuration, aligned or not
        if (cfg.viz_dump_interval and self.viz_fn is not None
                and step % cfg.viz_dump_interval != 0):
            pending.append(("viz_fn", self.viz_fn, state, step,
                            max(ordinal - 1, 0), False))
            flush()
        return state
