"""Solver non-convergence surfacing + escalation (PR 3 tentpole 2).

Every Krylov solve in the framework returns a ``SolveResult`` with
``iters``/``resnorm``/``converged`` — and until this PR every
integrator caller DISCARDED them: a Stokes solve that stagnated at
resnorm 1e-2 fed its garbage update straight into the next timestep,
and the first visible symptom was a NaN chunks later. This module is
the production answer:

- :func:`record_solve_stats` threads a solve's stats onto its owning
  solver object (``last_solve_stats``) so ``metrics_fn``/bench can log
  them WITHOUT re-running the solve. Eager solves record directly;
  traced solves record through ``jax.debug.callback`` only when the
  owner opted in (``record_stats=True``) — the default adds nothing to
  jitted/sharded paths.
- :func:`escalate_solve` walks a DECLARED fallback chain, mirroring
  the transfer engines' fallback-chain shape: each level names a cheap
  recipe (more FGMRES restarts, a longer Krylov basis, a more accurate inner
  preconditioner — the "tighter inner tol" knob) and the walk stops at
  the first level that converges. Level 0 converging returns its
  result untouched (bitwise the plain solve). Any walk past level 0
  lands a structured ``solver_escalation``/``solver_breakdown``
  incident; an exhausted chain raises :class:`SolverBreakdown`, which
  subclasses ``SimulationDiverged`` so the PR-2 supervisor treats it
  exactly like a divergence (rollback + dt backoff + retry).

Escalation is a HOST-side loop (each attempt re-traces eagerly with
its own static solver geometry), so it lives at the driver/setup level
— inside a jitted step the stats surface via the callback path and the
driver escalates between chunks.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence

from ibamr_tpu.utils.hierarchy_driver import SimulationDiverged


class SolverBreakdown(SimulationDiverged):
    """A solve escalated through its whole declared chain and still did
    not converge. Subclasses :class:`SimulationDiverged` so the
    supervisor's rollback-and-retry fires unchanged (a breakdown at
    large dt is routinely cured by the dt backoff)."""

    kind = "solver_breakdown"

    def __init__(self, context: str, attempts, step: Optional[int] = None):
        self.context = context
        self.attempts = list(attempts)
        self.step = -1 if step is None else step
        self.bad_leaves: list = []
        last = self.attempts[-1] if self.attempts else {}
        RuntimeError.__init__(
            self,
            f"solver breakdown in {context!r}: escalation chain "
            f"exhausted after {len(self.attempts)} attempts "
            f"(last level {last.get('level')!r}, resnorm "
            f"{last.get('resnorm')})")

    def incident_payload(self) -> dict:
        return {"context": self.context, "attempts": self.attempts}


# ---------------------------------------------------------------------------
# stats surfacing
# ---------------------------------------------------------------------------

def _is_tracer(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


def solve_stats_dict(sol, solver: str = "", level: str = "") -> dict:
    """Host-side dict from an (already concrete) SolveResult-like."""
    rec = {"iters": int(sol.iters), "resnorm": float(sol.resnorm),
           "converged": bool(sol.converged)}
    if solver:
        rec["solver"] = solver
    if level:
        rec["level"] = level
    return rec


def record_solve_stats(sink, sol, solver: str = "",
                       use_callback: bool = False,
                       mirrors: Sequence = ()) -> None:
    """Store ``{iters, resnorm, converged, solver}`` as
    ``sink.last_solve_stats`` (and on every object in ``mirrors``).

    Eager values are stored synchronously. Traced values (the solve is
    running inside jit) are recorded through ``jax.debug.callback``
    when ``use_callback`` is set — fired per execution, host-ordered,
    no added device sync — and silently skipped otherwise, so jitted
    and SPMD-sharded paths pay nothing unless the owner opted in.
    """
    sinks = (sink,) + tuple(m for m in mirrors if m is not None)
    if not any(_is_tracer(v) for v in (sol.iters, sol.resnorm,
                                       sol.converged)):
        rec = solve_stats_dict(sol, solver)
        for s in sinks:
            s.last_solve_stats = rec
        return
    if not use_callback:
        return
    import jax

    def _tap(iters, resnorm, converged):
        rec = {"iters": int(iters), "resnorm": float(resnorm),
               "converged": bool(converged)}
        if solver:
            rec["solver"] = solver
        for s in sinks:
            s.last_solve_stats = rec

    jax.debug.callback(_tap, sol.iters, sol.resnorm, sol.converged)


# ---------------------------------------------------------------------------
# the declared escalation chain (the transfer engines' fallback-chain shape)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EscalationLevel:
    """One link of a solve escalation chain. The scales multiply the
    base solve's geometry; ``inner_scale`` deepens whatever inner
    accuracy knob the owner exposes (preconditioner sweeps / inner
    tolerance — the attempt_fn decides what it means)."""

    name: str
    restarts_scale: int = 1
    m_scale: int = 1
    maxiter_scale: int = 1
    inner_scale: int = 1


ESCALATION_LEVELS: Dict[str, EscalationLevel] = {
    "base": EscalationLevel("base"),
    "restarts_x4": EscalationLevel("restarts_x4", restarts_scale=4),
    "deep_x4_inner_x2": EscalationLevel(
        "deep_x4_inner_x2", restarts_scale=4, m_scale=2, inner_scale=2),
}

# name -> next link (None terminates), like the transfer engines' chain: one
# flat registry, chains derived by walking it, no cycles by inspection
ESCALATION_FALLBACKS: Dict[str, Optional[str]] = {
    "base": "restarts_x4",
    "restarts_x4": "deep_x4_inner_x2",
    "deep_x4_inner_x2": None,
}


def escalation_chain(name: str = "base"):
    """The escalation order starting AT ``name`` (inclusive). Raises
    KeyError for unknown level names."""
    cur: Optional[str] = name
    if cur not in ESCALATION_LEVELS:
        raise KeyError(f"unknown escalation level {name!r}; known: "
                       f"{sorted(ESCALATION_LEVELS)}")
    chain = []
    while cur is not None:
        chain.append(ESCALATION_LEVELS[cur])
        cur = ESCALATION_FALLBACKS[cur]
    return chain


# ---------------------------------------------------------------------------
# precision escalation (PR 5): the spectral_dtype chain + f64 shadow audit
# ---------------------------------------------------------------------------

class PrecisionDrift(SimulationDiverged):
    """The strided f64 shadow audit found the mixed-precision fluid
    substep drifting past its pinned bound: the state is finite and the
    solver converged, but the fast path is lying. Subclasses
    :class:`SimulationDiverged` so the supervisor's rollback machinery
    fires — but the supervisor retries at the NEXT precision level
    (``PRECISION_FALLBACKS``) instead of backing dt off, because the
    cure is precision, not stability."""

    kind = "precision_drift"

    def __init__(self, step: int, *, drift: float, bound: float,
                 spectral_dtype: str, div_drift: Optional[float] = None):
        self.step = step
        self.drift = float(drift)
        self.bound = float(bound)
        self.spectral_dtype = spectral_dtype
        self.div_drift = None if div_drift is None else float(div_drift)
        self.bad_leaves: list = []      # nothing is non-finite
        RuntimeError.__init__(
            self,
            f"precision drift by step {step}: f64 shadow audit measured "
            f"relative substep drift {self.drift:.4g} > bound "
            f"{self.bound:.4g} at spectral_dtype={spectral_dtype!r} — "
            f"the mixed-precision fast path is out of tolerance")

    def incident_payload(self) -> dict:
        return {"drift": self.drift, "bound": self.bound,
                "spectral_dtype": self.spectral_dtype,
                "div_drift": self.div_drift}


# level name -> next link (None terminates): the engine-fallback /
# ESCALATION_FALLBACKS shape, applied to the spectral_dtype knob. The
# names are exactly the canonical_spectral_dtype aliases, so a level
# name can be assigned straight onto ``integ.spectral_dtype``.
PRECISION_LEVELS = ("bf16", "f32", "f64")
PRECISION_FALLBACKS: Dict[str, Optional[str]] = {
    "bf16": "f32",
    "f32": "f64",
    "f64": None,
}


def precision_level_name(spectral_dtype) -> str:
    """Map a canonical ``spectral_dtype`` knob value (None / jnp.bfloat16
    / jnp.float64 or their string aliases) to its PRECISION_LEVELS name."""
    import jax.numpy as jnp

    from ibamr_tpu.solvers.spectral_plan import canonical_spectral_dtype

    sd = canonical_spectral_dtype(spectral_dtype)
    if sd is None:
        return "f32"
    if sd is jnp.bfloat16:
        return "bf16"
    return "f64"


def precision_chain(name: str = "bf16"):
    """The precision escalation order starting AT ``name`` (inclusive)."""
    if name not in PRECISION_FALLBACKS:
        raise KeyError(f"unknown precision level {name!r}; known: "
                       f"{list(PRECISION_LEVELS)}")
    chain, cur = [], name
    while cur is not None:
        chain.append(cur)
        cur = PRECISION_FALLBACKS[cur]
    return chain


class ShadowAuditor:
    """Strided f64 shadow audit of the fused spectral fluid substep.

    Every ``every`` chunks, :meth:`maybe_audit` re-runs ONE
    representative Stokes substep from the current velocity twice —
    once at the integrator's configured ``spectral_dtype`` and once at
    f64 via the existing :class:`~ibamr_tpu.solvers.spectral_plan
    .SpectralPlan` — and compares the relative velocity drift (and the
    post-projection divergence gap) against pinned bounds. A breach
    raises :class:`PrecisionDrift`, which the supervisor answers with a
    rollback and a retry at the next ``PRECISION_FALLBACKS`` level.

    The audit is strided and OUTSIDE the jitted chunk (one extra
    substep per ``every`` chunks, amortized to noise) so the hot path's
    trace and transfer budget are untouched — pinned by the driver's
    ``trace_counts`` in tests.

    Default ``bound=0.02``: an order of magnitude above the pinned
    natural bf16 substep drift (~3e-3 vs the f64 oracle,
    tests/test_spectral_plan.py), so only a genuinely out-of-tolerance
    fast path trips it.
    """

    def __init__(self, every: int = 8, bound: float = 0.02,
                 div_bound: Optional[float] = None):
        if every < 1:
            raise ValueError("ShadowAuditor.every must be >= 1")
        self.every = every
        self.bound = float(bound)
        self.div_bound = None if div_bound is None else float(div_bound)
        self.chunks_seen = 0
        self.audits = 0
        self.history: list = []
        self.last: Optional[dict] = None

    def params(self) -> dict:
        """JSON-safe audit configuration for the flight-recorder
        fingerprint (what tools/replay.py re-arms the audit from)."""
        return {"every": self.every, "bound": self.bound,
                "div_bound": self.div_bound}

    @staticmethod
    def _fluid_parts(integ, state):
        """(ins-like integrator, ins-like state) — unwraps one IB layer."""
        ins = getattr(integ, "ins", None)
        if ins is not None and hasattr(state, "ins"):
            return ins, state.ins
        return integ, state

    def maybe_audit(self, integ, state, dt, step: int):
        """Called by the driver once per chunk; audits every ``every``-th
        call. Returns the audit record (or None off-cadence)."""
        self.chunks_seen += 1
        if self.chunks_seen % self.every:
            return None
        return self.audit(integ, state, dt, step=step)

    def audit(self, integ, state, dt, step: int):
        """One shadow audit; raises :class:`PrecisionDrift` on breach."""
        import jax.numpy as jnp
        import numpy as np

        from ibamr_tpu.ops import stencils
        from ibamr_tpu.solvers.spectral_plan import get_plan

        fluid, fstate = self._fluid_parts(integ, state)
        sdtype = getattr(fluid, "spectral_dtype", None)
        grid = fluid.grid
        rho = float(getattr(fluid, "rho", 1.0))
        mu = float(getattr(fluid, "mu", 0.0))
        u = fstate.u
        # representative single Stokes substep: backward-Euler viscous
        # solve + Leray projection of rho/dt * u — the exact algebra the
        # fused fast path runs each half-step, fed the live velocity
        alpha = rho / float(dt)
        beta = -0.5 * mu
        rhs = tuple((c * alpha) for c in u)
        plan = get_plan(rhs[0].shape, grid.dx, rhs[0].dtype)
        fast_u, _ = plan.substep(rhs, alpha, beta, (alpha, beta),
                                 spectral_dtype=sdtype)
        plan64 = get_plan(rhs[0].shape, grid.dx, jnp.float64)
        ref_u, _ = plan64.substep(
            tuple(c.astype(plan64.rdtype) for c in rhs),
            alpha, beta, (alpha, beta), spectral_dtype=None)
        scale = max(float(jnp.max(jnp.abs(c))) for c in ref_u)
        scale = max(scale, 1e-30)
        drift = max(
            float(jnp.max(jnp.abs(f.astype(plan64.rdtype)
                                  - r.astype(plan64.rdtype))))
            for f, r in zip(fast_u, ref_u)) / scale
        div_fast = float(jnp.max(jnp.abs(
            stencils.divergence(fast_u, grid.dx))))
        div_ref = float(jnp.max(jnp.abs(
            stencils.divergence(ref_u, grid.dx))))
        div_drift = abs(div_fast - div_ref) / max(scale, 1e-30)
        self.audits += 1
        level = precision_level_name(sdtype)
        rec = {"step": int(step), "spectral_dtype": level,
               "drift": drift, "bound": self.bound,
               "div_drift": div_drift, "div_bound": self.div_bound}
        self.last = rec
        self.history.append(rec)
        breached = (np.isfinite(drift) and drift > self.bound) or \
            (self.div_bound is not None and div_drift > self.div_bound)
        if breached:
            raise PrecisionDrift(step, drift=drift, bound=self.bound,
                                 spectral_dtype=level,
                                 div_drift=div_drift)
        return rec


def escalate_solve(attempt_fn: Callable, *, context: str = "solve",
                   chain=None, on_incident: Optional[Callable] = None,
                   step: Optional[int] = None):
    """Walk the chain until an attempt converges.

    ``attempt_fn(level: EscalationLevel, attempt: int) -> SolveResult``
    runs one EAGER solve at that level's geometry. The first converged
    attempt wins; level 0 converging returns its result with no extra
    work (bitwise the plain solve). Escalations past level 0 are
    reported to ``on_incident`` as one structured record::

        {"event": "solver_escalation"|"solver_breakdown",
         "kind": "solver_breakdown", "context": ...,
         "recovered": bool, "level": <winning level or None>,
         "attempts": [{level, iters, resnorm, converged}, ...]}

    and an exhausted chain raises :class:`SolverBreakdown` carrying the
    same attempts list.
    """
    chain = escalation_chain() if chain is None else list(chain)
    if not chain:
        raise ValueError("escalation chain must have at least one level")
    attempts = []
    for i, level in enumerate(chain):
        sol = attempt_fn(level, i)
        rec = solve_stats_dict(sol, level=level.name)
        attempts.append(rec)
        if rec["converged"]:
            if i > 0 and on_incident is not None:
                on_incident({"event": "solver_escalation",
                             "kind": "solver_breakdown",
                             "context": context, "recovered": True,
                             "level": level.name, "attempts": attempts})
            return sol
    if on_incident is not None:
        on_incident({"event": "solver_breakdown",
                     "kind": "solver_breakdown", "context": context,
                     "recovered": False, "level": None,
                     "attempts": attempts})
    raise SolverBreakdown(context, attempts, step=step)
