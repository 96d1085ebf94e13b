"""Fault-injection harness for the resilience layer (PR 2 tentpole 4).

The recovery machinery (atomic verified checkpoints, the
ResilientDriver rollback loop, engine degradation) is only trustworthy
if the failure paths are EXERCISED — a recovery path that has never run
is a second bug waiting behind the first. This module supplies the
deterministic fault injectors the resilience tests and the multichip
dryrun drill are built from:

- :func:`nan_injector_step` / :func:`inject_nan` — poison a named state
  leaf with NaN at a chosen step, inside or outside jit. The jittable
  wrapper is dt-gated so a supervised retry at backed-off dt passes
  cleanly (the injected fault models a too-aggressive timestep, the
  exact failure dt-backoff exists to cure).
- :func:`truncate_checkpoint` / :func:`corrupt_checkpoint` /
  :func:`drop_sidecar` — the three on-disk damage modes a crash or a
  bad disk can leave: a short file, flipped bytes at unchanged size,
  and an array file whose commit marker never landed.
- :func:`failing_checkpoint_writes` — make the Nth checkpoint write(s)
  raise, underneath the async writer's retry.
- :func:`run_crash_child` — the deterministic checkpoint-writer loop
  the SIGKILL-mid-write subprocess drill runs as its victim: the whole
  trajectory is a closed-form function of the step count
  (:func:`crash_state`), so the parent can verify any restored
  checkpoint bitwise without trusting the child.
- :func:`run_smoke` — a self-contained end-to-end drill (supervised
  NaN recovery + corruption fallback + flaky-write retry) wired into
  ``__graft_entry__.dryrun_multichip`` as path 16 and exposed as
  ``python -m tools.fault_injection --smoke``.
- :func:`bf16_drift_injector` / :func:`volume_leak_injector` (PR 5) —
  the silent-precision and invariant-violation faults the flight
  recorder + replay harness and the physics sentinels are drilled
  against, plus the :data:`ACTIVE_INJECTORS` registry that makes an
  injected fault part of the run fingerprint (so ``tools/replay.py``
  reproduces it BITWISE in a fresh process).
- :func:`run_replay_smoke` — record -> trip the shadow audit ->
  precision-escalate -> replay bitwise -> classify, as dryrun path 18
  and ``python -m tools.fault_injection --replay-smoke``.
- :func:`record_capsule_drill` — the victim process for the
  kill-and-replay drill: records a capsule, prints ``CAPSULE <dir>``
  and lingers for the parent's SIGKILL.
- :func:`corrupt_shard` / :func:`drop_shard` / :func:`tear_manifest` /
  :func:`stale_manifest_shard` (PR 6) — the on-disk failure modes a
  DISTRIBUTED writer adds: one shard of many damaged or lost, a torn
  commit marker, a shard rewritten after its manifest committed.
- :func:`run_sharded_crash_child` — the sharded SIGKILL-mid-commit
  victim loop (per-shard writes + manifest commit, closed-form
  trajectory, ``SAVED`` markers), and :func:`run_sharded_smoke` — the
  end-to-end sharded-checkpoint drill (no-gather save audit, elastic
  restore, damage inventory, concurrent-writer collision, supervised
  sharded rollback, ``tools.ckpt_fsck`` gate) wired as dryrun path 19
  and ``python -m tools.fault_injection --sharded-smoke``.
- :func:`lane_nan_injector` / :func:`lane_drift_injector` (PR 7) —
  faults confined to ONE lane of a vmapped fleet chunk, and
  :func:`run_fleet_smoke` — the end-to-end lane-quarantine drill (one
  poisoned lane, per-lane rollback + dt backoff, quarantine, healthy
  lanes bitwise untouched, sliced-capsule replay) wired as dryrun
  path 20 and ``python -m tools.fault_injection --fleet-smoke``.
- :func:`compile_storm_injector` / :func:`slow_lane_injector` /
  :func:`failing_build_injector` / :func:`kill_router_thread_injector`
  (PR 17) — SERVING-path faults against the warm-pool router: slow
  bucket compiles, straggler lanes, builds that raise, and build
  threads that die without publishing. These are latency/liveness
  faults, never state-value faults, so they are NOT ``recorded()`` —
  there is nothing for the flight recorder to replay bitwise.
  :func:`run_soak_smoke` composes them over the PR-17 open-loop load
  generator into the traffic-robustness drill (dryrun path 21,
  ``python -m tools.fault_injection --soak-smoke``): a chaos tenant
  burns through novel families and injected faults at a 4x burst
  while healthy tenants keep their warm p99, with the no-deadlock /
  no-lost-request / bounded-shed invariants pinned from the merged
  ledger.
- :func:`mix_shift_injector` / :func:`memory_pressure_injector`
  (PR 18) — ELASTICITY faults: the arrival mix rotates to an unseen
  bucket family mid-soak (pure schedule transform, bit-replayable),
  and the executable cache's bytes ceiling is squeezed mid-run.
  :func:`run_elastic_smoke` composes them into the elastic warm-pool
  drill (dryrun path 22, ``python -m tools.fault_injection
  --elastic-smoke``): the ElasticPoolManager must grow the shifted
  family before any of its requests shed, ride the brownout ladder
  without oscillating, shrink the cold family, and survive a
  checkpoint/restore restart with ZERO fresh XLA compiles.
- :func:`run_design_smoke` (PR 19) — the INVERSE-DESIGN drill (dryrun
  path 23, ``python -m tools.fault_injection --design-smoke``): the
  eel2d gait objective differentiated THROUGH the coupled rollout —
  the compiled adjoint must agree with an f64 central difference,
  three Adam iterations through ``DesignLoop`` must strictly decrease
  the objective, iteration 1 pays exactly one executable-cache MISS
  and iterations 2+ are pure HITS (zero warm compiles), and every
  iteration lands one ``design_iter`` ledger record.
- :func:`obs_dropout_injector` / :func:`obs_outlier_injector` /
  :func:`stale_obs_injector` / :func:`member_divergence_injector`
  (PR 20) — ASSIMILATION faults: dead, spiking and stale sensor
  channels as pure transforms of the assimilation cycle's
  ``obs_source`` seam, plus one ensemble member diverging mid-run
  (lane_nan mechanics, ``recorded()`` for capsule replay).
  :func:`run_assim_smoke` arms all four at once over the B-lane
  forecasting service (dryrun path 24, ``python -m
  tools.fault_injection --assim-smoke``): the QC gate rejects exactly
  the injected (channel, cycle, reason) triples, the divergent member
  is quarantined and excluded from the masked analysis statistics,
  every cycle lands a terminal ``assim_cycle`` record (zero lost),
  the final forecast error beats the open-loop ensemble, and the
  whole episode retraces nothing.

Everything here is deliberately boring and deterministic: no random
fuzzing, every fault lands at a named step/byte so a failure
reproduces.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import tempfile
import threading
import time

import numpy as np


# ---------------------------------------------------------------------------
# NaN injection
# ---------------------------------------------------------------------------

def _match_paths(state, leaf_path: str):
    """Pytree paths whose keystr contains ``leaf_path`` (e.g. ``"u[0]"``
    matches the first MAC velocity component of an INSState)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    return [jax.tree_util.keystr(p) for p, _ in flat
            if leaf_path in jax.tree_util.keystr(p)]


def inject_nan(state, leaf_path: str):
    """Host-side: return ``state`` with NaN written into every floating
    leaf whose path contains ``leaf_path``. Raises if nothing matches
    (a typo'd path must not silently inject nothing)."""
    import jax
    import jax.numpy as jnp

    hit = []

    def _poison(path, leaf):
        key = jax.tree_util.keystr(path)
        if leaf_path in key and hasattr(leaf, "dtype") \
                and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            hit.append(key)
            bad = jnp.asarray(leaf).at[...].set(jnp.nan)
            return bad
        return leaf

    out = jax.tree_util.tree_map_with_path(_poison, state)
    if not hit:
        raise KeyError(
            f"no floating leaf path contains {leaf_path!r}; "
            f"available: {_match_paths(state, '')}")
    return out


def nan_injector_step(step_fn, at_step: int, leaf_path: str = "u",
                      dt_gate: float | None = None,
                      step_attr: str = "k"):
    """Wrap ``step_fn(state, dt) -> state`` so the stepped state comes
    out poisoned (NaN in every floating leaf matching ``leaf_path``)
    exactly when its step counter ``state.<step_attr>`` equals
    ``at_step`` — jit/scan-safe (the fault is a ``jnp.where`` on traced
    values, not python control flow). ``step_attr`` may be dotted
    (``"ins.k"`` reaches the fluid counter inside a coupled IB state).

    ``dt_gate`` arms the fault only while ``dt >= dt_gate``: a
    supervised retry at backed-off dt then passes cleanly, modelling an
    instability that a smaller timestep cures. Without it the injector
    would re-fire on every retry and the supervisor could never win.
    """
    import jax
    import jax.numpy as jnp

    def wrapped(state, dt):
        out = step_fn(state, dt)
        k = out
        for attr in step_attr.split("."):
            k = getattr(k, attr)
        fire = jnp.asarray(k) == at_step
        if dt_gate is not None:
            fire = jnp.logical_and(fire, jnp.asarray(dt) >= dt_gate)
        hit = []

        def _poison(path, leaf):
            key = jax.tree_util.keystr(path)
            if leaf_path in key and hasattr(leaf, "dtype") \
                    and jnp.issubdtype(leaf.dtype, jnp.floating):
                hit.append(key)
                return jnp.where(fire, jnp.asarray(jnp.nan, leaf.dtype),
                                 leaf)
            return leaf

        out = jax.tree_util.tree_map_with_path(_poison, out)
        if not hit:
            raise KeyError(f"no floating leaf path contains {leaf_path!r}")
        return out

    return wrapped


# ---------------------------------------------------------------------------
# Silent-failure injectors (PR 3): finite-but-diverging growth, a
# stagnating linear operator, and a slow host step — the three failure
# shapes the vitals / escalation / watchdog layers each exist to catch
# ---------------------------------------------------------------------------

def growth_injector_step(step_fn, rate: float = 1.5,
                         leaf_path: str = "u",
                         dt_gate: float | None = None):
    """Wrap ``step_fn(state, dt) -> state`` so every floating leaf
    matching ``leaf_path`` is multiplied by ``rate`` per step — a
    FINITE exponential blow-up, the silent failure the plain finite
    flag cannot see until checkpoints already hold garbage. jit/scan
    safe (the factor is a traced ``jnp.where``).

    ``dt_gate`` arms the growth only while ``dt >= dt_gate``, so the
    supervisor's dt backoff cures it — modelling an instability whose
    growth rate a smaller timestep tames.
    """
    import jax
    import jax.numpy as jnp

    def wrapped(state, dt):
        out = step_fn(state, dt)
        fire = jnp.asarray(True) if dt_gate is None \
            else jnp.asarray(dt) >= dt_gate
        hit = []

        def _grow(path, leaf):
            key = jax.tree_util.keystr(path)
            if leaf_path in key and hasattr(leaf, "dtype") \
                    and jnp.issubdtype(leaf.dtype, jnp.floating):
                hit.append(key)
                factor = jnp.where(fire, jnp.asarray(rate, leaf.dtype),
                                   jnp.asarray(1.0, leaf.dtype))
                return leaf * factor
            return leaf

        out = jax.tree_util.tree_map_with_path(_grow, out)
        if not hit:
            raise KeyError(f"no floating leaf path contains {leaf_path!r}")
        return out

    return wrapped


def stagnating_operator(A, direction=None):
    """Wrap a pytree linear operator so it is SINGULAR along
    ``direction`` (default: the all-ones pytree): the wrapper projects
    the input off that direction before applying ``A``, so any rhs with
    a component outside the crippled range leaves a residual floor no
    Krylov iteration can pass — a deterministic stagnating solve (the
    escalation chain walks, every level fails, ``SolverBreakdown``).
    """
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.solvers.krylov import tree_axpy, tree_dot

    def wrapped(x):
        e = direction if direction is not None \
            else jax.tree_util.tree_map(jnp.ones_like, x)
        coef = tree_dot(e, x) / tree_dot(e, e)
        return A(tree_axpy(-coef, e, x))

    return wrapped


def slow_metrics(sleep_s: float, at_steps=None, metrics_fn=None):
    """A ``metrics_fn`` wrapper that sleeps ``sleep_s`` on the host —
    the watchdog drill's stalled chunk (from the outside a hung compile
    / dead device and a sleeping callback look identical: no beat).
    ``at_steps`` limits the stall to the named post-chunk steps
    (``None`` = every chunk)."""
    at = None if at_steps is None else {int(s) for s in at_steps}

    def wrapped(state, step):
        if at is None or int(step) in at:
            time.sleep(sleep_s)
        return metrics_fn(state, step) if metrics_fn is not None else None

    return wrapped


# ---------------------------------------------------------------------------
# Recorded injectors (PR 5): faults the flight recorder fingerprints so
# tools/replay.py can RE-ARM them in a fresh process — without this, a
# capsule of an injected failure would replay clean and read as
# not_reproduced. ACTIVE_INJECTORS maps injector name -> JSON-safe
# params for every currently-armed recorded fault.
# ---------------------------------------------------------------------------

ACTIVE_INJECTORS: dict = {}


@contextlib.contextmanager
def recorded(name: str, **params):
    """Register an armed fault in ``ACTIVE_INJECTORS`` for the duration
    of the block, so flight-recorder fingerprints (and therefore replay
    capsules) carry it. The caller still applies the actual injector;
    this context only makes it REPRODUCIBLE. Params must be JSON-safe
    and sufficient for :func:`apply_recorded_injectors` to rebuild the
    injector (see the per-name cases there)."""
    if name in ACTIVE_INJECTORS:
        raise ValueError(f"recorded injector {name!r} already armed")
    ACTIVE_INJECTORS[name] = dict(params)
    try:
        yield params
    finally:
        ACTIVE_INJECTORS.pop(name, None)


@contextlib.contextmanager
def bf16_drift_injector(scale: float = 0.35):
    """Deterministically bias the bf16 spectral path's split-real
    operand rounding by ``(1 + scale)`` — k-space algebra corruption
    that ONLY fires on the mixed-precision path (``_round_complex`` is
    not called at f32/f64), so precision escalation or an
    ``--override spectral_dtype=f64`` replay genuinely cures it. The
    drift is smooth and finite: the plain finite flag never trips, only
    the f64 shadow audit can see it. Registers itself in
    ``ACTIVE_INJECTORS`` as ``bf16_drift``.

    NOTE: the patch takes effect at TRACE time — jit executables
    compiled before entering the context keep the clean rounding. Clear
    relevant caches (or use fresh chunk shapes) when arming mid-process.
    """
    with _bare_bf16_drift(scale):
        with recorded("bf16_drift", scale=float(scale)):
            yield


def volume_leak_injector(step_fn, rate: float = 0.01,
                         leaf_path: str = "X",
                         dt_gate: float | None = None):
    """Wrap ``step_fn(state, dt) -> state`` so every floating leaf
    matching ``leaf_path`` (default: the IB marker positions) is
    contracted toward its centroid by ``rate`` per step — a secular
    enclosed-volume drift (membrane leakage). The state stays finite
    and smooth; only the volume sentinel (vitals slot 5) can see it.
    jit/scan-safe; ``dt_gate`` arms the leak only while
    ``dt >= dt_gate`` (the supervisor's backoff disarms it)."""
    import jax
    import jax.numpy as jnp

    def wrapped(state, dt):
        out = step_fn(state, dt)
        fire = jnp.asarray(True) if dt_gate is None \
            else jnp.asarray(dt) >= dt_gate
        hit = []

        def _leak(path, leaf):
            key = jax.tree_util.keystr(path)
            if leaf_path in key and hasattr(leaf, "dtype") \
                    and jnp.issubdtype(leaf.dtype, jnp.floating) \
                    and getattr(leaf, "ndim", 0) >= 1:
                hit.append(key)
                c = jnp.mean(leaf, axis=0, keepdims=True)
                factor = jnp.where(fire,
                                   jnp.asarray(1.0 - rate, leaf.dtype),
                                   jnp.asarray(1.0, leaf.dtype))
                return c + (leaf - c) * factor
            return leaf

        out = jax.tree_util.tree_map_with_path(_leak, out)
        if not hit:
            raise KeyError(f"no floating leaf path contains {leaf_path!r}")
        return out

    return wrapped


# ---------------------------------------------------------------------------
# Lane-targeted injectors (PR 7): faults that poison exactly ONE lane of
# a vmapped fleet chunk — the failure shape the lane-quarantine and
# per-lane-rollback machinery exists to contain. They wrap the STACKED
# (already-vmapped) step, so the fire condition can address lanes.
# ---------------------------------------------------------------------------

def lane_nan_injector(stacked_step, at_step: int, lane: int,
                      fleet_size: int, leaf_path: str = "u",
                      dt_gate: float | None = None,
                      step_attr: str = "k"):
    """Wrap a STACKED ``step_fn(state, dt_vec) -> state`` (every leaf
    lane-stacked, dt a (B,) vector) so exactly lane ``lane``'s rows of
    every floating leaf matching ``leaf_path`` come out NaN when that
    lane's step counter equals ``at_step`` — jit/scan/vmap-safe (the
    fault is a ``jnp.where`` on traced values). Other lanes' rows pass
    through BITWISE untouched (``jnp.where`` is elementwise), which is
    what the healthy-lanes-unperturbed drill assertion pins.

    ``dt_gate`` arms the fault only while the LANE'S dt is
    ``>= dt_gate``: a per-lane dt backoff then cures it. Without the
    gate the injector re-fires on every per-lane retry, driving the
    lane to retry exhaustion and quarantine — the drill's second act.
    """
    import jax
    import jax.numpy as jnp

    lane_ids = jnp.arange(int(fleet_size))

    def wrapped(state, dt):
        out = stacked_step(state, dt)
        k = out
        for attr in step_attr.split("."):
            k = getattr(k, attr)
        fire = jnp.logical_and(lane_ids == lane,
                               jnp.asarray(k) == at_step)
        if dt_gate is not None:
            fire = jnp.logical_and(fire, jnp.asarray(dt) >= dt_gate)
        hit = []

        def _poison(path, leaf):
            key = jax.tree_util.keystr(path)
            if leaf_path in key and hasattr(leaf, "dtype") \
                    and jnp.issubdtype(leaf.dtype, jnp.floating):
                hit.append(key)
                m = fire.reshape((int(fleet_size),)
                                 + (1,) * (leaf.ndim - 1))
                return jnp.where(m, jnp.asarray(jnp.nan, leaf.dtype),
                                 leaf)
            return leaf

        out = jax.tree_util.tree_map_with_path(_poison, out)
        if not hit:
            raise KeyError(f"no floating leaf path contains {leaf_path!r}")
        return out

    return wrapped


def lane_drift_injector(stacked_step, rate: float = 1.5, lane: int = 0,
                        fleet_size: int = 1, leaf_path: str = "u",
                        dt_gate: float | None = None):
    """Wrap a STACKED step so lane ``lane``'s rows of every floating
    leaf matching ``leaf_path`` are multiplied by ``rate`` per step — a
    FINITE exponential blow-up confined to one lane, the silent failure
    only the per-lane vitals triage (``HealthProbe.check_lanes``) can
    attribute to the right lane. ``dt_gate`` arms the drift only while
    the lane's dt is ``>= dt_gate`` (per-lane backoff cures it)."""
    import jax
    import jax.numpy as jnp

    lane_ids = jnp.arange(int(fleet_size))

    def wrapped(state, dt):
        out = stacked_step(state, dt)
        fire = lane_ids == lane
        if dt_gate is not None:
            fire = jnp.logical_and(fire, jnp.asarray(dt) >= dt_gate)
        hit = []

        def _grow(path, leaf):
            key = jax.tree_util.keystr(path)
            if leaf_path in key and hasattr(leaf, "dtype") \
                    and jnp.issubdtype(leaf.dtype, jnp.floating):
                hit.append(key)
                m = fire.reshape((int(fleet_size),)
                                 + (1,) * (leaf.ndim - 1))
                return leaf * jnp.where(m, jnp.asarray(rate, leaf.dtype),
                                        jnp.asarray(1.0, leaf.dtype))
            return leaf

        out = jax.tree_util.tree_map_with_path(_grow, out)
        if not hit:
            raise KeyError(f"no floating leaf path contains {leaf_path!r}")
        return out

    return wrapped


@contextlib.contextmanager
def apply_recorded_injectors(injectors: dict):
    """Re-arm the faults a replay manifest recorded. Context-style
    faults (``bf16_drift``) are entered for the block; step-level
    faults yield through the returned ``wrap(step_fn)`` function, which
    the replay harness applies to the rebuilt integrator's step. Param
    vocabularies match what :func:`recorded` blocks in this module and
    the tests register:

    - ``bf16_drift``: {scale}
    - ``nan``: {at_step, leaf_path, dt_gate} -> nan_injector_step
    - ``growth``: {rate, leaf_path, dt_gate} -> growth_injector_step
    - ``volume_leak``: {rate, leaf_path, dt_gate} -> volume_leak_injector
    - ``lane_nan`` / ``lane_drift``: lane-targeted faults; the wrap
      applies to the STACKED step (replay of a lane capsule builds a
      B=1 fleet chunk and transforms ``lane``/``fleet_size`` before
      calling this — see ``tools.replay._lane_injectors``)
    - ``member_divergence``: the assimilation drill's lane fault
      (lane_nan mechanics under its own name, same lane transform)

    Unknown names raise: silently dropping a recorded fault would turn
    every replay of it into a false ``not_reproduced``/"cured" verdict.
    """
    wrappers = []
    with contextlib.ExitStack() as stack:
        for name, params in (injectors or {}).items():
            params = dict(params)
            if name == "bf16_drift":
                stack.enter_context(
                    _bare_bf16_drift(scale=params.get("scale", 0.35)))
            elif name == "nan":
                wrappers.append(lambda fn, p=params:
                                nan_injector_step(fn, **p))
            elif name == "growth":
                wrappers.append(lambda fn, p=params:
                                growth_injector_step(fn, **p))
            elif name == "volume_leak":
                wrappers.append(lambda fn, p=params:
                                volume_leak_injector(fn, **p))
            elif name == "lane_nan":
                wrappers.append(lambda fn, p=params:
                                lane_nan_injector(fn, **p))
            elif name == "lane_drift":
                wrappers.append(lambda fn, p=params:
                                lane_drift_injector(fn, **p))
            elif name == "member_divergence":
                wrappers.append(lambda fn, p=params:
                                member_divergence_injector(fn, **p))
            else:
                raise KeyError(
                    f"replay manifest records unknown injector {name!r}")

        def wrap(step_fn):
            for w in wrappers:
                step_fn = w(step_fn)
            return step_fn

        yield wrap


@contextlib.contextmanager
def _bare_bf16_drift(scale: float):
    """bf16_drift patch WITHOUT the ACTIVE_INJECTORS registration
    (replay must not re-record the fault it is re-arming)."""
    from ibamr_tpu.solvers import spectral_plan as sp

    orig = sp._round_complex

    def biased(z, sdtype):
        return orig(z, sdtype) * (1.0 + scale)

    sp._round_complex = biased
    try:
        yield
    finally:
        sp._round_complex = orig


# ---------------------------------------------------------------------------
# On-disk checkpoint damage
# ---------------------------------------------------------------------------

def _ckpt_path(directory: str, step: int, ext: str = "npz") -> str:
    return os.path.join(directory, f"restore.{step:08d}.{ext}")


def truncate_checkpoint(directory: str, step: int,
                        keep_bytes: int | None = None) -> str:
    """Chop the array file short (default: half) — what a torn write
    WOULD look like if the writer were not atomic. The sidecar's size
    record must now flunk verification."""
    path = _ckpt_path(directory, step)
    size = os.path.getsize(path)
    keep = size // 2 if keep_bytes is None else keep_bytes
    with open(path, "r+b") as f:
        f.truncate(keep)
    return path

def corrupt_checkpoint(directory: str, step: int,
                       offset: int | None = None) -> str:
    """Flip one byte WITHOUT changing the size — the bad-disk/bitrot
    mode that only the CRC32 can catch."""
    path = _ckpt_path(directory, step)
    size = os.path.getsize(path)
    pos = size // 2 if offset is None else offset
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0xFF]))
    return path


def drop_sidecar(directory: str, step: int) -> str:
    """Remove the JSON commit marker: the array file may be perfect but
    without a sidecar the checkpoint never committed."""
    path = _ckpt_path(directory, step, "json")
    os.remove(path)
    return path


@contextlib.contextmanager
def failing_checkpoint_writes(fail_calls, exc_type=OSError):
    """Patch ``checkpoint._write_arrays`` so the 0-based call indices
    in ``fail_calls`` raise ``exc_type``. The async writer's retry
    looks the symbol up per attempt, so ``{0}`` fails only the first
    attempt and the retry lands. Yields the call counter dict."""
    from ibamr_tpu.utils import checkpoint as _ckpt

    fail = set(fail_calls)
    orig = _ckpt._write_arrays
    counter = {"calls": 0}

    def flaky(*args, **kwargs):
        i = counter["calls"]
        counter["calls"] += 1
        if i in fail:
            raise exc_type(f"injected checkpoint write failure (call {i})")
        return orig(*args, **kwargs)

    _ckpt._write_arrays = flaky
    try:
        yield counter
    finally:
        _ckpt._write_arrays = orig


# ---------------------------------------------------------------------------
# Crash-child loop (SIGKILL-mid-write victim)
# ---------------------------------------------------------------------------

def crash_state(step: int, n: int = 64) -> dict:
    """Closed-form deterministic trajectory: the state after ``step``
    iterations of a fixed contraction map. float64 numpy, so every
    process that evaluates it gets bitwise-identical leaves — the
    parent verifies a child's checkpoint by recomputing, not by
    trusting the (possibly killed) child."""
    u = np.linspace(0.0, 1.0, n)
    for k in range(1, step + 1):
        u = np.cos(u) * 0.9 + 0.01 * k
    return {"u": u, "k": np.int64(step)}


def run_crash_child(directory: str, num_steps: int, interval: int,
                    keep: int = 3) -> int:
    """The victim loop: resume from the newest VERIFIED checkpoint,
    iterate the contraction map, checkpoint every ``interval`` steps
    printing ``SAVED <k>`` markers (the parent kills on a marker).
    Returns the step reached."""
    from ibamr_tpu.utils.checkpoint import (latest_step,
                                            restore_checkpoint,
                                            save_checkpoint)

    start = latest_step(directory)
    if start is None:
        start, u = 0, crash_state(0)["u"]
    else:
        state, start, _ = restore_checkpoint(
            directory, template=crash_state(start), step=start)
        u = np.asarray(state["u"])
    print(f"START {start}", flush=True)
    for k in range(start + 1, num_steps + 1):
        u = np.cos(u) * 0.9 + 0.01 * k
        if k % interval == 0:
            save_checkpoint(directory, {"u": u, "k": np.int64(k)}, k,
                            keep=keep)
            print(f"SAVED {k}", flush=True)
    print("DONE", flush=True)
    return num_steps


# ---------------------------------------------------------------------------
# End-to-end smoke drill
# ---------------------------------------------------------------------------

def run_smoke(directory: str | None = None) -> dict:
    """Deterministic end-to-end resilience drill on a 16^2 INS run:

    1. supervised recovery — NaN injected at step 6 diverges the run;
       the ResilientDriver rolls back to the step-4 checkpoint, halves
       dt (which disarms the dt-gated injector) and completes;
    2. corruption fallback — flip a byte in the newest checkpoint and
       prove ``latest_step``/``restore_checkpoint`` fall back to the
       newest VERIFIED one;
    3. flaky-write retry — fail the next write's first attempt and
       prove the async writer's retry still lands a verified file.

    Returns (and the CLI prints) a one-line JSON summary. Raises on
    any failed expectation — wired into the multichip dryrun rotation,
    so a regression in the recovery path fails CI, not a real run.
    """
    import jax.numpy as jnp

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
    from ibamr_tpu.utils.checkpoint import (AsyncCheckpointWriter,
                                            latest_step,
                                            restore_checkpoint,
                                            verify_checkpoint)
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from ibamr_tpu.utils.supervisor import ResilientDriver

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_fault_smoke_")
        directory = tmp.name
    try:
        g = StaggeredGrid(n=(16, 16), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
        integ = INSStaggeredIntegrator(g, rho=1.0, mu=0.05)
        xf, yc = g.face_centers(0, jnp.float32)
        xc, yf = g.face_centers(1, jnp.float32)
        u = jnp.sin(2 * jnp.pi * xf) * jnp.cos(2 * jnp.pi * yc) + 0 * yc
        v = -jnp.cos(2 * jnp.pi * xc) * jnp.sin(2 * jnp.pi * yf) + 0 * xc
        st0 = integ.initialize(u0_arrays=(u, v))

        dt0 = 1e-3
        cfg = RunConfig(dt=dt0, num_steps=12, restart_interval=4,
                        health_interval=2)
        drv = HierarchyDriver(
            integ, cfg,
            step_fn=nan_injector_step(integ.step, at_step=6,
                                      leaf_path="u[0]",
                                      dt_gate=dt0 * 0.99))
        sup = ResilientDriver(drv, directory, max_retries=2,
                              dt_backoff=0.5, handle_signals=False)
        out = sup.run(st0)
        if int(out.k) != cfg.num_steps:
            raise AssertionError(f"supervised run stopped at {int(out.k)}")
        if not bool(jnp.all(jnp.isfinite(out.u[0]))):
            raise AssertionError("supervised run finished non-finite")
        div = [r for r in sup.incidents if r["event"] == "divergence"]
        if len(div) != 1 or div[0]["rollback_step"] != 4:
            raise AssertionError(f"unexpected incidents: {sup.incidents}")

        # 2. corruption fallback
        newest = latest_step(directory)
        corrupt_checkpoint(directory, newest)
        if verify_checkpoint(directory, newest):
            raise AssertionError("byte flip went undetected")
        fell_back = latest_step(directory)
        if fell_back is None or fell_back >= newest:
            raise AssertionError("latest_step did not fall back")
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _, got, _ = restore_checkpoint(directory, template=out)
        if got != fell_back:
            raise AssertionError("restore did not fall back")

        # 3. flaky-write retry under the async writer
        w = AsyncCheckpointWriter(directory, keep=3)
        try:
            with failing_checkpoint_writes({0}) as ctr:
                w.save(out, 99)
                w.wait()
            if ctr["calls"] != 2:
                raise AssertionError(f"expected a retry, saw {ctr}")
        finally:
            w.close()
        if not verify_checkpoint(directory, 99):
            raise AssertionError("retried write is not verified")

        return {"fault_smoke": "ok", "divergence_incidents": len(div),
                "rollback_step": div[0]["rollback_step"],
                "corrupt_step_skipped": newest,
                "fallback_step": fell_back,
                "flaky_write_calls": ctr["calls"]}
    finally:
        if tmp is not None:
            tmp.cleanup()


def run_silent_smoke(directory: str | None = None) -> dict:
    """Deterministic end-to-end SILENT-failure drill (PR 3, dryrun
    path 17) exercising all three early-warning layers:

    1. **health precursor** — a finite exponential velocity growth
       (``growth_injector_step``, dt-gated) on a 16^2 INS run trips the
       fused :class:`HealthProbe`'s functional-growth WARN streak; the
       ResilientDriver rolls back and backs dt off BEFORE any
       non-finite value ever materializes (every classified chunk must
       report ``finite == 1``), and the run completes;
    2. **solver escalation** — a restarted-GMRES-hostile diagonal
       system fails at the base geometry and at restarts_x4, converges
       at deep_x4_inner_x2 (the full declared chain walks, one
       recovered ``solver_escalation`` incident); the same system
       behind :func:`stagnating_operator` exhausts the chain and raises
       ``SolverBreakdown`` with a structured incident;
    3. **watchdog** — a slow host callback (``slow_metrics``) stalls a
       supervised run long past the rolling chunk expectation; the
       ResilientDriver-owned watchdog records a ``stall`` incident into
       the same ``incidents.jsonl`` and the heartbeat file holds the
       last REAL beat.

    Raises on any failed expectation; returns a one-line JSON summary.
    """
    import jax.numpy as jnp

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
    from ibamr_tpu.solvers.escalation import SolverBreakdown, escalate_solve
    from ibamr_tpu.solvers.krylov import fgmres
    from ibamr_tpu.utils.health import HealthProbe
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from ibamr_tpu.utils.supervisor import ResilientDriver
    from ibamr_tpu.utils.watchdog import RunWatchdog, read_heartbeat

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_silent_smoke_")
        directory = tmp.name
    try:
        # -- 1. finite-blowup precursor: rollback before any NaN ------
        g = StaggeredGrid(n=(16, 16), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
        integ = INSStaggeredIntegrator(g, rho=1.0, mu=0.05)
        xf, yc = g.face_centers(0, jnp.float32)
        xc, yf = g.face_centers(1, jnp.float32)
        u = jnp.sin(2 * jnp.pi * xf) * jnp.cos(2 * jnp.pi * yc) + 0 * yc
        v = -jnp.cos(2 * jnp.pi * xc) * jnp.sin(2 * jnp.pi * yf) + 0 * xc
        st0 = integ.initialize(u0_arrays=(u, v))

        dt0 = 1e-3
        probe = HealthProbe.for_integrator(integ, func_growth_warn=8.0,
                                           sustain=2)
        cfg = RunConfig(dt=dt0, num_steps=12, restart_interval=4,
                        health_interval=2)
        drv = HierarchyDriver(
            integ, cfg,
            step_fn=growth_injector_step(integ.step, rate=1.5,
                                         leaf_path="u",
                                         dt_gate=dt0 * 0.99),
            health_probe=probe)
        health_dir = os.path.join(directory, "health")
        sup = ResilientDriver(drv, health_dir, max_retries=2,
                              dt_backoff=0.5, handle_signals=False)
        out = sup.run(st0)
        if int(out.k) != cfg.num_steps:
            raise AssertionError(f"health drill stopped at {int(out.k)}")
        if not bool(jnp.all(jnp.isfinite(out.u[0]))):
            raise AssertionError("health drill finished non-finite")
        if any(rec["finite"] < 1.0 for rec in probe.history):
            raise AssertionError(
                "a non-finite value materialized — the precursor fired "
                "too late")
        hd = [r for r in sup.incidents
              if r["event"] == "divergence"
              and r.get("kind") == "health_degraded"]
        if len(hd) != 1 or hd[0]["rollback_step"] != 4:
            raise AssertionError(f"unexpected incidents: {sup.incidents}")
        if not hd[0].get("reasons"):
            raise AssertionError("health incident carries no reasons")

        # -- 2. solver escalation: recover, then exhaust --------------
        w = jnp.logspace(0, 2, 48)          # restarted-GMRES-hostile
        A = lambda x: w * x                 # noqa: E731
        b = jnp.ones(48)

        def attempt(level, _i):
            return fgmres(A, b, m=8 * level.m_scale, tol=1e-4,
                          restarts=1 * level.restarts_scale)

        esc_incidents = []
        sol = escalate_solve(attempt, context="silent_smoke_diag",
                             on_incident=esc_incidents.append)
        if not bool(sol.converged):
            raise AssertionError("escalated solve did not converge")
        if len(esc_incidents) != 1 \
                or esc_incidents[0]["event"] != "solver_escalation" \
                or not esc_incidents[0]["recovered"] \
                or len(esc_incidents[0]["attempts"]) != 3:
            raise AssertionError(f"unexpected escalation record: "
                                 f"{esc_incidents}")

        As = stagnating_operator(A)

        def attempt_stag(level, _i):
            return fgmres(As, b, m=8 * level.m_scale, tol=1e-4,
                          restarts=1 * level.restarts_scale)

        breakdown = None
        try:
            escalate_solve(attempt_stag, context="silent_smoke_stagnant",
                           on_incident=esc_incidents.append, step=42)
        except SolverBreakdown as e:
            breakdown = e
        if breakdown is None or breakdown.step != 42:
            raise AssertionError("stagnating solve did not break down")
        if esc_incidents[-1]["event"] != "solver_breakdown" \
                or esc_incidents[-1]["recovered"]:
            raise AssertionError(f"unexpected breakdown record: "
                                 f"{esc_incidents[-1]}")

        # -- 3. watchdog: the stalled chunk is an incident ------------
        cfg2 = RunConfig(dt=dt0, num_steps=8, health_interval=2)
        drv2 = HierarchyDriver(integ, cfg2)
        drv2.run(st0, start_step=6)         # warm the chunk compile
        drv2.metrics_fn = slow_metrics(1.2, at_steps={4})
        wd_dir = os.path.join(directory, "wd")
        wd = RunWatchdog(heartbeat_path=wd_dir, interval_s=0.05,
                         stall_factor=3.0, min_stall_s=0.4)
        sup2 = ResilientDriver(drv2, wd_dir, handle_signals=False,
                               watchdog=wd)
        sup2.run(st0)
        stalls = [r for r in sup2.incidents if r["event"] == "stall"]
        if not stalls or stalls[0].get("kind") != "stall":
            raise AssertionError(f"no stall incident: {sup2.incidents}")
        hb = read_heartbeat(os.path.join(wd_dir, "heartbeat.json"))
        if hb is None or hb["step"] is None:
            raise AssertionError(f"no usable heartbeat: {hb}")

        return {"silent_smoke": "ok",
                "health_rollback_step": hd[0]["rollback_step"],
                "health_reasons": hd[0]["reasons"],
                "escalation_recovered_level": esc_incidents[0]["level"],
                "breakdown_attempts": len(breakdown.attempts),
                "stall_incidents": len(stalls),
                "heartbeat_step": hb["step"]}
    finally:
        if tmp is not None:
            tmp.cleanup()


def _tg16_setup(spectral_dtype=None):
    """Shared 16^2 Taylor-Green INS setup for the drills."""
    import jax.numpy as jnp

    from ibamr_tpu.grid import StaggeredGrid
    from ibamr_tpu.integrators.ins import INSStaggeredIntegrator

    g = StaggeredGrid(n=(16, 16), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
    integ = INSStaggeredIntegrator(g, rho=1.0, mu=0.05,
                                   spectral_dtype=spectral_dtype)
    xf, yc = g.face_centers(0, jnp.float32)
    xc, yf = g.face_centers(1, jnp.float32)
    u = jnp.sin(2 * jnp.pi * xf) * jnp.cos(2 * jnp.pi * yc) + 0 * yc
    v = -jnp.cos(2 * jnp.pi * xc) * jnp.sin(2 * jnp.pi * yf) + 0 * xc
    return integ, integ.initialize(u0_arrays=(u, v))


def run_replay_smoke(directory: str | None = None) -> dict:
    """Deterministic end-to-end REPLAY drill (PR 5, dryrun path 18):

    1. **precision escalation** — a 16^2 INS run at
       ``spectral_dtype="bf16"`` with an injected spectral rounding
       bias (:func:`bf16_drift_injector`) trips the per-chunk f64
       :class:`~ibamr_tpu.solvers.escalation.ShadowAuditor` on the
       FIRST chunk; the supervisor dumps a replay capsule, escalates
       bf16 -> f32 with dt UNCHANGED, rolls back and completes — one
       schema-v3 ``precision_escalation`` incident with a ``replay``
       pointer;
    2. **bitwise replay** — ``tools.replay`` re-executes the capsule
       in-process (fresh traces): the baseline re-arms the recorded
       injector and must match the recorded post-chunk digest bitwise
       -> verdict ``reproduced``;
    3. **classification** — the same capsule under
       ``--override spectral_dtype=f64`` no longer drifts (the biased
       bf16 rounding is never invoked on the escalated path) -> verdict
       ``precision_dependent``.

    Raises on any failed expectation; returns a one-line JSON summary.
    """
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.solvers.escalation import ShadowAuditor
    from ibamr_tpu.utils.flight_recorder import FlightRecorder
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from ibamr_tpu.utils.supervisor import ResilientDriver
    from tools.replay import replay

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_replay_smoke_")
        directory = tmp.name
    try:
        integ, st0 = _tg16_setup(spectral_dtype="bf16")
        cfg = RunConfig(dt=1e-3, num_steps=8, restart_interval=4,
                        health_interval=2)
        drv = HierarchyDriver(integ, cfg,
                              recorder=FlightRecorder(capacity=4),
                              shadow_audit=ShadowAuditor(every=1,
                                                         bound=0.02))
        sup = ResilientDriver(drv, directory, max_retries=2,
                              handle_signals=False)
        with bf16_drift_injector(scale=0.35):
            # the biased rounding must reach the RETRACED chunk
            jax.clear_caches()
            out = sup.run(st0)
        if int(out.k) != cfg.num_steps:
            raise AssertionError(f"replay drill stopped at {int(out.k)}")
        if not bool(jnp.all(jnp.isfinite(out.u[0]))):
            raise AssertionError("replay drill finished non-finite")
        esc = [r for r in sup.incidents
               if r["event"] == "precision_escalation"]
        if len(esc) != 1:
            raise AssertionError(f"unexpected incidents: {sup.incidents}")
        rec = esc[0]
        if rec.get("schema") != 3 or not rec.get("replay"):
            raise AssertionError(f"incident is not replayable v3: {rec}")
        if (rec["spectral_dtype_before"], rec["spectral_dtype_after"]) \
                != ("bf16", "f32"):
            raise AssertionError(f"unexpected escalation: {rec}")
        if rec["dt"] != cfg.dt:
            raise AssertionError("precision escalation must not back "
                                 "dt off")

        base = replay(rec["replay"])
        if base["verdict"] != "reproduced" or not base["bitwise"]:
            raise AssertionError(f"baseline replay: {base}")
        cured = replay(rec["replay"],
                       overrides={"spectral_dtype": "f64"})
        if cured["verdict"] != "precision_dependent":
            raise AssertionError(f"override replay: {cured}")

        return {"replay_smoke": "ok",
                "escalation_step": rec["step"],
                "spectral_dtype_after": rec["spectral_dtype_after"],
                "drift": rec.get("drift"),
                "baseline_verdict": base["verdict"],
                "override_verdict": cured["verdict"],
                "capsule": rec["replay"]}
    finally:
        if tmp is not None:
            tmp.cleanup()


def record_capsule_drill(directory: str, linger: bool = True) -> str:
    """Victim process for the cross-mesh kill-and-replay drill: run a
    16^2 INS trajectory with a RECORDED NaN injection, let the
    supervisor dump the divergence capsule, print ``CAPSULE <dir>`` (the
    parent's kill marker) and linger until SIGKILL. The parent then
    replays the orphaned capsule on a DIFFERENT device mesh and pins it
    bitwise — capsules record unsharded host arrays, so mesh shape is
    not part of the reproduction contract."""
    from ibamr_tpu.utils.flight_recorder import FlightRecorder
    from ibamr_tpu.utils.hierarchy_driver import (HierarchyDriver,
                                                  RunConfig,
                                                  SimulationDiverged)
    from ibamr_tpu.utils.supervisor import ResilientDriver

    integ, st0 = _tg16_setup()
    cfg = RunConfig(dt=1e-3, num_steps=12, restart_interval=4,
                    health_interval=2)
    params = {"at_step": 6, "leaf_path": "u[0]"}
    with recorded("nan", **params):
        drv = HierarchyDriver(
            integ, cfg,
            step_fn=nan_injector_step(integ.step, **params),
            recorder=FlightRecorder(capacity=4))
        sup = ResilientDriver(drv, directory, max_retries=0,
                              handle_signals=False)
        try:
            sup.run(st0)
            raise AssertionError("injected NaN did not diverge the run")
        except SimulationDiverged:
            pass
    cap = sup.incidents[-1].get("replay")
    if not cap:
        raise AssertionError(f"no capsule dumped: {sup.incidents}")
    print(f"CAPSULE {cap}", flush=True)
    while linger:
        time.sleep(0.5)
    return cap


# ---------------------------------------------------------------------------
# Sharded-checkpoint damage (PR 6): the on-disk failure modes a
# DISTRIBUTED writer adds to the single-host inventory — one shard of
# many damaged, a torn commit marker, a shard rewritten after commit
# ---------------------------------------------------------------------------

def _shard_path(directory: str, step: int, shard: int) -> str:
    from ibamr_tpu.utils.checkpoint_sharded import _shard_name, _step_dir

    return os.path.join(_step_dir(directory, step), _shard_name(shard))


def corrupt_shard(directory: str, step: int, shard: int = 0,
                  offset: int | None = None) -> str:
    """Flip one byte of ONE shard file without changing its size — the
    single-device bitrot/bad-disk mode. Only the manifest's whole-file
    CRC for that shard can catch it; the other N-1 shards stay
    perfect, which is exactly why verification must be per-shard."""
    path = _shard_path(directory, step, shard)
    size = os.path.getsize(path)
    pos = size // 2 if offset is None else offset
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0xFF]))
    return path


def drop_shard(directory: str, step: int, shard: int = 0) -> str:
    """Delete ONE shard file of a committed step — the lost-host mode:
    the writer on that host died after the manifest committed, or its
    local disk was reclaimed. The manifest still names the shard, so
    verification flunks the step."""
    path = _shard_path(directory, step, shard)
    os.remove(path)
    return path


def tear_manifest(directory: str, step: int) -> str:
    """Replace a step's manifest with a truncated (invalid-JSON)
    prefix — what a NON-atomic manifest writer killed mid-write would
    leave. With the atomic protocol this state is only reachable by
    injection, which is the point: the reader must treat it exactly
    like the no-manifest uncommitted case."""
    from ibamr_tpu.utils.checkpoint_sharded import _step_dir

    path = os.path.join(_step_dir(directory, step), "manifest.json")
    with open(path) as f:
        payload = f.read()
    with open(path, "w") as f:
        f.write(payload[: max(1, len(payload) // 2)].rstrip("}"))
    return path


def stale_manifest_shard(directory: str, step: int,
                         shard: int = 0) -> str:
    """Rewrite ONE shard file AFTER the manifest committed (arrays
    scaled by 2 — a valid npz, wrong bytes): the
    stale-manifest-newer-shards mode a restarted writer racing an old
    step leaves behind. The shard parses fine; only the manifest's
    recorded digest exposes that manifest and shard no longer describe
    the same checkpoint."""
    path = _shard_path(directory, step, shard)
    with np.load(path) as z:
        arrays = {k: np.asarray(z[k]) * 2 for k in z.files}
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    return path


def run_sharded_crash_child(directory: str, num_steps: int,
                            interval: int, keep: int = 3,
                            n_devices: int = 8) -> int:
    """The sharded SIGKILL-mid-commit victim: the same closed-form
    :func:`crash_state` trajectory as :func:`run_crash_child`, but the
    state is sharded over an ``n_devices`` 1-D mesh and every
    checkpoint goes through :func:`save_sharded_checkpoint` — so the
    parent's kill lands between shard writes and the manifest commit
    (widen the window with ``IBAMR_SHARDED_COMMIT_DELAY_S``). Resumes
    from the newest VERIFIED sharded step; prints the same
    ``START``/``SAVED <k>``/``DONE`` markers.

    Requires f64 (the parent verifies restored leaves bitwise against
    the f64 closed form) — the CLI entry enables x64 before any jax
    compute."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from ibamr_tpu.utils.checkpoint_sharded import (latest_sharded_step,
                                                    restore_sharded,
                                                    save_sharded_checkpoint)

    devs = sorted(jax.devices(), key=lambda d: d.id)[:n_devices]
    mesh = Mesh(np.array(devs), ("x",))
    sh = NamedSharding(mesh, P("x"))
    rep = NamedSharding(mesh, P())

    def place(d):
        return {"u": jax.device_put(jnp.asarray(d["u"]), sh),
                "k": jax.device_put(jnp.asarray(d["k"]), rep)}

    start = latest_sharded_step(directory)
    if start is None:
        start, u = 0, crash_state(0)["u"]
    else:
        state, start, _ = restore_sharded(
            directory, place(crash_state(start)), step=start)
        u = np.asarray(state["u"])
    print(f"START {start}", flush=True)
    for k in range(start + 1, num_steps + 1):
        u = np.cos(u) * 0.9 + 0.01 * k
        if k % interval == 0:
            save_sharded_checkpoint(
                directory, place({"u": u, "k": np.int64(k)}), k,
                keep=keep, mesh=mesh)
            print(f"SAVED {k}", flush=True)
    print("DONE", flush=True)
    return num_steps


def run_sharded_smoke(directory: str | None = None) -> dict:
    """Deterministic end-to-end SHARDED-checkpoint drill (PR 6, dryrun
    path 19), on however many devices this process has (>= 2 for the
    sharding to mean anything; the dryrun runs it on the virtual
    8-device mesh):

    1. **no-gather save + verified roundtrip** — a mesh-sharded state
       saves through :func:`save_sharded_checkpoint` with every
       device->host transfer audited to be shard-sized (never the
       global array), verifies, and restores bitwise onto the SAME
       mesh;
    2. **elastic restore** — the same step restores bitwise onto ONE
       device (N->1) from the manifest's recorded layout;
    3. **damage inventory** — single-shard byte flip, dropped shard,
       torn manifest, and a stale-manifest-newer-shard rewrite each
       flunk verification; ``latest_sharded_step``/``restore_sharded``
       fall back to the previous verified step, never silently
       restoring damage;
    4. **concurrent-writer collision** — two threads commit the SAME
       step simultaneously; the atomic per-file protocol guarantees
       the step afterwards either verifies AND restores bitwise to one
       writer's state, or is detected as unverified — never a silent
       mix of the two;
    5. **supervised sharded rollback** — a dt-gated NaN injector
       diverges a sharded INS run under
       ``ResilientDriver(sharded=True)``: rollback restores the newest
       VERIFIED sharded step through the elastic path and the run
       completes, with the divergence incident recording the mesh spec
       in its capsule fingerprint;
    6. **fsck gate** — ``tools.ckpt_fsck`` audits the drill directory:
       it must flag the damaged steps (nonzero exit) and pass clean
       after ``--repair`` quarantines them.

    Raises on any failed expectation; returns a one-line JSON summary.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from ibamr_tpu.utils import checkpoint_sharded as cs
    from tools import ckpt_fsck

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_sharded_smoke_")
        directory = tmp.name
    try:
        n_dev = min(8, jax.device_count())
        devs = sorted(jax.devices(), key=lambda d: d.id)[:n_dev]
        mesh = Mesh(np.array(devs), ("x",))
        sh = NamedSharding(mesh, P("x"))

        n = 64
        base = np.linspace(-1.0, 1.0, n * n, dtype=np.float32)
        host = {"u": base.reshape(n, n), "k": np.int64(7)}
        state = {"u": jax.device_put(jnp.asarray(host["u"]), sh),
                 "k": jax.device_put(jnp.asarray(host["k"]),
                                     NamedSharding(mesh, P()))}

        # -- 1. no-gather save: audit every device->host transfer -----
        ckdir = os.path.join(directory, "ck")
        global_bytes = host["u"].nbytes
        fetched: list = []
        orig_fetch = cs._fetch_shard

        def counting_fetch(data):
            arr = orig_fetch(data)
            fetched.append(arr.nbytes)
            return arr

        cs._fetch_shard = counting_fetch
        try:
            cs.save_sharded_checkpoint(ckdir, state, 10, mesh=mesh)
        finally:
            cs._fetch_shard = orig_fetch
        grid_fetches = [b for b in fetched if b >= global_bytes]
        if n_dev > 1 and grid_fetches:
            raise AssertionError(
                f"sharded save fetched a global-sized array "
                f"({grid_fetches} bytes vs {global_bytes} global) — "
                f"the gather is back on the save path")
        if not cs.verify_sharded_checkpoint(ckdir, 10):
            raise AssertionError("fresh sharded step failed verify")

        r, got, _ = cs.restore_sharded(ckdir, state)
        if got != 10 or not np.array_equal(np.asarray(r["u"]),
                                           host["u"]):
            raise AssertionError("same-mesh sharded restore not bitwise")

        # -- 2. elastic N->1 ------------------------------------------
        one = devs[0]
        tmpl1 = {"u": jax.device_put(jnp.asarray(host["u"]), one),
                 "k": jax.device_put(jnp.asarray(host["k"]), one)}
        r1, _, _ = cs.restore_sharded(ckdir, tmpl1)
        if not np.array_equal(np.asarray(r1["u"]), host["u"]):
            raise AssertionError("elastic N->1 restore not bitwise")

        # -- 3. damage inventory --------------------------------------
        damaged = {}
        for step, damage in ((20, corrupt_shard), (30, drop_shard),
                             (40, tear_manifest),
                             (50, stale_manifest_shard)):
            cs.save_sharded_checkpoint(ckdir, state, step, mesh=mesh,
                                       keep=0)
            if damage is tear_manifest:
                damage(ckdir, step)
            else:
                damage(ckdir, step, shard=n_dev - 1)
            if cs.verify_sharded_checkpoint(ckdir, step):
                raise AssertionError(
                    f"{damage.__name__} went undetected at step {step}")
            damaged[damage.__name__] = step
        if cs.latest_sharded_step(ckdir) != 10:
            raise AssertionError(
                f"latest_sharded_step did not fall back to 10: "
                f"{cs.latest_sharded_step(ckdir)}")
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _, fell_back, _ = cs.restore_sharded(ckdir, state)
        if fell_back != 10:
            raise AssertionError("restore_sharded did not fall back")

        # -- 4. concurrent-writer collision ---------------------------
        import threading
        coll = os.path.join(directory, "collision")
        other = {"u": jax.device_put(jnp.asarray(host["u"] + 1.0), sh),
                 "k": state["k"]}
        errs: list = []

        def write(st):
            try:
                cs.save_sharded_checkpoint(coll, st, 60, mesh=mesh)
            except Exception as e:      # pragma: no cover - diagnostic
                errs.append(e)

        ts = [threading.Thread(target=write, args=(s,))
              for s in (state, other)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise AssertionError(f"collision writers raised: {errs}")
        collided_verified = cs.verify_sharded_checkpoint(coll, 60)
        if collided_verified:
            rc, _, _ = cs.restore_sharded(coll, state)
            ru = np.asarray(rc["u"])
            if not (np.array_equal(ru, host["u"])
                    or np.array_equal(ru, host["u"] + 1.0)):
                raise AssertionError(
                    "collision produced a verified FRANKENSTEIN step — "
                    "a mix of two writers' shards passed verification")
        else:
            # the manifest writer lost a shard-file race, so the step
            # is a detectable mix — the OTHER acceptable outcome. fsck
            # must flag it; --repair then deliberately spares a sole
            # damaged candidate (never delete the last one), so drop
            # the drill dir once detection is confirmed or the
            # clean-gate below could never pass.
            if ckpt_fsck.audit(coll)["clean"]:
                raise AssertionError(
                    "collision step failed verification but fsck "
                    "called the tree clean")
            import shutil
            shutil.rmtree(coll)

        # -- 5. supervised sharded rollback ---------------------------
        from ibamr_tpu.grid import StaggeredGrid
        from ibamr_tpu.integrators.ins import INSStaggeredIntegrator
        from ibamr_tpu.parallel.mesh import (make_sharded_ins_step,
                                             place_state)
        from ibamr_tpu.utils.flight_recorder import FlightRecorder
        from ibamr_tpu.utils.hierarchy_driver import (HierarchyDriver,
                                                      RunConfig)
        from ibamr_tpu.utils.supervisor import ResilientDriver

        g = StaggeredGrid(n=(16, 16), x_lo=(0.0, 0.0), x_up=(1.0, 1.0))
        integ = INSStaggeredIntegrator(g, rho=1.0, mu=0.05)
        xf, yc = g.face_centers(0, jnp.float32)
        xc, yf = g.face_centers(1, jnp.float32)
        u0 = jnp.sin(2 * jnp.pi * xf) * jnp.cos(2 * jnp.pi * yc) + 0 * yc
        v0 = -jnp.cos(2 * jnp.pi * xc) * jnp.sin(2 * jnp.pi * yf) + 0 * xc
        mesh2 = Mesh(np.array(devs[:min(2, n_dev)]), ("x",))
        st0 = place_state(integ.initialize(u0_arrays=(u0, v0)), g, mesh2)

        dt0 = 1e-3
        cfg = RunConfig(dt=dt0, num_steps=12, restart_interval=4,
                        health_interval=2)
        sup_dir = os.path.join(directory, "supervised")
        drv = HierarchyDriver(
            integ, cfg,
            step_fn=nan_injector_step(
                make_sharded_ins_step(integ, mesh2), at_step=6,
                leaf_path="u[0]", dt_gate=dt0 * 0.99),
            recorder=FlightRecorder(capacity=4))
        sup = ResilientDriver(drv, sup_dir, max_retries=2,
                              dt_backoff=0.5, handle_signals=False,
                              sharded=True, mesh=mesh2)
        out = sup.run(st0)
        if int(out.k) != cfg.num_steps:
            raise AssertionError(
                f"supervised sharded run stopped at {int(out.k)}")
        if not bool(jnp.all(jnp.isfinite(out.u[0]))):
            raise AssertionError("supervised sharded run non-finite")
        div = [r for r in sup.incidents if r["event"] == "divergence"]
        if len(div) != 1 or div[0]["rollback_step"] != 4:
            raise AssertionError(f"unexpected incidents: {sup.incidents}")
        if not cs._all_sharded_steps(sup_dir):
            raise AssertionError("supervised run wrote no sharded steps")
        import glob as _glob
        if _glob.glob(os.path.join(sup_dir, "restore.*.npz")):
            raise AssertionError(
                "sharded supervision wrote single-host checkpoints")
        if div[0].get("replay"):
            with open(os.path.join(div[0]["replay"],
                                   "manifest.json")) as f:
                cap_mesh = json.load(f)["fingerprint"].get("mesh")
            if not cap_mesh or cap_mesh.get("n_shards") \
                    != int(np.prod(mesh2.devices.shape)):
                raise AssertionError(
                    f"capsule fingerprint lacks the mesh spec: "
                    f"{cap_mesh}")

        # -- 6. fsck gate ---------------------------------------------
        rep = ckpt_fsck.audit(directory)
        n_bad = rep["counts"]["torn"] + rep["counts"]["corrupt"]
        if rep["clean"] or n_bad < len(damaged):
            raise AssertionError(
                f"fsck missed damage: {rep['counts']} vs {damaged}")
        rc = ckpt_fsck.main([directory, "--repair", "-q"])
        if rc != 1:
            raise AssertionError(f"fsck --repair exit {rc}, expected 1")
        rep2 = ckpt_fsck.audit(directory)
        if not rep2["clean"]:
            raise AssertionError(
                f"tree not clean after repair: {rep2['counts']}")
        if ckpt_fsck.main([directory, "-q"]) != 0:
            raise AssertionError("fsck exit nonzero on repaired tree")
        if cs.latest_sharded_step(ckdir) != 10:
            raise AssertionError("repair touched the verified step")

        return {"sharded_smoke": "ok", "n_devices": n_dev,
                "shard_fetches": len(fetched),
                "max_fetch_bytes": max(fetched),
                "global_bytes": global_bytes,
                "damage_detected": damaged,
                "collision_verified": bool(collided_verified),
                "rollback_step": div[0]["rollback_step"],
                "fsck_quarantined": n_bad}
    finally:
        if tmp is not None:
            tmp.cleanup()


def run_fleet_smoke(directory: str | None = None,
                    fleet_size: int = 8, bad_lane: int = 5) -> dict:
    """Deterministic end-to-end FLEET drill (PR 7, dryrun path 20): a
    B-lane vmapped ensemble of the 32^3 IB shell where ONE lane is
    poisoned mid-run, supervised by the lane-granular recovery loop.

    1. **one bad lane, one compiled trace** — B perturbed copies of the
       shell scenario step through a single vmapped chunk; an un-gated
       ``lane_nan_injector`` NaNs lane ``bad_lane`` at its 4th step.
       The driver's per-lane triage raises ``LaneFault`` naming exactly
       that lane;
    2. **per-lane rollback, then quarantine** — the supervisor restores
       ONLY the bad lane's slice from the newest verified lane-axis
       checkpoint and backs off that lane's dt (one ``lane_rollback``
       incident); the un-gated fault re-fires, retries exhaust, and the
       lane is QUARANTINED — restored rows frozen in-graph by the
       lane-alive mask (one ``lane_quarantine`` incident). The fleet
       completes; the whole recovery retraces NOTHING (one trace
       signature per chunk length);
    3. **healthy lanes untouched** — every surviving lane's final state
       is BITWISE identical to the same scenario run solo (a B=1 fleet
       chunk — the batch-size-invariance contract);
    4. **lane-sliced capsule** — the rollback incident's capsule is
       single-lane; ``tools.replay`` re-executes it unbatched (B=1,
       injector re-armed onto lane 0) and must match the recorded
       post-chunk digest bitwise -> verdict ``reproduced``.

    Raises on any failed expectation; returns a one-line JSON summary.
    Needs x64 (bitwise pins are f64) — enabled here if not already.
    """
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.utils.flight_recorder import (FlightRecorder,
                                                 factory_spec)
    from ibamr_tpu.utils.hierarchy_driver import HierarchyDriver, RunConfig
    from ibamr_tpu.utils.lanes import lane_slice, stack_lanes
    from ibamr_tpu.utils.supervisor import ResilientDriver
    from tools.replay import replay

    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)

    B, BAD = int(fleet_size), int(bad_lane)
    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_fleet_smoke_")
        directory = tmp.name
    try:
        kwargs = dict(n_cells=32, n_lat=16, n_lon=16, mu=0.05,
                      dtype="float64")
        integ, st0 = build_shell_example(**kwargs)
        # heterogeneous fleet: per-lane initial-velocity perturbation
        lane_states = [st0._replace(ins=st0.ins._replace(
            u=tuple(c * (1.0 + 0.01 * i) + 1e-4 * (i + 1)
                    for c in st0.ins.u))) for i in range(B)]
        fleet0 = stack_lanes(lane_states)

        dt0 = 1e-3
        cfg = RunConfig(dt=dt0, num_steps=8, restart_interval=2,
                        health_interval=2)
        inj = dict(at_step=4, lane=BAD, fleet_size=B,
                   leaf_path="u[0]", step_attr="ins.k")
        with recorded("lane_nan", **inj):
            drv = HierarchyDriver(
                integ, cfg, lanes=B,
                fleet_step_wrap=lambda s: lane_nan_injector(s, **inj),
                recorder=FlightRecorder(capacity=4, spec=factory_spec(
                    "ibamr_tpu.models.shell3d", "build_shell_example",
                    **kwargs)))
            sup = ResilientDriver(drv, directory, max_retries=1,
                                  dt_backoff=0.5, handle_signals=False)
            out = sup.run(fleet0)

        k = np.asarray(out.ins.k)
        healthy = [i for i in range(B) if i != BAD]
        if any(int(k[i]) != cfg.num_steps for i in healthy):
            raise AssertionError(f"healthy lanes did not finish: {k}")
        if drv.lane_alive[BAD]:
            raise AssertionError("bad lane was never quarantined")
        bad_u = np.asarray(out.ins.u[0][BAD])
        if not np.isfinite(bad_u).all():
            raise AssertionError(
                "quarantined lane holds non-finite rows — the restore "
                "before freeze did not land")
        if float(drv.lane_dt[BAD]) != dt0 * 0.5:
            raise AssertionError(
                f"bad lane dt not backed off once: {drv.lane_dt}")
        if any(float(d) != dt0 for i, d in enumerate(drv.lane_dt)
               if i != BAD):
            raise AssertionError("a healthy lane's dt was touched")
        rolls = [r for r in sup.incidents
                 if r["event"] == "lane_rollback"]
        quars = [r for r in sup.incidents
                 if r["event"] == "lane_quarantine"]
        if len(rolls) != 1 or len(quars) != 1:
            raise AssertionError(f"unexpected incidents: "
                                 f"{[r['event'] for r in sup.incidents]}")
        if rolls[0]["lane"] != BAD or quars[0]["lane"] != BAD:
            raise AssertionError("incidents name the wrong lane")
        if not rolls[0]["from_checkpoint"]:
            raise AssertionError("rollback did not come from a "
                                 "verified checkpoint")
        # the recovery must never retrace: one signature per length
        if any(c != 1 for c in drv.trace_counts.values()):
            raise AssertionError(f"fleet recovery retraced: "
                                 f"{drv.trace_counts}")

        # -- 3. healthy lanes bitwise equal to solo (B=1) runs --------
        ref_cfg = RunConfig(dt=dt0, num_steps=8, health_interval=2)
        for i in healthy:
            ref_drv = HierarchyDriver(integ, ref_cfg, lanes=1)
            ref = ref_drv.run(stack_lanes([lane_states[i]]))
            got = jax.tree_util.tree_leaves(lane_slice(out, i))
            want = jax.tree_util.tree_leaves(lane_slice(ref, 0))
            if any(np.asarray(a).tobytes() != np.asarray(b).tobytes()
                   for a, b in zip(got, want)):
                raise AssertionError(
                    f"healthy lane {i} is not bitwise equal to its "
                    f"solo run — the quarantine machinery perturbed a "
                    f"lane it had no business touching")

        # -- 4. the lane-sliced capsule replays bitwise ---------------
        cap = rolls[0].get("replay")
        if not cap:
            raise AssertionError(f"rollback incident has no capsule: "
                                 f"{rolls[0]}")
        manifest = json.load(open(os.path.join(cap, "manifest.json")))
        if manifest.get("lane", {}).get("index") != BAD \
                or manifest.get("lane", {}).get("fleet_size") != B:
            raise AssertionError(f"capsule lane record wrong: "
                                 f"{manifest.get('lane')}")
        res = replay(cap)
        if res["verdict"] != "reproduced" or not res["bitwise"]:
            raise AssertionError(f"lane capsule replay: {res}")

        return {"fleet_smoke": "ok", "fleet_size": B, "bad_lane": BAD,
                "healthy_final_step": cfg.num_steps,
                "bad_lane_final_step": int(k[BAD]),
                "lane_rollbacks": len(rolls),
                "lane_quarantines": len(quars),
                "trace_counts": {str(n): c for n, c
                                 in drv.trace_counts.items()},
                "capsule": cap,
                "replay_verdict": res["verdict"]}
    finally:
        if tmp is not None:
            tmp.cleanup()


# ---------------------------------------------------------------------------
# serving-path chaos (PR 17): faults against the warm-pool router
# ---------------------------------------------------------------------------
#
# All four injectors monkey-patch the router's seams for the duration
# of a ``with`` block and restore them on exit. They are deliberately
# NOT ``recorded()``: they perturb latency and liveness, never state
# values, so there is no bitwise replay story — the soak drill's
# invariants are the reproduction.


@contextlib.contextmanager
def compile_storm_injector(extra_s: float = 0.5):
    """Every bucket build (the whole cost of a serving miss) takes
    ``extra_s`` longer — the host-side model of a compile storm, where
    novel families pile onto the build executor and cold requests wait.
    Warm pools are untouched (the patch sits on
    ``WarmPool.ensure_compiled``, which only runs at build time)."""
    from ibamr_tpu.serve.router import WarmPool

    orig = WarmPool.ensure_compiled

    def stormy(self):
        time.sleep(float(extra_s))
        return orig(self)

    WarmPool.ensure_compiled = stormy
    try:
        yield
    finally:
        WarmPool.ensure_compiled = orig


@contextlib.contextmanager
def slow_lane_injector(extra_s: float = 0.25, match=None):
    """Straggler: every compiled-chunk invocation on pools whose spec
    satisfies ``match`` (default: all pools) eats a host-side
    ``extra_s`` sleep first. Scoping ``match`` to the chaos family is
    how the soak proves a straggling tenant cannot drag a healthy
    tenant's p99 — slots, not speed, are the shared resource."""
    from ibamr_tpu.serve.router import WarmPool

    orig = WarmPool.chunk

    def straggler(self, length):
        ex = orig(self, length)
        if match is not None and not match(self.spec):
            return ex

        def slow_exec(*a, **k):
            time.sleep(float(extra_s))
            return ex(*a, **k)

        return slow_exec

    WarmPool.chunk = straggler
    try:
        yield
    finally:
        WarmPool.chunk = orig


@contextlib.contextmanager
def failing_build_injector(n_failures: int = 1,
                           message: str = "injected build failure"):
    """The first ``n_failures`` bucket builds raise — the transient
    compile failure the router's jittered-backoff retry budget exists
    for. Yields the live countdown list (``[remaining]``) so a drill
    can assert the faults were actually consumed."""
    from ibamr_tpu.serve.router import WarmPool

    orig = WarmPool.ensure_compiled
    remaining = [int(n_failures)]
    lock = threading.Lock()

    def flaky(self):
        with lock:
            fail = remaining[0] > 0
            if fail:
                remaining[0] -= 1
        if fail:
            raise RuntimeError(message)
        return orig(self)

    WarmPool.ensure_compiled = flaky
    try:
        yield remaining
    finally:
        WarmPool.ensure_compiled = orig


@contextlib.contextmanager
def kill_router_thread_injector(n_kills: int = 1):
    """The first ``n_kills`` pool-build threads DIE without publishing
    (``_build_pool`` returns before setting the flight event) — the
    harshest router liveness fault: every waiter on that flight would
    hang forever if the sliced-wait dead-thread failover did not
    exist. Yields the live countdown list (``[remaining]``)."""
    from ibamr_tpu.serve import router as _router

    orig = _router.WarmPoolRouter._build_pool
    remaining = [int(n_kills)]
    lock = threading.Lock()

    def killed(self, spec, flight):
        with lock:
            kill = remaining[0] > 0
            if kill:
                remaining[0] -= 1
        if kill:
            return  # thread exits: no pool, no error, no event
        return orig(self, spec, flight)

    _router.WarmPoolRouter._build_pool = killed
    try:
        yield remaining
    finally:
        _router.WarmPoolRouter._build_pool = orig


def mix_shift_injector(seed: int, duration_s: float, rate_rps: float,
                       shift_frac: float = 0.5,
                       shifted_family=(("n_lon", 12),),
                       burst_factor: float = 2.0):
    """Mix-shift fault (PR 18): a deterministic arrival schedule whose
    mix ROTATES to an unseen bucket family at ``shift_frac`` of the
    run — the traffic pattern a fixed warm-pool set cannot survive
    (every post-shift request would cold-compile or shed). Pure
    schedule transform, no monkey-patching: the same seed replays the
    same shift bit-for-bit. Returns ``(arrivals, shifted_family_str)``
    where the string matches the ``family`` field of
    ``request_admit``/``pool_scale`` ledger records."""
    from ibamr_tpu.serve.loadgen import (SCENARIO_MIX, ScenarioRequest,
                                         poisson_burst_schedule)

    shifted_mix = tuple(
        dataclasses.replace(s, family=tuple(shifted_family))
        for s in SCENARIO_MIX)
    arrivals = poisson_burst_schedule(
        seed=seed, duration_s=duration_s, rate_rps=rate_rps,
        burst_factor=burst_factor,
        mix_schedule=[(0.0, SCENARIO_MIX),
                      (float(shift_frac), shifted_mix)])
    fam = dict(shifted_family)
    probe = ScenarioRequest(
        tenant="probe", n_cells=fam.get("n_cells", 8),
        n_lat=fam.get("n_lat", 6), n_lon=fam.get("n_lon", 8),
        engine=fam.get("engine"),
        spectral_dtype=fam.get("spectral_dtype"),
        mu=fam.get("mu", 0.05))
    return arrivals, str(probe.family())


@contextlib.contextmanager
def memory_pressure_injector(cache, max_bytes: int):
    """Memory-pressure fault (PR 18): squeeze the executable cache's
    bytes ceiling mid-run (the ``aot_cache_bytes`` watermark the
    brownout pressure signal reads), restoring the original ceiling on
    exit. Yields the live eviction count ``[n]`` from the initial
    squeeze so a drill can assert what the pressure actually cost."""
    orig = cache.max_bytes
    evicted = [cache.set_max_bytes(int(max_bytes))]
    try:
        yield evicted
    finally:
        cache.set_max_bytes(orig)


def run_elastic_smoke(directory: str | None = None,
                      duration_s: float = 5.0, rate_rps: float = 8.0,
                      time_scale: float = 0.5,
                      shift_frac: float = 0.4) -> dict:
    """Deterministic elasticity drill (PR 18, dryrun path 22): a
    mid-soak MIX SHIFT onto an unseen family plus MEMORY PRESSURE on
    the executable cache drive the ``ElasticPoolManager`` through
    grow, brownout, shrink, and a crash-safe restart, and the
    invariants are pinned from the merged ledger:

    1. **no lost request** — every admitted ``trace_id`` reaches
       exactly one terminal record, shift or no shift;
    2. **scale-up before shed** — the shifted family's ``pool_scale``
       grow decision lands BEFORE any of its requests shed, and the
       family is eventually served warm;
    3. **brownout without oscillation** — the precompile backlog +
       bytes watermark push the mode ladder into brownout, it
       de-escalates through the dwell guard, and the total number of
       mode transitions stays bounded (no flapping);
    4. **elastic shrink** — the pre-shift family decays cold and is
       released (executables + bytes), never while serving;
    5. **restart drill** — ``serving_manifest.json`` is checkpointed,
       a FRESH router+cache restores it with bounded-concurrency
       re-warm and ZERO fresh XLA compiles (aot-cache ``cold_source``
       manifest attribution), then serves warm on the first request.

    Raises on any failed expectation; returns a one-line JSON summary
    (``tools/slo.py check --elastic`` evaluates the same ledger
    against SLO.json's ``elastic_slos``)."""
    from ibamr_tpu import obs as _obs
    from ibamr_tpu.serve import aot_cache
    from ibamr_tpu.serve.autoscale import (ElasticPoolManager,
                                           ScalePolicy,
                                           restore_serving_manifest)
    from ibamr_tpu.serve.capacity import capacity_report
    from ibamr_tpu.serve.loadgen import (SOAK_POLICIES,
                                         run_open_loop,
                                         traffic_summary)
    from ibamr_tpu.serve.router import (BucketSpec, ScenarioRequest,
                                        WarmPoolRouter)

    max_transitions = 6
    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_elastic_smoke_")
        directory = tmp.name
    try:
        ledger_path = os.path.join(directory, "elastic_ledger.jsonl")
        manifest_path = os.path.join(directory,
                                     "serving_manifest.json")
        # the cross-process compile layer: restart re-warms through
        # XLA's disk cache (repo-default dir; never fatal if absent)
        aot_cache.enable_persistent_cache(min_compile_secs=0.0)
        cache = aot_cache.ExecutableCache(
            directory=os.path.join(directory, "cache"))
        spec = BucketSpec(n_cells=8, n_lat=6, n_lon=8, lanes=2,
                          chunk_steps=2)
        router = WarmPoolRouter([spec], cache=cache,
                                allow_dynamic=True,
                                policies=dict(SOAK_POLICIES))
        # backlog>=1 trips brownout: one async grow IS the pressure
        # this drill exercises; de-escalation dwell bounds flapping
        manager = ElasticPoolManager(
            router,
            policy=ScalePolicy(grow_share=0.08, grow_min_arrivals=2,
                               shrink_share=0.02, min_dwell_s=2.0,
                               idle_evict_s=6.0,
                               brownout_backlog=1,
                               brownout_exit_backlog=0,
                               urgent_share=0.15,
                               mode_min_dwell_s=0.5),
            manifest_path=manifest_path)

        arrivals, shifted_family = mix_shift_injector(
            seed=0, duration_s=duration_s, rate_rps=rate_rps,
            shift_frac=shift_frac)
        shift_t = shift_frac * duration_s
        pre = [a for a in arrivals if a.t < shift_t]
        post = [dataclasses.replace(a, t=a.t - shift_t)
                for a in arrivals if a.t >= shift_t]

        with _obs.ledger(ledger_path):
            with _obs.span("elastic_smoke/warm"):
                router.warm(spec)
            base_family = str(spec.family())

            with _obs.span("elastic_smoke/pre_shift",
                           arrivals=len(pre)):
                run1 = run_open_loop(router, pre,
                                     time_scale=time_scale,
                                     join_timeout_s=120.0)
            # mid-soak: the mix rotates to the unseen family while the
            # cache's bytes ceiling is squeezed (generous enough that
            # the shifted family still fits — the watermark is
            # pressure, not sabotage)
            ceiling = max(int(cache.bytes() * 3), 1)
            with _obs.span("elastic_smoke/shifted_open_loop",
                           arrivals=len(post)), \
                    memory_pressure_injector(cache, ceiling):
                run2 = run_open_loop(router, post,
                                     time_scale=time_scale,
                                     join_timeout_s=180.0)

            # settle: idle ticks decay the mix + drain the mode
            # ladder back to healthy and let the cold family shrink
            t_settle = time.monotonic()
            while time.monotonic() - t_settle < 20.0:
                manager.tick()
                shrunk = any(e["action"] == "shrink"
                             for e in manager.scale_events)
                if manager.mode == "healthy" and shrunk:
                    break
                time.sleep(0.25)
            manager.tick()

            # -- 5. the restart drill --------------------------------
            manager.save_manifest()
            if manager.drain(timeout_s=120.0):
                raise AssertionError("builds/watchers never finished "
                                     "before the restart drill")
            router2, manager2, restore_stats = \
                restore_serving_manifest(manifest_path)
            fam = dict((("n_lon", 12),))
            probe = router2.serve([ScenarioRequest(
                tenant="interactive-restart", n_cells=8, n_lat=6,
                n_lon=fam["n_lon"], steps=2,
                tenant_class="interactive")])[0]
            router2.drain_builds(timeout_s=60.0)
            _obs.chunk_boundary()

        # -- invariant 1: no lost request ----------------------------
        for run in (run1, run2):
            if run["hung_threads"]:
                raise AssertionError(
                    f"{run['hung_threads']} producer threads never "
                    f"finished — the elastic drill deadlocked")
            if run["errors"]:
                raise AssertionError(
                    f"serve() raised under the mix shift: "
                    f"{run['errors'][:3]}")
        records = list(_obs.read_ledger(ledger_path))
        admits = [r for r in records
                  if r.get("kind") == "request_admit"]
        terminals: dict = {}
        for r in records:
            if r.get("kind") in ("request", "request_shed"):
                tid = r.get("trace_id")
                terminals[tid] = terminals.get(tid, 0) + 1
        lost = [r["trace_id"] for r in admits
                if terminals.get(r["trace_id"], 0) == 0]
        doubled = [r["trace_id"] for r in admits
                   if terminals.get(r["trace_id"], 0) > 1]
        if lost or doubled:
            raise AssertionError(
                f"terminal-record invariant broken: {len(lost)} lost, "
                f"{len(doubled)} doubled (first: "
                f"{(lost + doubled)[:3]})")

        # -- invariant 2: scale-up before shed for the shifted mix ---
        grows = [r for r in records if r.get("kind") == "pool_scale"
                 and r.get("action") == "grow"
                 and r.get("family") == shifted_family]
        if not grows:
            raise AssertionError(
                f"the shifted family {shifted_family} never got a "
                f"grow decision — the mix estimator is blind")
        first_grow_seq = min(r["seq"] for r in grows)
        shifted_tids = {r["trace_id"] for r in admits
                        if r.get("family") == shifted_family}
        shifted_sheds = [r for r in records
                         if r.get("kind") == "request_shed"
                         and r.get("trace_id") in shifted_tids]
        early = [r for r in shifted_sheds
                 if r.get("seq", 0) < first_grow_seq]
        if early:
            raise AssertionError(
                f"{len(early)} shifted-family requests shed BEFORE "
                f"the grow decision (seq {first_grow_seq})")
        warmed = [r for r in records if r.get("kind") == "pool_scale"
                  and r.get("action") == "warmed"
                  and r.get("family") == shifted_family]
        shifted_warm = [r for r in records if r.get("kind") == "request"
                        and r.get("trace_id") in shifted_tids
                        and not r.get("cold")]
        if not warmed or not shifted_warm:
            raise AssertionError(
                f"shifted family never published warm "
                f"(warmed={len(warmed)}, warm_served="
                f"{len(shifted_warm)})")

        # -- invariant 3: brownout entry/exit without oscillation ----
        modes = [r for r in records if r.get("kind") == "serve_mode"]
        if not any(r["mode"] == "brownout" for r in modes):
            raise AssertionError(
                "the grow backlog never tripped brownout — the "
                "pressure signal is dead")
        if len(modes) > max_transitions:
            raise AssertionError(
                f"{len(modes)} mode transitions (> {max_transitions})"
                f" — the ladder is oscillating")
        if manager.mode != "healthy":
            raise AssertionError(
                f"mode never de-escalated (stuck {manager.mode})")

        # -- invariant 4: elastic shrink of the cold family ----------
        shrinks = [r for r in records if r.get("kind") == "pool_scale"
                   and r.get("action") == "shrink"]
        if not any(r.get("family") == base_family for r in shrinks):
            raise AssertionError(
                f"the pre-shift family {base_family} was never "
                f"shrunk after going cold")
        if shifted_family not in {str(f)
                                  for f in router.live_families()}:
            raise AssertionError(
                "the shifted (hot) family is not live after shrink")

        # -- invariant 5: restart reached warm with zero fresh builds
        if restore_stats["fresh_compiles"] != 0:
            raise AssertionError(
                f"restart drill paid {restore_stats['fresh_compiles']}"
                f" fresh compiles (cold_source attribution) — the "
                f"persistent layer did not survive the crash")
        if restore_stats["warmed"] == 0 or restore_stats["errors"]:
            raise AssertionError(
                f"restart re-warm failed: {restore_stats}")
        if probe.shed or probe.cold or not probe.ok:
            raise AssertionError(
                f"first post-restart request was not a warm serve: "
                f"cold={probe.cold} shed={probe.shed} ok={probe.ok}")

        results = run1["results"] + run2["results"]
        wall = run1["wall_s"] + run2["wall_s"]
        summary = traffic_summary(results, wall)
        cap = capacity_report(records, p99_ceiling_s=2.0)
        if cap["prediction"]["rps"] is None:
            raise AssertionError(
                "capacity model unevaluable — no warm samples in the "
                "elastic ledger")
        return {"elastic_smoke": "ok",
                "arrivals": len(arrivals),
                "admitted": len(admits),
                "lost": 0,
                "shed": summary["shed"],
                "mode_transitions": len(modes),
                "grows": len(grows),
                "shrinks": len(shrinks),
                "scale_up_s": max(r.get("warm_s", 0.0)
                                  for r in warmed),
                "restart_warm_s": restore_stats["warm_s"],
                "restart_fresh_compiles":
                    restore_stats["fresh_compiles"],
                "cache_bytes": cache.bytes(),
                "predicted_rps": cap["prediction"]["rps"],
                "measured_rps": summary["requests_per_s"],
                "wall_s": round(wall, 3),
                "ledger": (None if tmp is not None else ledger_path)}
    finally:
        if tmp is not None:
            tmp.cleanup()


def run_soak_smoke(directory: str | None = None,
                   duration_s: float = 5.0, rate_rps: float = 8.0,
                   time_scale: float = 0.5,
                   chaos_rate_rps: float = 3.0) -> dict:
    """Deterministic traffic-robustness drill (PR 17, dryrun path 21):
    the open-loop load generator drives a warm-pool router under ALL
    FOUR serving chaos injectors at once, and the liveness invariants
    are pinned from the merged ledger.

    1. **healthy traffic, chaos tenant burning** — seeded Poisson
       arrivals with a 4x burst window over the heavy-tailed
       interactive/batch mix share the router with a ``chaos``-class
       tenant whose requests land on NOVEL families (fresh bucket
       compiles) while a compile storm slows every build, the first
       build raises (retry fuel), one build thread is killed
       mid-flight, and the chaos families' lanes straggle;
    2. **no deadlock** — every producer thread joins inside the
       drill's bounded window (``hung_threads == 0``);
    3. **no lost request** — every ``request_admit`` trace_id in the
       ledger reaches EXACTLY one terminal record (``request`` or
       ``request_shed``), storm or no storm;
    4. **bounded shed** — healthy classes shed at most
       ``max_healthy_shed_rate``; the chaos class may shed freely
       (that is admission control doing its job, not a failure);
    5. **healthy p99 within band** — healthy tenants' warm first-step
       p99 stays inside the committed ``soak_warm_p99_s`` band while
       the chaos tenant burns.

    Raises on any failed expectation; returns a one-line JSON summary.
    """
    from ibamr_tpu import obs as _obs
    from ibamr_tpu.serve import aot_cache
    from ibamr_tpu.serve.loadgen import (SOAK_POLICIES, Scenario,
                                         poisson_burst_schedule,
                                         run_open_loop, traffic_summary)
    from ibamr_tpu.serve.router import BucketSpec, WarmPoolRouter

    max_healthy_shed_rate = 0.10
    healthy_warm_p99_band_s = 2.0

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_soak_smoke_")
        directory = tmp.name
    try:
        ledger_path = os.path.join(directory, "soak_ledger.jsonl")
        spec = BucketSpec(n_cells=8, n_lat=6, n_lon=8, lanes=2,
                          chunk_steps=2)
        router = WarmPoolRouter(
            [spec],
            cache=aot_cache.ExecutableCache(
                directory=os.path.join(directory, "cache")),
            allow_dynamic=True, policies=dict(SOAK_POLICIES))

        with _obs.ledger(ledger_path):
            with _obs.span("soak_smoke/warm"):
                router.warm(spec)

            # healthy mix on the pre-warmed family; chaos tenant on
            # two NOVEL families (distinct n_lon -> fresh builds)
            arrivals = poisson_burst_schedule(
                seed=0, duration_s=duration_s, rate_rps=rate_rps,
                burst_factor=4.0)
            chaos_mix = (Scenario("chaos/storm_probe", 1.0, "chaos",
                                  steps=1),)
            for j, n_lon in enumerate((10, 12)):
                arrivals += poisson_burst_schedule(
                    seed=100 + j, duration_s=duration_s,
                    rate_rps=chaos_rate_rps / 2.0, burst_factor=4.0,
                    mix=chaos_mix, n_lon=n_lon, tenants_per_class=1)
            arrivals.sort(key=lambda a: a.t)

            chaos_family = (lambda s: s.n_lon != 8)
            with _obs.span("soak_smoke/chaos_open_loop",
                           arrivals=len(arrivals)), \
                    compile_storm_injector(extra_s=0.2), \
                    failing_build_injector(n_failures=1) as build_faults, \
                    kill_router_thread_injector(n_kills=1) as kills, \
                    slow_lane_injector(extra_s=0.2, match=chaos_family):
                run = run_open_loop(router, arrivals,
                                    time_scale=time_scale,
                                    join_timeout_s=120.0)
            _obs.chunk_boundary()

        # -- 2. no deadlock ------------------------------------------
        # deadline-shed chaos requests leave their bucket builds
        # running; those threads must also terminate (and must do so
        # before interpreter exit, or teardown aborts the process)
        still = router.drain_builds(timeout_s=120.0)
        if still:
            raise AssertionError(
                f"{still} pool builds never finished — a build "
                f"thread is wedged")
        if run["hung_threads"]:
            raise AssertionError(
                f"{run['hung_threads']} producer threads never "
                f"finished — the router deadlocked under chaos")
        if run["errors"]:
            raise AssertionError(
                f"serve() raised under chaos (every fault must "
                f"terminate as a shed, not an exception): "
                f"{run['errors'][:3]}")
        if build_faults[0] != 0 or kills[0] != 0:
            raise AssertionError(
                f"injected faults not consumed: {build_faults[0]} "
                f"build failures, {kills[0]} kills left — the drill "
                f"did not exercise what it claims")

        # -- 3. no lost request, from the ledger alone ---------------
        records = list(_obs.read_ledger(ledger_path))
        admits = [r["trace_id"] for r in records
                  if r.get("kind") == "request_admit"]
        terminals: dict = {}
        for r in records:
            if r.get("kind") in ("request", "request_shed"):
                tid = r.get("trace_id")
                terminals[tid] = terminals.get(tid, 0) + 1
        lost = [t for t in admits if terminals.get(t, 0) == 0]
        doubled = [t for t in admits if terminals.get(t, 0) > 1]
        if lost:
            raise AssertionError(
                f"{len(lost)} admitted requests have NO terminal "
                f"record (first: {lost[:3]}) — requests were lost")
        if doubled:
            raise AssertionError(
                f"{len(doubled)} admitted requests have multiple "
                f"terminal records (first: {doubled[:3]})")

        # -- 4. bounded shed for healthy classes ---------------------
        summary = traffic_summary(run["results"], run["wall_s"])
        healthy_sub = healthy_shed = 0
        for cls, c in summary["classes"].items():
            if cls != "chaos":
                healthy_sub += c["submitted"]
                healthy_shed += c["shed"]
        healthy_rate = (healthy_shed / healthy_sub) if healthy_sub else 0.0
        if healthy_rate > max_healthy_shed_rate:
            raise AssertionError(
                f"healthy classes shed {healthy_rate:.2%} "
                f"(> {max_healthy_shed_rate:.0%}) — the chaos tenant "
                f"stole healthy capacity")

        # -- 5. healthy warm p99 within band -------------------------
        healthy_warm = sorted(
            r["first_step_s"] for r in records
            if r.get("kind") == "request"
            and r.get("tenant_class") in ("interactive", "batch")
            and not r.get("cold"))
        if not healthy_warm:
            raise AssertionError("no healthy warm completions — the "
                                 "soak never reached the warm path")
        import math
        p99 = healthy_warm[min(len(healthy_warm) - 1,
                               max(0, math.ceil(0.99 * len(healthy_warm))
                                   - 1))]
        if p99 > healthy_warm_p99_band_s:
            raise AssertionError(
                f"healthy warm p99 {p99:.3f}s blew the "
                f"{healthy_warm_p99_band_s}s band while the chaos "
                f"tenant burned")

        chaos = summary["classes"].get("chaos", {})
        return {"soak_smoke": "ok",
                "arrivals": len(arrivals),
                "admitted": len(admits),
                "lost": 0,
                "healthy_shed_rate": round(healthy_rate, 4),
                "chaos_submitted": chaos.get("submitted", 0),
                "chaos_shed": chaos.get("shed", 0),
                "chaos_completed": chaos.get("completed", 0),
                "retried": summary["retried"],
                "healthy_warm_p99_s": round(float(p99), 4),
                "hung_threads": 0,
                "wall_s": round(run["wall_s"], 3)}
    finally:
        if tmp is not None:
            tmp.cleanup()


def run_design_smoke(directory: str | None = None,
                     num_iters: int = 3, lr: float = 0.05) -> dict:
    """Deterministic inverse-design drill (PR 19, dryrun path 23): the
    eel2d gait objective (``design.eel_gait`` — swim displacement
    differentiated THROUGH the ConstraintIB rollout) on a tiny f64
    config, with the adjoint-at-primal-cost contract pinned end to end:

    1. **adjoint correctness** — the jitted ``value_and_grad`` of the
       rollout objective agrees with an f64 central difference on the
       gait amplitude to 1e-6 relative (the custom-VJP chain through
       spectral solve + packed transfers + scan is a DERIVATIVE, not
       an approximation);
    2. **strict descent** — ``num_iters`` Adam iterations through
       :class:`~ibamr_tpu.design.DesignLoop` produce strictly
       decreasing objectives (every update helped);
    3. **zero warm compiles** — iteration 1 pays exactly one
       executable-cache MISS (the single AOT compile of the fused
       value_and_grad + Adam iterate); every later iteration is one
       cache HIT and zero misses, so a warm design iteration
       structurally cannot retrace or recompile;
    4. **ledger coverage** — each iteration lands one ``design_iter``
       record in the attached run ledger (the same records
       ``tools/obs.py summary`` renders as the design-loop block).

    Raises on any failed expectation; returns a one-line JSON summary.
    """
    import jax
    import jax.numpy as jnp

    from ibamr_tpu import obs as _obs
    from ibamr_tpu.design import DesignLoop, build_eel_gait_problem
    from ibamr_tpu.serve.aot_cache import ExecutableCache

    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)

    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_design_smoke_")
        directory = tmp.name
    try:
        t_all = time.perf_counter()
        objective, params0 = build_eel_gait_problem(
            n=24, ns=17, num_steps=10, dtype=jnp.float64)

        # 1. adjoint correctness: compiled grad vs central difference
        # on the gait amplitude (f64; FD step sized for ~1e-10 trunc)
        loop = DesignLoop(objective, params0, lr=lr,
                          cache=ExecutableCache(), label="eel_smoke")
        _, grads = jax.jit(loop.value_and_grad_fn())(params0)
        g_a0 = float(grads["A0"])
        obj = jax.jit(objective)
        a0 = float(params0["A0"])
        fd_eps = 1e-5

        def at(a):
            p = dict(params0)
            p["A0"] = jnp.asarray(a, jnp.float64)
            return float(obj(p))

        fd = (at(a0 + fd_eps) - at(a0 - fd_eps)) / (2.0 * fd_eps)
        fd_rel = abs(g_a0 - fd) / max(abs(fd), 1e-30)
        if fd_rel > 1e-6:
            raise AssertionError(
                f"adjoint disagrees with central difference: "
                f"grad {g_a0:.12e} vs FD {fd:.12e} "
                f"(rel {fd_rel:.3e} > 1e-6)")

        # 2-4. the loop itself, ledger attached
        ledger = _obs.RunLedger(
            os.path.join(directory, "design_ledger.jsonl"))
        prev = _obs.attach(ledger)
        try:
            res = loop.run(num_iters)
        finally:
            _obs.detach()
            if prev is not None:
                _obs.attach(prev)
            ledger.close()

        objs = [it.objective for it in res.history]
        for earlier, later in zip(objs, objs[1:]):
            if not later < earlier:
                raise AssertionError(
                    f"objective did not strictly decrease: {objs}")
        first = res.history[0]
        if first.cache_misses != 1:
            raise AssertionError(
                f"iteration 1 should pay exactly one compile, "
                f"paid {first.cache_misses}")
        for it in res.history[1:]:
            if it.cache_misses != 0 or it.cache_hits != 1:
                raise AssertionError(
                    f"warm iteration {it.iteration} not served from "
                    f"cache: hits={it.cache_hits} "
                    f"misses={it.cache_misses}")
        recs = [r for r in _obs.read_ledger(ledger.path)
                if r.get("kind") == "design_iter"]
        if len(recs) != num_iters:
            raise AssertionError(
                f"expected {num_iters} design_iter ledger records, "
                f"found {len(recs)}")

        return {"design_smoke": "ok",
                "iterations": num_iters,
                "objectives": [round(v, 10) for v in objs],
                "fd_rel_err": float(f"{fd_rel:.3e}"),
                "grad_A0": float(f"{g_a0:.6e}"),
                "cold_misses": first.cache_misses,
                "warm_misses": sum(
                    it.cache_misses for it in res.history[1:]),
                "warm_wall_s": round(sum(
                    it.wall_s for it in res.history[1:]), 3),
                "cold_wall_s": round(first.wall_s, 3),
                "ledger_records": len(recs),
                "wall_s": round(time.perf_counter() - t_all, 3)}
    finally:
        if tmp is not None:
            tmp.cleanup()


# ---------------------------------------------------------------------------
# assimilation faults (PR 20): bad sensors and bad members
# ---------------------------------------------------------------------------
#
# The first three injectors wrap the cycle's ``obs_source`` seam — a
# pure schedule transform over the sensor stream (which channels go
# bad, at which cycles), so an armed drill is bit-reproducible from
# its parameters alone. ``member_divergence_injector`` is a lane-
# confined STATE fault (the lane_nan shape) and is ``recorded()`` so
# capsules of an assimilating run carry it.

def obs_dropout_injector(source, channels, at_cycles):
    """Wrap an ``obs_source`` so the named channels read NaN (a dead
    sensor) at the named cycles — the QC gate must reject each with
    reason ``dropout`` and the analysis must proceed on the rest."""
    chans, cycs = list(channels), {int(c) for c in at_cycles}

    def wrapped(cycle, step):
        b = source(cycle, step)
        if b is None or cycle not in cycs:
            return b
        b = dataclasses.replace(b, values=b.values.copy())
        b.values[chans] = np.nan
        return b

    return wrapped


def obs_outlier_injector(source, channels, at_cycles,
                         magnitude: float = 50.0):
    """Wrap an ``obs_source`` so the named channels spike by
    ``magnitude`` observation-sigmas (an electrical transient) at the
    named cycles — far beyond any plausible innovation, so the QC
    gate's background check rejects each with reason ``outlier``."""
    chans, cycs = list(channels), {int(c) for c in at_cycles}

    def wrapped(cycle, step):
        b = source(cycle, step)
        if b is None or cycle not in cycs:
            return b
        b = dataclasses.replace(b, values=b.values.copy())
        b.values[chans] += magnitude * np.sqrt(b.r[chans])
        return b

    return wrapped


def stale_obs_injector(source, channels, at_cycles,
                       age_s: float = 1e6):
    """Wrap an ``obs_source`` so the named channels arrive ``age_s``
    seconds old (a feed replaying its last value) at the named cycles
    — the QC gate must reject each with reason ``stale``."""
    chans, cycs = list(channels), {int(c) for c in at_cycles}

    def wrapped(cycle, step):
        b = source(cycle, step)
        if b is None or cycle not in cycs:
            return b
        b = dataclasses.replace(b, age_s=b.age_s.copy())
        b.age_s[chans] = age_s
        return b

    return wrapped


def member_divergence_injector(stacked_step, at_step: int, lane: int,
                               fleet_size: int,
                               leaf_path: str = "u[0]",
                               dt_gate: float | None = None,
                               step_attr: str = "ins.k"):
    """One ensemble MEMBER diverges mid-run: lane ``lane``'s rows go
    NaN at its ``at_step`` (the :func:`lane_nan_injector` mechanics
    under the assimilation drill's name). The fleet triage must
    quarantine the member, and the masked analysis statistics must
    exclude it instead of averaging a diverged state into every other
    lane — the failure mode ensemble filters are famously soft on."""
    return lane_nan_injector(stacked_step, at_step=at_step, lane=lane,
                             fleet_size=fleet_size,
                             leaf_path=leaf_path, dt_gate=dt_gate,
                             step_attr=step_attr)


def run_assim_smoke(directory: str | None = None, fleet_size: int = 6,
                    cycles: int = 6, steps_per_cycle: int = 2,
                    bad_lane: int | None = None) -> dict:
    """Deterministic end-to-end ASSIMILATION drill (PR 20, dryrun path
    24): the B-lane shell fleet runs as a forecasting service while
    ALL FOUR assimilation injectors are armed at once —

    1. **bad sensors rejected, not assimilated** — a dropped channel
       (NaN), a 50-sigma outlier spike and a stale feed each hit a
       distinct channel at a distinct cycle; the QC gate must reject
       exactly those (channel, cycle, reason) triples as structured
       ``assim_qc_reject`` ledger records while the analysis proceeds
       on the surviving channels;
    2. **bad member quarantined, not averaged in** — one lane's state
       goes NaN mid-run; the lane-granular supervisor quarantines it
       and the masked ensemble statistics exclude it from every
       subsequent analysis (its rows ride through frozen);
    3. **zero lost cycles** — every cycle lands exactly one terminal
       ``assim_cycle`` ledger record (skipped or analyzed), through
       quarantine and QC rejections alike;
    4. **the filter earns its keep** — the final cycle's forecast
       error (rms innovation over accepted channels) beats the
       open-loop ensemble (same fleet, same injected member fault, no
       analysis) against the same sensors;
    5. **zero retraces** — the whole episode (quarantine, rejections,
       per-lane dt backoff) runs one trace signature per chunk length
       and exactly two analysis-executable compiles (observe +
       analyze), everything after a pure cache hit.

    Raises on any failed expectation; returns a one-line JSON summary
    (``tools/slo.py check --assim`` evaluates the same ledger against
    SLO.json's ``assim_slos``). Needs x64 — enabled here if not
    already."""
    import jax
    import jax.numpy as jnp

    from ibamr_tpu import obs as _obs
    from ibamr_tpu.assim import (AssimConfig, AssimilationCycle,
                                 ObservationOperator, masked_moments,
                                 stream_from_list, synthesize_batches)
    from ibamr_tpu.instruments import InstrumentPanel, make_meters
    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.serve.aot_cache import ExecutableCache
    from ibamr_tpu.utils.flight_recorder import (FlightRecorder,
                                                 factory_spec)
    from ibamr_tpu.utils.health import HealthProbe
    from ibamr_tpu.utils.lanes import stack_lanes
    from ibamr_tpu.assim import qc as _aqc

    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)

    B = int(fleet_size)
    BAD = B - 1 if bad_lane is None else int(bad_lane)
    n_cyc, spc = int(cycles), int(steps_per_cycle)
    dt0 = 1e-3
    t_all = time.perf_counter()
    tmp = None
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="ibamr_assim_smoke_")
        directory = tmp.name
    try:
        kwargs = dict(n_cells=16, n_lat=8, n_lon=16, mu=0.05,
                      dtype="float64")
        integ, st0 = build_shell_example(**kwargs)
        n_lon = kwargs["n_lon"]
        # two flow meters: latitude rings of the shell (closed loops)
        loops = [[2 * n_lon + j for j in range(n_lon)],
                 [5 * n_lon + j for j in range(n_lon)]]
        panel = InstrumentPanel(integ.ins.grid,
                                make_meters(loops, closed=True,
                                            dtype=jnp.float64))
        op = ObservationOperator(panel)

        # truth trajectory -> noisy synthetic sensors (twin experiment)
        st, truth_states = st0, []
        for _ in range(n_cyc):
            for _ in range(spc):
                st = integ.step(st, dt0)
            truth_states.append(st)
        sigma = 1e-5
        batches = synthesize_batches(op, truth_states, sigma=sigma,
                                     seed=7)
        names = op.channel_names()

        # heterogeneous ensemble: additive per-lane velocity offsets
        # (the initial shell state is quiescent — multiplicative
        # perturbations would leave the ensemble degenerate)
        lane_states = [st0._replace(ins=st0.ins._replace(
            u=tuple(c + 2e-3 * (i + 1) for c in st0.ins.u)))
            for i in range(B)]
        fleet0 = stack_lanes(lane_states)

        # the four injectors, armed at once: three sensor faults on
        # distinct (channel, cycle) slots + one diverging member
        injected = {(1, names[0], "dropout"),
                    (2, names[1], "outlier"),
                    (3, names[2], "stale")}
        member_inj = dict(at_step=spc + 1, lane=BAD, fleet_size=B,
                          leaf_path="u[0]", step_attr="ins.k")
        source = stream_from_list(batches)
        source = obs_dropout_injector(source, [0], [1])
        # the spike must clear the background check however wide the
        # ensemble is: 2e4 obs-sigmas dwarfs any plausible HPH^T
        source = obs_outlier_injector(source, [1], [2],
                                      magnitude=2e4)
        source = stale_obs_injector(source, [2], [3])

        ledger_path = os.path.join(directory, "assim_ledger.jsonl")
        cfg = AssimConfig(steps_per_cycle=spc, dt=dt0,
                          qc=_aqc.QCConfig(k_sigma=6.0))
        cache = ExecutableCache()
        probe = HealthProbe.for_integrator(integ)
        with _obs.ledger(ledger_path):
            with recorded("member_divergence", **member_inj):
                cyc = AssimilationCycle(
                    integ, op, B, cfg, probe=probe, cache=cache,
                    fleet_step_wrap=lambda s:
                        member_divergence_injector(s, **member_inj),
                    recorder=FlightRecorder(capacity=4,
                                            spec=factory_spec(
                        "ibamr_tpu.models.shell3d",
                        "build_shell_example", **kwargs)))
                out = cyc.run(fleet0, batches, directory=directory,
                              obs_source=source, max_retries=1)

        # -- 2. the diverged member is quarantined, stats exclude it --
        if cyc.driver.lane_alive[BAD]:
            raise AssertionError("diverged member never quarantined")
        if not all(cyc.driver.lane_alive[i] for i in range(B)
                   if i != BAD):
            raise AssertionError("a healthy member was quarantined")

        records = list(_obs.read_ledger(ledger_path))

        # -- 1. exactly the injected bad observations were rejected ---
        rej = {(r["cycle"], r["instrument"], r["reason"])
               for r in records if r.get("kind") == "assim_qc_reject"}
        if not injected <= rej:
            raise AssertionError(
                f"injected bad observations not all rejected: "
                f"missing {injected - rej}")
        extra = rej - injected
        if extra:
            raise AssertionError(
                f"QC rejected healthy observations: {extra}")

        # -- 3. zero lost cycles --------------------------------------
        cyc_recs = [r for r in records
                    if r.get("kind") == "assim_cycle"]
        done = {r["cycle"] for r in cyc_recs}
        if done != set(range(n_cyc)):
            raise AssertionError(
                f"lost cycles: {sorted(set(range(n_cyc)) - done)}")
        analyzed = [r for r in cyc_recs if not r.get("skipped")]
        if not analyzed:
            raise AssertionError("no cycle ever analyzed")

        # -- 5. zero retraces / zero steady-state compiles ------------
        if any(c != 1 for c in cyc.driver.trace_counts.values()):
            raise AssertionError(
                f"fleet chunk retraced: {cyc.driver.trace_counts}")
        stats = cache.stats()
        if stats["misses"] != 2:
            raise AssertionError(
                f"expected exactly 2 analysis compiles (observe + "
                f"analyze), got {stats['misses']}")

        # -- 4. the filter beats the open-loop ensemble ---------------
        # open loop: same fleet, same member fault, no analysis
        ol_cfg = AssimConfig(steps_per_cycle=spc, dt=dt0)
        ol = AssimilationCycle(
            integ, op, B, ol_cfg, probe=HealthProbe.for_integrator(integ),
            cache=ExecutableCache(),
            fleet_step_wrap=lambda s:
                member_divergence_injector(s, **member_inj))
        ol_dir = os.path.join(directory, "open_loop")
        os.makedirs(ol_dir, exist_ok=True)
        ol_out = ol.run(fleet0, directory=ol_dir, n_cycles=n_cyc,
                        obs_source=lambda c, s: None, max_retries=1)

        def _forecast_err(fleet_state, alive, batch):
            pred = np.asarray(jax.vmap(op)(fleet_state))
            ybar, _, _ = masked_moments(jnp.asarray(pred),
                                        jnp.asarray(alive))
            d = np.asarray(batch.values) - np.asarray(ybar)
            d = d[np.isfinite(d)]
            return float(np.sqrt(np.mean(d * d)))

        clean_final = batches[-1]
        err_assim = _forecast_err(out, cyc.driver.lane_alive,
                                  clean_final)
        err_open = _forecast_err(ol_out, ol.driver.lane_alive,
                                 clean_final)
        if not err_assim < err_open:
            raise AssertionError(
                f"assimilation did not beat the open loop: "
                f"{err_assim:.3e} vs {err_open:.3e}")

        # land the drill verdict in the ledger itself (append-only:
        # reopening continues the seq) — tools/slo.py check --assim
        # computes its SLIs from the ledger ALONE, and the
        # open-loop baseline exists nowhere else
        with _obs.ledger(ledger_path):
            _obs.emit("assim_summary", cycles=n_cyc, fleet_size=B,
                      bad_lane=BAD, forecast_error=err_assim,
                      open_loop_error=err_open,
                      analysis_compiles=stats["misses"],
                      analysis_cache_hits=stats["hits"],
                      final_inflation=cyc.inflation,
                      inflation_escalations=len(cyc.escalations))

        return {"assim_smoke": "ok", "fleet_size": B,
                "bad_lane": BAD, "cycles": n_cyc,
                "qc_rejections": sorted(
                    [list(t) for t in rej]),
                "lost_cycles": 0,
                "analysis_compiles": stats["misses"],
                "analysis_cache_hits": stats["hits"],
                "forecast_error": float(f"{err_assim:.6e}"),
                "open_loop_error": float(f"{err_open:.6e}"),
                "final_inflation": cyc.inflation,
                "ledger": ledger_path,
                "wall_s": round(time.perf_counter() - t_all, 3)}
    finally:
        if tmp is not None:
            tmp.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="deterministic fault-injection drills")
    ap.add_argument("--smoke", action="store_true",
                    help="run the end-to-end resilience drill")
    ap.add_argument("--silent-smoke", action="store_true",
                    help="run the silent-failure drill (health vitals "
                         "+ solver escalation + watchdog)")
    ap.add_argument("--replay-smoke", action="store_true",
                    help="run the record -> escalate -> replay drill")
    ap.add_argument("--crash-child", metavar="DIR",
                    help="run the checkpoint-writer victim loop in DIR")
    ap.add_argument("--sharded-crash-child", metavar="DIR",
                    help="run the SHARDED checkpoint-writer victim loop "
                         "in DIR (forces the CPU backend with "
                         "--n-devices virtual devices and x64)")
    ap.add_argument("--sharded-smoke", action="store_true",
                    help="run the sharded-checkpoint drill (no-gather "
                         "save, elastic restore, damage inventory, "
                         "collision, supervised rollback, fsck gate)")
    ap.add_argument("--soak-smoke", action="store_true",
                    help="run the traffic-robustness soak drill "
                         "(open-loop load + serving chaos injectors)")
    ap.add_argument("--elastic-smoke", action="store_true",
                    help="run the elastic warm-pool drill (mix shift "
                         "+ memory pressure -> grow/brownout/shrink + "
                         "crash-safe restart)")
    ap.add_argument("--assim-smoke", action="store_true",
                    help="run the fault-tolerant ensemble data "
                         "assimilation drill (QC-rejected bad "
                         "sensors, quarantined divergent member, "
                         "zero lost cycles, filter beats open loop)")
    ap.add_argument("--design-smoke", action="store_true",
                    help="run the inverse-design drill (eel2d gait "
                         "objective: FD-checked adjoint, strict Adam "
                         "descent, zero warm compiles)")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="run the lane-quarantine fleet drill (vmapped "
                         "ensemble, one poisoned lane, per-lane "
                         "rollback -> quarantine, sliced-capsule "
                         "replay)")
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--record-capsule", metavar="DIR",
                    help="record a divergence capsule in DIR, print "
                         "CAPSULE <dir> and linger for SIGKILL")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--interval", type=int, default=5)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--dir", default=None,
                    help="work directory for --smoke (default: temp)")
    args = ap.parse_args(argv)
    if args.crash_child:
        run_crash_child(args.crash_child, args.steps, args.interval,
                        keep=args.keep)
        return 0
    if args.sharded_crash_child:
        # the victim must never touch the chip, and the parent
        # verifies its f64 closed-form trajectory bitwise — pin the
        # CPU backend and x64 BEFORE any jax compute
        from ibamr_tpu.utils.backend_guard import force_cpu
        jax = force_cpu(args.n_devices)
        jax.config.update("jax_enable_x64", True)
        run_sharded_crash_child(args.sharded_crash_child, args.steps,
                                args.interval, keep=args.keep,
                                n_devices=args.n_devices)
        return 0
    if args.sharded_smoke:
        # same backend pin as the crash child: the drill needs the
        # virtual CPU mesh, never the chip
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu(args.n_devices)
        print(json.dumps(run_sharded_smoke(args.dir)), flush=True)
        return 0
    if args.fleet_smoke:
        # the drill is vmap-parallel, not device-parallel — one CPU
        # device suffices; f64 bitwise pins need x64 before any compute
        from ibamr_tpu.utils.backend_guard import force_cpu
        jax = force_cpu(1)
        jax.config.update("jax_enable_x64", True)
        print(json.dumps(run_fleet_smoke(args.dir)), flush=True)
        return 0
    if args.soak_smoke:
        # bounded CPU soak — pin the backend before any jax compute
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu(1)
        print(json.dumps(run_soak_smoke(args.dir)), flush=True)
        return 0
    if args.elastic_smoke:
        # bounded CPU elasticity drill — same backend pin as the soak
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu(1)
        print(json.dumps(run_elastic_smoke(args.dir)), flush=True)
        return 0
    if args.design_smoke:
        # tiny f64 design loop — one CPU device; the drill enables
        # x64 itself (the FD check needs it before any jax compute)
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu(1)
        print(json.dumps(run_design_smoke(args.dir)), flush=True)
        return 0
    if args.assim_smoke:
        # tiny f64 twin experiment — one CPU device; the drill
        # enables x64 itself (deterministic filter pins need it)
        from ibamr_tpu.utils.backend_guard import force_cpu
        force_cpu(1)
        print(json.dumps(run_assim_smoke(args.dir)), flush=True)
        return 0
    if args.record_capsule:
        record_capsule_drill(args.record_capsule)
        return 0
    if args.smoke:
        print(json.dumps(run_smoke(args.dir)), flush=True)
        return 0
    if args.silent_smoke:
        print(json.dumps(run_silent_smoke(args.dir)), flush=True)
        return 0
    if args.replay_smoke:
        print(json.dumps(run_replay_smoke(args.dir)), flush=True)
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    # ``python -m tools.fault_injection`` executes this file as
    # ``__main__`` — a SECOND module object from the canonical
    # ``tools.fault_injection`` the flight recorder fingerprints
    # ``ACTIVE_INJECTORS`` from. Delegate to the canonical import so
    # ``recorded`` blocks land in the registry replays read.
    import tools.fault_injection as _canonical
    raise SystemExit(_canonical.main())
