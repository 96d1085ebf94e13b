"""Deterministic incident replay harness (PR 5 tentpole 2).

``incidents/<step>/replay.npz`` + ``manifest.json`` (dumped by the
:class:`~ibamr_tpu.utils.flight_recorder.FlightRecorder` through the
supervisor) is a self-contained capsule of the failing chunk: the
pre-chunk state, the run fingerprint (integrator spec, engine,
``spectral_dtype``, armed fault injectors, audit params) and the
post-chunk digest (per-leaf CRC32s + vitals). This tool re-executes
the capsule in a fresh process:

1. **baseline** — rebuild the integrator exactly per the fingerprint,
   re-arm the recorded injectors, run the chunk, and pin the produced
   state BITWISE against the recorded post-chunk CRCs;
2. **substitution** — ``--override engine=…``,
   ``--override spectral_dtype=…`` and ``--dt-scale`` re-run the same
   capsule under one substitution;
3. **verdict** — a structured classification of what the failure
   depends on::

       reproduced          baseline matched bitwise (and the override,
                           if any, still failed)
       engine_dependent    baseline reproduced; swapping the transfer
                           engine cured it
       precision_dependent baseline reproduced; escalating
                           spectral_dtype cured it
       not_reproduced      the baseline re-execution did not match the
                           recorded digest (environment drift — the
                           fingerprint says what to look at)

   A dt-scale cure is reported via ``dt_dependent: true`` on a
   ``reproduced`` verdict.

Usage::

    python -m tools.replay CKPT_DIR/incidents/00000004 \
        [--override spectral_dtype=f64] [--override engine=mxu] \
        [--dt-scale 0.5] [--json]

Cross-mesh: capsules record UNSHARDED host arrays, so a capsule
recorded on one device replays on any mesh size (pinned by
tests/test_replay.py on the CPU virtual 8-device mesh). Capsules from
SHARDED runs additionally carry the mesh spec in their fingerprint
(stamped by ``ResilientDriver(sharded=True, mesh=...)``): the default
replay still runs them on 1 device, while ``--sharded`` re-executes
the recorded sharded program — degrading to a failure-reproduction pin
(``mesh_degraded``) when fewer devices are available than the incident
ran on.

Lane capsules (fleet runs): a capsule whose manifest carries a
``lane`` record is a SINGLE lane sliced out of a lane-batched fleet
chunk. It replays as a B=1 fleet chunk (vmapped step + freeze mask —
the program shape whose lanes are batch-size invariant), with recorded
``lane_nan``/``lane_drift`` injectors transformed onto lane 0; the
bitwise pin is against the recorded lane-sliced digest, independent of
the original fleet size.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class ReplayError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# incident log reading (schema v2/v3 tolerant)
# ---------------------------------------------------------------------------

def read_incidents(path: str) -> list:
    """Read ``incidents.jsonl`` tolerantly across schema versions:
    records written before v3 (no ``schema`` field) read as
    ``schema=2`` with ``replay=None``, so a log that spans an upgrade
    parses uniformly."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            rec.setdefault("schema", 2)
            rec.setdefault("replay", None)
            out.append(rec)
    return out


# ---------------------------------------------------------------------------
# capsule loading / integrator rebuild
# ---------------------------------------------------------------------------

def load_capsule(capsule_dir: str):
    """(manifest, {path: np.ndarray}) from a capsule directory."""
    mpath = os.path.join(capsule_dir, "manifest.json")
    if not os.path.exists(mpath):
        raise ReplayError(f"no manifest.json in {capsule_dir!r}")
    with open(mpath) as f:
        manifest = json.load(f)
    npz = os.path.join(capsule_dir,
                       manifest.get("state_file", "replay.npz"))
    with np.load(npz) as z:
        arrays = {k: z[k] for k in z.files}
    return manifest, arrays


_ENGINE_TO_KWARG = {"scatter": False, "mxu": True, "auto": None}


def rebuild(manifest: dict, overrides: dict | None = None):
    """(integ, template_state) per the manifest fingerprint, with
    ``overrides`` substituted (``spectral_dtype`` -> the spectral knob,
    ``engine`` -> the factory's ``use_fast_interaction``; any other key
    substitutes into factory kwargs verbatim)."""
    overrides = dict(overrides or {})
    spec = manifest["fingerprint"]["integrator"]
    kind = spec.get("kind")
    if kind == "ins":
        import jax.numpy as jnp

        from ibamr_tpu.grid import StaggeredGrid
        from ibamr_tpu.integrators.ins import INSStaggeredIntegrator

        if "engine" in overrides:
            raise ReplayError("--override engine applies to factory "
                              "capsules (the plain INS integrator has "
                              "no transfer engine)")
        gd = spec["grid"]
        grid = StaggeredGrid(n=tuple(gd["n"]), x_lo=tuple(gd["x_lo"]),
                             x_up=tuple(gd["x_up"]))
        wall = spec.get("wall_axes")
        integ = INSStaggeredIntegrator(
            grid, rho=spec["rho"], mu=spec["mu"],
            convective_op_type=spec["convective_op_type"],
            dtype=jnp.dtype(spec["dtype"]),
            wall_axes=None if wall is None else tuple(wall),
            spectral_dtype=overrides.get("spectral_dtype",
                                         spec.get("spectral_dtype")))
        return integ, integ.initialize()
    if kind == "factory":
        mod = importlib.import_module(spec["module"])
        fn = getattr(mod, spec["name"])
        kwargs = dict(spec.get("kwargs", {}))
        for key, val in overrides.items():
            if key == "engine":
                kwargs["use_fast_interaction"] = \
                    _ENGINE_TO_KWARG.get(val, val)
            else:
                kwargs[key] = val
        out = fn(**kwargs)
        if isinstance(out, tuple):
            integ, template = out[0], out[1]
        else:
            integ, template = out, out.initialize()
        return integ, template
    raise ReplayError(
        f"capsule integrator spec kind={kind!r} is not replayable "
        f"(record an explicit factory spec on the FlightRecorder)")


def effective_engine(manifest: dict, overrides: dict | None) -> str | None:
    """The engine label the (possibly overridden) rebuild runs with —
    what engine-gated recorded injectors arm against."""
    overrides = overrides or {}
    if "engine" in overrides:
        return str(overrides["engine"])
    return manifest["fingerprint"].get("engine")


def state_from_capsule(manifest: dict, arrays: dict, template):
    """Rebuild the device pytree: capsule arrays are keyed by the
    checkpoint path convention in recorded ``leaf_order``."""
    import jax
    import jax.numpy as jnp

    from ibamr_tpu.utils.checkpoint import _path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    order = manifest["leaf_order"]
    keys = [_path_str(p) for p, _ in flat]
    if set(keys) != set(order):
        raise ReplayError(
            f"capsule/template leaf mismatch: capsule has "
            f"{sorted(set(order) - set(keys))} extra, template has "
            f"{sorted(set(keys) - set(order))} extra")
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(arrays[k]) for k in keys])


# ---------------------------------------------------------------------------
# chunk execution + failure classification
# ---------------------------------------------------------------------------

def rebuild_mesh(mesh_spec: dict):
    """The recorded device mesh, rebuilt on THIS process's devices —
    same axis shape and names, devices in id order (the shard-index
    convention of ``checkpoint_sharded``). Raises :class:`ReplayError`
    when fewer devices are available than the incident ran on."""
    import jax
    from jax.sharding import Mesh

    shape = tuple(int(s) for s in mesh_spec["shape"])
    need = int(np.prod(shape))
    devs = sorted(jax.devices(), key=lambda d: d.id)
    if len(devs) < need:
        raise ReplayError(
            f"capsule was recorded on a {shape} mesh ({need} devices); "
            f"only {len(devs)} available")
    names = mesh_spec.get("axis_names") or \
        [f"ax{i}" for i in range(len(shape))]
    return Mesh(np.array(devs[:need]).reshape(shape), tuple(names))


def execute_chunk(integ, state, dt: float, length: int, step_wrap=None,
                  step_fn=None):
    """Re-execute the failing chunk: the same jitted
    ``lax.scan(step, ...)`` the driver compiled, minus the cadence
    machinery. ``step_fn`` substitutes a prebuilt step (the sharded
    one) for ``integ.step``. Where the run scanned the integrator's own
    step with nothing re-armed around it, the driver carried its
    transfer context through the scan, and so does this. Returns the
    post-chunk state."""
    import jax

    from ibamr_tpu.utils.hierarchy_driver import offers_carry, scan_steps

    plain = integ.step if step_fn is None else step_fn
    step = plain if step_wrap is None else step_wrap(plain)
    carried = (integ if step_fn is None and step is plain
               and offers_carry(integ) else None)

    @jax.jit
    def chunk(s, dt_):
        return scan_steps(step, s, dt_, length, carried=carried)[0]

    return chunk(state, dt)


def execute_lane_chunk(integ, state, dt: float, length: int,
                       step_wrap=None):
    """Re-execute a LANE capsule's chunk as a B=1 fleet chunk: vmapped
    step, per-lane dt vector, lane-alive freeze mask — the same program
    shape :meth:`HierarchyDriver._build_fleet_chunk` compiles, which is
    the bitwise solo reference for any lane of any fleet (the
    batch-size-invariance contract in ``ibamr_tpu.utils.lanes``). The
    classic unbatched scan is NOT used here: it fuses differently and
    drifts by ULPs from the fleet execution the digest was recorded
    from. ``step_wrap`` (re-armed lane injectors, already transformed
    to lane 0 of a size-1 fleet) wraps the STACKED step."""
    import jax
    import jax.numpy as jnp

    stacked = jax.tree_util.tree_map(lambda l: jnp.asarray(l)[None],
                                     state)
    vstep = jax.vmap(integ.step, in_axes=(0, 0))
    if step_wrap is not None:
        vstep = step_wrap(vstep)

    @jax.jit
    def chunk(s, d, alive):
        def body(x, _):
            new = vstep(x, d)
            frozen = jax.tree_util.tree_map(
                lambda nl, ol: jnp.where(
                    alive.reshape((1,) + (1,) * (nl.ndim - 1)), nl, ol),
                new, x)
            return frozen, None

        out, _ = jax.lax.scan(body, s, None, length=length)
        return out

    out = chunk(stacked, jnp.asarray([dt]), jnp.ones(1, dtype=bool))
    return jax.tree_util.tree_map(lambda l: l[0], out)


def digest_state(post_state) -> dict:
    from ibamr_tpu.utils.checkpoint import _gather_arrays, _leaf_crc

    arrays = _gather_arrays(post_state)
    return {k: _leaf_crc(v) for k, v in arrays.items()}


def _all_finite(state) -> bool:
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(state):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                return False
    return True


def chunk_failed(manifest: dict, integ, post_state, dt: float) -> bool:
    """Did THIS execution exhibit the recorded failure? Kind-specific:
    non-finite leaves for divergence-family incidents; a recomputed
    shadow audit breach for ``precision_drift`` (the state itself is
    finite in that family)."""
    kind = (manifest.get("incident") or {}).get("kind", "divergence")
    finite = _all_finite(post_state)
    if not finite:
        return True
    if kind == "precision_drift":
        from ibamr_tpu.solvers.escalation import (PrecisionDrift,
                                                  ShadowAuditor)

        audit = manifest["fingerprint"].get("audit") or {}
        aud = ShadowAuditor(every=1, bound=audit.get("bound", 0.02),
                            div_bound=audit.get("div_bound"))
        try:
            aud.audit(integ, post_state, dt,
                      step=manifest["chunk"]["start_step"]
                      + manifest["chunk"]["length"])
        except PrecisionDrift:
            return True
        return False
    return False


# ---------------------------------------------------------------------------
# the replay entry point
# ---------------------------------------------------------------------------

def _x64_scope(manifest):
    """Execute under the RECORDED x64 mode. A capsule recorded by a
    standalone run (x64 off) replayed inside the test harness (x64 on)
    would trace its np-derived constants at f64 instead of f32 — a
    different computation, so the bitwise pin fails for a reason that
    has nothing to do with the incident. Old capsules without the flag
    replay under the current mode."""
    import contextlib

    import jax

    rec = manifest["fingerprint"].get("x64")
    if rec is None or bool(rec) == bool(jax.config.jax_enable_x64):
        return contextlib.nullcontext()
    return jax.enable_x64(bool(rec))


def _run_once(manifest, arrays, overrides, dt_scale, sharded=False):
    import jax

    from tools.fault_injection import apply_recorded_injectors

    injectors = dict(manifest["fingerprint"].get("injectors") or {})
    engine = effective_engine(manifest, overrides)
    lane_rec = manifest.get("lane")
    if lane_rec is not None and sharded:
        raise ReplayError("lane capsules replay unbatched (B=1); "
                          "--sharded does not apply")
    # engine-gated faults arm only when the effective engine matches
    armed = {}
    for name, params in injectors.items():
        if name == "engine_nan":
            p = dict(params)
            gate = p.pop("engine", None)
            if gate is not None and engine is not None \
                    and _norm_engine(gate) != _norm_engine(engine):
                continue
            armed["nan"] = p
        elif name in ("lane_nan", "lane_drift") and lane_rec is not None:
            # lane capsule: a fault aimed at THIS lane re-arms onto
            # lane 0 of the B=1 replay fleet; a fault aimed at any
            # OTHER lane could never fire here and is dropped
            p = dict(params)
            if int(p.get("lane", -1)) != int(lane_rec["index"]):
                continue
            p["lane"] = 0
            p["fleet_size"] = 1
            armed[name] = p
        else:
            armed[name] = params
    with apply_recorded_injectors(armed) as wrap, _x64_scope(manifest):
        # patched module functions must reach the trace: executables
        # compiled before the patch would replay the CLEAN computation
        jax.clear_caches()
        integ, template = rebuild(manifest, overrides)
        state = state_from_capsule(manifest, arrays, template)
        dt = float(manifest["chunk"]["dt"]) * float(dt_scale)
        if lane_rec is not None:
            post = execute_lane_chunk(integ, state, dt,
                                      int(manifest["chunk"]["length"]),
                                      step_wrap=wrap)
        else:
            step_fn = None
            if sharded:
                # re-execute the SAME sharded program the incident ran:
                # rebuild the recorded mesh, re-place the capsule state
                # under the spatial sharding, and scan the sharded step
                from ibamr_tpu.parallel.mesh import (make_sharded_step,
                                                     place_state)
                mesh = rebuild_mesh(manifest["fingerprint"]["mesh"])
                state = place_state(state, integ.grid, mesh)
                step_fn = make_sharded_step(integ, mesh)
            post = execute_chunk(integ, state, dt,
                                 int(manifest["chunk"]["length"]),
                                 step_wrap=wrap, step_fn=step_fn)
        crcs = digest_state(post)
        failed = chunk_failed(manifest, integ, post, dt)
    return {"leaf_crcs": crcs, "failed": failed,
            "finite": _all_finite(post)}


def _norm_engine(label) -> str:
    try:
        from ibamr_tpu.models.engine_resolver import normalize_engine_name
        return normalize_engine_name(label)
    except Exception:
        return str(label).lower()


def replay(capsule_dir: str, overrides: dict | None = None,
           dt_scale: float = 1.0, sharded: bool = False) -> dict:
    """Full replay: baseline bitwise pin, optional substitution run,
    structured verdict. See the module docstring for the verdict
    vocabulary.

    ``sharded=True`` re-executes on the RECORDED mesh (the fingerprint
    carries the mesh spec of a sharded run). When fewer devices are
    available than the incident ran on, the replay degrades to the
    single-device program with ``mesh_degraded: true`` and the bitwise
    pin relaxes to the failure-reproduction pin — a cross-mesh digest
    mismatch there says nothing about the incident. The DEFAULT
    (``sharded=False``) replays any capsule on one device: capsule
    arrays are unsharded host copies, the cross-mesh guarantee."""
    manifest, arrays = load_capsule(capsule_dir)
    recorded_post = manifest.get("post")
    mesh_spec = (manifest.get("fingerprint") or {}).get("mesh")
    mesh_degraded = False
    use_sharded = False
    if sharded:
        if not mesh_spec or int(mesh_spec.get("n_shards", 1)) <= 1:
            raise ReplayError(
                "sharded replay requested but the capsule records no "
                "multi-device mesh (was the run supervised with "
                "ResilientDriver(sharded=True, mesh=...)?)")
        import jax
        need = int(np.prod([int(s) for s in mesh_spec["shape"]]))
        if jax.device_count() >= need:
            use_sharded = True
        else:
            mesh_degraded = True

    base = _run_once(manifest, arrays, overrides=None, dt_scale=1.0,
                     sharded=use_sharded)
    if recorded_post and recorded_post.get("leaf_crcs"):
        bitwise = base["leaf_crcs"] == {
            k: int(v) for k, v in recorded_post["leaf_crcs"].items()}
        if not bitwise and mesh_degraded:
            # the recorded digest belongs to the sharded program we
            # could not rebuild — pin failure reproduction instead
            bitwise = base["failed"]
    else:
        # no recorded digest (e.g. a stall capsule): fall back to the
        # weaker failure-reproduction pin
        bitwise = base["failed"]

    result = {
        "capsule": os.path.abspath(capsule_dir),
        "kind": (manifest.get("incident") or {}).get("kind"),
        "bitwise": bool(bitwise),
        "baseline_failed": bool(base["failed"]),
        "override": dict(overrides) if overrides else None,
        "dt_scale": float(dt_scale),
        "override_failed": None,
        "dt_dependent": None,
        "recorded_mesh": mesh_spec,
        "sharded_replay": use_sharded,
        "mesh_degraded": mesh_degraded,
    }
    has_sub = bool(overrides) or dt_scale != 1.0
    if has_sub:
        sub = _run_once(manifest, arrays, overrides=overrides,
                        dt_scale=dt_scale, sharded=use_sharded)
        result["override_failed"] = bool(sub["failed"])

    if not bitwise:
        verdict = "not_reproduced"
    elif not has_sub:
        verdict = "reproduced" if base["failed"] else "not_reproduced"
    elif result["override_failed"]:
        verdict = "reproduced"
    elif overrides and "spectral_dtype" in overrides:
        verdict = "precision_dependent"
    elif overrides and "engine" in overrides:
        verdict = "engine_dependent"
    else:
        verdict = "reproduced"
        result["dt_dependent"] = True
    result["verdict"] = verdict
    from ibamr_tpu import obs as _obs
    _obs.counter("replay_verdicts_total", verdict=verdict).inc()
    _obs.emit("replay", verdict=verdict, step=result.get("step"),
              override_failed=result.get("override_failed"))
    return result


def newest_capsule(root: str) -> str | None:
    """Newest ``incidents/<step>`` capsule dir under a checkpoint root
    (or an incidents dir itself)."""
    cand = root
    if os.path.isdir(os.path.join(root, "incidents")):
        cand = os.path.join(root, "incidents")
    if not os.path.isdir(cand):
        return None
    caps = [os.path.join(cand, d) for d in sorted(os.listdir(cand))
            if os.path.exists(os.path.join(cand, d, "manifest.json"))]
    return caps[-1] if caps else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="re-execute an incident replay capsule, bitwise-"
                    "pinned against its recorded post-chunk digest")
    ap.add_argument("capsule", help="incidents/<step> capsule directory")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="substitute one knob (engine=…, "
                         "spectral_dtype=…, or a factory kwarg)")
    ap.add_argument("--dt-scale", type=float, default=1.0,
                    help="re-run the chunk at dt * SCALE")
    ap.add_argument("--sharded", action="store_true",
                    help="re-execute on the capsule's recorded device "
                         "mesh (degrades to 1 device with a "
                         "failure-reproduction pin when fewer devices "
                         "are available)")
    ap.add_argument("--json", action="store_true",
                    help="print the full result dict as JSON")
    args = ap.parse_args(argv)

    overrides = {}
    for item in args.override:
        if "=" not in item:
            ap.error(f"--override {item!r}: expected KEY=VALUE")
        key, val = item.split("=", 1)
        overrides[key.strip()] = val.strip()

    result = replay(args.capsule, overrides=overrides or None,
                    dt_scale=args.dt_scale, sharded=args.sharded)
    if args.json:
        print(json.dumps(result, indent=1))
    else:
        print(f"verdict: {result['verdict']} "
              f"(bitwise={result['bitwise']}, "
              f"baseline_failed={result['baseline_failed']}, "
              f"override_failed={result['override_failed']})")
    return 0 if result["verdict"] != "not_reproduced" else 3


if __name__ == "__main__":
    raise SystemExit(main())
