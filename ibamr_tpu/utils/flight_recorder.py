"""Flight recorder: bounded ring of chunk-entry states + run
fingerprint, dumped as a bitwise-replayable capsule on any incident
(PR 5 tentpole 1).

At production scale an incident that cannot be reproduced offline is
unfixable: the PR-2/3 incident records say WHAT went wrong (kind,
vitals, attempts) but not enough to re-execute the failing computation.
The recorder closes that gap:

- :meth:`FlightRecorder.snapshot` is called by
  :class:`~ibamr_tpu.utils.hierarchy_driver.HierarchyDriver` once per
  chunk, BEFORE the jitted chunk consumes the state. The snapshot is a
  HOST copy (``device_get`` -> numpy), which makes it donation-safe by
  construction: with ``RunConfig(donate=True)`` the chunk invalidates
  the device buffers it was passed, but the ring holds independent host
  memory. (``ResilientDriver`` forces donate off anyway; the bare
  driver is the hazard this fixes.)
- The ring is bounded (``capacity`` entries, a handful of chunks), so
  recording costs one host copy of the state per chunk and a few
  states of host RAM — the overhead bound (< 2% of chunk wall at the
  CPU smoke size) is pinned in tests/test_replay.py via the recorder's
  own ``overhead_s`` accounting.
- :meth:`FlightRecorder.dump_incident` writes
  ``incidents/<step>/replay.npz`` (the pre-chunk state) plus
  ``manifest.json``: the run fingerprint (config digest, integrator
  spec, engine + fallback chain, ``spectral_dtype``, jax/numpy
  versions, device count/platform, x64 flag, RNG keys, active fault
  injectors, shadow-audit params) and — when the driver is available —
  the POST-chunk digest: per-leaf CRC32s and the fused vitals vector of
  the state the failing chunk produces, computed by re-executing the
  recorded chunk once through the driver's own compiled executable
  (the incident path is cold; one extra chunk is free). ``tools/
  replay.py`` re-executes the capsule in a fresh process and pins
  bitwise against that digest.

Capsule layout::

    incidents/<step>/replay.npz     # pre-chunk state, checkpoint layout
    incidents/<step>/manifest.json  # fingerprint + chunk + post digest

Both files are written with the checkpoint module's atomic-write
discipline (temp + fsync + rename), so a capsule is never torn — the
manifest is written LAST and is the commit marker.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from ibamr_tpu.utils.checkpoint import (_atomic_write, _gather_arrays,
                                        _leaf_crc, _path_str)

CAPSULE_SCHEMA = 1


def _json_safe(obj):
    """Best-effort conversion of config/spec values to JSON types."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return repr(obj)


def canonicalize(obj):
    """Deterministic JSON-safe form of fingerprint/cache-key material:
    values through :func:`_json_safe` (tuples -> lists, numpy scalars ->
    python), dict keys RECURSIVELY sorted. Two semantically identical
    configs that differ only in dict insertion order canonicalize (and
    therefore digest) identically — the serving cache
    (ibamr_tpu/serve/aot_cache.py) keys whole compiled executables on
    these digests, so key stability is a correctness property, not a
    nicety."""
    def _sort(v):
        if isinstance(v, dict):
            return {k: _sort(v[k]) for k in sorted(v)}
        if isinstance(v, list):
            return [_sort(x) for x in v]
        return v
    return _sort(_json_safe(obj))


def _engine_label(val) -> Optional[str]:
    """Normalize an engine selection value (the ``use_fast_interaction``
    vocabulary) to a stable string label."""
    from ibamr_tpu.models.engine_resolver import normalize_engine_name
    return "auto" if val is None else normalize_engine_name(val)


def describe_integrator(integ) -> dict:
    """Reconstructible spec of an integrator: enough for
    ``tools/replay.py`` to rebuild it in a fresh process. The INS
    integrator is described field-by-field; anything else falls back
    to an opaque record (replayable only via an explicit factory
    ``spec`` passed to the recorder)."""
    if integ is None:
        return {"kind": "opaque", "class": None}
    grid = getattr(integ, "grid", None)
    if (grid is not None and hasattr(integ, "rho")
            and hasattr(integ, "convective_op_type")
            and hasattr(integ, "initialize")):
        import jax.numpy as jnp

        from ibamr_tpu.solvers.escalation import precision_level_name

        wall = getattr(integ, "wall_axes", None)
        return {
            "kind": "ins",
            "grid": {"n": [int(v) for v in grid.n],
                     "x_lo": [float(v) for v in grid.x_lo],
                     "x_up": [float(v) for v in grid.x_up]},
            "rho": float(integ.rho), "mu": float(integ.mu),
            "convective_op_type": str(integ.convective_op_type),
            "dtype": str(jnp.dtype(integ.dtype)),
            "wall_axes": None if wall is None else [bool(w) for w in wall],
            "spectral_dtype": precision_level_name(
                getattr(integ, "spectral_dtype", None)),
        }
    return {"kind": "opaque", "class": type(integ).__name__}


def factory_spec(module: str, name: str, **kwargs) -> dict:
    """Spec for an integrator built by a module-level factory (e.g.
    ``ibamr_tpu.models.shell3d.build_shell_example``): replay imports
    ``module``, calls ``name(**kwargs)`` and expects ``(integ, state)``
    (or an integrator with ``initialize()``). Overrides substitute into
    ``kwargs`` by key (``engine`` maps onto ``use_fast_interaction``)."""
    return {"kind": "factory", "module": module, "name": name,
            "kwargs": _json_safe(kwargs)}


@dataclasses.dataclass
class ChunkSnapshot:
    """One ring entry: the host copy of the state ENTERING a chunk.
    Fleet chunks store the per-lane dt VECTOR and lane-alive mask
    (host copies); solo chunks keep the scalar dt and ``alive=None``."""
    step: int
    dt: Any                           # float, or (B,) ndarray in fleet mode
    length: int
    paths: List[str]                  # leaf order for unflatten
    arrays: Dict[str, np.ndarray]     # path -> host copy
    treedef: Any
    wall_time: float
    alive: Optional[np.ndarray] = None

    def covers(self, step: Optional[int]) -> bool:
        return (step is None
                or self.step <= step <= self.step + self.length)


class FlightRecorder:
    """Bounded ring of pre-chunk host snapshots + the run fingerprint.

    Parameters
    ----------
    capacity:
        Ring depth in chunks. The newest entry covering the incident
        step becomes the capsule; a handful suffices (the supervisor
        dumps on the FIRST raise).
    spec:
        Optional explicit integrator spec (see :func:`factory_spec`)
        overriding the derived :func:`describe_integrator` record —
        required for replay of anything but the plain INS integrator.
    extra_fingerprint:
        Extra JSON-safe fields merged into the fingerprint (mesh shape,
        run labels, ...).
    """

    def __init__(self, capacity: int = 4, spec: Optional[dict] = None,
                 extra_fingerprint: Optional[dict] = None):
        if capacity < 1:
            raise ValueError("FlightRecorder.capacity must be >= 1")
        self.capacity = capacity
        self.ring: "deque[ChunkSnapshot]" = deque(maxlen=capacity)
        self.spec = spec
        self.extra = dict(extra_fingerprint or {})
        self.snapshots = 0
        self.overhead_s = 0.0         # cumulative snapshot cost (the
        #                               < 2%-of-chunk-wall observable)
        self.dumps: List[str] = []
        self._integ = None
        self._cfg = None

    # -- recording -----------------------------------------------------------

    def snapshot(self, state, *, step: int, dt, length: int,
                 integ=None, cfg=None, alive=None) -> None:
        """Host-copy the pre-chunk state into the ring. Called by the
        driver BEFORE the (possibly donated) chunk consumes ``state`` —
        the copy is what makes recording donation-safe. Fleet chunks
        pass the (B,) per-lane dt vector and lane-alive mask."""
        import jax

        t0 = time.perf_counter()
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        paths, arrays = [], {}
        for path, leaf in flat:
            key = _path_str(path)
            paths.append(key)
            arrays[key] = np.asarray(jax.device_get(leaf))
        dt_val = float(dt) if np.ndim(dt) == 0 \
            else np.array(dt, dtype=np.float64)
        self.ring.append(ChunkSnapshot(
            step=int(step), dt=dt_val, length=int(length),
            paths=paths, arrays=arrays, treedef=treedef,
            wall_time=time.time(),
            alive=None if alive is None else np.array(alive, dtype=bool)))
        if integ is not None:
            self._integ = integ
        if cfg is not None:
            self._cfg = cfg
        self.snapshots += 1
        self.overhead_s += time.perf_counter() - t0

    def entry_for_step(self, step: Optional[int]) -> Optional[ChunkSnapshot]:
        """Newest ring entry whose chunk covers ``step`` (fallback: the
        newest entry — an incident always belongs to the last chunk
        started)."""
        for entry in reversed(self.ring):
            if entry.covers(step):
                return entry
        return self.ring[-1] if self.ring else None

    def restore(self, entry: ChunkSnapshot):
        """Device state rebuilt from a ring entry's host arrays."""
        import jax
        import jax.numpy as jnp

        leaves = [jnp.asarray(entry.arrays[k]) for k in entry.paths]
        return jax.tree_util.tree_unflatten(entry.treedef, leaves)

    # -- fingerprint ---------------------------------------------------------

    def fingerprint(self, driver=None) -> dict:
        """The run identity a replay must reproduce. JSON-safe."""
        import jax

        integ = driver.integ if driver is not None else self._integ
        cfg = driver.cfg if driver is not None else self._cfg
        cfg_dict = (_json_safe(dataclasses.asdict(cfg))
                    if dataclasses.is_dataclass(cfg) else {})
        digest = hashlib.sha256(
            json.dumps(cfg_dict, sort_keys=True).encode()).hexdigest()
        spec = self.spec if self.spec is not None \
            else describe_integrator(integ)
        try:
            from ibamr_tpu.solvers.escalation import precision_level_name
            fluid = getattr(integ, "ins", integ)
            sd = precision_level_name(
                getattr(fluid, "spectral_dtype", None))
        except Exception:
            sd = None
        engine, chain = self._engine_info(integ, spec)
        try:
            from tools.fault_injection import ACTIVE_INJECTORS
            injectors = _json_safe(dict(ACTIVE_INJECTORS))
        except Exception:
            injectors = {}
        audit = None
        sa = getattr(driver, "shadow_audit", None)
        if sa is not None:
            audit = sa.params()
        fp = {
            "config": cfg_dict, "config_digest": digest,
            "integrator": spec,
            "spectral_dtype": sd,
            "engine": engine, "engine_chain": chain,
            "jax_version": jax.__version__,
            "numpy_version": np.__version__,
            "device_count": jax.device_count(),
            "platform": jax.default_backend(),
            "mesh_shape": self.extra.get("mesh_shape"),
            # sharded runs stamp the full mesh spec (shape, axis names,
            # shard count) via ResilientDriver(mesh=...) so replay can
            # rebuild the sharded program — or knowingly degrade when
            # fewer devices are available than the incident ran on
            "mesh": self.extra.get("mesh"),
            "x64": bool(jax.config.jax_enable_x64),
            # the framework threads no RNG through the run loop; the
            # slot exists so stochastic physics can stamp its keys via
            # extra_fingerprint without a schema bump
            "rng_keys": self.extra.get("rng_keys"),
            "injectors": injectors,
            "audit": audit,
        }
        for k, v in self.extra.items():
            fp.setdefault(k, _json_safe(v))
        # canonical form: dict insertion order must never leak into
        # run_id / serving-cache digests
        return canonicalize(fp)

    def run_id(self, driver=None) -> str:
        """The 16-hex run identity the observability ledger stamps on
        every record — a digest of :meth:`fingerprint`, so the ledger,
        the incident capsules and the replay verdicts of one run all
        cross-reference by the same id."""
        from ibamr_tpu.obs import run_id_from_fingerprint
        return run_id_from_fingerprint(self.fingerprint(driver=driver))

    def observe(self, integ=None, cfg=None) -> None:
        """Bind integrator/config context for fingerprinting WITHOUT
        taking a ring snapshot — the serving cache keys entries on the
        fingerprint of an integrator it never runs through a driver."""
        if integ is not None:
            self._integ = integ
        if cfg is not None:
            self._cfg = cfg

    @staticmethod
    def _engine_info(integ, spec):
        """(engine label, fallback chain) actually in use, best-effort.
        The RESOLVED name stamped by the factory (``ib.engine_name``,
        post-auto-resolution and post-fallback) wins over the factory
        spec's alias — the fingerprint must describe what runs, not
        what was asked for."""
        label = None
        ib_resolved = getattr(getattr(integ, "ib", None),
                              "engine_name", None)
        if ib_resolved is not None:
            label = str(ib_resolved)
        if label is None and spec.get("kind") == "factory":
            kwargs = spec.get("kwargs", {})
            if "use_fast_interaction" in kwargs:
                label = _engine_label(kwargs["use_fast_interaction"])
        if label is None:
            ib = getattr(integ, "ib", None)
            fast = getattr(ib, "fast", None)
            if ib is not None:
                label = (type(fast).__name__ if fast is not None
                         else "scatter")
        if label is None:
            return None, None
        try:
            from ibamr_tpu.models.engine_resolver import fallback_chain
            return label, list(fallback_chain(label))
        except Exception:
            return label, None

    # -- capsule dump --------------------------------------------------------

    def dump_incident(self, *, directory: str, kind: str,
                      step: Optional[int] = None,
                      event: Optional[str] = None,
                      driver=None,
                      lane: Optional[int] = None) -> Optional[str]:
        """Write ``<directory>/<step>/replay.npz`` + ``manifest.json``
        for the newest ring entry covering ``step``. Returns the
        capsule directory (or None when the ring is empty). A second
        incident landing on the same chunk reuses the existing capsule
        (the state is identical; only the first dump pays).

        ``lane`` (fleet runs) slices the lane-stacked snapshot down to
        that lane's rows: the capsule is SINGLE-LANE (``-L<k>`` suffix
        on the directory), carries a ``lane`` manifest record with the
        original ``lane_index``/``fleet_size``, and replays unbatched —
        ``tools/replay.py`` re-executes it as a B=1 fleet chunk, the
        bitwise-equal solo form of the failing lane."""
        entry = self.entry_for_step(step)
        if entry is None:
            return None
        fleet = np.ndim(entry.dt) > 0
        suffix = "" if lane is None else f"-L{lane:03d}"
        cap_dir = os.path.join(directory, f"{entry.step:08d}{suffix}")
        manifest_path = os.path.join(cap_dir, "manifest.json")
        if os.path.exists(manifest_path):
            return cap_dir
        os.makedirs(cap_dir, exist_ok=True)
        npz_path = os.path.join(cap_dir, "replay.npz")
        if lane is not None:
            arrays = {k: np.ascontiguousarray(v[lane])
                      for k, v in entry.arrays.items()}
            chunk_dt = float(entry.dt[lane]) if fleet \
                else float(entry.dt)
        else:
            arrays = entry.arrays
            chunk_dt = [float(v) for v in entry.dt] if fleet \
                else entry.dt
        _atomic_write(npz_path, lambda f: np.savez(f, **arrays))
        post = None
        if driver is not None and kind != "stall":
            # a stalled chunk may hang again on re-execution — replay
            # of a stall capsule is interactive business, not dump-time
            post = self._post_digest(entry, driver, lane=lane)
        manifest = {
            "capsule_schema": CAPSULE_SCHEMA,
            "incident": {"kind": kind, "event": event,
                         "step": step},
            "chunk": {"start_step": entry.step, "length": entry.length,
                      "dt": chunk_dt},
            "state_file": "replay.npz",
            "leaf_order": entry.paths,
            "pre_leaf_crcs": {k: _leaf_crc(arrays[k])
                              for k in entry.paths},
            "post": post,
            "fingerprint": self.fingerprint(driver),
            "time": time.time(),
        }
        try:
            # a capsule dumped while serving names the request(s) whose
            # trace crosses it — the ledger timeline and the capsule
            # then cross-reference by trace_id, not just run_id
            from ibamr_tpu.obs import bus as _bus
            tids = _bus.current_trace()
            if tids:
                if len(tids) == 1:
                    manifest["trace_id"] = tids[0]
                else:
                    manifest["trace_ids"] = list(tids)
        except Exception:
            pass
        if lane is not None:
            fleet_size = (len(entry.dt) if fleet else
                          getattr(driver, "lanes", None))
            manifest["lane"] = {"index": int(lane),
                                "fleet_size": None if fleet_size is None
                                else int(fleet_size)}
        elif fleet:
            manifest["fleet"] = {
                "size": len(entry.dt),
                "alive": None if entry.alive is None
                else [bool(a) for a in entry.alive]}
        _atomic_write(manifest_path,
                      lambda f: f.write(json.dumps(
                          manifest, indent=1).encode()))
        self.dumps.append(cap_dir)
        return cap_dir

    def _post_digest(self, entry: ChunkSnapshot, driver,
                     lane: Optional[int] = None) -> Optional[dict]:
        """Per-leaf CRC32s + vitals of the state the recorded chunk
        produces, via ONE re-execution through the driver's own
        compiled chunk (cold path: incidents are rare by construction).
        For a lane capsule the digest is of the LANE'S slice of the
        fleet re-execution — bitwise what a B=1 replay must reproduce.
        The vitals are the chunk's health vector as it crosses to the
        host: a chunk that carries its transfer context ends it with
        its ``[refreshes, falls]`` tally. None when re-execution itself
        fails."""
        try:
            import jax.numpy as jnp

            state = self.restore(entry)
            if np.ndim(entry.dt) > 0:
                alive = entry.alive if entry.alive is not None \
                    else np.ones(len(entry.dt), dtype=bool)
                out, health = driver._chunk(entry.length)(
                    state, jnp.asarray(entry.dt), jnp.asarray(alive))
            else:
                out, health = driver._chunk(entry.length)(state, entry.dt)
            if lane is not None:
                import jax
                out = jax.tree_util.tree_map(lambda l: l[lane], out)
                h = np.asarray(health)
                vit = h[:, lane] if h.ndim == 2 else h[lane:lane + 1]
            else:
                vit = np.asarray(health).reshape(-1)
            arrays = _gather_arrays(out)
            return {
                "leaf_crcs": {k: _leaf_crc(v) for k, v in arrays.items()},
                "vitals": [float(v) for v in vit],
                "finite": bool(np.isfinite(
                    np.concatenate([np.asarray(v, dtype=np.float64).
                                    reshape(-1) for v in arrays.values()
                                    if np.issubdtype(v.dtype,
                                                     np.floating)])).all()),
            }
        except Exception:
            return None
