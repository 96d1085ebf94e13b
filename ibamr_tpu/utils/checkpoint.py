"""Checkpoint / restart of simulation state pytrees.

Reference parity: ``SAMRAI::tbox::RestartManager`` + per-object
``putToDatabase`` serialization to per-rank HDF5 (SURVEY.md §5.4). TPU-first
redesign: the ENTIRE simulation state is one functional pytree (grid arrays,
marker arrays, integrator scalars), so checkpointing is a single pytree
serialization — no object graph walking. Restarting on a different device
mesh re-shards on load (the analog of the reference's restart-on-different-
rank-count support).

Format: one ``.npz`` per checkpoint holding every leaf keyed by its pytree
path, plus a small JSON sidecar for metadata. No pickle anywhere.

Crash safety (the RestartManager durability contract, SURVEY.md §5.4):
every file lands via write-to-temp + ``fsync`` + ``os.replace``, so a
kill at ANY instant leaves either the previous complete checkpoint or
the new complete one — never a truncated ``restore.*.npz`` that
``latest_step`` would select and ``restore_checkpoint`` crash on. The
sidecar is written AFTER the array file and carries an integrity record
(per-leaf CRC32 plus a whole-file digest); a checkpoint is *verified*
iff its sidecar parses and the digests match. ``latest_step`` /
``restore_checkpoint`` skip unverified checkpoints and fall back to the
newest verified one, and ``_prune`` never deletes the last verified
checkpoint — so no sequence of crashes loses more than one checkpoint
interval (pinned by tests/test_resilience.py, including a SIGKILL-mid-
write subprocess drill).
"""

from __future__ import annotations

import json
import os
import re
import threading
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ibamr_tpu import obs as _obs


def _esc(s: str) -> str:
    # escape the path separator so dict keys containing '/' cannot collide
    # with genuine nesting ({"a/b": x} vs {"a": {"b": y}})
    return s.replace("%", "%25").replace("/", "%2F")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(_esc(str(p.key)))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(_esc(str(p.name)))
        elif isinstance(p, jax.tree_util.FlattenedIndexKey):
            parts.append(_esc(str(p.key)))
        else:
            parts.append(re.sub(r"[^\w]", "", str(p)))
    return "/".join(parts) if parts else "_root"


SCHEMA_VERSION = 1


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification (truncated file,
    flipped bytes, or a tampered/missing sidecar)."""


def state_schema(state: Any) -> Dict[str, Any]:
    """Schema fingerprint of a state pytree: every leaf's path, shape
    and dtype (the putToDatabase registry analog). Stored in the
    metadata sidecar so restore can DIAGNOSE refactored state layouts
    instead of silently orphaning old checkpoints (VERDICT round 1,
    weak #9)."""
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    return {
        "version": SCHEMA_VERSION,
        "leaves": {
            _path_str(p): [list(np.shape(l)),
                           str(getattr(l, "dtype", np.asarray(l).dtype))]
            for p, l in leaves},
    }


def _schema_diff(stored: Dict[str, Any], current: Dict[str, Any]) -> str:
    s_leaves = stored.get("leaves", {})
    c_leaves = current["leaves"]
    lines = []
    for k in sorted(set(s_leaves) - set(c_leaves)):
        lines.append(f"  checkpoint-only leaf: {k} {s_leaves[k]}")
    for k in sorted(set(c_leaves) - set(s_leaves)):
        lines.append(f"  template-only leaf:   {k} {c_leaves[k]}")
    for k in sorted(set(c_leaves) & set(s_leaves)):
        if s_leaves[k][0] != c_leaves[k][0]:
            lines.append(f"  shape mismatch at {k}: checkpoint "
                         f"{s_leaves[k][0]} vs template {c_leaves[k][0]}")
        elif _dtype_kind(s_leaves[k][1]) != _dtype_kind(c_leaves[k][1]):
            # width changes (f64 checkpoint -> f32 run) are a supported
            # cast; KIND changes (float -> int) are a refactor
            lines.append(f"  dtype-kind mismatch at {k}: checkpoint "
                         f"{s_leaves[k][1]} vs template {c_leaves[k][1]}")
    return "\n".join(lines)


def _dtype_kind(name: str) -> str:
    """numpy kind, with ml_dtypes extensions (bfloat16 etc., numpy kind
    'V') classified as floating so f32 <-> bf16 restarts stay legal."""
    import jax.numpy as jnp

    try:
        if jnp.issubdtype(jnp.dtype(name), jnp.floating):
            return "f"
    except TypeError:
        pass
    return np.dtype(name).kind


def _gather_arrays(state: Any) -> Dict[str, np.ndarray]:
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    return {_path_str(path): np.asarray(jax.device_get(leaf))
            for path, leaf in leaves}


def _leaf_crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _file_crc(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as f:
        while True:
            buf = f.read(chunk)
            if not buf:
                break
            crc = zlib.crc32(buf, crc)
    return crc & 0xFFFFFFFF


def _fsync_dir(directory: str) -> None:
    # durability of the os.replace itself (a crash after replace but
    # before the directory entry hits disk could resurrect the old name)
    try:
        fd = os.open(directory, os.O_RDONLY)
    except OSError:
        return                      # e.g. non-POSIX fs; best effort
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _atomic_write(path: str, write_fn) -> None:
    """Write via temp name + fsync + os.replace: the file at ``path``
    is always either absent, the old complete version, or the new
    complete version — never torn. The temp name is pid- AND
    thread-unique: two concurrent writers of the same path (the
    sharded collision drill; an async writer racing a sync preemption
    save) must each write their own temp, or they interleave into one
    file and the LAST replace publishes torn bytes."""
    _atomic_write_digest(path, write_fn)


def _atomic_write_digest(path: str, write_fn):
    """:func:`_atomic_write` that also returns ``(crc32, size)`` of the
    written bytes — computed from the PRIVATE temp file BEFORE the
    replace. Re-reading the published path after ``os.replace`` races
    concurrent writers of the same path: the digest of whoever
    replaced LAST would land in THIS writer's integrity record, and
    that mixed record can pass whole-file verification while the
    per-leaf digests disagree (caught by the sharded collision
    drill)."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        crc = _file_crc(tmp)
        size = os.path.getsize(tmp)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    _fsync_dir(os.path.dirname(path) or ".")
    return crc, size


def _write_arrays(directory: str, arrays: Dict[str, np.ndarray],
                  schema: Dict[str, Any], step: int,
                  metadata: Optional[Dict[str, Any]], keep: int,
                  lanes: Optional[int] = None) -> str:
    os.makedirs(directory, exist_ok=True)
    fname = os.path.join(directory, f"restore.{step:08d}.npz")
    npz_crc, npz_size = _atomic_write_digest(
        fname, lambda f: np.savez(f, **arrays))
    meta = dict(metadata or {})
    meta["step"] = step
    meta["schema"] = schema
    # integrity record: per-leaf CRCs catch in-file tampering down to
    # the leaf; the whole-file digest makes verification a single
    # sequential read. The digest comes from the temp file BEFORE the
    # replace (never re-read the published path: a concurrent writer's
    # bytes could land there in between), and the sidecar is written
    # AFTER the npz replace, so a complete sidecar implies a complete
    # array file (the commit marker).
    meta["integrity"] = {
        "leaves": {k: _leaf_crc(v) for k, v in arrays.items()},
        "npz_crc32": npz_crc,
        "npz_size": npz_size,
    }
    if lanes is not None:
        # lane-axis extension (fleet checkpoints): per-lane CRC32 of
        # every lane-stacked leaf's rows, so one corrupt lane's slice
        # is diagnosed (and every OTHER lane stays restorable via
        # restore_lane) instead of condemning the whole step
        meta["integrity"]["lanes"] = {
            "count": int(lanes),
            "leaves": {k: [_leaf_crc(v[i]) for i in range(int(lanes))]
                       for k, v in arrays.items()
                       if v.ndim >= 1 and v.shape[0] == int(lanes)},
        }
    payload = json.dumps(meta).encode()
    _atomic_write(fname.replace(".npz", ".json"),
                  lambda f: f.write(payload))
    _prune(directory, keep)
    return fname


def _read_sidecar(directory: str, step: int) -> Optional[Dict[str, Any]]:
    """Parse the sidecar; None if absent or torn (invalid JSON)."""
    path = os.path.join(directory, f"restore.{step:08d}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def verify_checkpoint(directory: str, step: int) -> bool:
    """True iff step's checkpoint is complete and intact: the sidecar
    parses and the array file matches its recorded size and whole-file
    CRC32. Legacy sidecars (written before the integrity record
    existed) are accepted — they predate atomic writes but refusing
    them would orphan every pre-upgrade run."""
    fname = os.path.join(directory, f"restore.{step:08d}.npz")
    if not os.path.exists(fname):
        return False
    meta = _read_sidecar(directory, step)
    if meta is None:
        return False
    integ = meta.get("integrity")
    if integ is None:
        return True                 # legacy checkpoint: trusted as-is
    try:
        if os.path.getsize(fname) != integ.get("npz_size"):
            return False
        return _file_crc(fname) == integ.get("npz_crc32")
    except OSError:
        return False


def save_checkpoint(directory: str, state: Any, step: int,
                    metadata: Optional[Dict[str, Any]] = None,
                    keep: int = 3,
                    lanes: Optional[int] = None) -> str:
    """Serialize a state pytree. Returns the checkpoint file path.
    ``lanes`` (fleet runs) records per-lane leaf CRCs in the sidecar so
    :func:`restore_lane` can salvage healthy lanes from a step whose
    file is damaged elsewhere."""
    # device->host copy and file write, apart (spans in obs's ring and
    # on a profiler capture's timeline)
    with _obs.span("checkpoint/fetch", step=step):
        arrays = _gather_arrays(state)
    with _obs.span("checkpoint/commit", step=step):
        return _write_arrays(directory, arrays, state_schema(state),
                             step, metadata, keep, lanes=lanes)


def _all_steps(directory: str) -> list:
    steps = []
    for f in os.listdir(directory):
        m = re.fullmatch(r"restore\.(\d+)\.npz", f)
        if m:
            steps.append(int(m.group(1)))
    return sorted(steps)


def _prune(directory: str, keep: int) -> None:
    # stale temp files are debris from a killed writer (a *different*
    # process: our own pid's temps are live in the async worker);
    # names are ``<path>.tmp.<pid>.<tid>`` (legacy debris may lack <tid>)
    for f in os.listdir(directory):
        m = re.search(r"\.tmp\.(\d+)(?:\.\d+)?$", f)
        if m and int(m.group(1)) != os.getpid():
            try:
                os.remove(os.path.join(directory, f))
            except OSError:
                pass
    if keep <= 0:
        return
    steps = _all_steps(directory)
    doomed = steps[:-keep]
    if not doomed:
        return
    # the newest VERIFIED checkpoint is sacrosanct: if every younger
    # checkpoint is corrupt, deleting it would leave nothing to roll
    # back to — prune must never shorten the recovery chain to zero
    last_verified = next((s for s in reversed(steps)
                          if verify_checkpoint(directory, s)), None)
    for s in doomed:
        if s == last_verified:
            continue
        os.remove(os.path.join(directory, f"restore.{s:08d}.npz"))
        side = os.path.join(directory, f"restore.{s:08d}.json")
        if os.path.exists(side):
            os.remove(side)


def latest_step(directory: str,
                verified_only: bool = True) -> Optional[int]:
    """Newest restorable step. With ``verified_only`` (the default)
    corrupt or sidecar-less checkpoints are skipped — the answer is the
    newest checkpoint :func:`verify_checkpoint` vouches for, never a
    truncated file a crash left behind."""
    if not os.path.isdir(directory):
        return None
    steps = _all_steps(directory)
    if not verified_only:
        return steps[-1] if steps else None
    return next((s for s in reversed(steps)
                 if verify_checkpoint(directory, s)), None)


class AsyncCheckpointWriter:
    """Asynchronous checkpoint writes (S6 parallel-I/O completion):
    the disk write runs on a single worker thread, overlapping with the
    next compute steps — the TPU analog of the reference's parallel
    HDF5 dumps off the critical path.

    The device->host gather happens SYNCHRONOUSLY inside ``save``:
    deferring it to the worker would read buffers that a
    donate_argnums step (bench.py's pattern) has already invalidated.
    The gather is the cheap part (HBM->host DMA); the write is what
    overlaps. One worker keeps writes ordered; a failed write surfaces
    ONCE on the next ``save``/``wait`` and is then dropped (a
    checkpoint failure must not poison the rest of the run).

    The pending queue is BOUNDED: each queued save pins a full host
    copy of the state, so an unbounded burst of ``save`` calls against
    a slow disk queues arbitrary host memory. At ``max_pending``
    outstanding writes, ``overflow="block"`` (default) applies
    backpressure — ``save`` waits for the oldest write to land first —
    while ``overflow="drop"`` sheds the NEW save and counts it in
    ``dropped_saves`` (checkpoints are periodic: a dropped one widens
    the recovery interval, it cannot corrupt anything). Current
    backlog is ``queue_depth()``, surfaced in the watchdog heartbeat
    by :class:`~ibamr_tpu.utils.supervisor.ResilientDriver`.

    Usage::

        w = AsyncCheckpointWriter(rst_dir, keep=3)
        ...
        w.save(state, step)        # returns immediately
        ...
        w.wait()                   # drain before exit / restart
    """

    def __init__(self, directory: str, keep: int = 3,
                 max_pending: int = 2, overflow: str = "block",
                 lanes: Optional[int] = None):
        from concurrent.futures import ThreadPoolExecutor

        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        if overflow not in ("block", "drop"):
            raise ValueError("overflow must be 'block' or 'drop'")
        self.directory = directory
        self.keep = keep
        self.max_pending = max_pending
        self.overflow = overflow
        self.lanes = lanes
        self.dropped_saves = 0
        self._exec = ThreadPoolExecutor(max_workers=1)
        self._pending = []

    def queue_depth(self) -> int:
        """Writes enqueued but not yet finished (each pins one host
        copy of the state). Completed futures stay in ``_pending`` so
        ``_raise_finished`` still surfaces their failures."""
        return sum(1 for f in self._pending if not f.done())

    def _raise_finished(self):
        # drop completed futures FIRST so a raised failure is reported
        # exactly once and never blocks later saves/close
        done = [f for f in self._pending if f.done()]
        self._pending = [f for f in self._pending if not f.done()]
        for f in done:
            f.result()              # re-raise the worker failure here

    @staticmethod
    def _write_with_retry(directory, arrays, schema, step, metadata,
                          keep, lanes=None):
        # one retry before surfacing: a transient fs hiccup (NFS blip,
        # ENOSPC race with the pruner) must not cost the interval —
        # the atomic-replace protocol makes the retry idempotent.
        # `_write_arrays` is looked up per call so fault injection
        # (tools.fault_injection.failing_checkpoint_writes) sees both
        # attempts.
        import time as _time

        t0 = _time.perf_counter()
        try:
            try:
                return _write_arrays(directory, arrays, schema, step,
                                     metadata, keep, lanes=lanes)
            except Exception:
                return _write_arrays(directory, arrays, schema, step,
                                     metadata, keep, lanes=lanes)
        finally:
            _obs.histogram("ckpt_commit_seconds",
                           writer="single").observe(
                _time.perf_counter() - t0)

    def save(self, state: Any, step: int,
             metadata: Optional[Dict[str, Any]] = None):
        """Gather and enqueue one checkpoint write. Returns the write
        future, or ``None`` when the save was shed under
        ``overflow="drop"`` backlog."""
        self._raise_finished()
        if self.queue_depth() >= self.max_pending:
            if self.overflow == "drop":
                self.dropped_saves += 1
                _obs.counter("ckpt_dropped_saves_total",
                             writer="single").inc()
                return None
            # backpressure: the oldest pending write must land before
            # this save may pin another host copy of the state; wait
            # without .result() so _raise_finished surfaces a failure
            # exactly once
            import concurrent.futures as _cf
            oldest = next((f for f in self._pending if not f.done()),
                          None)
            if oldest is not None:
                _cf.wait([oldest])
            self._raise_finished()
        arrays = _gather_arrays(state)      # sync: donation-safe
        schema = state_schema(state)
        fut = self._exec.submit(self._write_with_retry, self.directory,
                                arrays, schema, step, metadata,
                                self.keep, self.lanes)
        self._pending.append(fut)
        _obs.gauge("ckpt_queue_depth",
                   writer="single").set(self.queue_depth())
        return fut

    def wait(self) -> None:
        """Block until every enqueued checkpoint is on disk (re-raises
        the first worker failure; failed futures are dropped)."""
        pending, self._pending = self._pending, []
        for f in pending:
            f.result()

    def close(self) -> None:
        try:
            self.wait()
        finally:
            self._exec.shutdown(wait=True)


def restore_checkpoint(directory: str, template: Any,
                       step: Optional[int] = None,
                       sharding_fn=None):
    """Restore a state pytree.

    ``template`` is a pytree with the same structure (e.g. a freshly
    initialized state); its leaves supply structure, dtype and (if the
    stored array disagrees in dtype) the cast target. ``sharding_fn``, if
    given, maps (path_str, np_array) -> jax.Array for re-sharding onto a
    possibly different device mesh.

    With ``step=None`` the newest VERIFIED checkpoint is restored:
    corrupt or sidecar-less checkpoints (what a kill mid-write leaves
    behind) are skipped with a warning, falling back through older
    checkpoints until one loads. An explicit ``step`` raises
    :class:`CheckpointCorruptError` if that checkpoint fails
    verification. Schema mismatches (a refactored state layout) raise
    ``ValueError`` in both modes — that is a diagnosis, not corruption.

    Returns (state, step, metadata).
    """
    with _obs.span("checkpoint/restore", step=step):
        return _restore_checkpoint(directory, template, step, sharding_fn)


def _restore_checkpoint(directory, template, step, sharding_fn):
    if step is not None:
        fname = os.path.join(directory, f"restore.{step:08d}.npz")
        if not os.path.exists(fname):
            raise FileNotFoundError(fname)
        if not verify_checkpoint(directory, step):
            raise CheckpointCorruptError(
                f"checkpoint {fname} failed integrity verification "
                f"(truncated/corrupt file or missing sidecar)")
        return _load_step(directory, step, template, sharding_fn)

    steps = _all_steps(directory) if os.path.isdir(directory) else []
    if not steps:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    import warnings

    for s in reversed(steps):
        if not verify_checkpoint(directory, s):
            warnings.warn(
                f"skipping unverified checkpoint step {s} in "
                f"{directory} (corrupt or sidecar-less — a crash "
                f"mid-write leaves exactly this)")
            continue
        try:
            return _load_step(directory, s, template, sharding_fn)
        except CheckpointCorruptError as e:
            warnings.warn(f"skipping checkpoint step {s}: {e}")
    raise FileNotFoundError(
        f"no verified checkpoints in {directory} "
        f"({len(steps)} candidate(s), all corrupt)")


def _load_step(directory: str, step: int, template: Any, sharding_fn):
    fname = os.path.join(directory, f"restore.{step:08d}.npz")
    data = np.load(fname)
    metadata: Dict[str, Any] = _read_sidecar(directory, step) or {}
    leaf_crcs = (metadata.get("integrity") or {}).get("leaves", {})

    paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    # schema validation: a refactored state NamedTuple produces a clear
    # named diff instead of a bare missing-key error deep in the loop
    stored_schema = metadata.get("schema")
    if stored_schema is not None:
        diff = _schema_diff(stored_schema, state_schema(template))
        if diff:
            raise ValueError(
                f"checkpoint {fname} was written with an incompatible "
                f"state schema (version "
                f"{stored_schema.get('version', '?')}):\n{diff}")

    new_leaves = []
    for path, leaf in paths_and_leaves:
        key = _path_str(path)
        if key not in data:
            raise KeyError(f"checkpoint {fname} missing leaf {key!r}")
        arr = data[key]
        if key in leaf_crcs and _leaf_crc(arr) != leaf_crcs[key]:
            raise CheckpointCorruptError(
                f"checkpoint {fname}: leaf {key!r} fails its recorded "
                f"CRC32 — the array file and sidecar disagree")
        tgt_dtype = getattr(leaf, "dtype", None)
        if tgt_dtype is not None and arr.dtype != tgt_dtype:
            arr = arr.astype(tgt_dtype)
        if sharding_fn is not None:
            new_leaves.append(sharding_fn(key, arr))
        elif hasattr(leaf, "sharding"):
            # an UNCOMMITTED template leaf (the fresh single-device
            # state) must restore uncommitted: a committed argument
            # lowers with sharding annotations, i.e. to a different
            # program than the fresh run compiled — the restart would
            # miss the compile cache and pay the whole compile again
            # (130 s at the flagship, PR 23 chip run)
            new_leaves.append(
                jax.device_put(arr, leaf.sharding)
                if getattr(leaf, "committed", True) else jnp.asarray(arr))
        else:
            new_leaves.append(arr)
    state = jax.tree_util.tree_unflatten(treedef, new_leaves)
    return state, step, metadata


def restore_lane(directory: str, template: Any, lane: int,
                 step: Optional[int] = None):
    """Restore ONE lane's slice from a lane-axis checkpoint into
    ``template`` (the current lane-stacked fleet state).

    Only lane ``lane``'s rows are touched — every other lane's rows of
    ``template`` are returned bitwise-untouched, which is what makes a
    per-lane rollback safe for the healthy lanes. Verification is
    per-lane: the sidecar's ``integrity.lanes`` record (written when
    checkpoints are saved with ``lanes=``) lets a step whose file is
    corrupt in ANOTHER lane's rows still serve this lane, so one bad
    lane's corruption cannot widen its neighbours' recovery interval.
    Pre-lane sidecars (no ``integrity.lanes``) fall back to whole-leaf
    CRCs.

    Walks newest -> oldest (or only ``step`` when given) and returns
    ``(patched_state, checkpoint_step)``; ``None`` when no checkpoint
    can vouch for this lane (caller falls back to the initial state).
    """
    if not os.path.isdir(directory):
        return None
    steps = [step] if step is not None else \
        list(reversed(_all_steps(directory)))
    import warnings

    for s in steps:
        try:
            return _load_lane_step(directory, s, template, lane), s
        except (CheckpointCorruptError, KeyError, ValueError,
                OSError) as e:
            warnings.warn(
                f"restore_lane: skipping step {s} for lane {lane}: {e}")
    return None


def _load_lane_step(directory: str, step: int, template: Any,
                    lane: int):
    fname = os.path.join(directory, f"restore.{step:08d}.npz")
    if not os.path.exists(fname):
        raise FileNotFoundError(fname)
    meta = _read_sidecar(directory, step)
    if meta is None:
        raise CheckpointCorruptError(
            "sidecar missing or unparseable (torn write)")
    integ = meta.get("integrity") or {}
    lane_rec = integ.get("lanes")
    if lane_rec is not None and lane >= int(lane_rec.get("count", 0)):
        raise ValueError(
            f"lane {lane} out of range for fleet of "
            f"{lane_rec.get('count')}")
    lane_crcs = (lane_rec or {}).get("leaves", {})
    leaf_crcs = integ.get("leaves", {})

    data = np.load(fname)
    paths_and_leaves, treedef = \
        jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for path, leaf in paths_and_leaves:
        key = _path_str(path)
        if key not in data:
            raise KeyError(f"checkpoint {fname} missing leaf {key!r}")
        arr = data[key]
        if arr.shape != tuple(np.shape(leaf)):
            raise ValueError(
                f"checkpoint {fname}: leaf {key!r} shape {arr.shape} "
                f"!= fleet state shape {tuple(np.shape(leaf))}")
        if arr.ndim < 1 or lane >= arr.shape[0]:
            raise ValueError(
                f"checkpoint {fname}: leaf {key!r} has no lane {lane}")
        sl = arr[lane]
        if key in lane_crcs:
            rec = lane_crcs[key]
            if lane >= len(rec) or _leaf_crc(np.asarray(sl)) != \
                    int(rec[lane]):
                raise CheckpointCorruptError(
                    f"checkpoint {fname}: lane {lane} of leaf {key!r} "
                    f"fails its recorded per-lane CRC32")
        elif key in leaf_crcs and _leaf_crc(arr) != leaf_crcs[key]:
            # pre-lane sidecar: the whole leaf must verify
            raise CheckpointCorruptError(
                f"checkpoint {fname}: leaf {key!r} fails its recorded "
                f"CRC32 and carries no per-lane record")
        tgt_dtype = getattr(leaf, "dtype", None)
        if tgt_dtype is not None and sl.dtype != tgt_dtype:
            sl = sl.astype(tgt_dtype)
        new_leaves.append(jnp.asarray(leaf).at[lane].set(sl))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)
