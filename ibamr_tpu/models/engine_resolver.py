"""The transfer engines: which exist, how each is built, what it
degrades to, and which one ``auto`` means.

One table, :data:`ENGINES`, has a row per engine name. Every list of
engine names elsewhere (the input key ``IBMethod { transfer_engine }``,
the ``use_fast_interaction`` keyword of ``build_shell_example``, the
``IBAMR_TRANSFER_ENGINE`` override, the tuning DB's ``engine`` field,
the autotuner's menu and probe set, the degradation chain of
docs/RESILIENCE.md) is read from it. ``ops/`` holds the engines and
knows none of their names.

``build_shell_example(use_fast_interaction=None)`` ("auto") resolves
here. The serving cache (ibamr_tpu/serve/aot_cache.py) keys
executables on the RESOLVED engine, and the measured-search autotuner
(ibamr_tpu/tune/, docs/TUNING.md) publishes winners here:

1. ``IBAMR_TRANSFER_ENGINE`` env var: an explicit operator override
   (validated against the engine vocabulary; ``"auto"``/empty defers).
2. A JSON tuning database: ``IBAMR_TUNING_DB`` env var when set (the
   values ``none``/``off``/``0`` disable DB lookup entirely), else the
   committed ``TUNING_DB.json`` at the repo root when it exists.
   Schema v1 (``{"schema": 1, "entries": [...]}``; the legacy
   schema-less ``{"entries": [...]}`` form is still read). Entries
   match on grid shape, marker count, spectral dtype, platform and
   chunk length; the MOST SPECIFIC match wins, with file order as the
   deterministic tiebreak (earlier wins at equal specificity)::

       {"schema": 1, "entries": [
         {"engine": "packed_bf16", "n": [256, 256, 256],
          "platform": "tpu", "spectral_dtype": "f32",
          "provenance": {"platform": "tpu"}},
         {"engine": "packed", "markers_min": 4096}
       ]}

   Recognized match fields (all optional; an entry with none matches
   everything): ``n_cells`` (exact cubic extent), ``n`` (exact grid
   list), ``markers_min`` / ``markers_max`` (inclusive marker-count
   band), ``spectral_dtype`` (the fluid transform precision knob),
   ``platform`` (jax backend name), ``chunk_length`` (scan chunk
   length — only matched when the caller resolves for a specific
   length; a pinned field the query does not supply does NOT match).
   An entry whose ``provenance.platform`` differs from the current
   backend is SKIPPED silently — a CPU-measured winner can never steer
   a TPU run, and the committed TPU-measured defaults fall through to
   the heuristic on the CPU test backend.
3. The built-in heuristic: the round-5 promotion (occupancy-packed
   when the grid is tile-divisible and the marker count is large
   enough to matter; scatter otherwise).

The resolver returns a RESOLVED engine name — never ``"auto"`` — so the
flight-recorder fingerprint and the serving cache key always reflect
what actually runs. A bad override or a corrupt tuning DB raises at
build time (fail-fast: a typo'd engine name must die here, not silently
fall back and poison a cache key). DB consultations are counted on the
telemetry bus (``tuning_db_{hits,fallbacks,provenance_skips}_total``)
so `tools/obs.py summary` can report hit/fallback efficacy per run.
"""

from __future__ import annotations

import json
import os
import warnings
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

from ibamr_tpu import obs as _obs


def _overflow_cap(vertices) -> int:
    return max(2048, vertices.shape[0] // 4)


def _build_mxu(grid, vertices, kernel):
    from ibamr_tpu.ops.interaction_fast import FastInteraction, suggest_cap
    # pole-clustered tiles overflow into the compact scatter path; keep
    # the dense per-tile capacity bounded so padding FLOPs stay sane
    # (the packed layouts size chunks instead)
    cap = min(suggest_cap(grid, vertices, kernel=kernel, tile=8,
                          slack=1.2), 1024)
    return FastInteraction(grid, kernel=kernel, tile=8, cap=cap,
                           overflow_cap=_overflow_cap(vertices))


def _packed_layout(grid, vertices, kernel) -> dict:
    from ibamr_tpu.ops.interaction_packed import suggest_chunks
    return dict(kernel=kernel, tile=8, chunk=128,
                nchunks=suggest_chunks(grid, vertices, kernel=kernel,
                                       tile=8, chunk=128, slack=1.3),
                overflow_cap=_overflow_cap(vertices))


def _build_packed(grid, vertices, kernel, bf16=False):
    import jax.numpy as jnp

    from ibamr_tpu.ops.interaction_packed import PackedInteraction
    return PackedInteraction(
        grid, **_packed_layout(grid, vertices, kernel),
        compute_dtype=jnp.bfloat16 if bf16 else None)


def _build_pallas_packed(grid, vertices, kernel):
    from ibamr_tpu.ops.pallas_interaction import PallasPackedInteraction
    return PallasPackedInteraction(
        grid, **_packed_layout(grid, vertices, kernel))


def _build_hybrid_bf16(grid, vertices, kernel):
    import jax.numpy as jnp

    from ibamr_tpu.ops.pallas_interaction import HybridPackedInteraction
    return HybridPackedInteraction(
        grid, **_packed_layout(grid, vertices, kernel),
        compute_dtype=jnp.bfloat16)


class EngineRow(NamedTuple):
    """``build(grid, vertices, kernel)`` constructs the engine (None
    for the scatter/gather path of IBMethod itself; engine modules are
    imported only when their row is built). ``fallback`` is the next
    link of the degradation chain: a row whose construction or compile
    fails gives way to it, trading measured speed for availability.
    ``probed`` rows get a build-time compile probe under
    ``probe="auto"``: the Pallas-backed ones, whose Mosaic lowering is
    what a chip's compiler has refused in the field; probing plain-XLA
    rows would tax every build for a failure never observed."""
    build: Callable
    fallback: Optional[str]
    probed: bool = False


# THE table. ``scatter`` is the oracle every other row is tested
# against and the end of every fallback chain. "auto" is deliberately
# absent: resolution must terminate in a row.
ENGINES = {
    "scatter": EngineRow(lambda grid, vertices, kernel: None, None),
    "mxu": EngineRow(_build_mxu, "scatter"),
    "packed": EngineRow(_build_packed, "scatter"),
    "packed_bf16": EngineRow(partial(_build_packed, bf16=True), "packed"),
    "pallas_packed": EngineRow(_build_pallas_packed, "packed",
                               probed=True),
    "hybrid_bf16": EngineRow(_build_hybrid_bf16, "packed_bf16",
                             probed=True),
}

RESOLVED_ENGINES = tuple(ENGINES)
PROBED_ENGINES = frozenset(k for k, row in ENGINES.items() if row.probed)


def normalize_engine_name(name) -> str:
    """Map the ``use_fast_interaction`` vocabulary (True/False/str) to
    a row name of :data:`ENGINES`."""
    if name is True:
        return "mxu"
    if name is False or name is None:
        return "scatter"
    return str(name).lower()


def _validate(name: str, source: str) -> str:
    if name not in ENGINES:
        raise ValueError(
            f"{source}: unknown transfer engine {name!r}; expected one "
            f"of {RESOLVED_ENGINES}")
    return name


def fallback_chain(name) -> list:
    """The degradation order starting AT ``name`` (inclusive), ending
    at "scatter". Raises KeyError for unknown engine names."""
    chain = [normalize_engine_name(name)]
    while ENGINES[chain[-1]].fallback is not None:
        chain.append(ENGINES[chain[-1]].fallback)
    return chain


def construct_transfer_engine(name, grid, vertices, kernel: str):
    """Construct the named transfer engine against ``grid`` for a
    structure with marker positions ``vertices``. ``name`` uses the
    ``use_fast_interaction`` vocabulary (True/False/str); "scatter"
    returns None (the IBMethod scatter/gather path). Raises on
    unsatisfiable geometry (a grid the 8-tile does not divide);
    :func:`build_engine_with_fallback` turns such failures into
    degradation instead of death."""
    name = _validate(normalize_engine_name(name), "construct_transfer_engine")
    return ENGINES[name].build(grid, vertices, kernel)


def probe_transfer_engine(fast, vertices) -> None:
    """Trace AND compile (without executing) a bucket + spread +
    interp composition at the real marker shapes — the cheap stand-in
    for 'does this engine's first step survive': trace-time failures
    (a monkeypatched or buggy engine method) and XLA/Mosaic compile
    failures (the round-2 Pallas remote-compile stall) both surface
    here, at build time, where degradation is still possible."""
    if fast is None:
        return
    import jax
    import jax.numpy as jnp

    X = jnp.asarray(vertices)
    F = jnp.zeros_like(X)

    def fn(F, X):
        b = fast.buckets(X)
        g = fast.spread_vel(F, X, b=b)
        return fast.interpolate_vel(g, X, b=b)

    jax.jit(fn).lower(F, X).compile()


def build_engine_with_fallback(name, grid, vertices, kernel: str,
                               probe="auto"):
    """Construct ``name``'s transfer engine, degrading down its
    :func:`fallback_chain` when construction or compile fails: each
    failure logs a warning naming the failed engine and its
    replacement, counts on ``engine_fallbacks_total{engine,to}`` (the
    warning tells a human once, the counter shows the degradation in
    every later ledger snapshot), and the run continues on the next
    engine instead of dying. ``probe`` is True / False / "auto" (probe
    only the rows marked ``probed``). The terminal "scatter" link
    cannot fail (engine None). Returns ``(engine_or_None,
    engine_name)``."""
    chain = fallback_chain(name)
    for eng_name, nxt in zip(chain, chain[1:] + [None]):
        try:
            fast = construct_transfer_engine(eng_name, grid, vertices,
                                             kernel)
            if probe is True or (probe == "auto"
                                 and eng_name in PROBED_ENGINES):
                probe_transfer_engine(fast, vertices)
            return fast, eng_name
        except Exception as e:
            if nxt is None:
                raise
            _obs.counter("engine_fallbacks_total", engine=eng_name,
                         to=nxt).inc()
            warnings.warn(
                f"transfer engine {eng_name!r} failed to "
                f"build/compile ({type(e).__name__}: {e}); degrading "
                f"to {nxt!r}", RuntimeWarning)


# -- which engine "auto" means ------------------------------------------------

ENV_ENGINE = "IBAMR_TRANSFER_ENGINE"
ENV_TUNING_DB = "IBAMR_TUNING_DB"

# IBAMR_TUNING_DB sentinel values that disable DB lookup (including
# the committed default DB)
DB_DISABLE_VALUES = ("none", "off", "0")

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
DEFAULT_DB_PATH = os.path.join(REPO_ROOT, "TUNING_DB.json")

DB_SCHEMA = 1

# match-field specificity weights: an exact grid list outranks a cubic
# extent; every other pinned field counts 1. The sum is the entry's
# specificity score; most-specific-match-wins with file order breaking
# ties (earlier wins) — deterministic, never first-match-in-file-order
# (overlapping entries used to silently shadow each other).
MATCH_FIELDS = ("n_cells", "n", "markers_min", "markers_max",
                "spectral_dtype", "platform", "chunk_length")
_FIELD_WEIGHT = {"n": 2}

_HITS = _obs.counter("tuning_db_hits_total")
_FALLBACKS = _obs.counter("tuning_db_fallbacks_total")
_PROV_SKIPS = _obs.counter("tuning_db_provenance_skips_total")


def default_rule(n: Sequence[int], n_markers: int, support: int) -> str:
    """The built-in promotion: auto requires tile divisibility AND the
    make_geometry minimum extent (tile + support + 1) so small grids
    fall back to the scatter path instead of raising (ADVICE round 1).
    Round 5: auto picks the occupancy-PACKED engine — the on-chip
    shootout measured it 2.6x the bucketed-MXU engine at 256^3 (9.19
    vs 3.53 steps/s) and 4.2x at 128^3, roundoff-exact vs the scatter
    oracle (bf16 compression stays opt-in: exactness is the default
    contract).

    The rule sees extents, marker count and kernel support, nothing of
    the cloud's shape: a non-cubic grid (the packed layout tiles every
    axis but the last, so only ``n[:-1]`` has to divide) carrying a
    cloud that is no shell (a solid ball of volumetric ConstraintIB
    markers, which fills its tiles where a shell leaves most empty)
    resolves to ``packed`` all the same, an EXACT float32 engine; its
    chunk count is then sized from the concrete cloud at build time
    (``suggest_chunks``). The tuning DB's ``packed_bf16`` row pins the
    whole cubic ``n`` and a shell's marker band, so a 160 x 160 x 256
    tank with 58k markers does not match it by its 256-wide last
    axis."""
    eligible = (
        n_markers >= 4096
        and all(v % 8 == 0 for v in n[:-1])
        and all(v >= 8 + support + 1 for v in n[:-1]))
    return "packed" if eligible else "scatter"


def normalize_spectral_dtype(value) -> str:
    """Canonical spectral-dtype token for matching: ``None`` means the
    full-precision default ("f32")."""
    return str(value).strip().lower() if value else "f32"


# parsed-DB cache keyed on (path, mtime) — resolve_engine runs once per
# build, but the serving router builds many pools per process
_db_cache: dict = {}


def load_tuning_db(path: str) -> list:
    """Entries of a tuning-DB file; raises on unreadable/malformed input
    (a configured-but-broken DB is an error, not a silent fallback).
    Accepts schema v1 (``{"schema": 1, "entries": [...]}``) and the
    legacy schema-less form."""
    try:
        mtime = os.path.getmtime(path)
        cached = _db_cache.get(path)
        if cached is not None and cached[0] == mtime:
            return cached[1]
    except OSError:
        mtime = None
    with open(path) as f:
        doc = json.load(f)
    schema = doc.get("schema")
    if schema is not None and schema != DB_SCHEMA:
        raise ValueError(
            f"tuning DB {path}: unknown schema {schema!r} "
            f"(this build reads schema {DB_SCHEMA})")
    entries = doc.get("entries")
    if not isinstance(entries, list):
        raise ValueError(
            f"tuning DB {path}: expected a top-level 'entries' list")
    if mtime is not None:
        _db_cache[path] = (mtime, entries)
    return entries


def entry_specificity(entry: dict) -> int:
    """Specificity score: the weighted count of pinned match fields
    (``n`` counts double — an exact grid list is more specific than a
    cubic extent). Ties resolve to file order (earlier wins)."""
    return sum(_FIELD_WEIGHT.get(f, 1) for f in MATCH_FIELDS
               if entry.get(f) is not None)


def entry_matches(entry: dict, n: Sequence[int], n_markers: int,
                  spectral_dtype: Optional[str] = None,
                  platform: Optional[str] = None,
                  chunk_length: Optional[int] = None) -> bool:
    """Does ``entry`` match the query configuration? A pinned field the
    query does not supply (platform unknown, no chunk length) does NOT
    match — steering on unknown context would be a guess, and the
    heuristic is a better guess."""
    if entry.get("n_cells") is not None:
        if not all(int(v) == int(entry["n_cells"]) for v in n):
            return False
    if entry.get("n") is not None:
        if [int(v) for v in entry["n"]] != [int(v) for v in n]:
            return False
    if entry.get("markers_min") is not None \
            and n_markers < int(entry["markers_min"]):
        return False
    if entry.get("markers_max") is not None \
            and n_markers > int(entry["markers_max"]):
        return False
    if entry.get("spectral_dtype") is not None:
        if (normalize_spectral_dtype(entry["spectral_dtype"])
                != normalize_spectral_dtype(spectral_dtype)):
            return False
    if entry.get("platform") is not None:
        if platform is None \
                or str(entry["platform"]).lower() != str(platform).lower():
            return False
    if entry.get("chunk_length") is not None:
        if chunk_length is None \
                or int(entry["chunk_length"]) != int(chunk_length):
            return False
    return True


def provenance_compatible(entry: dict,
                          platform: Optional[str]) -> bool:
    """A ``provenance.platform`` pin restricts an entry to the backend
    it was measured on — a CPU-measured winner must never steer a TPU
    run (and vice versa). Unknown current platform fails closed."""
    prov = entry.get("provenance") or {}
    pinned = prov.get("platform")
    if pinned is None:
        return True
    return (platform is not None
            and str(pinned).lower() == str(platform).lower())


def current_platform() -> Optional[str]:
    """The active jax backend name, or None when jax is unavailable
    (entries pinning a platform then never match — fail closed)."""
    try:
        import jax
        return jax.default_backend()
    except Exception:
        return None


def lookup_tuning_db(entries: list, n: Sequence[int], n_markers: int,
                     spectral_dtype: Optional[str] = None,
                     platform: Optional[str] = None,
                     chunk_length: Optional[int] = None
                     ) -> Optional[dict]:
    """The winning DB entry for a query, or None. Most-specific-match
    wins; equal specificity resolves to file order (earlier wins).
    Provenance-incompatible entries are skipped (counted) before
    matching."""
    best, best_score = None, -1
    for entry in entries:
        if not isinstance(entry, dict):
            raise ValueError(f"tuning DB entry is not an object: "
                             f"{entry!r}")
        if not provenance_compatible(entry, platform):
            _PROV_SKIPS.inc()
            continue
        if not entry_matches(entry, n, n_markers,
                             spectral_dtype=spectral_dtype,
                             platform=platform,
                             chunk_length=chunk_length):
            continue
        score = entry_specificity(entry)
        if score > best_score:      # ties keep the EARLIER entry
            best, best_score = entry, score
    return best


def resolve_engine(n: Sequence[int], n_markers: int, support: int,
                   env: Optional[dict] = None, *,
                   spectral_dtype: Optional[str] = None,
                   platform: Optional[str] = None,
                   chunk_length: Optional[int] = None) -> str:
    """Resolve the ``auto`` engine alias to a concrete engine name for a
    grid of extents ``n`` carrying ``n_markers`` markers under a delta
    kernel of half-width ``support``. Resolution order: env override,
    tuning DB (most-specific match; see module docstring), built-in
    heuristic. ``env`` substitutes for ``os.environ`` in tests;
    ``platform`` defaults to the active jax backend."""
    env = os.environ if env is None else env
    override = str(env.get(ENV_ENGINE, "") or "").strip().lower()
    if override and override != "auto":
        return _validate(override, f"${ENV_ENGINE}")
    db_path = str(env.get(ENV_TUNING_DB, "") or "").strip()
    if db_path.lower() in DB_DISABLE_VALUES:
        return default_rule(n, n_markers, support)
    if not db_path and os.path.exists(DEFAULT_DB_PATH):
        db_path = DEFAULT_DB_PATH
    if db_path:
        entries = load_tuning_db(db_path)
        if platform is None:
            platform = current_platform()
        hit = lookup_tuning_db(
            entries, n, n_markers, spectral_dtype=spectral_dtype,
            platform=platform, chunk_length=chunk_length)
        if hit is not None:
            _HITS.inc()
            return _validate(str(hit.get("engine", "")).lower(),
                             f"tuning DB {db_path}")
        _FALLBACKS.inc()
    return default_rule(n, n_markers, support)
