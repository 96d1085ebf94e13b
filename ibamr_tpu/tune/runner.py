"""Measured trials: compile once through the AOT cache, time warm steps.

A trial is one :class:`~ibamr_tpu.tune.space.Candidate` built into a
real integrator (``engine_fallback=False`` — a degraded build would
time the WRONG engine and poison the DB) whose L-step scan chunk is
AOT-compiled through the PR-11 :class:`ExecutableCache`. The compile
is paid once per candidate family ever (the second trial of a
candidate is a cache HIT — pinned by tests/test_tune.py); the timed
leg runs only warm executions under an ``obs.span`` with the
async-dispatch block-on discipline (drain before start, block before
stop — the ``tools/microbench_*`` idiom), so a trial measures steady
steps/s, not dispatch or compile.

Chunk length is a REAL graph knob, not a timing detail: the scan of
length L is its own executable (cache-key material: ``kind:
tune_chunk, length: L``), and longer chunks amortize per-dispatch
host cost — which is why the search grid includes it and the DB can
pin it.

Every trial lands on the telemetry bus as a ``tune_trial`` ledger
record plus ``tune_{trials,errors}_total`` counters, so
``tools/obs.py summary`` renders the measured ranking next to the
serving block.
"""

from __future__ import annotations

import time
from dataclasses import asdict, dataclass, field
from typing import Optional, Sequence

from ibamr_tpu import obs as _obs
from ibamr_tpu.tune.space import (Candidate, DEFAULT_ENGINES,
                                  enumerate_space, make_probe_fn)

_TRIALS = _obs.counter("tune_trials_total")
_PRUNED = _obs.counter("tune_pruned_total")
_ERRORS = _obs.counter("tune_errors_total")


@dataclass
class TrialResult:
    candidate: Candidate
    steps_per_s: float = 0.0
    ms_per_step: float = 0.0
    compile_s: float = 0.0
    cache_hit: bool = False
    recompiles: int = 0
    error: Optional[str] = None

    def row(self) -> dict:
        out = asdict(self.candidate)
        out.update(steps_per_s=round(self.steps_per_s, 4),
                   ms_per_step=round(self.ms_per_step, 4),
                   compile_s=round(self.compile_s, 3),
                   cache_hit=self.cache_hit, error=self.error)
        return out


def chunk_callable(integ, length: int):
    """The L-step scan chunk the trial times — one executable per
    (family, length), exactly the dispatch-amortization graph a
    production driver runs."""
    import jax

    def chunk(state, dt):
        def body(s, _):
            return integ.step(s, dt), None
        s, _ = jax.lax.scan(body, state, None, length=int(length))
        return s
    return chunk


def run_trial(candidate: Candidate, *, n_cells: int = 16,
              n_lat: int = 8, n_lon: int = 16, dt: float = 5e-5,
              reps: int = 3, mu: float = 0.05, cache=None,
              label: str = "") -> TrialResult:
    """One measured trial. Build failures are reported in
    ``TrialResult.error`` (counted), never raised — the search must
    finish its grid even when one candidate dies on this backend."""
    import jax

    from ibamr_tpu.models.shell3d import build_shell_example
    from ibamr_tpu.serve import aot_cache

    cache = cache if cache is not None else aot_cache.get_cache()
    L = int(candidate.chunk_length)
    res = TrialResult(candidate=candidate)
    try:
        integ, state = build_shell_example(
            n_cells=n_cells, n_lat=n_lat, n_lon=n_lon, radius=0.25,
            aspect=1.2, stiffness=1.0, rest_length_factor=0.75,
            mu=mu, use_fast_interaction=candidate.engine,
            spectral_dtype=candidate.spectral_dtype,
            engine_fallback=False)
        fp = aot_cache.step_fingerprint(integ)
        before = cache.stats()
        chunk = chunk_callable(integ, L)
        entry = cache.get_or_compile(
            fp,
            lambda: aot_cache.aot_compile(chunk, (state, dt)),
            extra={"kind": "tune_chunk", "length": L,
                   "args": aot_cache.arg_signature((state, dt))},
            label=label or f"tune:{candidate.label()}")
        after = cache.stats()
        res.compile_s = entry.compile_s
        res.cache_hit = after["hits"] > before["hits"]
        res.recompiles = after["misses"] - before["misses"]
        exe = entry.executable
        with _obs.span("tune/trial", engine=candidate.engine,
                       spectral_dtype=candidate.spectral_dtype,
                       chunk_length=L, n=n_cells):
            jax.block_until_ready(exe(state, dt))   # drain warm-up
            t0 = time.perf_counter()
            out = state
            for _ in range(int(reps)):
                out = exe(out, dt)
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
        per_step = elapsed / max(int(reps) * L, 1)
        res.steps_per_s = 1.0 / max(per_step, 1e-12)
        res.ms_per_step = per_step * 1e3
        _TRIALS.inc()
    except Exception as e:  # noqa: BLE001 - the grid must finish
        res.error = f"{type(e).__name__}: {e}"
        _ERRORS.inc()
    _obs.emit("tune_trial", n=n_cells, markers=n_lat * n_lon,
              engine=candidate.engine,
              spectral_dtype=candidate.spectral_dtype, chunk_length=L,
              steps_per_s=round(res.steps_per_s, 4),
              compile_s=round(res.compile_s, 3),
              cache_hit=res.cache_hit, error=res.error)
    return res


@dataclass
class SearchResult:
    config: dict
    trials: list = field(default_factory=list)
    pruned: list = field(default_factory=list)

    def ranking(self) -> list:
        ok = [t for t in self.trials if t.error is None]
        return sorted(ok, key=lambda t: t.steps_per_s, reverse=True)

    def winner(self) -> Optional[TrialResult]:
        r = self.ranking()
        return r[0] if r else None

    def runner_up(self) -> Optional[TrialResult]:
        """Best trial of a DIFFERENT engine than the winner — the
        margin the check gate re-validates is engine-vs-engine, not
        chunk-length-vs-chunk-length of the same engine."""
        r = self.ranking()
        if not r:
            return None
        return next((t for t in r[1:]
                     if t.candidate.engine != r[0].candidate.engine),
                    None)

    def to_dict(self) -> dict:
        w, ru = self.winner(), self.runner_up()
        margin = (round(w.steps_per_s / max(ru.steps_per_s, 1e-12), 4)
                  if w and ru else None)
        return {
            "config": self.config,
            "trials": [t.row() for t in self.trials],
            "pruned": [{**asdict(c), "reason": r}
                       for c, r in self.pruned],
            "winner": w.row() if w else None,
            "runner_up": ru.row() if ru else None,
            "margin": margin,
        }


def search(*, n_cells: int = 16, n_lat: int = 8, n_lon: int = 16,
           engines: Sequence[str] = DEFAULT_ENGINES,
           spectral_dtypes: Sequence[str] = ("f32", "bf16"),
           chunk_lengths: Sequence[int] = (1, 4), reps: int = 3,
           dt: float = 5e-5, probe: bool = True, cache=None,
           kernel: str = "IB_4") -> SearchResult:
    """Walk the engine x spectral_dtype x chunk-length grid for ONE
    configuration key, measured. Ineligible candidates are pruned
    statically (never timed); Pallas candidates are compile-probe
    gated when ``probe``."""
    from ibamr_tpu.ops.delta import get_kernel

    support, _ = get_kernel(kernel)
    n = (int(n_cells),) * 3
    n_markers = int(n_lat) * int(n_lon)
    probe_fn = (make_probe_fn(n, n_lat, n_lon, kernel=kernel)
                if probe else None)
    with _obs.span("tune/search", n=n_cells, markers=n_markers):
        candidates, pruned = enumerate_space(
            n, n_markers, support, engines=tuple(engines),
            spectral_dtypes=tuple(spectral_dtypes),
            chunk_lengths=tuple(chunk_lengths), probe_fn=probe_fn)
        for _ in pruned:
            _PRUNED.inc()
        result = SearchResult(
            config={"n": list(n), "n_cells": int(n_cells),
                    "n_lat": int(n_lat), "n_lon": int(n_lon),
                    "markers": n_markers, "dt": dt, "reps": int(reps),
                    "engines": list(engines),
                    "spectral_dtypes": [str(s) for s in spectral_dtypes],
                    "chunk_lengths": [int(L) for L in chunk_lengths]},
            pruned=pruned)
        for cand in candidates:
            result.trials.append(run_trial(
                cand, n_cells=n_cells, n_lat=n_lat, n_lon=n_lon,
                dt=dt, reps=reps, cache=cache))
    return result


def db_entry_from_search(result: SearchResult, *, platform: str,
                         timestamp: str, device_kind=None,
                         jax_version=None, git_rev=None,
                         source=None) -> Optional[dict]:
    """The publication: winner -> one schema-v1 DB entry whose match
    fields pin the measured configuration (exact grid, factor-2 marker
    band, spectral dtype, platform) and whose provenance pins the
    backend it was measured on. Returns None when nothing ran."""
    from ibamr_tpu.tune import db as _db

    w, ru = result.winner(), result.runner_up()
    if w is None:
        return None
    n_markers = result.config["markers"]
    measured = {"steps_per_s": round(w.steps_per_s, 4),
                "chunk_length": w.candidate.chunk_length,
                "reps": result.config["reps"],
                "n_lat": result.config["n_lat"],
                "n_lon": result.config["n_lon"]}
    if ru is not None:
        measured.update(
            runner_up=ru.candidate.engine,
            runner_up_steps_per_s=round(ru.steps_per_s, 4),
            runner_up_chunk_length=ru.candidate.chunk_length,
            margin=round(w.steps_per_s / max(ru.steps_per_s, 1e-12),
                         4))
    prov = _db.make_provenance(
        platform, timestamp, device_kind=device_kind,
        jax_version=jax_version, git_rev=git_rev, source=source)
    return _db.make_entry(
        w.candidate.engine, n=result.config["n"],
        markers_min=max(1, n_markers // 2), markers_max=n_markers * 2,
        spectral_dtype=w.candidate.spectral_dtype, platform=platform,
        measured=measured, provenance=prov)
