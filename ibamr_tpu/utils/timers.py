"""Hierarchical wall-clock timers.

Reference parity: ``SAMRAI::tbox::TimerManager`` + ``IBAMR_TIMER_START/STOP``
macros (SURVEY.md §5.1): named timers bracketing significant methods, with a
hierarchical report at shutdown. On TPU the analog must account for async
dispatch, so the context manager optionally blocks on a pytree of arrays
before reading the clock; within jitted code use ``jax.named_scope`` (we wrap
it) so the names also show up in ``jax.profiler`` traces.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Optional

import jax


class Timer:
    """Re-entrant named timer: nested start/stop pairs with the same name are
    supported (recursive methods bracketed by one timer, as in the reference's
    TimerManager)."""

    __slots__ = ("name", "total", "count", "_starts")

    def __init__(self, name: str):
        self.name = name
        self.total = 0.0
        self.count = 0
        self._starts: list = []

    def start(self) -> None:
        self._starts.append(time.perf_counter())

    def stop(self, block_on=None) -> float:
        if not self._starts:
            raise RuntimeError(f"Timer {self.name!r}: stop() without start()")
        if block_on is not None:
            jax.block_until_ready(block_on)
        dt = time.perf_counter() - self._starts.pop()
        # only the outermost frame of a re-entrant timer accumulates, so
        # `total` stays wall-clock (matching SAMRAI's exclusive-timer report)
        if not self._starts:
            self.add(dt)
        return dt

    def add(self, seconds: float) -> None:
        """Account one interval timed elsewhere (the run loop's chunk
        wall, which its own span already brackets)."""
        self.total += seconds
        self.count += 1


class TimerManager:
    """Process-wide named-timer registry with a report table."""

    _instance: Optional["TimerManager"] = None

    def __init__(self):
        self.timers: Dict[str, Timer] = {}

    @classmethod
    def instance(cls) -> "TimerManager":
        if cls._instance is None:
            cls._instance = TimerManager()
        return cls._instance

    def get(self, name: str) -> Timer:
        if name not in self.timers:
            self.timers[name] = Timer(name)
        return self.timers[name]

    @contextmanager
    def scope(self, name: str, block_on=None):
        # span emission (PR 9): the scope IS a telemetry span — one
        # bookkeeping path, not two. The span enters jax.named_scope,
        # blocks on `block_on` before its clock read, and closes into
        # the attached run ledger; the Timer accumulates immediately
        # after (same wall time to within microseconds), keeping the
        # report() table alive for callers that never attach a ledger.
        from ibamr_tpu.obs import span as _span

        t = self.get(name)
        t.start()
        try:
            with _span(name, block_on=block_on):
                yield t
        finally:
            t.stop()

    def report(self) -> str:
        if not self.timers:
            return "TimerManager: no timers recorded"
        width = max(len(n) for n in self.timers) + 2
        lines = [f"{'Timer':<{width}}{'Calls':>8}{'Total (s)':>12}{'Mean (ms)':>12}"]
        for name in sorted(self.timers, key=lambda n: -self.timers[n].total):
            t = self.timers[name]
            mean_ms = 1e3 * t.total / max(t.count, 1)
            lines.append(f"{name:<{width}}{t.count:>8}{t.total:>12.4f}{mean_ms:>12.3f}")
        return "\n".join(lines)

    def reset(self) -> None:
        self.timers.clear()


@contextmanager
def timer(name: str, block_on=None):
    """Module-level convenience: ``with timer("IB::spreadForce"): ...``"""
    with TimerManager.instance().scope(name, block_on=block_on) as t:
        yield t


@contextmanager
def profile_trace(log_dir: Optional[str], stage: Optional[str] = None):
    """Capture a jax/XLA device profile for the enclosed region
    (SURVEY.md §5.1 — the deep-dive layer under TimerManager's wall
    timers, viewable in TensorBoard / Perfetto). No-op when ``log_dir``
    is falsy, so call sites can thread a ``--profile DIR`` flag through
    unconditionally. The ``named_scope`` annotations that TimerManager
    already emits show up as trace regions.

    PR 10 rides the bus: the capture runs inside an
    ``obs.span("profile_trace", capture_dir=..., stage=...)``, and a
    ``profile`` ledger record lands when the trace closes — so
    ``tools/obs.py tail`` shows a profile landing live, and the ledger
    names the capture dir ``tools/prof.py attribute`` should be
    pointed at. Telemetry-off runs pay only the span's no-op path.

    When the capture closes cleanly and the run loop has called chunk
    programs (``obs.programs()``), their instruction -> ``op_name`` and
    instruction -> phase maps land beside the capture
    (``op_names.json``): the chip's trace
    names operations ``%fusion.N`` only, and ``tools/prof.py
    attribute`` names the step's phases from this map, offline. That
    reads each program's compiled text once more (``deviceprof
    .program_names``): the price of having asked for a profile."""
    if not log_dir:
        yield
        return
    import jax.profiler as _prof

    from ibamr_tpu import obs
    from ibamr_tpu.obs import deviceprof

    with obs.span("profile_trace", capture_dir=str(log_dir),
                  stage=stage):
        _prof.start_trace(log_dir)
        try:
            yield
        finally:
            _prof.stop_trace()
            obs.emit("profile", capture_dir=str(log_dir), stage=stage)
        if obs.programs():
            deviceprof.write_names(log_dir)
