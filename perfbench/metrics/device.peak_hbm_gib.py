"""device layer: ``memory_stats()["peak_bytes_in_use"]`` after the window
and the recovery, before the reference runs.  Source: program_counter.
Moves: step_ms."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
