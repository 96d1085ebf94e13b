"""driver layer: seconds of the recovery's ``driver/chunk/sync`` span: the
host's wait for the device work of the new driver's first chunk.
Source: program_span.  Moves: recover_s."""
from perfbench import intervals


def read(ctx):
    spans = intervals.ring()
    iv = spans and intervals.recovery(ctx, spans)
    if not iv:
        return None
    got = intervals.inside(spans, iv, "driver/chunk/sync")
    return intervals.covered_s(got, iv) if got else None
