"""checkpoint layer: seconds of the ``restore_checkpoint`` call of the
recovery (the benchmark's spy).  Source: host_clock.  Moves: recover_s."""


def read(ctx):
    rec = ctx.get("recover")
    return rec["restore_s"] if rec else None
