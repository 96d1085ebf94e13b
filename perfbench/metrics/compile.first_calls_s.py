"""compile layer: wall seconds of the first call of each chunk program in
set-up (trace, lower, and compile or persistent-cache read), summed.
Source: host_clock.  Moves: setup_s."""


def read(ctx):
    vals = list(ctx["first_calls"].values())
    return sum(vals) if vals else None
