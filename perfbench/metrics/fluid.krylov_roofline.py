"""fluid solve kernel: the least time the chip could take for the traced
chunks' saddle solves (``work_open.restart_bytes`` for each restart cycle
their ``driver/chunk/krylov`` spans count, each of ``iters / restarts``
Arnoldi iterations, over the HBM peak: bandwidth bounds every part of the
solve) over the device time under ``fluid/krylov`` and its sub-phases
(``fluid.krylov_ms``), whatever implements the solve.
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms
from perfbench.readers import peaks_of
from perfbench.work_open import mg_levels, restart_bytes


def read(ctx):
    got = phase_ms(ctx)
    tr = ctx.get("trace")
    if got is None or "fluid/krylov" not in got or not ctx["chunks"]:
        return None
    ms = sum(v for k, v in got.items()
             if k == "fluid/krylov" or k.startswith("fluid/krylov/"))
    if not ms:
        return None
    try:
        from ibamr_tpu import obs
    except ImportError:
        return None
    # the traced chunks' spans: those that closed after the window did
    t1 = ctx["chunks"][-1]["t_end"]
    traced = [s for s in obs.spans() if s["t0"] >= t1
              and s["path"].endswith("driver/chunk/krylov")]
    if not traced:
        return None
    cycles = sum(s["attrs"]["restarts"] for s in traced)
    if not cycles:
        return None
    m = sum(s["attrs"]["iters"] for s in traced) // cycles
    n = ctx["grid_n"]
    least_ms = 1e3 * restart_bytes(n, m, mg_levels(n)) * cycles / tr["steps"] \
        / peaks_of(ctx)["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
