"""fluid solve kernel: the least time the chip could take for the step's
transforms (``work.transform_bytes_per_step`` over the HBM peak; bandwidth
bounds it) over the fft operations' device time per step.
Source: device_trace.  Moves: step_ms."""
from perfbench.readers import class_ms_per_step, peaks_of
from perfbench.work import transform_bytes_per_step


def read(ctx):
    fft_ms = class_ms_per_step(ctx, "fft")
    if not fft_ms:
        return None
    least_ms = 1e3 * transform_bytes_per_step(ctx["grid_n"]) \
        / peaks_of(ctx)["hbm_bytes_per_s"]
    return 100.0 * least_ms / fft_ms
