"""fluid solve kernel: the least time the chip could take for the step's
convective operator (``work_fluid.convect_bytes_per_step`` over the HBM
peak; bandwidth bounds it) over the device time under the ``fluid/convect``
phase, whatever implements the operator.
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms
from perfbench.readers import peaks_of
from perfbench.work_fluid import convect_bytes_per_step


def read(ctx):
    got = phase_ms(ctx)
    if got is None or not got.get("fluid/convect"):
        return None
    least_ms = 1e3 * convect_bytes_per_step(ctx["grid_n"]) \
        / peaks_of(ctx)["hbm_bytes_per_s"]
    return 100.0 * least_ms / got["fluid/convect"]
