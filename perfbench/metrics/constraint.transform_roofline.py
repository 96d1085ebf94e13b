"""fluid solve kernel: the least time the chip could take for the axis
transforms of one ConstraintIB step over the walled solve as dense products
(``work_constraint.transform_least_s``: FIVE solves, the fluid step's four and
the re-projection's; the larger of their operations over the bf16 peak and
their bytes over the HBM peak) over the device time under the
``fluid/transforms`` phase, whatever implements them.
Source: device_trace.  Moves: step_ms."""
from perfbench.obsread import phase_ms
from perfbench.readers import peaks_of
from perfbench.work_constraint import transform_least_s


def read(ctx):
    got = phase_ms(ctx)
    if got is None or not got.get("fluid/transforms"):
        return None
    return 100.0 * 1e3 * transform_least_s(ctx["grid_n"], peaks_of(ctx)) \
        / got["fluid/transforms"]
