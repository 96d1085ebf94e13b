"""``correct`` has to come out false where it should.  These drive the rest
of a run (everything but the harness's look for a chip) at 16^3 on the CPU
with the timed path broken underneath, once for each fault the cells can
have, and with the lower-precision control in the program's place.
Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests``.
"""
import argparse
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

CELL = "ex4_shell_128.production"


def drive(fault=None, control=None, seed=11):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0,
                              trace=0, rehearse=True, control=control)
    return harness.run(args, time.perf_counter(), require_chip=False,
                       fault=fault)


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"setup_s", "step_ms", "recover_s"}
    assert res["compared"]["recover.restore_mismatch"]["value"] == 0.0
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [
    "state_unchanged",     # the chunk returns the state it was given
    "half_markers",        # every second marker left out of the transfers
    "answer_altered",      # the velocity altered where it is produced
    "restore_altered",     # the restored state differs from the saved one
])
def test_fault_is_not_correct(fault):
    res = drive(fault=fault)
    assert not res["correct"], (fault, res["compared"])


def test_control_is_not_correct(control="bf16"):
    """The reference in the nearest lower precision, put in the program's
    place, fails at least one compared number."""
    res = drive(control=control)
    over = [k for k, c in res["control"].items() if c["value"] > c["limit"]]
    assert over, (control, res["control"])
