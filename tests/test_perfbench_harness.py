"""The benchmark harness's own cases in tier-1 (ROADMAP D14): a fixture that
is not a shell runs through it and is judged, an adapter that lacks a name is
refused by it, every configuration of ``BENCHMARK.json`` names files that
load, and windows close on whole periods of the traffic.  The cases live with
the benchmark (``perfbench/tests/test_harness.py``: CPU, 16^3) and are
imported, not copied."""

from perfbench.tests.test_harness import *  # noqa: F401,F403


def test_cylinder_rehearsal_reads_the_window_metrics():
    """A traced rehearsal of ``dfg3d_2z.advance`` (the duct at 12 x 12 x
    64, one step a chunk) reports the metrics a window of too few chunks
    leaves out: ``driver.chunk_p90_ms`` is None under ten chunks, and a
    line without a metric the cell lists is no result."""
    import argparse
    import time

    from perfbench import harness

    args = argparse.Namespace(workload="dfg3d_2z.advance", seed=2147483713,
                              seconds=0.5, trace=1, rehearse=True,
                              control=None)
    res = harness.run(args, time.perf_counter(), require_chip=False)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    assert res["attempted"] >= 10
    for name in ("driver.chunk_p90_ms", "driver.chunk_median_ms",
                 "callbacks.share_pct", "fluid.krylov_iters"):
        assert res["metrics"][name]["value"] is not None, name
    assert res["metrics"]["fluid.krylov_iters"]["value"] >= 40
