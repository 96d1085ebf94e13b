"""The benchmark harness's own cases in tier-1 (ROADMAP D14): a fixture that
is not a shell runs through it and is judged, an adapter that lacks a name is
refused by it, every configuration of ``BENCHMARK.json`` names files that
load, and windows close on whole periods of the traffic.  The cases live with
the benchmark (``perfbench/tests/test_harness.py``: CPU, 16^3) and are
imported, not copied."""

from perfbench.tests.test_harness import *  # noqa: F401,F403
