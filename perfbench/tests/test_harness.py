"""The harness takes a configuration's entry point, builder, state, seeder
and reference from the configuration's own files: a fixture that is NOT a
shell (``data/ins_periodic``: the periodic fluid solve alone, in no
benchmark) runs through it and is judged; and windows close on whole periods
of the traffic.  CPU, 16^3.
Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests``.
"""
import argparse
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

FIXTURE = os.path.join(ROOT, "perfbench", "tests", "data", "ins_periodic")


def drive(fault=None, seed=2147483655):
    args = argparse.Namespace(workload="ins_periodic.advance", seed=seed,
                              seconds=0.5, trace=0, rehearse=True,
                              control=None)
    return harness.run(
        args, time.perf_counter(), require_chip=False, fault=fault,
        bench=harness.load_json(os.path.join(FIXTURE, "benchmark.json")))


@pytest.mark.parametrize("fault", [None, "state_unchanged", "answer_altered"])
def test_fixture_that_is_not_a_shell(fault):
    res = drive(fault=fault)
    assert res["correct"] == (fault is None), (fault, res["compared"])
    assert set(res["compared"]) == {"window.du"}
    assert set(res["metrics"]) == {"setup_s", "step_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("name", harness.ADAPTER_NAMES)
def test_adapter_that_lacks_a_name_is_refused_by_it(name, tmp_path):
    text = open(os.path.join(FIXTURE, "adapter.py")).read()
    broken = tmp_path / "adapter.py"
    broken.write_text(text.replace(f"def {name}(", f"def _{name}(")
                      .replace(f"\n{name} = ", f"\n_{name} = "))
    with pytest.raises(SystemExit, match=repr(name)):
        harness.load_adapter(str(broken))


def test_traffic_that_recovers_needs_a_spied_restore():
    bench = harness.load_json(os.path.join(FIXTURE, "benchmark.json"))
    bench["workloads"][0]["traffic"] = "production"
    args = argparse.Namespace(workload="ins_periodic.advance", seed=1,
                              seconds=0.5, trace=0, rehearse=True)
    with pytest.raises(SystemExit, match="restore"):
        harness.run(args, time.perf_counter(), require_chip=False,
                    bench=bench)


def test_every_configuration_names_files_that_load():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for entry in bench["configs"]:
        config = harness.load_json(os.path.join(ROOT, entry["file"]))
        adapter = harness.load_adapter(os.path.join(ROOT, config["adapter"]))
        main = harness.load_module(os.path.join(ROOT, config["entry"]),
                                   "entry_under_test")
        for name in ("main", adapter.BUILDER, *adapter.SPIED.values()):
            assert callable(getattr(main, name)), (entry["name"], name)
        harness.load_module(os.path.join(ROOT, config["reference"]),
                            "reference_under_test")
        assert config["limits"] and config["seed_data"]


def window_steps(monkeypatch, traffic: str, seconds: float, step_s: float):
    """Drive ``Probe.boundary`` as ``HierarchyDriver.run`` would, on a fake
    clock that a step advances by ``step_s``; returns ``(steps in the
    window, step at which it opened, step at which it closed)``."""
    mix = harness.load_json(os.path.join(ROOT, "perfbench", "traffic",
                                         traffic + ".json"))
    clock = [0.0]
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock[0])
    probe = harness.Probe(seconds, mix["warm_steps"], 0, None,
                          period_steps=mix["period_steps"])
    driver = types.SimpleNamespace(last_chunk_wall_s=0.0)
    cadences = [i for i in mix["set"]["Main"].values() if i]
    step = 0
    with pytest.raises(harness.WindowClosed):
        while True:
            n = min([20] + [i - step % i for i in cadences])
            probe.boundary(driver, n, lambda state: (state, None))
            step += n
            clock[0] += n * step_s
    return sum(c["steps"] for c in probe.chunks), probe.window_step0, step


@pytest.mark.parametrize("step_s", [0.0263, 0.0803, 0.0361, 0.097, 0.31])
def test_windows_close_on_whole_periods(monkeypatch, step_s):
    # advance: the first chunk boundary at or after ``seconds``
    steps, opened, closed = window_steps(monkeypatch, "advance", 30.0, step_s)
    assert opened == 40 and closed == opened + steps
    assert steps % 20 == 0
    assert (steps - 20) * step_s < 30.0 <= steps * step_s
    # production (20/20/10): the first multiple of 200 steps at or after it
    steps, opened, closed = window_steps(monkeypatch, "production", 30.0,
                                         step_s)
    assert opened == 200 and closed == opened + steps
    assert steps % 200 == 0
    assert (steps - 200) * step_s < 30.0 <= steps * step_s
