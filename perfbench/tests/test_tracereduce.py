"""The reduction from trace to device numbers, on a hand-made trace whose
answers are known and on a small cut of a trace recorded on the chip.
Run by hand: ``python -m pytest perfbench/tests``."""
import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import tracereduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def handmade():
    ms = 1_000_000
    ops = [["while.1", 0, 100 * ms],            # parent of the next four
           ["fft.3", 0, 30 * ms],
           ["fusion.7", 30 * ms, 20 * ms],
           ["convolution.2", 50 * ms, 25 * ms],
           ["scatter.4", 80 * ms, 10 * ms],
           # idle 100..140 under bench/viz_fn, then one more op
           ["sort.9", 140 * ms, 10 * ms]]
    host = [["bench/viz_fn", 101 * ms, 38 * ms],
            ["bench/sync", 90 * ms, 60 * ms],
            ["python_noise", 0, 150 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_chunk", 0, 150 * ms]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def test_handmade_trace():
    r = tracereduce.reduce(handmade(), steps=2)
    assert r["busy_s"] == pytest.approx(0.110)
    assert r["window_s"] == pytest.approx(0.150)
    c = r["op_class_s"]
    assert c["fft"] == pytest.approx(0.030)
    assert c["dot"] == pytest.approx(0.025)
    assert c["scatter_sort"] == pytest.approx(0.020)
    assert c["other"] == pytest.approx(0.020)
    assert c["loop"] == pytest.approx(0.015)     # the while's own time
    # attributed + unattributed = total: the classes add up to busy
    assert sum(c.values()) == pytest.approx(r["busy_s"])
    assert r["device_ops"][0] == ["fft.3 [fft]", pytest.approx(0.030)]
    # the gap is named by the annotation that covers most of it
    assert r["idle_gaps"] == [["bench/sync", pytest.approx(0.040)]]


def test_classes():
    names = {"fusion.7": "jit(chunk)/while/body/jit(fft)/fft",
             "fusion.8": "jit(chunk)/while/body/jit(_take)/gather",
             "fusion.9": "jit(chunk)/while/body/qc,qcm->qm/dot_general"}
    for name, cls in (("%fusion.7 = f32[8] fusion(f32[8] %copy.1)", "fft"),
                      ("fusion.8", "scatter_sort"), ("fusion.9", "dot"),
                      ("fusion.10", "other")):
        assert tracereduce.classify(name, names) == cls, name
    text = ('  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kCustom, '
            'calls=%fc, metadata={op_name="jit(chunk)/jit(fft)/fft" '
            'source_file="x.py"}\n  ROOT %t = (f32[8]) tuple(%fusion.7)')
    assert tracereduce.op_names_from_hlo(text) == {
        "fusion.7": "jit(chunk)/jit(fft)/fft"}
    for name, cls in (("fft.12", "fft"), ("%fft.1", "fft"),
                      ("convolution.5", "dot"), ("dot.3", "dot"),
                      ("scatter.2", "scatter_sort"),
                      ("gather.1", "scatter_sort"), ("sort.4", "scatter_sort"),
                      ("copy.8", "copy"), ("while.2", "loop"),
                      ("fusion.99", "other")):
        assert tracereduce.classify(name) == cls, name


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.json"))) or [None])
def test_recorded_chip_trace(path):
    if path is None:
        pytest.skip("no recorded trace beside the test")
    trace = tracereduce.load(path)
    r = tracereduce.reduce(trace, op_names=trace.get("op_names"))
    assert r["device_plane"].startswith("/device:TPU:")
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["op_class_s"].values()) == pytest.approx(
        r["busiest_busy_s"], rel=1e-6)
    assert r["self_total_s"] == pytest.approx(r["busiest_busy_s"], rel=1e-6)
    assert {"fft", "dot"} <= set(r["op_class_s"])
    assert r["device_ops"] and r["device_ops"][0][1] > 0
