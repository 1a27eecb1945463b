"""The reduction from a profiler trace to device metrics."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    ops = [("fusion.1", 100.0, 100.0), ("my_kernel", 150.0, 100.0),
           ("while.2", 600.0, 50.0), ("fusion.1", 600.0, 50.0),
           ("outside", 2000.0, 10.0)]
    return {"devices": {"/device:TPU:0": {
        "all_ops": ops, "ops": T._leaves(ops),
        "modules": [("jit_walk", 100.0, 200.0)]}},
        "annotations": [(T.WINDOW, 0.0, 1000.0),
                        ("bench.request", 0.0, 400.0)]}


def span(name, path, t0, t1, tid):
    return SimpleNamespace(name=name, path=path, t0_ns=t0, t1_ns=t1,
                           _tid=tid)


def test_busy_idle_and_op_times():
    r = T.reduce(synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    # union of [100, 250] and [600, 650]; the op at 2000 is outside
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["op_s"]["fusion.1"] == pytest.approx(150e-9)
    assert r["op_s"]["my_kernel"] == pytest.approx(100e-9)
    assert "while.2" not in r["op_s"]  # it holds fusion.1: not a leaf
    assert "outside" not in r["op_s"]
    assert r["module_s"]["jit_walk"] == pytest.approx(200e-9)
    assert T.op_seconds(r, r"kernel$") == pytest.approx(100e-9)
    # gaps [0,100] [250,600] [650,1000]: the first lies in the request
    idle = dict(r["idle_gaps"])
    assert idle["bench.request"] == pytest.approx(100e-9)
    assert idle["host outside any span"] == pytest.approx(700e-9)


def test_idle_gaps_named_by_the_innermost_host_span():
    offset = 5000.0  # host clock = trace clock + offset
    spans = [span("online", "online", 5000, 6000, 1),
             span("op:linear", "online/op:linear", 5240, 5640, 2),
             span("wire.recv", "online/wire.recv", 5240, 5640, 1)]
    r = T.reduce(synthetic(), host=T.HostSpans(spans, offset, client_tid=1))
    idle = dict(r["idle_gaps"])
    assert idle["server online/op:linear"] == pytest.approx(350e-9)
    assert idle["client online"] == pytest.approx(450e-9)


def test_a_trace_without_window_or_device_is_refused():
    t = synthetic()
    t["annotations"] = []
    with pytest.raises(ValueError):
        T.reduce(t)
    t = synthetic()
    t["devices"] = {}
    with pytest.raises(ValueError):
        T.reduce(t)


@pytest.fixture(scope="module")
def small():
    """A trace recorded on one TPU v5e by ``record_trace.py``: one small
    netlist evaluated, then garbled, with the Pallas executor, with host
    sleeps of 50 ms before each inside the window."""
    return T.load(str(DATA / "small.xplane.pb"))


def test_recorded_trace_has_the_harness_annotations(small):
    names = [n for n, _, _ in small["annotations"]]
    assert names.count(T.WINDOW) == 1
    assert "bench.request" in names and "bench.bundle" in names
    assert list(small["devices"]) == ["/device:TPU:0"]


def test_recorded_trace_reduces(small):
    r = T.reduce(small)
    assert 0.1 < r["window_s"] < 1.0
    # two host sleeps of 50 ms: the device is idle most of the window
    assert 0.0 < r["busy_s"] < r["window_s"] - 0.1
    assert r["idle_gaps"][0][0] == "host outside any span"
    assert len(r["device_ops"]) == 10
    assert any(k.startswith("jit__eval_fn(") for k in r["module_s"])
    assert any(k.startswith("jit__garble_fn(") for k in r["module_s"])


def reader_module(name):
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("reader", ["gc_eval_hbm_pct", "gc_garble_hbm_pct"])
def test_kernel_patterns_find_the_pallas_kernels(small, reader):
    r = T.reduce(small)
    t = T.op_seconds(r, reader_module(reader).KERNEL)
    assert 0.0 < t < r["busy_s"]


def test_garble_module_pattern_finds_the_walk(small):
    import re

    pattern = reader_module("garble_dev_s").MODULE
    r = T.reduce(small)
    hits = [k for k in r["module_s"] if re.search(pattern, k)]
    assert hits and all(k.startswith("jit__garble_fn(") for k in hits)
