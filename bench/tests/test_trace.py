"""CPU tests of the trace reduction: a small trace recorded on a TPU v5e
(three iterations of a paged-decode kernel call and a small matmul, with the
harness's host spans around them), and a hand-built one."""
import os
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from bench.lib import trace as T  # noqa: E402

FIXTURE = Path(__file__).with_name("data") / "decode_kernel.xplane.pb"


def test_recorded_trace_busy_idle_and_kernel_time():
    red = T.reduce_planes(T.load(str(FIXTURE)))
    assert red["chips"] == 1
    # the window is the host spans' extent: three iterations of ~3.3 ms
    assert 0.009 < red["window_s"] < 0.011
    # three decode-kernel calls of ~13 us each, in the device's op line
    assert T.kernel_seconds(red["ops"], "paged_decode_attention") \
        == pytest.approx(3.9219e-05)
    assert 0 < red["busy_s"] < 0.001
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0.99 < idle < 1.0
    bd, by_span = T.breakdown(red)
    assert bd["device_ops"][0][0] == "paged_decode_attention"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # the long gaps fall in the 2 ms sleeps between iterations
    assert bd["idle_gaps"][0][0] == "bench.wait_arrival"
    assert by_span["bench.wait_arrival"] > by_span.get("engine.step", 0)
    assert sum(by_span.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_short_name():
    assert T.short_name("%paged_decode_attention.1 = bf16[4] custom-call(x)") \
        == "paged_decode_attention"
    assert T.short_name("%copy-start.41 = (bf16[1]) copy-start(y)") \
        == "copy-start"
    assert T.short_name("%fusion = f32[] fusion(z)") == "fusion"


def test_hand_built_trace():
    ev = T.Event
    dev = T.Plane("/device:TPU:0", [T.Line("XLA Ops", [
        ev("%a.1 = f32[] a()", 100, 50), ev("%b = f32[] b()", 120, 60),
        ev("%a.2 = f32[] a()", 400, 100)])])
    host = T.Plane("/host:CPU", [T.Line("python3", [
        ev("engine.step", 0, 300), ev("bench.wait_arrival", 300, 300)])])
    red = T.reduce_planes([dev, host])
    # union [100, 180) + [400, 500): 180 ns busy in a 600 ns window
    assert red["busy_s"] == pytest.approx(180e-9)
    assert red["window_s"] == pytest.approx(600e-9)
    assert red["ops"] == {"a": pytest.approx(150e-9),
                          "b": pytest.approx(60e-9)}
    gaps = sorted(red["gaps"], key=lambda g: -g[1])
    assert gaps[0] == ("engine.step", pytest.approx(220e-9))     # 180-400
    assert ("engine.step", pytest.approx(100e-9)) in gaps         # 0-100
    assert ("bench.wait_arrival", pytest.approx(100e-9)) in gaps  # 500-600


def test_no_device_ops_is_an_error():
    host = T.Plane("/host:CPU", [T.Line("python3", [
        T.Event("engine.step", 0, 10)])])
    with pytest.raises(ValueError):
        T.reduce_planes([host])
