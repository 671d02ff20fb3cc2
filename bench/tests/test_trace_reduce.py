"""The trace reduction against a small trace recorded on one v5e (a jitted
matmul loop under the benchmark's host spans) and against hand-made
events with known answers."""

from pathlib import Path

import pytest

from trace_reduce import read_events, reduce_events

TRACE = Path(__file__).parent / "data" / "v5e_matmul_loop.xplane.pb"


def test_recorded_v5e_trace():
    devices, spans = read_events(TRACE)
    assert list(devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    assert len(ops) == 20                      # 5 calls x 4 ops
    window = [s for s in spans if s[0] == "bench.window"]
    assert len(window) == 1
    out = reduce_events(devices, spans)
    assert out["window_s"] == pytest.approx(
        (window[0][2] - window[0][1]) / 1e9)
    # the ops of this trace do not overlap: busy is their summed duration
    # inside the window (the trace places the device's clock about 1 ms
    # before the host's, so the first call's ops fall before the window)
    lo, hi = window[0][1], window[0][2]
    assert out["busy_s"] == pytest.approx(
        sum(max(0, min(e, hi) - max(s, lo)) for _, s, e in ops) / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["idle_share"] == pytest.approx(
        1 - out["busy_s"] / out["window_s"])
    names = [n for n, _ in out["device_ops"]]
    assert set(names[:2]) == {"fusion", "convolution_tanh_fusion"}
    # the five long gaps are the host's 2 ms sleeps inside bench.input
    assert [n for n, _ in out["idle_gaps"][:5]] == ["input"] * 5
    assert all(s > 0.002 for _, s in out["idle_gaps"][:5])


def test_union_window_and_labels():
    ms = 1_000_000
    devices = {
        "/device:TPU:0": [("%a = f32[] x", 1 * ms, 4 * ms),
                          ("%b = f32[] y", 3 * ms, 5 * ms),   # overlaps a
                          ("%a = f32[] x", 8 * ms, 9 * ms),
                          ("%c = f32[] z", 0, 2 * ms)],       # before window
        "/device:TPU:1": [("%a = f32[] x", 2 * ms, 6 * ms)],
    }
    spans = [("bench.window", 1 * ms, 11 * ms),
             ("bench.input", 5 * ms, 8 * ms),
             ("bench.block", 9 * ms, 11 * ms)]
    out = reduce_events(devices, spans, top=3)
    assert out["window_s"] == pytest.approx(0.010)
    # device 0: [1,5] + [8,9] = 5 ms; device 1: 4 ms; mean 4.5 ms
    assert out["busy_s"] == pytest.approx(0.0045)
    assert out["idle_share"] == pytest.approx(0.55)
    ops = dict(out["device_ops"])
    # a's self time loses the milliseconds that c (clipped) and b overlap
    assert ops["a"] == pytest.approx((1 + 1 + 4) / 2 / 1e3)
    assert ops["b"] == pytest.approx(2 / 2 / 1e3)
    assert ops["c"] == pytest.approx(1 / 2 / 1e3)
    gaps = out["idle_gaps"]
    assert gaps[0] == ["none", pytest.approx(0.005)] or \
        gaps[0][1] == pytest.approx(0.005)
    assert ["input", pytest.approx(0.003)] in gaps
    assert ["block", pytest.approx(0.002)] in gaps


def test_nested_ops_count_self_time():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("%while.1 = (f32[]) w", 0, 10 * ms),
                                 ("%fusion.2 = f32[] f", 1 * ms, 4 * ms),
                                 ("%fusion.3 = f32[] g", 5 * ms, 9 * ms)]}
    out = reduce_events(devices, [])
    assert out["busy_s"] == pytest.approx(0.010)
    assert dict(out["device_ops"]) == {"fusion.3": pytest.approx(0.004),
                                       "fusion.2": pytest.approx(0.003),
                                       "while.1": pytest.approx(0.003)}


def test_no_device_ops_reads_nothing():
    assert reduce_events({"/device:TPU:0": []}, []) == {}
