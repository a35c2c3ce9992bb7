"""The trace reduction on small traces: busy union, clipping to the
window, kernel and program time, exposed host time, idle gaps by open
span."""
import json
import os

import pytest

from bench import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev(HOST, "python", "bench.window", 100, 1000),
        ev(HOST, "python", "bench.engine_call", 100, 400),
        ev(HOST, "python", "bench.dispatch", 100, 50),
        ev(HOST, "python", "bench.finalize", 150, 350),
        ev(HOST, "python", "bench.engine_call", 600, 400),
        # ops: one before the window (clipped), overlapping pair, kernel
        ev(DEV, "XLA Ops", "fusion.1", 50, 100),           # 100..150 in
        ev(DEV, "XLA Ops", "distance_topk_gather_alive_kernel", 200, 200),
        ev(DEV, "XLA Ops", "fusion.2", 350, 100),          # overlaps
        ev(DEV, "XLA Ops", "distance_topk_gather_alive_kernel", 650, 300),
        ev(DEV, "XLA Modules", "jit__megastep(123)", 150, 300),
        ev(DEV, "XLA Modules", "jit__megastep(123)", 650, 300),
        ev(DEV, "XLA Ops", "late", 1200, 50),              # after window
    ]


def test_busy_union_clipped_to_window():
    red = tr.reduce_events(synthetic())
    assert red.window == (100.0, 1100.0)
    assert red.n_devices == 1
    # [100,150) + [200,450) + [650,950)
    assert red.busy[DEV] == [(100.0, 150.0), (200.0, 450.0),
                             (650.0, 950.0)]
    assert red.busy_ns() == 50 + 250 + 300
    assert red.busy_ns(100, 500) == 50 + 250


def test_kernel_and_program_time():
    red = tr.reduce_events(synthetic())
    assert red.op_ns(r"distance_topk_gather") == (500.0, 2)
    assert red.module_ns(r"_megastep") == (600.0, 2)


def test_exposed_host_time_per_call():
    red = tr.reduce_events(synthetic())
    # call 1: 400 wall - 300 busy = 100 ns; call 2: 400 - 300 = 100 ns
    assert tr.exposed_ms(red) == pytest.approx(100 / 1e6)
    assert tr.exposed_ms(red, "bench.none") is None


def test_idle_gaps_labelled_by_innermost_open_span():
    red = tr.reduce_events(synthetic())
    gaps = tr.idle_gaps(red)
    # [150,200) inside finalize; [450,500) inside finalize; [500,600)
    # between calls; [600,650) and [950,1000) inside the second call;
    # [1000,1100) after it
    assert gaps == [("bench.finalize", 50e-9), ("bench.finalize", 50e-9),
                    ("bench.window", 100e-9), ("bench.engine_call", 50e-9),
                    ("bench.engine_call", 50e-9), ("bench.window", 100e-9)]
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["distance_topk_gather_alive_kernel",
                                  pytest.approx(500e-9)]
    assert dict((k, v) for k, v in b["idle_gaps"]) == pytest.approx(
        {"bench.finalize": 100e-9, "bench.window": 200e-9,
         "bench.engine_call": 100e-9})


def test_window_must_be_unique():
    with pytest.raises(ValueError):
        tr.reduce_events([e for e in synthetic()
                          if e.name != "bench.window"])


def test_merge_intervals():
    assert tr.merge_intervals([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) \
        == [(1, 4), (5, 8)]


def recorded():
    with open(os.path.join(FIXTURES, "bulk_two_calls.json")) as f:
        return [tr.Event(*row) for row in json.load(f)["events"]]


def test_recorded_chip_trace():
    """Two 4,096-query calls of ``sift1m.bulk`` on a TPU v5 lite: the
    Mosaic gather kernel is nearly all of the megastep program, and the
    device is idle about 1% of the window, mostly while the results are
    fetched."""
    red = tr.reduce_events(recorded())
    assert red.n_devices == 1
    assert len(red.spans_named("bench.engine_call")) == 2
    kernel_ns, n_kernel = red.op_ns(r'custom_call_target="tpu_custom_call"')
    module_ns, n_module = red.module_ns(r"_megastep")
    assert n_kernel == n_module == 2
    assert kernel_ns == pytest.approx(1277480755.0)
    assert module_ns == pytest.approx(1299616604.0)
    assert red.busy_ns() == pytest.approx(1299613406.0)
    assert red.window_ns == pytest.approx(1310950612.0)
    assert tr.exposed_ms(red) == pytest.approx(5.658343)
    b = tr.breakdown(red, 3)
    assert b["device_ops"][0] == ["%_megastep.1 (Mosaic kernel)",
                                  pytest.approx(1.277480755)]
    assert b["idle_gaps"][0][0] == "bench.finalize"
    idle = sum(v for _, v in tr.breakdown(red)["idle_gaps"])
    assert idle == pytest.approx((red.window_ns - red.busy_ns()) / 1e9)
