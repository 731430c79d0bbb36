"""bench/trace_reduce.py on hand-made events and on a trace recorded on
a TPU v5e (``data/v5e_sample.xplane.pb``: three 512x512 matmul+tanh
programs inside a ``bench_window`` annotation)."""
import os

import pytest

from bench import trace_reduce

SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "v5e_sample.xplane.pb")


def events():
    # window 1000..2000 ns; device 0 busy 1100-1300 and 1250-1400 (union
    # 300 ns) and 1900-2100 (100 ns inside); device 1 busy 1500-1600
    return {
        "host": {"bench-profiler": [("bench_window", 1000.0, 1000.0)],
                 "topo-shard-0": [("PjitFunction(step)", 1400.0, 450.0),
                                  ("harvest", 1420.0, 100.0)]},
        "devices": {
            "/device:TPU:1": [("cg", 1500.0, 100.0)],
            "/device:TPU:0": [("fusion.1", 1100.0, 200.0),
                              ("cg", 1250.0, 150.0),
                              ("cg", 1900.0, 200.0),
                              ("before", 100.0, 50.0)],
        },
    }


def test_busy_is_the_union_inside_the_window():
    out = trace_reduce.reduce(events(), chips=2)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"]["/device:TPU:0"] == pytest.approx(400e-9)
    assert out["busy_s"]["/device:TPU:1"] == pytest.approx(100e-9)


def test_only_the_cells_chips_count():
    out = trace_reduce.reduce(events(), chips=1)
    assert list(out["busy_s"]) == ["/device:TPU:0"]


def test_breakdown():
    out = trace_reduce.reduce(events(), chips=1)["breakdown"]
    assert out["device_ops"][0] == ["cg", pytest.approx(250e-9)]
    gaps = out["idle_gaps"]
    # gaps of device 0: 1400-1900 (500 ns), 1000-1100 (100 ns)
    assert gaps[0][1] == pytest.approx(500e-9)
    assert gaps[0][0] == "PjitFunction(step)"
    assert gaps[1] == ["no host event", pytest.approx(100e-9)]


def test_missing_window_is_an_error():
    ev = events()
    ev["host"]["bench-profiler"] = []
    with pytest.raises(ValueError, match="bench_window"):
        trace_reduce.reduce(ev, chips=1)


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded trace")
def test_recorded_v5e_trace():
    out = trace_reduce.reduce(trace_reduce.load(SAMPLE), chips=1)
    assert list(out["busy_s"]) == ["/device:TPU:0"]
    busy, window = out["busy_s"]["/device:TPU:0"], out["window_s"]
    assert 0 < busy < window
    assert out["breakdown"]["device_ops"]
