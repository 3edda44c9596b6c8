"""The trace reduction: on hand-made events, and on a small trace
recorded on a TPU v5e (``data/small.xplane.pb``: one traced window of
``bench/run.py`` at 16 servers)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_summary_of_hand_made_events():
    # window 0..100 ns; modules: one sweep 10-40, one other 60-70 (the
    # device is busy while they run); ops 10-30, 20-40 and 60-70; the
    # host was in bench.policy over 40-60 and in bench.sweep over 0-40
    events = {
        "host": [("bench.window", 0.0, 100.0), ("bench.sweep", 0.0, 40.0),
                 ("bench.policy", 40.0, 60.0)],
        "devices": [{
            "ops": [("while", 10.0, 30.0), ("fusion", 20.0, 40.0),
                    ("copy", 60.0, 70.0)],
            "modules": [("jit_sweep_carry(1)", 10.0, 40.0),
                        ("jit_other(2)", 60.0, 70.0)]}]}
    s = devtrace.summarize(events)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)          # 10-40 and 60-70
    assert s["kernel_s"] == pytest.approx(30e-9)
    assert s["kernel_runs"] == 1 and not s["cut"]
    assert s["device_ops"][0] == ["while", pytest.approx(20e-9)]
    gaps = {name: sec for name, sec in s["idle_gaps"]}
    assert gaps["bench.policy"] == pytest.approx(20e-9)  # 40-60
    assert gaps["bench.window"] == pytest.approx(30e-9)  # 70-100
    assert s["idle_gaps"][0] == ["bench.window", pytest.approx(30e-9)]


def test_no_window_or_no_device_gives_nothing():
    assert devtrace.summarize({"host": [], "devices": [
        {"ops": [("x", 0.0, 1.0)], "modules": []}]}) is None
    assert devtrace.summarize({"host": [("bench.window", 0.0, 1.0)],
                               "devices": []}) is None


def test_recorded_trace():
    # one 12-lane frontier answer at 16 servers, one shard of 256 steps
    s = devtrace.summarize(devtrace.read(RECORDED))
    assert s is not None and not s["cut"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["kernel_runs"] == 1
    assert 0 < s["kernel_s"] <= s["busy_s"] * 1.0001
    assert s["device_ops"][0][0].startswith("%while")
    assert s["idle_gaps"]


def test_program_spans_name_the_gaps():
    # a program span (ns from the window's start) covers the 40-60 gap
    events = {
        "host": [("bench.window", 1000.0, 1100.0),
                 ("bench.sweep", 1000.0, 1100.0)],
        "devices": [{"ops": [("while", 1010.0, 1040.0),
                             ("while", 1060.0, 1100.0)], "modules": []}],
        # no module line: the ops are the busy time
        "program_spans": [("obs.stream.reference", 35.0, 65.0)]}
    s = devtrace.summarize(events)
    assert s["idle_gaps"][0] == ["obs.stream.reference",
                                 pytest.approx(20e-9)]
    assert s["busy_s"] == pytest.approx(70e-9)


def test_a_cut_trace_is_read_up_to_the_cut():
    # the device's buffer ran out at 50 ns: the sweep run 40-60 is not
    # whole, and the window ends at the cut
    events = {
        "host": [("bench.window", 0.0, 100.0)],
        "devices": [{"ops": [], "cut_at": 50.0,
                     "modules": [("jit_sweep(1)", 10.0, 30.0),
                                 ("jit_sweep(1)", 40.0, 60.0)]}]}
    s = devtrace.summarize(events)
    assert s["cut"] and s["window_s"] == pytest.approx(50e-9)
    assert s["kernel_runs"] == 1
    assert s["kernel_s"] == pytest.approx(20e-9)
    assert s["busy_s"] == pytest.approx(30e-9)      # 10-30 and 40-50


def test_a_cut_before_any_program_is_not_a_cut():
    events = {
        "host": [("bench.window", 0.0, 100.0)],
        "devices": [{"ops": [], "cut_at": 5.0,
                     "modules": [("jit_sweep(1)", 10.0, 30.0)]}]}
    s = devtrace.summarize(events)
    assert not s["cut"] and s["window_s"] == pytest.approx(100e-9)
