"""The roofline count against a hand count, and each per-layer metric
reader on a synthetic context."""
import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline  # noqa: E402

PEAKS = json.loads((BENCH / "peaks.json").read_text())["kinds"]["TPU v5 lite"]


def test_sweep_work_matches_a_hand_count():
    # 2 traces with 10 + 20 arrivals (60 events), 3 calls of 16, 16 and
    # 64 lanes (96 in all), 256 servers in 32 groups:
    #   ops   = 7 * 256 * 96 * 30                  = 5,160,960
    #   bytes = 3 * 60 * 12 + 2 * 96 * 2 * (2 * 256 + 32) * 2
    #         = 2,160 + 417,792                     = 419,952
    ops, nbytes = roofline.sweep_work(arrivals=30, events=60, lanes=96,
                                      calls=3, traces=2, servers=256,
                                      groups=32)
    assert ops == 5_160_960
    assert nbytes == 419_952


def test_least_time_names_the_binding_bound():
    t, bound = roofline.least_time(393e12, 1.0, PEAKS)
    assert bound == "ops" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(1, 819e9, PEAKS)
    assert bound == "bytes" and t == pytest.approx(1.0)
    assert roofline.share_pct(393e12, 1.0, 4.0, PEAKS) == pytest.approx(25)


def _ctx(**kw):
    ctx = {"answers": 4,
           "obs": {"span.stream.compile.total_s": 2.0,
                   "span.stream.reference.total_s": 6.0,
                   "span.stream.upload_wait.total_s": 0.4,
                   "span.batch.reject_rates.count": 50,
                   "span.stream_batch.reject_rates.count": 10},
           "layers": {"policy": 1.2},
           "device": {"busy_s": 30.0, "window_s": 40.0, "kernel_s": 20.0,
                      "kernel_runs": 5},
           # 10 sweep modules ran, 5 of them whole in the trace
           "work": {"ops": 786e12, "bytes": 2.0, "steps": 4_000_000,
                    "runs": 10},
           "peaks": PEAKS}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("name,value", [
    ("policy_s", 0.3), ("prep_s", 2.0),
    ("upload_wait_s", 0.1), ("sweep_calls", 15.0),
    ("scan_us_per_step", 10.0), ("sweep_roofline_pct", 5.0),
    ("device_idle_pct", 25.0)])
def test_reader(name, value):
    reader = importlib.import_module(f"metrics.{name}")
    assert reader.read(_ctx()) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "policy_s", "prep_s", "upload_wait_s", "sweep_calls",
    "scan_us_per_step", "sweep_roofline_pct", "device_idle_pct"])
def test_reader_with_nothing_to_read_returns_none(name):
    empty = _ctx(obs={}, layers={}, device=None,
                 work={"ops": None, "bytes": None, "steps": 0, "runs": 0})
    assert importlib.import_module(f"metrics.{name}").read(empty) is None


def test_every_per_layer_metric_has_a_reader():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for metric in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{metric['name']}.py").exists()
