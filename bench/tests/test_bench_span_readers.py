"""The readers of the program's engine spans and step counter
(``engine_build_s``, ``sweep_wait_s``, ``wait_us_per_step``) on a
synthetic context, and silence where a program records none of them."""
import importlib
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

SEARCH_OBS = {"span.replay.compile.total_s": 0.6,
              "span.batch.upload.total_s": 0.2,
              "span.batch.compute.total_s": 14.0,
              "sweep.steps": 1_000_000,
              "span.batch.reject_rates.total_s": 15.0}
FRONTIER_OBS = {"span.stream.compile.total_s": 3.8,
                "span.stream.compute.total_s": 16.0,
                "sweep.steps": 720_000}


def _read(name, obs, answers=2):
    reader = importlib.import_module(f"metrics.{name}")
    return reader.read({"answers": answers, "obs": obs, "layers": {},
                        "device": None, "work": None, "peaks": None})


@pytest.mark.parametrize("name,obs,value", [
    ("engine_build_s", SEARCH_OBS, 0.4),
    ("engine_build_s", {"span.batch.upload.total_s": 0.2}, 0.1),
    ("sweep_wait_s", SEARCH_OBS, 7.0),
    ("sweep_wait_s", FRONTIER_OBS, 8.0),
    ("sweep_wait_s", {"span.replay.compute.total_s": 1.0,
                      "span.batch.compute.total_s": 2.0,
                      "span.stream.compute.total_s": 3.0}, 3.0),
    ("wait_us_per_step", SEARCH_OBS, 14.0),
    ("wait_us_per_step", FRONTIER_OBS, 1e6 * 16.0 / 720_000)])
def test_span_reader(name, obs, value):
    assert _read(name, obs) == pytest.approx(value)


@pytest.mark.parametrize("name,obs", [
    ("engine_build_s", {}),
    ("engine_build_s", FRONTIER_OBS),      # streamed engines only
    ("sweep_wait_s", {}),
    ("sweep_wait_s", {"sweep.steps": 10}),
    ("wait_us_per_step", {}),
    # a program that times its scans but counts no steps
    ("wait_us_per_step", {"span.stream.compute.total_s": 16.0}),
    ("wait_us_per_step", {"sweep.steps": 10})])
def test_span_reader_with_nothing_to_read_returns_none(name, obs):
    assert _read(name, obs) is None
