"""The reader of the Pond policy's percentile span (``percentiles_s``)
on a synthetic context, and silence where a program records no such
span."""
import importlib
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

POND_OBS = {"span.policy.decide.total_s": 3.2,
            "span.policy.percentiles.total_s": 1.3,
            "policy.percentile_rows": 360_000}


def _read(obs, answers=2):
    reader = importlib.import_module("metrics.percentiles_s")
    return reader.read({"answers": answers, "obs": obs, "layers": {},
                        "device": None, "work": None, "peaks": None})


def test_percentiles_reader_reads_span_per_answer():
    assert _read(POND_OBS) == pytest.approx(0.65)


@pytest.mark.parametrize("obs", [
    {},
    # a program whose policy records no percentile span
    {"span.policy.decide.total_s": 3.2}])
def test_percentiles_reader_with_nothing_to_read_returns_none(obs):
    assert _read(obs) is None
