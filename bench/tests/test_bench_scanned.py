"""The roofline of the scanned work against a hand count, the readers of
``ingest_s``, ``unscanned_step_pct`` and ``scanned_roofline_pct`` on a
synthetic context, and the plain trace-file reader against the
program's writer."""
import importlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import csvtrace  # noqa: E402
import roofline_scanned  # noqa: E402

PEAKS = json.loads((BENCH / "peaks.json").read_text())["kinds"]["TPU v5 lite"]


def test_scanned_work_matches_a_hand_count():
    # 3 dispatches of 2 traces on 256 servers in 32 groups: 4 lanes over
    # 10 + 20 arrivals, 2 lanes over 5 + 5, 2 lanes over 0 + 1
    #   lane_arrivals = 4 * 30 + 2 * 10 + 2 * 1     = 142
    #   fit_cells     = 256 * 142                   = 36,352
    #   ops           = 7 * 36,352                  = 254,464
    #   events        = 60 + 20 + 2                 = 82
    #   carry_cells   = 2 * 2 * (4 + 2 + 2) * (2 * 256 + 32) = 17,408
    #   bytes         = 12 * 82 + 2 * 17,408        = 35,800
    ops, nbytes = roofline_scanned.scanned_work(
        fit_cells=36_352, events=82, carry_cells=17_408)
    assert ops == 254_464
    assert nbytes == 35_800


OBS = {"span.ingest.load.total_s": 30.0, "span.ingest.load.count": 4,
       "stream.steps_unscanned": 250, "sweep.steps": 750,
       "sweep.kernel.pallas": 10, "sweep.fit_cells": 393e12 / 7,
       "sweep.events_scanned": 0, "sweep.carry_cells": 1}
DEVICE = {"busy_s": 3.0, "window_s": 40.0, "kernel_s": 2.0,
          "kernel_runs": 5}


def _ctx(obs=OBS, device=DEVICE):
    return {"answers": 2, "obs": obs, "layers": {}, "device": device,
            "work": None, "peaks": PEAKS}


@pytest.mark.parametrize("name,value", [
    ("ingest_s", 15.0), ("unscanned_step_pct", 25.0),
    # 1 s of int8 work, half of it in the 5 of 10 dispatches the trace
    # holds whole, over their 2 s
    ("scanned_roofline_pct", 25.0)])
def test_reader(name, value):
    reader = importlib.import_module(f"metrics.{name}")
    assert reader.read(_ctx()) == pytest.approx(value)


def test_unscanned_step_pct_reads_zero_where_nothing_was_left():
    reader = importlib.import_module("metrics.unscanned_step_pct")
    ctx = _ctx(obs=dict(OBS, **{"stream.steps_unscanned": 0}))
    assert reader.read(ctx) == 0.0


# the parent's program counts neither the unscanned steps nor the work
PARENT_OBS = {"sweep.steps": 750, "sweep.kernel.pallas": 10,
              "span.stream.compute.count": 10}


@pytest.mark.parametrize("name,obs,device", [
    ("ingest_s", {}, DEVICE),
    ("ingest_s", PARENT_OBS, DEVICE),
    ("unscanned_step_pct", {}, DEVICE),
    ("unscanned_step_pct", PARENT_OBS, DEVICE),
    ("scanned_roofline_pct", {}, DEVICE),
    ("scanned_roofline_pct", PARENT_OBS, DEVICE),
    ("scanned_roofline_pct", OBS, None),          # no device trace
    ("scanned_roofline_pct", OBS, dict(DEVICE, kernel_runs=0))])
def test_reader_with_nothing_to_read_returns_none(name, obs, device):
    reader = importlib.import_module(f"metrics.{name}")
    assert reader.read(_ctx(obs, device)) is None


def test_plain_reader_reads_what_the_program_writes(tmp_path):
    from repro.core import traces
    vms = traces.Population(seed=0).sample_vms(300, 2 * 86400.0, seed=3)
    path = tmp_path / "trace.csv.gz"
    traces.save_trace_csv(vms, str(path))
    cols = csvtrace.read(path)
    loaded = traces.load_trace_file(str(path))
    for name in csvtrace.COLUMNS:
        np.testing.assert_array_equal(
            cols[name], np.array([getattr(v, name) for v in loaded], float))
    assert np.abs(cols["arrival"]
                  - np.sort([v.arrival for v in vms])).max() <= 5e-4
    np.testing.assert_array_equal(np.sort(cols["mem_gb"]),
                                  np.sort([v.mem_gb for v in vms]))
