"""The frontier read from trace files and Pond's month-long search,
through the harness at 16 servers on the CPU; and ``correct`` false
where their ingest, sweep or window skipping is broken underneath, or
where the control stands in for the program."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import csvtrace  # noqa: E402
import run  # noqa: E402
from answers import frontier_csv  # noqa: E402
from cell import Cell, load_json  # noqa: E402

CSV = "static15_c256_p16_d30.frontier_csv"
MONTH = "pond_c256_p16_d30.search"
# 2 days of 16 servers, in shards small enough that the searches stream
# and skip shards
SMALL = {"n_servers": 16, "trace_days": 2, "max_events_per_shard": 512,
         "server_gb": [96, 384, 4], "pool_gb": [0, 256, 3]}
SEED = 2 ** 31 + 17


@pytest.mark.parametrize("workload,metric", [(CSV, "ingest_s"),
                                             (MONTH, "unscanned_step_pct")])
def test_new_cell_through_the_harness_is_correct(workload, metric):
    r = run.run_cell(workload, SEED, 0.2, True, require_tpu=False,
                     overrides=SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert r["metrics"][metric]["value"] > 0
    # the CPU trace has no device plane
    assert "scanned_roofline_pct" not in r["metrics"]


def test_trace_files_hold_the_generated_traces():
    cell = _small_cell(CSV, SEED)
    for path, tr in zip(frontier_csv.trace_files(cell), cell.traces):
        cols = csvtrace.read(path)
        assert len(cols["arrival"]) == len(tr)
        np.testing.assert_array_equal(cols["cores"], tr.cores)
        np.testing.assert_array_equal(cols["mem_gb"], tr.mem_gb)
        # the file rounds times to the millisecond
        assert np.abs(cols["arrival"] - tr.arrival).max() <= 5e-4


def _shifted_load(real):
    """An ingest that moves one VM's arrival by a millisecond."""
    def load(*a, **k):
        vms = real(*a, **k)
        vms[len(vms) // 2].arrival += 1e-3
        return vms
    return load


def test_broken_ingest_is_not_correct(monkeypatch):
    from repro.core import traces
    monkeypatch.setattr(traces, "load_trace_file",
                        _shifted_load(traces.load_trace_file))
    r = run.run_cell(CSV, SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]
    assert r["checks"]["ingest_off_vms"]["value"] > 0


def test_broken_frontier_sweep_on_files_is_not_correct(monkeypatch):
    from repro.core import replay_engine
    cls = replay_engine.CompiledReplayStreamBatch
    real = cls.reject_rates
    monkeypatch.setattr(cls, "reject_rates",
                        lambda self, *a, **k: np.zeros_like(
                            real(self, *a, **k)))
    r = run.run_cell(CSV, SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]


def test_skipping_a_shard_too_many_is_not_correct(monkeypatch):
    # window skipping that skips one shard past what the reference
    # allows: lanes that would diverge there replay the reference
    from repro.core import replay_engine
    real = replay_engine._skip_count
    monkeypatch.setattr(
        replay_engine, "_skip_count",
        lambda ref, s, p, n: min(real(ref, s, p, n) + 1, n))
    r = run.run_cell(MONTH, SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]


def _small_cell(workload: str, seed: int) -> Cell:
    config_name, traffic_name = workload.split(".")
    config = load_json("configs", config_name)
    config["cluster"]["n_servers"] = SMALL["n_servers"]
    config["trace_days"] = SMALL["trace_days"]
    traffic = load_json("traffic", traffic_name)
    traffic.update({k: v for k, v in SMALL.items() if k in traffic})
    cell = Cell(config, traffic, seed)
    cell.make_traces()
    return cell


def test_frontier_csv_control_is_not_correct():
    cell = _small_cell(CSV, 2)
    checks = frontier_csv.check(cell, frontier_csv.control(cell))
    assert checks["ingest_off_vms"][0] == 0
    assert checks["reject_gap_vms"][0] > checks["reject_gap_vms"][1]
