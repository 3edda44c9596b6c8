"""``correct`` comes out false when the timed path is broken underneath,
and when the control (the plain reference in 2 GB slices) stands in for
the program."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402
from answers import frontier, search  # noqa: E402
from cell import Cell, load_json  # noqa: E402
from test_bench_answers import SMALL  # noqa: E402

CELLS = {"search": "static15_c256_p16_d3.search",
         "frontier": "static15_c256_p16_d30.frontier"}
POND = "pond_c256_p16_d3.search"
SEED = 2  # a seed on which the 16-server control departs from the reference


def _altered(rates):
    """One (trace, lane) reject count off by one where it is made: lane
    1, which in the frontier is the tight lane the check always
    replays."""
    out = np.array(rates, float)
    out[0, min(1, out.shape[1] - 1)] += 1.0 / 375
    return out


def _half_batch(rates):
    """The first half of the traces priced, the rest copied from it."""
    out = np.array(rates, float)
    out[len(out) // 2:] = out[:len(out) // 2]
    return out


def _unchanged(rates):
    """The sweep leaves its state as it found it: nothing rejected."""
    return np.zeros_like(np.asarray(rates, float))


@pytest.mark.parametrize("fault", [_altered, _half_batch, _unchanged])
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_broken_sweep_is_not_correct(kind, fault, monkeypatch):
    from repro.core import replay_engine
    for cls in (replay_engine.CompiledReplayBatch,
                replay_engine.CompiledReplayStreamBatch):
        real = cls.reject_rates
        monkeypatch.setattr(
            cls, "reject_rates",
            lambda self, *a, _real=real, **k: fault(_real(self, *a, **k)))
    r = run.run_cell(CELLS[kind], SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]


def _small_cell(workload: str) -> Cell:
    config_name, traffic_name = workload.split(".")
    config = load_json("configs", config_name)
    config["cluster"]["n_servers"] = SMALL["n_servers"]
    config["trace_days"] = SMALL["trace_days"]
    traffic = load_json("traffic", traffic_name)
    traffic.update({k: v for k, v in SMALL.items() if k in traffic})
    cell = Cell(config, traffic, SEED)
    cell.make_traces()
    return cell


def test_frontier_control_is_not_correct():
    cell = _small_cell(CELLS["frontier"])
    checks = frontier.check(cell, frontier.control(cell))
    assert checks["reject_gap_vms"][0] > checks["reject_gap_vms"][1]


@pytest.mark.parametrize("workload", [CELLS["search"], POND])
def test_search_control_is_not_correct(workload):
    cell = _small_cell(workload)
    checks = search.check(cell, search.control(cell))
    assert any(v > lim for v, lim in checks.values()), checks


def test_inflated_pool_search_is_not_correct(monkeypatch):
    # the pool search returns more pool than the least feasible one
    from repro.core import replay_engine
    real = replay_engine.pool_search_multi
    monkeypatch.setattr(
        replay_engine, "pool_search_multi",
        lambda *a, **k: 1.5 * real(*a, **k) + 4.0)
    r = run.run_cell(CELLS["search"], SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]
    c = r["checks"]["pool_excess_pct"]
    assert c["value"] > c["limit"]


def _all_local(real, vms, policy, cp=None, *a, **k):
    return real(vms, "local", None, *a, **k)


def _no_predictor(real, vms, policy, cp=None, *a, **k):
    # Pond's walk with the UM model's quantile read as 0: nothing pooled
    # but the fully pooled VMs
    cp.um_model = None
    return real(vms, policy, cp, *a, **k)


@pytest.mark.parametrize("fault", [_all_local, _no_predictor])
def test_wrong_pond_split_is_not_correct(fault, monkeypatch):
    from repro.core import cluster_sim
    real = cluster_sim.policy_decisions
    monkeypatch.setattr(cluster_sim, "policy_decisions",
                        lambda *a, **k: fault(real, *a, **k))
    r = run.run_cell(POND, SEED, 0.2, False, require_tpu=False,
                     overrides=SMALL)
    assert not r["correct"], r["checks"]
    assert r["checks"]["decisions_off_vms"]["value"] > 0
