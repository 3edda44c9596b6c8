"""``chip_smoke.py`` rehearsed on the CPU at a small size.

The script refuses to run anywhere but on a TPU; these tests call its
phase function directly to check the main path's wiring (oracle parity,
no numpy fallback) and check that the refusal itself happens.
"""
import importlib.util
import os

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_path_small_matches_oracle(chip_smoke):
    report = chip_smoke.main_path(n_servers=16, days=3.0, k=2, n_vms=2000,
                                  max_events_per_shard=1024)
    assert report["ok"]
    assert len(report["oracle"]) == 6
    assert all(o["equal"] for o in report["oracle"])
    assert report["backend_numpy"] == 0
    assert report["n_shards"] > 1 and report["device_scans"] > 0
    assert report["sweep_calls"] > 0


def test_refuses_without_tpu(chip_smoke):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (None, 0)
