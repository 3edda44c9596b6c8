"""One search answer and one frontier answer through the harness, at 16
servers on the CPU; and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SMALL = {"n_servers": 16, "trace_days": 1, "max_events_per_shard": 1024,
         "server_gb": [96, 384, 4], "pool_gb": [0, 256, 3]}


@pytest.mark.parametrize("workload", ["static15_c256_p16_d3.search",
                                      "static15_c256_p16_d30.frontier",
                                      "pond_c256_p16_d3.search"])
def test_answer_through_the_harness_is_correct(workload):
    r = run.run_cell(workload, 2 ** 31 + 17, 0.5, False,
                     require_tpu=False, overrides=SMALL)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"answer_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_traced_run_reads_the_layers():
    # with --trace 1 the per-layer metrics come from the layer clock and
    # the program's spans; the CPU trace has no device plane, so the
    # device metrics stay out
    r = run.run_cell("static15_c256_p16_d30.frontier", 5, 0.2, True,
                     require_tpu=False, overrides=SMALL)
    assert r["correct"], r["checks"]
    assert "policy_s" in r["metrics"] and "prep_s" in r["metrics"]
    assert "device_idle_pct" not in r["metrics"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "static15_c256_p16_d3.search", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_every_cell_names_existing_files():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert (BENCH / "configs" / f"{w['config']}.json").exists()
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "answers" / f"{traffic['answer']}.py").exists()
    for c in bench["configs"]:
        assert json.loads((BENCH.parent / c["file"]).read_text())[
            "name"] == c["name"]
