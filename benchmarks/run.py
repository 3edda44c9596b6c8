"""Benchmark runner: one module per paper figure/table + roofline report.

  PYTHONPATH=src python -m benchmarks.run [--full]
  PYTHONPATH=src python -m benchmarks.run --perf-smoke

``--perf-smoke`` times only the fig3 quick path on the batched replay
engine and emits ``experiments/BENCH_replay.json`` (wall seconds,
candidate-events/sec, measured speedup vs the scalar oracle) so future
PRs can track the replay-throughput trajectory.  Every run is stamped
with its provenance (git sha, jax backend, device kind, timestamp);
with ``POND_TRACE=1`` the engine counters (jit-cache hits/misses,
padding waste, shard spans) are merged in and a Chrome trace lands at
``experiments/trace_perf_smoke.json`` (view on ui.perfetto.dev).

Every run keeps jax's persistent compilation cache
(``repro.core.compile_cache``): in ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else in ``<repo>/.jax_cache``, so a second run skips XLA
compilation.  With ``POND_TRACE=1`` the per-family ``jit.*.lower``
spans quantify the cold-vs-warm lowering cost (summed into the
``jit_lower_total_s`` bench key).  The runner exits non-zero when any
module raised.

Multi-device keys (``device_*``, ``overlap_ratio``) record the
trace-axis-sharded stream batch; CPU-only hosts must export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run
for the stage to engage (it records itself skipped otherwise).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

from repro.core import compile_cache

MODULES = [
    "benchmarks.azure_e2e",
    "benchmarks.fig2_stranding",
    "benchmarks.fig3_poolsize",
    "benchmarks.fig4_sensitivity",
    "benchmarks.fig7_latency",
    "benchmarks.fig16_spill",
    "benchmarks.fig17_li_model",
    "benchmarks.fig17_sensitivity",
    "benchmarks.fig18_um_model",
    "benchmarks.fig20_combined",
    "benchmarks.fig21_e2e",
    "benchmarks.fig_availability",
    "benchmarks.fig_topology",
    "benchmarks.kernel_bench",
    "benchmarks.latency_bench",
    "benchmarks.roofline",
]


def _fail_family_probe():
    """Tiny availability sweep so the ``jit.fail.*`` cache family shows
    up in the perf-smoke counters (``fig_availability`` itself is not
    part of the smoke path)."""
    from benchmarks import common
    from repro.core import cluster_sim, replay_engine
    from repro.runtime.fault import FailureSchedule
    horizon = 86400.0
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                    gb_per_core=4.0)
    vms = common.population().sample_vms(400, horizon, seed=3,
                                         start_id=9 * 10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    sched = FailureSchedule.generate(horizon, cfg.n_groups, 6 * 3600.0,
                                     1800.0, seed=0)
    eng = replay_engine.CompiledReplay(vms, dec, cfg,
                                       failure_schedule=sched)
    full_gb = cfg.gb_per_core * cfg.cores_per_server
    t0 = time.time()
    r = eng.availability([full_gb, full_gb * 0.8], [64.0, 64.0])
    return {"n_vms": len(vms), "n_failures": int(sched.n_failures),
            "wall_s": round(time.time() - t0, 3),
            "reject_rates": [round(float(x), 6) for x in r.reject_rate]}


def perf_smoke(cache_dir: str):
    """Time the fig3 quick path; emit experiments/BENCH_replay.json.

    Alongside the single-trace engine numbers this records the
    multi-trace batch benchmark (the K=8 seed batch priced in ONE
    vmapped sweep vs looping the engine per seed, on a 16-point frontier
    and on the narrow 2-probe shape where per-seed sweeps are
    fixed-cost-dominated) and the sharded streaming benchmark
    (``CompiledReplayStream``: events/s, shard count, peak shard bytes,
    overhead vs the monolithic sweep — the cost of bounding peak
    event-tensor memory).

    Since the compiled policy engine (``core/policy_engine.py``) it
    also records policy-decision throughput — compiled pond decisions
    on a >=100k-VM trace (VMs/s, speedup vs the scalar control-plane
    walk, bit-exactness on the timed subset) — plus the (tau x fp)
    grid-sweep benchmark behind ``benchmarks/fig17_sensitivity.py``.

    Since the latency/QoS grid engine (``core/latency_engine.py``) it
    also records the ``latency_*`` keys from
    ``benchmarks/latency_bench.py``: the slowdown-band, zNUMA-spill and
    LI+Eq.(1) grid passes timed against the scalar figure loops they
    replaced (grid cells, wall seconds, per-pass speedups — each gated
    at >=5x — and bitwise parity vs the scalar oracles).

    Since the unified sweep core it additionally records the
    ``stream_batch_*`` keys from ``benchmarks/azure_e2e.py``: the
    K-seed batched streaming sweep (``CompiledReplayStreamBatch``) vs
    looping the streaming engine per seed at the same shard budget,
    and the end-to-end chunked-dump replay (ingest VMs/s,
    candidate-events/s, peak shard bytes).

    Since the multi-pod fleet engine it also records the ``topology_*``
    keys from ``benchmarks/fig_topology.py``: the compiled topology
    grid (one pod scan pricing every (savings, pool-budget, topology)
    lane) timed against the scalar ``replay_multi_pool`` oracle loop —
    gated at >=5x — plus its bit-exactness verdict.

    Since the device-sharding layer it also records the ``device_*``
    keys from ``azure_e2e.device_shard_bench``: the K-seed stream
    batch with its trace axis ``shard_map``-partitioned across every
    visible jax device vs the single-device sweep (ms, events/s,
    speedup, bit-exactness) plus the double-buffer ``overlap_ratio``
    (fraction of shard-upload time hidden behind compute).  Export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first on
    CPU-only hosts or the stage records itself skipped.
    """
    from benchmarks import (azure_e2e, fig3_poolsize, fig17_sensitivity,
                            fig_topology, latency_bench)
    from repro.core import obs
    rec = obs.get_recorder()
    t0 = time.time()
    res = fig3_poolsize.run(quick=True)
    wall = time.time() - t0          # fig3-only: comparable across PRs
    e2e_res = azure_e2e.run(quick=True)
    t1 = time.time()
    policy = fig17_sensitivity.policy_decision_bench()
    print(f"  policy decisions: {policy['n_vms']} VMs in "
          f"{policy['compiled_s']}s ({policy['vms_per_sec']:.0f} VMs/s, "
          f"{policy['speedup_vs_scalar']}x vs scalar walk, "
          f"bit_exact={policy['bit_exact_subset']})")
    grid_res = fig17_sensitivity.run(quick=True)
    policy_wall = time.time() - t1
    lat = latency_bench.latency_bench(quick=True)
    print(f"  latency grids: {lat['grid_cells']} cells in "
          f"{lat['wall_s']}s (min {lat['min_speedup']}x vs scalar "
          f"figure loops, bit_exact={lat['bit_exact']})")
    topo = fig_topology.run(quick=True)
    fail = _fail_family_probe()
    print(f"  fail-family probe: {fail['n_vms']} VMs, "
          f"{fail['n_failures']} failures in {fail['wall_s']}s")
    batched = res.get("batched", {})
    narrow = batched.get("narrow2", {})
    streaming = res.get("streaming", {})
    sb = e2e_res.get("stream_batch", {})
    dev = e2e_res.get("device_shard", {})
    e2e = e2e_res.get("e2e", {})
    bench = {
        "benchmark": "fig3_poolsize.quick",
        "wall_s": round(wall, 3),
        "savings_wall_s": res.get("wall_s"),
        "events_per_sec": res.get("engine", {}).get("events_per_sec"),
        "candidate_events": res.get("engine", {}).get("candidate_events"),
        "replay_speedup_vs_scalar": res.get("replay_speedup"),
        "batched_k": batched.get("k"),
        "batched_bit_exact": all(
            batched.get(s, {}).get("bit_exact", False)
            for s in ("frontier16", "narrow2")),
        "batched_speedup_vs_seed_loop": narrow.get("speedup"),
        "batched_speedup_shape": "narrow2 (2 probes/seed)",
        "batched_frontier_speedup": batched.get("frontier16",
                                                {}).get("speedup"),
        "batched_events_per_sec": batched.get("frontier16",
                                              {}).get("events_per_sec"),
        "streaming_n_shards": streaming.get("n_shards"),
        "streaming_max_events_per_shard":
            streaming.get("max_events_per_shard"),
        "streaming_peak_shard_bytes": streaming.get("peak_shard_bytes"),
        "streaming_events_per_sec": streaming.get("events_per_sec"),
        "streaming_overhead_vs_monolithic":
            streaming.get("overhead_vs_monolithic"),
        "streaming_bit_exact": streaming.get("bit_exact"),
        "stream_batch_k": sb.get("k"),
        "stream_batch_n_shards": sb.get("n_shards"),
        "stream_batch_max_events_per_shard":
            sb.get("max_events_per_shard"),
        "stream_batch_peak_shard_bytes": sb.get("peak_shard_bytes"),
        "stream_batch_speedup_vs_stream_loop": sb.get("speedup"),
        "stream_batch_events_per_sec": sb.get("events_per_sec"),
        "stream_batch_bit_exact": sb.get("bit_exact"),
        "stream_batch_e2e_n_vms": e2e.get("n_vms"),
        "stream_batch_e2e_ingest_vms_per_sec":
            e2e.get("ingest_vms_per_sec"),
        "stream_batch_e2e_events_per_sec": e2e.get("events_per_sec"),
        "stream_batch_e2e_vms_per_sec": e2e.get("vms_per_sec"),
        "stream_batch_e2e_peak_shard_bytes": e2e.get("peak_shard_bytes"),
        "stream_batch_claims_pass": all(
            c["ok"] for c in e2e_res.get("claims", [])),
        "device_n_devices": dev.get("n_devices"),
        "device_skipped": dev.get("skipped"),
        "device_stream_batch_ms": dev.get("device_ms"),
        "device_single_ms": dev.get("single_ms"),
        "device_speedup_vs_single": dev.get("speedup_vs_single"),
        "device_stream_batch_events_per_sec": dev.get("events_per_sec"),
        "device_bit_exact": dev.get("bit_exact"),
        "overlap_ratio": dev.get("overlap_ratio"),
        "policy_bench_wall_s": round(policy_wall, 3),
        "policy_n_vms": policy.get("n_vms"),
        "policy_vms_per_sec": policy.get("vms_per_sec"),
        "policy_compiled_s": policy.get("compiled_s"),
        "policy_speedup_vs_scalar": policy.get("speedup_vs_scalar"),
        "policy_bit_exact": policy.get("bit_exact_subset"),
        "policy_grid_cells": grid_res.get("grid_cells"),
        "policy_grid_wall_s": grid_res.get("grid_wall_s"),
        "policy_grid_pricing_wall_s": grid_res.get("pricing_wall_s"),
        "policy_grid_claims_pass": all(
            c["ok"] for c in grid_res.get("claims", [])),
        "latency_grid_cells": lat.get("grid_cells"),
        "latency_wall_s": lat.get("wall_s"),
        "latency_min_speedup_vs_scalar": lat.get("min_speedup"),
        "latency_bands_speedup": lat["passes"]["bands"]["speedup"],
        "latency_spill_speedup": lat["passes"]["spill"]["speedup"],
        "latency_combine_speedup": lat["passes"]["combine"]["speedup"],
        "latency_bit_exact": lat.get("bit_exact"),
        "latency_claims_pass": bool(
            lat.get("bit_exact") and lat.get("min_speedup", 0.0) >= 5.0),
        "topology_lanes": topo.get("n_lanes"),
        "topology_events": topo.get("n_events"),
        "topology_compiled_s": topo.get("compiled_s"),
        "topology_oracle_s": topo.get("oracle_s"),
        "topology_speedup_vs_oracle": topo.get("speedup_vs_oracle"),
        "topology_bit_exact": any(
            c["claim"].startswith("fleet sweep bit-exact") and c["ok"]
            for c in topo.get("claims", [])),
        "topology_claims_pass": all(
            c["ok"] for c in topo.get("claims", [])),
        "fail_probe_n_vms": fail.get("n_vms"),
        "fail_probe_n_failures": fail.get("n_failures"),
        "fail_probe_wall_s": fail.get("wall_s"),
        "claims_pass": all(c["ok"] for c in res.get("claims", [])),
    }
    # provenance stamp: a BENCH_replay.json without backend/sha/
    # timestamp is uninterpretable a week later
    manifest = obs.run_manifest()
    bench["git_sha"] = manifest["git_sha"]
    bench["backend"] = manifest["backend"]
    bench["device_kind"] = manifest["device_kind"]
    bench["timestamp"] = manifest["timestamp"]
    bench["manifest"] = manifest
    bench["compilation_cache_dir"] = cache_dir
    bench["compilation_cache_entries"] = len(os.listdir(cache_dir))
    if rec.enabled:
        bench["obs"] = rec.metrics()
        # cold-vs-warm lowering cost: a warm rerun against the same
        # cache dir drives this toward zero
        bench["jit_lower_total_s"] = round(sum(
            v for k, v in bench["obs"].items()
            if k.startswith("span.jit.") and k.endswith(".lower.total_s")
        ), 3)
    lower = bench.get("jit_lower_total_s")
    print(f"  compilation cache: "
          f"{bench['compilation_cache_entries']} entries at {cache_dir}"
          + (f", jit lowering {lower}s this run" if lower is not None
             else ""))
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/BENCH_replay.json", "w") as f:
        json.dump(bench, f, indent=1)
    if rec.enabled:
        trace_path = rec.to_chrome_trace(
            "experiments/trace_perf_smoke.json", manifest=manifest)
        print(f"  chrome trace -> {trace_path} "
              f"(drop on ui.perfetto.dev)")
    print(f"perf-smoke: {wall:.1f}s wall, "
          f"{bench['events_per_sec']} candidate-events/s, batched K="
          f"{bench['batched_k']} {bench['batched_speedup_vs_seed_loop']}x"
          f" vs seed loop, streaming {bench['streaming_n_shards']} "
          f"shards {bench['streaming_events_per_sec']} ev/s, stream "
          f"batch K={bench['stream_batch_k']} "
          f"{bench['stream_batch_speedup_vs_stream_loop']}x vs stream "
          f"loop, device shard "
          f"{bench['device_speedup_vs_single'] or 'skipped'}"
          f"{'x' if bench['device_speedup_vs_single'] else ''} on "
          f"{bench['device_n_devices'] or 1} devices, policy "
          f"{bench['policy_vms_per_sec']} VMs/s "
          f"({bench['policy_speedup_vs_scalar']}x), latency grids "
          f"{bench['latency_min_speedup_vs_scalar']}x min, topology "
          f"grid {bench['topology_lanes']} lanes "
          f"{bench['topology_speedup_vs_oracle']}x vs oracle "
          f"-> experiments/BENCH_replay.json "
          f"(sha {manifest['git_sha'][:12]}, {manifest['backend']})")
    return bench


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--perf-smoke", action="store_true",
                    help="time the fig3 quick replay path and emit "
                         "experiments/BENCH_replay.json")
    args = ap.parse_args(argv)
    cache_dir = compile_cache.enable()
    if args.perf_smoke:
        perf_smoke(cache_dir)
        return
    out = {}
    n_pass = n_fail = 0
    raised = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(name)
            res = mod.run(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            raised.append(name)
            res = {"error": str(e),
                   "claims": [{"claim": f"{name} runs", "ok": False,
                               "detail": str(e)}]}
        out[name] = res
        for c in res.get("claims", []):
            n_pass += c["ok"]
            n_fail += not c["ok"]
        print(f"  ({time.time() - t0:.0f}s)\n")
    os.makedirs("experiments", exist_ok=True)
    def default(o):
        try:
            return float(o)
        except Exception:
            return str(o)
    with open("experiments/benchmarks.json", "w") as f:
        json.dump(out, f, indent=1, default=default)
    print(f"=== paper-claim checks: {n_pass} PASS / {n_fail} FAIL ===")
    print("results -> experiments/benchmarks.json")
    if raised:
        sys.exit(f"benchmark modules raised: {', '.join(raised)}")


if __name__ == "__main__":
    main()
