"""Render EXPERIMENTS.md tables from the dry-run JSON artifacts.

  PYTHONPATH=src python -m benchmarks.report [--outdir experiments/dryrun]
  PYTHONPATH=src python -m benchmarks.report --what replay

The ``replay`` table tracks the batched replay engine's throughput
trajectory from ``experiments/BENCH_replay.json`` (written by
``python -m benchmarks.run --perf-smoke``); the ``policy`` table
renders the compiled policy engine's decision throughput and grid-sweep
numbers from the same artifact.

Observability additions (``core/obs.py``):

  PYTHONPATH=src python -m benchmarks.report --what obs

``--what obs`` renders the engine counter table (jit-cache hits vs
misses, padding waste, span timings) recorded by a ``POND_TRACE=1``
perf-smoke run.

``--what device`` renders the multi-device sharding table
(``device_*``/``overlap_ratio`` keys from a perf-smoke run with
several visible jax devices — on CPU hosts export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first).
"""
from __future__ import annotations

import argparse
import json
import os


def _load(outdir, mesh):
    d = os.path.join(outdir, mesh)
    rows = []
    if not os.path.isdir(d):
        return rows
    for f in sorted(os.listdir(d)):
        rows.append(json.load(open(os.path.join(d, f))))
    return rows


def dryrun_table(outdir: str) -> str:
    lines = ["| arch | shape | mesh | status | GB/dev | fits 16GiB | "
             "compile s |", "|---|---|---|---|---|---|---|"]
    for mesh in ("single", "multi"):
        for r in _load(outdir, mesh):
            if r["status"] == "ok":
                m = r["memory"]
                lines.append(
                    f"| {r['arch']} | {r['shape']} | {mesh} | ok | "
                    f"{m['device_total_bytes'] / 2 ** 30:.2f} | "
                    f"{'yes' if m['fits_16GiB'] else 'NO'} | "
                    f"{r['t_compile_s']} |")
            else:
                why = (r.get("skip_reason") or
                       str(r.get("error", ""))[:60])
                lines.append(f"| {r['arch']} | {r['shape']} | {mesh} | "
                             f"{r['status']} | — | — | {why} |")
    return "\n".join(lines)


def roofline_table(outdir: str) -> str:
    lines = ["| arch | shape | compute s | memory s | collective s | "
             "dominant | useful (6ND/HLO) | MODEL_FLOPS (global) |",
             "|---|---|---|---|---|---|---|---|"]
    for r in _load(outdir, "single"):
        if r["status"] != "ok":
            continue
        rl = r["roofline"]
        uf = rl.get("useful_flops_ratio")
        lines.append(
            f"| {r['arch']} | {r['shape']} | {rl['compute_s']:.2e} | "
            f"{rl['memory_s']:.2e} | {rl['collective_s']:.2e} | "
            f"**{rl['dominant']}** | "
            f"{uf and round(min(uf, 9.99), 3)} | "
            f"{rl['model_flops_global']:.2e} |")
    return "\n".join(lines)


def collective_mix(outdir: str) -> str:
    lines = ["| arch | shape | all-reduce GiB | all-gather GiB | "
             "a2a GiB | rs GiB | permute GiB |",
             "|---|---|---|---|---|---|---|"]
    for r in _load(outdir, "single"):
        if r["status"] != "ok":
            continue
        bc = r["hlo_counts"]["by_collective"]
        gib = lambda k: bc.get(k, 0.0) / 2 ** 30
        lines.append(
            f"| {r['arch']} | {r['shape']} | {gib('all-reduce'):.2f} | "
            f"{gib('all-gather'):.2f} | {gib('all-to-all'):.2f} | "
            f"{gib('reduce-scatter'):.2f} | "
            f"{gib('collective-permute'):.2f} |")
    return "\n".join(lines)


def replay_table(path: str = "experiments/BENCH_replay.json") -> str:
    lines = ["| benchmark | wall s | savings wall s | cand-events/s | "
             "speedup vs scalar | claims |",
             "|---|---|---|---|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `python -m benchmarks.run --perf-smoke`) "
                     "| — | — | — | — | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    lines.append(
        f"| {r.get('benchmark', '?')} | {r.get('wall_s', '—')} | "
        f"{r.get('savings_wall_s', '—')} | "
        f"{r.get('events_per_sec', '—')} | "
        f"{r.get('replay_speedup_vs_scalar', '—')}x | "
        f"{'PASS' if r.get('claims_pass') else 'FAIL'} |")
    if r.get("batched_k"):
        lines += ["", "### Multi-trace batch (one vmapped sweep vs "
                  "per-seed engine loop)", "",
                  "| K seeds | narrow-probe speedup | frontier speedup | "
                  "batched cand-events/s | bit-exact |",
                  "|---|---|---|---|---|",
                  f"| {r['batched_k']} | "
                  f"{r.get('batched_speedup_vs_seed_loop', '—')}x "
                  f"({r.get('batched_speedup_shape', '')}) | "
                  f"{r.get('batched_frontier_speedup', '—')}x | "
                  f"{r.get('batched_events_per_sec', '—')} | "
                  f"{'yes' if r.get('batched_bit_exact') else 'NO'} |"]
    if r.get("streaming_n_shards"):
        peak = r.get("streaming_peak_shard_bytes") or 0
        lines += ["", "### Streaming shards (bounded-memory out-of-core "
                  "replay, carried state)", "",
                  "| shards | shard budget (events) | peak shard tensor | "
                  "cand-events/s | overhead vs monolithic | bit-exact |",
                  "|---|---|---|---|---|---|",
                  f"| {r['streaming_n_shards']} | "
                  f"{r.get('streaming_max_events_per_shard', '—')} | "
                  f"{peak / 2 ** 10:.0f} KiB | "
                  f"{r.get('streaming_events_per_sec', '—')} | "
                  f"{r.get('streaming_overhead_vs_monolithic', '—')}x | "
                  f"{'yes' if r.get('streaming_bit_exact') else 'NO'} |"]
    if r.get("stream_batch_k"):
        peak = r.get("stream_batch_peak_shard_bytes") or 0
        lines += ["", "### Streaming trace batch (K streams, one "
                  "vmapped carry sweep per shard)", "",
                  "| K seeds | shards | shard budget | peak stacked "
                  "tensor | speedup vs stream loop | cand-events/s | "
                  "bit-exact |",
                  "|---|---|---|---|---|---|---|",
                  f"| {r['stream_batch_k']} | "
                  f"{r.get('stream_batch_n_shards', '—')} | "
                  f"{r.get('stream_batch_max_events_per_shard', '—')} | "
                  f"{peak / 2 ** 10:.0f} KiB | "
                  f"{r.get('stream_batch_speedup_vs_stream_loop', '—')}x"
                  f" | {r.get('stream_batch_events_per_sec', '—')} | "
                  f"{'yes' if r.get('stream_batch_bit_exact') else 'NO'}"
                  " |"]
        if r.get("stream_batch_e2e_n_vms"):
            e2e_peak = r.get("stream_batch_e2e_peak_shard_bytes") or 0
            lines += ["", "### Azure-dump end to end (chunked ingest + "
                      "streaming replay, `benchmarks/azure_e2e.py`)", "",
                      "| dump VMs | ingest VMs/s | sweep cand-events/s | "
                      "e2e VMs/s | peak shard tensor |",
                      "|---|---|---|---|---|",
                      f"| {r['stream_batch_e2e_n_vms']} | "
                      f"{r.get('stream_batch_e2e_ingest_vms_per_sec', '—')}"
                      f" | {r.get('stream_batch_e2e_events_per_sec', '—')}"
                      f" | {r.get('stream_batch_e2e_vms_per_sec', '—')} | "
                      f"{e2e_peak / 2 ** 10:.0f} KiB |"]
    return "\n".join(lines)


def policy_table(path: str = "experiments/BENCH_replay.json") -> str:
    """Compiled policy-engine throughput (written by ``run.py
    --perf-smoke`` since the batched prediction pipeline)."""
    lines = ["| trace VMs | compiled s | VMs/s | speedup vs scalar walk "
             "| bit-exact | grid cells | grid eval s |",
             "|---|---|---|---|---|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `python -m benchmarks.run --perf-smoke`) "
                     "| — | — | — | — | — | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    if r.get("policy_n_vms") is None:
        lines.append("| (re-run `python -m benchmarks.run --perf-smoke` "
                     "to record the policy benchmark) | — | — | — | — "
                     "| — | — |")
        return "\n".join(lines)
    lines.append(
        f"| {r['policy_n_vms']} | {r.get('policy_compiled_s', '—')} | "
        f"{r.get('policy_vms_per_sec', '—')} | "
        f"{r.get('policy_speedup_vs_scalar', '—')}x | "
        f"{'yes' if r.get('policy_bit_exact') else 'NO'} | "
        f"{r.get('policy_grid_cells', '—')} | "
        f"{r.get('policy_grid_wall_s', '—')} |")
    return "\n".join(lines)


def latency_table(path: str = "experiments/BENCH_replay.json") -> str:
    """Latency/QoS grid-engine pass timings (written by ``run.py
    --perf-smoke`` since ``core/latency_engine.py``)."""
    lines = ["| grid cells | wall s | bands | spill | combine | "
             "min speedup | bit-exact |",
             "|---|---|---|---|---|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `python -m benchmarks.run --perf-smoke`) "
                     "| — | — | — | — | — | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    if r.get("latency_grid_cells") is None:
        lines.append("| (re-run `python -m benchmarks.run --perf-smoke` "
                     "to record the latency benchmark) | — | — | — | — "
                     "| — | — |")
        return "\n".join(lines)
    lines.append(
        f"| {r['latency_grid_cells']} | {r.get('latency_wall_s', '—')} | "
        f"{r.get('latency_bands_speedup', '—')}x | "
        f"{r.get('latency_spill_speedup', '—')}x | "
        f"{r.get('latency_combine_speedup', '—')}x | "
        f"{r.get('latency_min_speedup_vs_scalar', '—')}x | "
        f"{'yes' if r.get('latency_bit_exact') else 'NO'} |")
    return "\n".join(lines)


def topology_table(path: str = "experiments/BENCH_replay.json") -> str:
    """Multi-pod topology-grid timings (written by ``run.py
    --perf-smoke`` since the fleet engine / ``fig_topology.py``)."""
    lines = ["| lanes | events | compiled s | oracle s | speedup | "
             "bit-exact | claims |",
             "|---|---|---|---|---|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `python -m benchmarks.run --perf-smoke`) "
                     "| — | — | — | — | — | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    if r.get("topology_lanes") is None:
        lines.append("| (re-run `python -m benchmarks.run --perf-smoke` "
                     "to record the topology benchmark) | — | — | — | — "
                     "| — | — |")
        return "\n".join(lines)
    lines.append(
        f"| {r['topology_lanes']} | {r.get('topology_events', '—')} | "
        f"{r.get('topology_compiled_s', '—')} | "
        f"{r.get('topology_oracle_s', '—')} | "
        f"{r.get('topology_speedup_vs_oracle', '—')}x | "
        f"{'yes' if r.get('topology_bit_exact') else 'NO'} | "
        f"{'PASS' if r.get('topology_claims_pass') else 'FAIL'} |")
    return "\n".join(lines)


def device_table(path: str = "experiments/BENCH_replay.json") -> str:
    """Multi-device sharded stream-batch numbers (written by ``run.py
    --perf-smoke`` since the device-sharding layer; needs >= 2 visible
    jax devices — forced on CPU hosts via ``XLA_FLAGS``)."""
    lines = ["| devices | K seeds | sharded ms | single ms | speedup | "
             "cand-events/s | overlap ratio | bit-exact |",
             "|---|---|---|---|---|---|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `python -m benchmarks.run --perf-smoke`) "
                     "| — | — | — | — | — | — | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    if r.get("device_n_devices") is None:
        lines.append("| (re-run `python -m benchmarks.run --perf-smoke` "
                     "to record the device benchmark) | — | — | — | — | "
                     "— | — | — |")
        return "\n".join(lines)
    if r.get("device_skipped"):
        lines.append(f"| 1 — {r['device_skipped']} | — | — | — | — | — "
                     "| — | — |")
        return "\n".join(lines)
    lines.append(
        f"| {r['device_n_devices']} | {r.get('stream_batch_k', '—')} | "
        f"{r.get('device_stream_batch_ms', '—')} | "
        f"{r.get('device_single_ms', '—')} | "
        f"{r.get('device_speedup_vs_single', '—')}x | "
        f"{r.get('device_stream_batch_events_per_sec', '—')} | "
        f"{r.get('overlap_ratio', '—')} | "
        f"{'yes' if r.get('device_bit_exact') else 'NO'} |")
    return "\n".join(lines)


def obs_table(path: str = "experiments/BENCH_replay.json") -> str:
    """Engine counter table from a ``POND_TRACE=1`` perf-smoke run:
    jit-cache hits/misses per kernel family, padding-waste ratios,
    span aggregates, device-transfer bytes."""
    lines = ["| counter | value |", "|---|---|"]
    if not os.path.isfile(path):
        lines.append("| (run `POND_TRACE=1 python -m benchmarks.run "
                     "--perf-smoke`) | — |")
        return "\n".join(lines)
    r = json.load(open(path))
    ob = r.get("obs")
    if not ob:
        lines.append("| (re-run with `POND_TRACE=1` to record the "
                     "engine counters) | — |")
        return "\n".join(lines)
    man = r.get("manifest", {})
    head = (f"run {man.get('timestamp', '?')} · sha "
            f"{str(man.get('git_sha', '?'))[:12]} · "
            f"{man.get('backend', '?')}/{man.get('device_kind', '?')}")
    for k in sorted(ob):
        lines.append(f"| `{k}` | {ob[k]} |")
    return head + "\n\n" + "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--what", default="all",
                    choices=["all", "dryrun", "roofline", "collectives",
                             "replay", "policy", "latency", "topology",
                             "device", "obs"])
    args = ap.parse_args()
    if args.what in ("all", "dryrun"):
        print("### Dry-run matrix\n")
        print(dryrun_table(args.outdir))
        print()
    if args.what in ("all", "roofline"):
        print("### Roofline terms (single pod, per device per step)\n")
        print(roofline_table(args.outdir))
        print()
    if args.what in ("all", "collectives"):
        print("### Collective mix (single pod, wire GiB/device/step)\n")
        print(collective_mix(args.outdir))
        print()
    if args.what in ("all", "replay"):
        print("### Replay-engine throughput (batched event sweeps)\n")
        print(replay_table())
        print()
    if args.what in ("all", "policy"):
        print("### Policy-engine throughput (compiled decision "
              "pipeline + grid sweep)\n")
        print(policy_table())
        print()
    if args.what in ("all", "latency"):
        print("### Latency/QoS grid engine (vectorized figure passes "
              "vs scalar loops)\n")
        print(latency_table())
        print()
    if args.what in ("all", "topology"):
        print("### Multi-pod topology grid (compiled fleet scan vs "
              "scalar oracle loop)\n")
        print(topology_table())
        print()
    if args.what in ("all", "device"):
        print("### Multi-device sharded streaming (trace-axis "
              "shard_map + double-buffered uploads)\n")
        print(device_table())
        print()
    if args.what in ("all", "obs"):
        print("### Engine observability counters (POND_TRACE=1 "
              "perf-smoke)\n")
        print(obs_table())


if __name__ == "__main__":
    main()
