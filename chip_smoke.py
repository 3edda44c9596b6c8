"""Chip smoke run: price a 256-server, 30-day Pond cluster on one TPU.

  python chip_smoke.py              # main path on one chip
  python chip_smoke.py --chips 4    # sharded sweeps on a four-chip host

The main path is the one every paper figure goes through: seeded VM
traces get policy decisions, their events compile into streamed shards,
and the ``sweep_core`` sweep kernels (on a TPU one Pallas kernel per
call) price the lockstep provisioning searches of
``cluster_sim.savings_analysis_batched`` for the local, static and pond
policies (the searches
``examples/cluster_savings.py`` runs, with its trained LI/UM control
planes).  The cluster is 256 servers x 64 cores in pool groups of 16
sockets; K=2 traces cover 30 days at 75% core utilization, streamed in
shards of 65,536 events.  For trace 0 each policy's searched answer and
the candidate 1 GB below it are priced again on the chip and must equal
the scalar oracle ``cluster_sim.replay_reject_rate`` bit for bit; no
sweep may fall back to the host numpy path (``replay.backend_numpy``).

``--chips 4`` runs only the sharded phase: K=4 static-policy streams
priced with ``devices=4`` on the trace axis (``CompiledReplayStreamBatch``)
and on the candidate-lane axis (one ``CompiledReplayStream``), each equal
bit for bit to the same sweep on one device, with outputs spread over
four devices and every dispatch counted as the Pallas kernel
(``sweep.kernel.pallas``).

The script exits non-zero, before any work, when JAX's first device is
not a TPU, and whenever a check fails.  Its last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

POLICIES = ("local", "static", "pond")


def _log(msg: str) -> None:
    print(msg, flush=True)


def _cluster(n_servers: int):
    from repro.core import cluster_sim
    return cluster_sim.ClusterConfig(n_servers=n_servers,
                                     cores_per_server=64, pool_sockets=16)


def _sample(cfg, days: float, k: int, n_vms: int | None):
    """K seeded traces sized for 75% core utilization over ``days``."""
    from repro.core import cluster_sim, traces
    horizon = days * 86400.0
    n = n_vms or cluster_sim.arrivals_for_util(cfg, 0.75, horizon)
    pop = traces.Population(seed=0)
    vms_list = [pop.sample_vms(n, horizon, seed=2 + i, start_id=10 ** 6)
                for i in range(k)]
    return pop, horizon, vms_list


@contextlib.contextmanager
def _count_compiles():
    """XLA backend compiles inside the block, via ``jax.monitoring``."""
    import jax
    seen = {"n": 0, "s": 0.0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _span_s(metrics: dict, name: str) -> float:
    return metrics.get(f"span.{name}.total_s", 0.0)


def main_path(n_servers: int = 256, days: float = 30.0, k: int = 2,
              n_vms: int | None = None,
              max_events_per_shard: int = 65536) -> dict:
    """Phase 1: the three policy searches plus the oracle check.

    Returns a report; ``report["ok"]`` is the verdict.  Sizes are
    parameters so a CPU test can run the same code on a small cluster.
    """
    from examples.cluster_savings import fit_models
    from repro.core import cluster_sim, obs, replay_engine
    from repro.core.control_plane import ControlPlane, ControlPlaneConfig
    from repro.core.pool_manager import PoolManager

    cfg = _cluster(n_servers)
    rec = obs.Recorder()
    stages: dict[str, float] = {}
    with obs.use_recorder(rec), _count_compiles() as compiles:
        t = time.perf_counter()
        pop, horizon, vms_list = _sample(cfg, days, k, n_vms)
        stages["sample"] = time.perf_counter() - t
        _log(f"cluster: {cfg.n_servers} servers x {cfg.cores_per_server} "
             f"cores, {cfg.n_groups} pool groups of {cfg.pool_sockets} "
             f"sockets; {k} traces x {len(vms_list[0])} VMs over "
             f"{days:g} days")

        t = time.perf_counter()
        li, um, hist, *_ = fit_models(pop, horizon)
        stages["train"] = time.perf_counter() - t

        t = time.perf_counter()
        decisions = {}
        for policy in POLICIES:
            cps = [ControlPlane(
                ControlPlaneConfig(li_threshold=0.05, um_quantile=0.05),
                li, um, PoolManager(pool_gb=4096, buffer_gb=64),
                history=dict(hist)) if policy == "pond" else None
                for _ in vms_list]
            decisions[policy] = [
                cluster_sim.policy_decisions(
                    vms, policy, cp, static_pool_frac=0.15,
                    as_arrays=True)[0]
                for vms, cp in zip(vms_list, cps)]
        stages["decisions"] = time.perf_counter() - t

        t = time.perf_counter()
        cache: dict = {}
        results = {
            policy: cluster_sim.savings_analysis_batched(
                vms_list, cfg, policy, decisions=decisions[policy],
                cache=cache, max_events_per_shard=max_events_per_shard)
            for policy in POLICIES}
        searches_s = time.perf_counter() - t
        m = rec.metrics()
        stages["compile"] = _span_s(m, "stream.compile")
        stages["reference"] = _span_s(m, "stream.reference")
        stages["sweep"] = (_span_s(m, "stream_batch.reject_rates")
                           - stages["reference"])
        stages["search"] = searches_s - stages["compile"] - \
            stages["reference"] - stages["sweep"]
        sweep_calls = m.get("span.stream_batch.reject_rates.count", 0)
        for policy in POLICIES:
            for i, r in enumerate(results[policy]):
                _log(f"  {policy:6s} trace {i}: savings {r.savings:+.4f} "
                     f"server_gb {r.server_gb:.3f} pool_gb/group "
                     f"{r.pool_group_gb:.3f} reject {r.reject_rate:.6f}")

        t = time.perf_counter()
        oracle = []
        for policy in POLICIES:
            r = results[policy][0]
            s, p = r.server_gb, r.pool_group_gb
            if p >= 1.0:                        # pool binds: 1 GB less
                below = (s, math.floor(p) - 1.0)
            else:
                below = (math.floor(s) - 1.0, p)
            cand_s = [s, below[0]]
            cand_p = [p, below[1]]
            stream = replay_engine.CompiledReplayStream(
                vms_list[0], decisions[policy][0], cfg,
                max_events_per_shard=max_events_per_shard)
            chip = stream.reject_rates(cand_s, cand_p)
            dec = decisions[policy][0].as_vmdecisions()
            for j in range(2):
                ref = cluster_sim.replay_reject_rate(
                    vms_list[0], dec, cfg, cand_s[j], cand_p[j])
                oracle.append({"policy": policy, "server_gb": cand_s[j],
                               "pool_gb": cand_p[j],
                               "chip": float(chip[j]), "oracle": ref,
                               "equal": float(chip[j]) == ref})
                _log(f"  oracle {policy:6s} ({cand_s[j]:.3f}, "
                     f"{cand_p[j]:.3f}): chip {chip[j]!r} oracle {ref!r}")
        stages["oracle"] = time.perf_counter() - t
    m = rec.metrics()
    report = {
        "n_vms": [len(v) for v in vms_list],
        "n_events": [int(n) for n in cache["local_batch"].n_events],
        "n_shards": int(cache["local_batch"].n_shards),
        "savings": {p: [r.savings for r in results[p]] for p in POLICIES},
        "stages_s": stages,
        "sweep_calls": sweep_calls,
        "jit_misses": sum(v for key, v in m.items()
                          if key.startswith("jit.")
                          and key.endswith(".miss")),
        "xla_compiles": compiles["n"],
        "xla_compile_s": compiles["s"],
        "device_scans": m.get("span.stream.compute.count", 0),
        "scan_s": _span_s(m, "stream.compute"),
        "upload_wait_s": _span_s(m, "stream.upload_wait"),
        "backend_numpy": m.get("replay.backend_numpy", 0),
        "oracle": oracle,
    }
    report["ok"] = bool(
        all(o["equal"] for o in oracle) and report["backend_numpy"] == 0
        and report["device_scans"] > 0
        and all(math.isfinite(x) for v in report["savings"].values()
                for x in v))
    _log("stages (s): " + ", ".join(f"{key} {v:.2f}"
                                    for key, v in stages.items()))
    _log(f"events per trace {report['n_events']} in {report['n_shards']} "
         f"shards; {sweep_calls} sweep calls, {report['device_scans']} "
         f"shard scans, {report['jit_misses']} jit-cache misses, "
         f"{compiles['n']} XLA compiles ({compiles['s']:.2f}s); "
         f"replay.backend_numpy {report['backend_numpy']}; oracle parity "
         f"{sum(o['equal'] for o in oracle)}/{len(oracle)}")
    _log(f"shard scans {report['scan_s']:.2f}s in all (dispatch to "
         f"block_until_ready), upload waits {report['upload_wait_s']:.2f}s")
    return report


def sharded(n_servers: int = 256, days: float = 30.0, k: int = 4,
            n_devices: int = 4, n_vms: int | None = None,
            max_events_per_shard: int = 65536) -> dict:
    """Four-chip phase: trace- and lane-sharded sweeps against one device."""
    import jax
    import numpy as np
    from repro.core import cluster_sim, obs, replay_engine

    # every sweep dispatch runs the kernel the platform selects
    kernel, other = ("pallas", "scan") if jax.devices()[0].platform \
        == "tpu" else ("scan", "pallas")
    cfg = _cluster(n_servers)
    _, _, vms_list = _sample(cfg, days, k, n_vms)
    streams = [replay_engine.CompiledReplayStream(
        vms, cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.15,
                                          as_arrays=True)[0],
        cfg, max_events_per_shard=max_events_per_shard)
        for vms in vms_list]
    batch = replay_engine.CompiledReplayStreamBatch(streams)
    hi = cfg.cores_per_server * 6.0
    server_gb = np.linspace(hi * 0.5, hi, 16)
    pool_gb = np.linspace(0.0, 2.0 * hi, 16)
    report = {"n_vms": [len(v) for v in vms_list],
              "n_shards": batch.n_shards}
    plans = (("trace", batch), ("lane", streams[0]))
    for plan, engine in plans:
        runs = {}
        for devices in (None, n_devices):
            rec = obs.Recorder()
            with obs.use_recorder(rec):
                t = time.perf_counter()
                rates = engine.reject_rates(server_gb, pool_gb,
                                            devices=devices)
                wall = time.perf_counter() - t
            runs[devices] = (rates, wall, rec.metrics())
        one, many = runs[None], runs[n_devices]
        spread = {key: v for key, v in many[2].items()
                  if key.startswith("sweep.out_devices.")}
        counts = {name: [r[2].get(key, 0) for r in (one, many)]
                  for name, key in (
                      ("dispatches", "span.stream.compute.count"),
                      (kernel, f"sweep.kernel.{kernel}"),
                      (other, f"sweep.kernel.{other}"))}
        report[plan] = {
            "bit_exact": bool(np.array_equal(one[0], many[0])),
            "out_devices": spread, "kernel_counts": counts,
            "backend_numpy": one[2].get("replay.backend_numpy", 0)
            + many[2].get("replay.backend_numpy", 0),
            "wall_s_one": one[1], "wall_s_sharded": many[1]}
        _log(f"{plan} plan: bit-exact {report[plan]['bit_exact']}, "
             f"outputs {spread}, wall {one[1]:.2f}s on 1 device vs "
             f"{many[1]:.2f}s on {n_devices} (first calls, compiles "
             f"included); dispatches, by kernel, on 1 and on "
             f"{n_devices} devices {counts}")
    report["ok"] = all(
        report[plan]["bit_exact"] and report[plan]["backend_numpy"] == 0
        and report[plan]["kernel_counts"][kernel]
        == report[plan]["kernel_counts"]["dispatches"]
        and min(report[plan]["kernel_counts"]["dispatches"]) > 0
        and not any(report[plan]["kernel_counts"][other])
        and set(report[plan]["out_devices"])
        == {f"sweep.out_devices.{n_devices}"}
        for plan, _ in plans)
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX's first device is "
                 f"{devs[0].platform!r})")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    from repro.core import compile_cache
    _log(f"{len(devs)} x {devs[0].device_kind}, jax {jax.__version__}, "
         f"compile cache {compile_cache.enable()}")
    t = time.perf_counter()
    report = sharded() if args.chips == 4 else main_path()
    _log(f"phase wall {time.perf_counter() - t:.2f}s")
    if not report["ok"]:
        sys.exit("chip_smoke: a check failed (see above)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
