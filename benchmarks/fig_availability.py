"""Savings-vs-availability frontier under the Pond §4.2 failure model.

Pond's DRAM savings come from pooling — and pooling concentrates blast
radius: when an EMC fails, every VM holding slices on it is affected at
once.  This benchmark prices that trade in one batched pass per domain
size: the failure-rate axis (one :class:`FailureSchedule` per MTBF)
rides the trace axis of ``CompiledReplayBatch.availability`` — K
(trace, schedule) rows, each pricing the whole DRAM-savings candidate
grid inside a single vmapped ``lax.scan`` — while the domain-size axis
(servers per EMC group) loops outside, since it changes the cluster
shape.  Both mitigation policies (remigrate-to-local vs kill) are
priced on identical schedules.

Emits ``experiments/fig_availability.json`` when run as a script (the
CI chaos job uploads it as an artifact).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import cluster_sim, replay_engine
from repro.runtime.fault import FailureSchedule

REPAIR_S = 1800.0                      # 30 min EMC repair outage


def _frontier(cfg, vms, dec, mtbfs, horizon, dram_fracs):
    """Price the (failure-rate x DRAM-savings x mitigation) grid for
    one domain size; returns per-mitigation metric arrays (K, n_cand)
    plus the schedules used."""
    scheds = [FailureSchedule.generate(horizon, cfg.n_groups, m, REPAIR_S,
                                       seed=i)
              for i, m in enumerate(mtbfs)]
    engines = [replay_engine.CompiledReplay(vms, dec, cfg,
                                            failure_schedule=s)
               for s in scheds]
    batch = replay_engine.CompiledReplayBatch(engines)
    full_gb = cfg.gb_per_core * cfg.cores_per_server
    server = np.round(full_gb * np.asarray(dram_fracs))
    pool = np.full_like(server, np.ceil(engines[0].peak_pool_demand()))
    out = {}
    for mit in ("remigrate", "kill"):
        out[mit] = batch.availability(server, pool, mitigation=mit)
    return out, scheds, engines, server, pool


def run(quick: bool = True) -> dict:
    print("== Availability: savings vs blast radius frontier ==")
    horizon = 2 * 86400 if quick else 6 * 86400
    mtbfs = [4 * 3600.0, 24 * 3600.0] if quick else \
        [2 * 3600.0, 8 * 3600.0, 24 * 3600.0, 96 * 3600.0]
    dram_fracs = [1.0, 0.85, 0.7, 0.55] if quick else \
        [1.0, 0.9, 0.8, 0.7, 0.6, 0.5]
    domain_sockets = [8, 32] if quick else [8, 16, 32, 64]
    res = {"mtbf_h": [m / 3600 for m in mtbfs],
           "dram_fracs": dram_fracs, "repair_s": REPAIR_S,
           "domains": {}}
    t0 = time.time()
    aff_per_fail_by_domain = {}
    for sockets in domain_sockets:
        cfg = cluster_sim.ClusterConfig(n_servers=16,
                                        pool_sockets=sockets,
                                        gb_per_core=4.0)
        n = cluster_sim.arrivals_for_util(cfg, 0.8, horizon)
        vms = common.population().sample_vms(n, horizon, seed=11,
                                             start_id=7 * 10 ** 6)
        dec, _ = cluster_sim.policy_decisions(vms, "static",
                                              static_pool_frac=0.25)
        out, scheds, engines, server, pool = _frontier(
            cfg, vms, dec, mtbfs, horizon, dram_fracs)
        n_fail = np.array([s.n_failures for s in scheds])
        dom = {"servers_per_group": cfg.servers_per_group,
               "n_groups": cfg.n_groups, "n_vms": len(vms),
               "server_gb": server.tolist(), "pool_gb": pool.tolist(),
               "n_failures": n_fail.tolist(),
               "dram_savings_pct": [round(100 * (1 - f), 1)
                                    for f in dram_fracs]}
        for mit, r in out.items():
            dom[mit] = {
                "reject_rate": np.asarray(r.reject_rate).tolist(),
                "affected": np.asarray(r.affected).tolist(),
                "killed": np.asarray(r.killed).tolist(),
                "remigrated": np.asarray(r.remigrated).tolist(),
                "lost_vm_minutes":
                    np.asarray(r.lost_vm_minutes).tolist(),
                "remigration_success_rate": np.round(
                    r.remigration_success_rate, 4).tolist(),
            }
        res["domains"][sockets] = dom
        # mean blast radius (VMs affected per failure, kill policy at
        # full DRAM) for the domain-size claim
        k = out["kill"]
        aff_per_fail_by_domain[sockets] = float(
            (np.asarray(k.affected)[:, 0]
             / np.maximum(n_fail, 1)).mean())
        print(f"  {cfg.servers_per_group} servers/EMC-group: "
              f"{aff_per_fail_by_domain[sockets]:.1f} VMs affected "
              f"per failure (kill, full DRAM)")
    res["wall_s"] = round(time.time() - t0, 2)

    # spot-check bit-exactness vs the scalar oracle on the smallest cell
    cfg = cluster_sim.ClusterConfig(n_servers=16,
                                    pool_sockets=domain_sockets[0],
                                    gb_per_core=4.0)
    n = cluster_sim.arrivals_for_util(cfg, 0.8, horizon)
    vms = common.population().sample_vms(n, horizon, seed=11,
                                         start_id=7 * 10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    sched = FailureSchedule.generate(horizon, cfg.n_groups, mtbfs[0],
                                     REPAIR_S, seed=0)
    eng = replay_engine.CompiledReplay(vms, dec, cfg,
                                       failure_schedule=sched)
    sgb = [cfg.gb_per_core * cfg.cores_per_server * dram_fracs[-1]]
    pgb = [np.ceil(eng.peak_pool_demand())]
    jx = eng.availability(sgb, pgb, "remigrate", per_failure=False)
    orc = cluster_sim.replay_with_failures(vms, dec, cfg, sgb[0], pgb[0],
                                           sched, "remigrate")
    exact = all(np.asarray(getattr(jx, f)).tolist() == [getattr(orc, f)]
                for f in ("reject_rate", "affected", "killed",
                          "remigrated", "lost_vm_minutes"))
    common.claim(res, "failure sweep bit-exact vs scalar oracle", exact,
                 "tightest cell, XLA sweep vs replay_with_failures")

    d0 = res["domains"][domain_sockets[0]]
    hi_rate, lo_rate = 0, len(mtbfs) - 1      # mtbfs sorted ascending
    common.claim(
        res, "more frequent failures affect more VMs (kill)",
        all(d["kill"]["affected"][hi_rate][0]
            >= d["kill"]["affected"][lo_rate][0]
            for d in res["domains"].values()),
        f"affected at MTBF {mtbfs[0]/3600:.0f}h vs "
        f"{mtbfs[-1]/3600:.0f}h, full DRAM")
    common.claim(
        res, "remigration recovers VM-minutes vs kill at full DRAM",
        all(d["remigrate"]["lost_vm_minutes"][i][0]
            <= d["kill"]["lost_vm_minutes"][i][0]
            for d in res["domains"].values()
            for i in range(len(mtbfs))),
        f"lost minutes, every rate row, {len(res['domains'])} domains")
    common.claim(
        res, "DRAM savings erode remigration headroom",
        all(d["remigrate"]["remigration_success_rate"][i][-1]
            <= d["remigrate"]["remigration_success_rate"][i][0] + 1e-9
            for d in res["domains"].values()
            for i in range(len(mtbfs))),
        f"remig success at {100*(1-dram_fracs[-1]):.0f}% savings <= "
        "full DRAM, every rate row")
    small, large = domain_sockets[0], domain_sockets[-1]
    common.claim(
        res, "larger failure domains widen the blast radius",
        aff_per_fail_by_domain[large] >= aff_per_fail_by_domain[small],
        f"{aff_per_fail_by_domain[small]:.1f} VMs/failure at "
        f"{small//2} servers/group vs "
        f"{aff_per_fail_by_domain[large]:.1f} at {large//2}")
    return res


if __name__ == "__main__":
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    out = run(quick=not args.full)
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/fig_availability.json", "w") as f:
        json.dump(out, f, indent=1)
    print("results -> experiments/fig_availability.json")
