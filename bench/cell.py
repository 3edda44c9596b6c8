"""One benchmark cell: a deployment (``bench/configs/<name>.json``) under
a traffic mix (``bench/traffic/<name>.json``).

The cell turns the two files into what the answer kinds under
``bench/answers/`` need: the cluster shape, the seeded traces over the
configuration's ``trace_days`` (as plain columns for the reference and
as the program's VM records), Pond's predictors where the policy needs
them, the policy's decisions, and the host-clock layer timers.  It
holds no cell name: everything comes from the two files.
"""
from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path

import numpy as np

import predictors
import tracegen

BENCH = Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


class LayerClock:
    """Host seconds per layer, plus a profiler annotation around each
    layer call so the device trace can say what the host was doing."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def layer(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        c = config["cluster"]
        self.n_servers = int(c["n_servers"])
        self.cores_per_server = int(c["cores_per_server"])
        self.servers_per_group = max(
            1, int(c["pool_sockets"]) // int(c["sockets_per_server"]))
        self.policy = config["policy"]
        self.horizon_s = float(config["trace_days"]) * tracegen.DAY_S
        self.n_vms = tracegen.arrivals_for_util(
            self.n_servers, self.cores_per_server, float(traffic["util"]),
            self.horizon_s)
        self.traces: list[tracegen.Trace] = []
        self.vms_list: list = []
        self.pop: tracegen.Population | None = None
        self.models: dict | None = None

    # ------------------------------------------------------------ set-up --
    def cluster_config(self):
        from repro.core import cluster_sim
        c = self.config["cluster"]
        return cluster_sim.ClusterConfig(
            n_servers=self.n_servers, cores_per_server=self.cores_per_server,
            gb_per_core=float(c["gb_per_core"]),
            pool_sockets=int(c["pool_sockets"]))

    def population(self) -> tracegen.Population:
        p = self.config["population"]
        return tracegen.Population.make(int(p["n_customers"]),
                                        int(p["seed"]))

    def make_traces(self) -> None:
        """K traces from ``--seed``; each a fixed count of VMs."""
        self.pop = pop = self.population()
        self.traces = [tracegen.sample(pop, self.n_vms, self.horizon_s,
                                       seed=[self.seed, k])
                       for k in range(int(self.traffic["traces"]))]
        self.vms_list = [tracegen.to_vms(tr, pop) for tr in self.traces]
        if self.policy["name"] == "pond":
            train = tracegen.sample(pop, int(self.policy["train_vms"]),
                                    self.horizon_s, seed=[self.seed, 1 << 20])
            self.models = predictors.fit(train, tracegen.meta(train, pop),
                                         self.policy)

    # ------------------------------------------------------------ answer --
    def decisions(self, vms_list: list) -> list:
        """The policy's per-VM split, one ``PolicyDecisions`` per trace."""
        from repro.core import cluster_sim
        p = self.policy
        out = []
        for vms in vms_list:
            cp = None
            if p["name"] == "pond":
                from repro.core.control_plane import (ControlPlane,
                                                      ControlPlaneConfig)
                from repro.core.pool_manager import PoolManager
                li, um = predictors.to_program(self.models, float(p["pdm"]))
                cp = ControlPlane(
                    ControlPlaneConfig(li_threshold=float(p["li_threshold"]),
                                       um_quantile=float(p["um_quantile"]),
                                       pdm=float(p["pdm"])),
                    li, um, PoolManager(*p["pool_manager"]),
                    history=dict(self.models["history"]))
            out.append(cluster_sim.policy_decisions(
                vms, p["name"], cp,
                static_pool_frac=float(p.get("static_pool_frac", 0.0)),
                latency=int(p["latency_pct"]), pdm=float(p["pdm"]),
                spill_harm_prob=float(p["spill_harm_prob"]),
                as_arrays=True)[0])
        return out

    # --------------------------------------------------------- reference --
    def columns(self, k: int) -> dict:
        tr = self.traces[k]
        return {"arrival": tr.arrival, "lifetime": tr.lifetime,
                "cores": tr.cores, "mem_gb": tr.mem_gb}

    def meta(self, k: int) -> np.ndarray:
        return tracegen.meta(self.traces[k], self.pop)

    def shape(self) -> dict:
        return {"n_servers": self.n_servers,
                "cores_per_server": self.cores_per_server,
                "servers_per_group": self.servers_per_group}

