"""Seeded VM traces for the benchmark: a vectorised copy of the program's
synthetic generator (``repro.core.traces.Population`` and
``Population.sample_vms``).

The copy keeps every prior and every draw of the original: customers
with Zipf(0.7) popularity, a stratified latent intensity ``u``, a beta
untouched-memory prior, a daily phase and a burstiness each; arrivals
spread over the horizon and pulled towards the customer's phase; cores
from {2, 4, 8, 16, 32, 48}, GB per core from {2, 4, 8}, lognormal
lifetimes clipped to [300 s, 30 days]; Pond's Fig 5 slowdown bands at
182% and 222% latency; 32 PMU features with 6% counterexamples.  The
draws are made a column at a time instead of a VM at a time, so a
180k-VM trace takes about a second instead of half a minute.  The
streams differ from the program's, so the traces match it in
distribution (``bench/tests/test_bench_tracegen.py``), not VM for VM.

Because the copy lives with the benchmark, a change to the program's
generator does not change the benchmark's traffic.

:func:`sample` returns plain numpy columns (:class:`Trace`); the plain
reference replays those.  :func:`to_vms` wraps them as the program's
``traces.VM`` records for the system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np

N_PMU_FEATURES = 32

# piecewise slowdown bands (cum_prob, lo, hi), as in core/traces.py
BANDS_182 = ((0.26, 0.0, 0.01), (0.43, 0.01, 0.05),
             (0.79, 0.05, 0.25), (1.0, 0.25, 0.50))
BANDS_222 = ((0.23, 0.0, 0.01), (0.37, 0.01, 0.05),
             (0.63, 0.05, 0.25), (1.0, 0.25, 0.60))

CORES = (2, 4, 8, 16, 32, 48)
CORES_P = (.30, .25, .20, .15, .07, .03)
GB_PER_CORE = (2.0, 4.0, 8.0)
GB_PER_CORE_P = (.35, .45, .20)
DAY_S = 86400.0


def piecewise(u: np.ndarray, bands) -> np.ndarray:
    out = np.zeros_like(u)
    prev = 0.0
    for cum, lo, hi in bands:
        m = (u >= prev) & (u < cum)
        out[m] = lo + (u[m] - prev) / max(cum - prev, 1e-9) * (hi - lo)
        prev = cum
    return out


@dataclasses.dataclass
class Population:
    """Customer priors (``traces.Population``), drawn from ``seed``."""
    popularity: np.ndarray
    u: np.ndarray
    untouched: np.ndarray
    vm_type: np.ndarray
    location: np.ndarray
    guest_os: np.ndarray
    phase: np.ndarray
    burstiness: np.ndarray

    @classmethod
    def make(cls, n_customers: int = 200, seed: int = 0) -> "Population":
        rng = np.random.default_rng(seed)
        w = 1.0 / np.arange(1, n_customers + 1) ** 0.7
        pop = w / w.sum()
        perm = rng.permutation(n_customers)
        p_perm = pop[perm]
        u = np.empty(n_customers)
        u[perm] = np.cumsum(p_perm) - p_perm / 2
        return cls(popularity=pop, u=u,
                   untouched=rng.beta(2.0, 2.0, n_customers),
                   vm_type=rng.integers(0, 12, n_customers),
                   location=rng.integers(0, 6, n_customers),
                   guest_os=rng.integers(0, 4, n_customers),
                   phase=rng.uniform(0, DAY_S, n_customers),
                   burstiness=rng.uniform(0.2, 0.9, n_customers))

    @property
    def n_customers(self) -> int:
        return len(self.popularity)


@dataclasses.dataclass
class Trace:
    """One trace as columns, sorted by arrival (``vm_id`` ascending)."""
    vm_id: np.ndarray       # int64
    customer: np.ndarray    # int64
    cores: np.ndarray       # int64
    mem_gb: np.ndarray      # float64, whole GB
    arrival: np.ndarray     # float64 seconds
    lifetime: np.ndarray    # float64 seconds
    untouched: np.ndarray   # float64 fraction
    slow182: np.ndarray
    slow222: np.ndarray
    pmu: np.ndarray         # (n, 32) float32

    def __len__(self) -> int:
        return len(self.vm_id)


def pmu_features(u: np.ndarray, rng) -> np.ndarray:
    """PMU/TMA counters (``Population._pmu``), one row per VM."""
    n = len(u)
    f = np.zeros((n, N_PMU_FEATURES), np.float32)
    confuse = rng.random(n) < 0.06
    eff_u = np.where(confuse, rng.random(n) * 0.15, u)
    f[:, 0] = np.clip(0.02 + 0.55 * eff_u ** 1.4
                      + rng.normal(0, 0.015, n), 0, 1)
    f[:, 1] = np.clip(f[:, 0] + 0.06 + 0.25 * rng.random(n)
                      + np.abs(rng.normal(0, 0.05, n)), 0, 1)
    f[:, 2] = np.clip(0.3 * eff_u + rng.normal(0, 0.05, n), 0, 1)
    f[:, 3] = np.clip(2.6 - 2.0 * eff_u + rng.normal(0, 0.2, n), 0.1, 4)
    f[:, 4] = np.clip(0.5 * eff_u + rng.normal(0, 0.1, n), 0, 1)
    f[:, 5] = np.clip(rng.normal(0.2, 0.1, n), 0, 1)
    f[:, 6] = np.clip(rng.normal(0.1, 0.05, n), 0, 1)
    f[:, 7:] = rng.random((n, N_PMU_FEATURES - 7))
    return f


def sample(pop: Population, n: int, horizon_s: float, seed,
           start_id: int = 0) -> Trace:
    """``n`` VMs over ``horizon_s`` seconds (``sample_vms``)."""
    rng = np.random.default_rng(seed)
    custs = rng.choice(pop.n_customers, n, p=pop.popularity)
    base = rng.uniform(0, horizon_s, n)
    tod = np.where(rng.random(n) < pop.burstiness[custs],
                   (pop.phase[custs] + rng.normal(0, 3 * 3600, n)) % DAY_S,
                   rng.uniform(0, DAY_S, n))
    arrivals = np.minimum(np.floor(base / DAY_S) * DAY_S + tod,
                          horizon_s - 1)
    order = np.argsort(arrivals, kind="stable")
    custs, arrivals = custs[order], arrivals[order]
    u = np.clip(pop.u[custs] + rng.normal(0, 0.02, n), 0, 0.999999)
    cores = rng.choice(np.array(CORES), n, p=CORES_P)
    ratio = rng.choice(np.array(GB_PER_CORE), n, p=GB_PER_CORE_P)
    untouched = np.clip(pop.untouched[custs] + rng.normal(0, 0.10, n), 0, 1)
    life = np.clip(rng.lognormal(np.log(2 * 3600), 1.4, n), 300, 30 * DAY_S)
    return Trace(vm_id=start_id + np.arange(n, dtype=np.int64),
                 customer=custs.astype(np.int64),
                 cores=cores.astype(np.int64), mem_gb=cores * ratio,
                 arrival=arrivals, lifetime=life, untouched=untouched,
                 slow182=piecewise(u, BANDS_182),
                 slow222=piecewise(u, BANDS_222),
                 pmu=pmu_features(u, rng))


def to_vms(trace: Trace, pop: Population) -> list:
    """The trace as the program's ``traces.VM`` records."""
    from repro.core.traces import VM
    cols = (trace.vm_id.tolist(), trace.customer.tolist(),
            pop.vm_type[trace.customer].tolist(),
            pop.location[trace.customer].tolist(),
            pop.guest_os[trace.customer].tolist(), trace.cores.tolist(),
            trace.mem_gb.tolist(), trace.arrival.tolist(),
            trace.lifetime.tolist(), trace.untouched.tolist(),
            trace.slow182.tolist(), trace.slow222.tolist(), trace.pmu)
    return [VM(*row) for row in zip(*cols)]


def meta(trace: Trace, pop: Population) -> np.ndarray:
    """Each VM's type, cores, memory, location and guest OS, the
    metadata Pond's untouched-memory model reads."""
    c = trace.customer
    return np.column_stack([pop.vm_type[c], trace.cores, trace.mem_gb,
                            pop.location[c], pop.guest_os[c]]
                           ).astype(np.float64)


def arrivals_for_util(n_servers: int, cores_per_server: int,
                      util: float, horizon_s: float,
                      mean_cores: float = 9.3,
                      mean_life_s: float = 1.9e4) -> int:
    """VM count that drives the cluster to ``util`` core utilisation
    (``cluster_sim.arrivals_for_util``)."""
    return int(util * n_servers * cores_per_server * horizon_s
               / (mean_cores * mean_life_s))
