"""The benchmark's trace generator against the program's: the same
distributions, within sampling error."""
import sys
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[2] / "src")]

import tracegen  # noqa: E402

N = 20_000
HORIZON = 10 * tracegen.DAY_S


def _program():
    from repro.core import traces
    vms = traces.Population(seed=0).sample_vms(N, HORIZON, seed=3)
    return {"cores": np.array([v.cores for v in vms], float),
            "ratio": np.array([v.mem_gb / v.cores for v in vms]),
            "lifetime": np.array([v.lifetime for v in vms]),
            "arrival": np.array([v.arrival for v in vms]),
            "customer": np.array([v.customer for v in vms]),
            "untouched": np.array([v.untouched for v in vms]),
            "slow182": np.array([v.slow182 for v in vms]),
            "dram_bound": np.array([v.pmu[0] for v in vms])}


def _copy():
    tr = tracegen.sample(tracegen.Population.make(200, 0), N, HORIZON,
                         seed=[7, 0])
    return {"cores": tr.cores.astype(float), "ratio": tr.mem_gb / tr.cores,
            "lifetime": tr.lifetime, "arrival": tr.arrival,
            "customer": tr.customer, "untouched": tr.untouched,
            "slow182": tr.slow182, "dram_bound": tr.pmu[:, 0]}


def test_population_priors_are_the_programs():
    from repro.core import traces
    prog = traces.Population(seed=0)
    copy = tracegen.Population.make(200, 0)
    for a, b in ((prog.cust_popularity, copy.popularity),
                 (prog.cust_u, copy.u), (prog.cust_untouched,
                                         copy.untouched),
                 (prog.cust_phase, copy.phase),
                 (prog.cust_burstiness, copy.burstiness)):
        np.testing.assert_array_equal(a, b)


def test_statistics_match_within_sampling_error():
    a, b = _program(), _copy()
    for key in ("cores", "ratio", "untouched", "slow182", "dram_bound"):
        se = np.hypot(a[key].std(), b[key].std()) / np.sqrt(N)
        assert abs(a[key].mean() - b[key].mean()) < 5 * se, key
    # lifetime median: lognormal(log 2h, 1.4), a few percent at this N
    med_a, med_b = np.median(a["lifetime"]), np.median(b["lifetime"])
    assert abs(med_a / med_b - 1) < 0.06
    # arrivals per day: the same count, spread over the same days
    per_day_a = np.bincount((a["arrival"] // tracegen.DAY_S).astype(int))
    per_day_b = np.bincount((b["arrival"] // tracegen.DAY_S).astype(int))
    assert len(per_day_a) == len(per_day_b) == 10
    assert np.abs(per_day_a - per_day_b).max() < 5 * np.sqrt(N / 10)
    # customer shares: the same Zipf popularity
    share_a = np.bincount(a["customer"], minlength=200) / N
    share_b = np.bincount(b["customer"], minlength=200) / N
    se = np.sqrt(share_a * (1 - share_a) / N) * np.sqrt(2)
    assert (np.abs(share_a - share_b) < 5 * se + 1e-3).all()


def test_trace_is_sorted_whole_gb_and_seeded():
    pop = tracegen.Population.make(200, 0)
    t1 = tracegen.sample(pop, 1000, HORIZON, seed=[2 ** 31 + 9, 1])
    t2 = tracegen.sample(pop, 1000, HORIZON, seed=[2 ** 31 + 9, 1])
    np.testing.assert_array_equal(t1.arrival, t2.arrival)
    assert (np.diff(t1.arrival) >= 0).all()
    assert (t1.mem_gb == np.floor(t1.mem_gb)).all()
    vms = tracegen.to_vms(t1, pop)
    assert vms[5].cores == t1.cores[5] and vms[5].arrival == t1.arrival[5]
