"""The benchmark's own predictors: the program's model classes, holding
the benchmark's weights, score every VM as the plain reference does."""
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import predictors  # noqa: E402
import reference  # noqa: E402
import tracegen  # noqa: E402

POLICY = {"latency_pct": 182, "pdm": 0.05, "um_quantile": 0.05}


def _models():
    pop = tracegen.Population.make(60, 0)
    train = tracegen.sample(pop, 400, 2 * tracegen.DAY_S, seed=[7, 1])
    trace = tracegen.sample(pop, 300, 2 * tracegen.DAY_S, seed=[7, 2])
    return pop, predictors.fit(train, tracegen.meta(train, pop), POLICY), \
        trace


def test_reference_scores_as_the_program():
    pop, models, tr = _models()
    li, um = predictors.to_program(models, POLICY["pdm"])
    p, um_ref, has = reference.pond_predictions(
        tr.customer, tr.untouched, tr.pmu, tracegen.meta(tr, pop), models)
    np.testing.assert_allclose(p, li.p_sensitive_batch(tr.pmu), atol=1e-6)
    assert 0 < p.min() and p.max() < 1
    # the program's walk over the same trace, one VM at a time
    hist = {c: list(v) for c, v in models["history"].items()}
    for i in range(0, len(tr), 37):
        h = hist.get(int(tr.customer[i]), [])
        assert has[i] == (len(h) + int((tr.customer[:i]
                                        == tr.customer[i]).sum()) >= 3)
    feat = np.column_stack([
        np.full((len(tr), 4), 0.5), tracegen.meta(tr, pop)]).astype(
            np.float32)
    no_hist = ~has
    np.testing.assert_allclose(um_ref[no_hist],
                               um.predict(feat[no_hist]), atol=1e-5)


def test_predictors_learn_the_trace():
    # held-out VMs: the forest ranks slowed VMs above the others, and the
    # UM quantile stays under most VMs' untouched share (tau = 0.05)
    pop, models, tr = _models()
    p, um, _ = reference.pond_predictions(
        tr.customer, tr.untouched, tr.pmu, tracegen.meta(tr, pop), models)
    sens = tr.slow182 >= POLICY["pdm"]
    assert p[sens].mean() > p[~sens].mean() + 0.2
    assert (tr.untouched < um).mean() < 0.2
