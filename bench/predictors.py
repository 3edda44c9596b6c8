"""Pond's two predictors, trained by the benchmark from the seed.

The latency-insensitivity (LI) model is a random forest over a VM's 32
PMU counters that gives the probability that running on pool memory
slows the VM past the PDM; the untouched-memory (UM) model is a
quantile GBM over the customer's history percentiles and the VM's
metadata (Pond §4.4).  This is a copy of the program's recipe
(``predictors/trees.py``, ``forest.py``, ``gbm.py`` and
``examples/cluster_savings.fit_models``): the same CART splits, forest
of 40 trees of depth 7, GBM of 60 stages of depth 4 at learning rate
0.15, trained on a seeded set of VMs.  The benchmark makes the weights,
so the plain reference (``reference.pond_split``) may evaluate them,
and a change to the program's training leaves the benchmark's
predictors as they are.

A tree is a dict of flat arrays (``feature``, ``threshold``, ``left``,
``right``, ``value``) and its ``depth``; :func:`to_program` wraps them
in the program's model classes for the control plane under test.
"""
from __future__ import annotations

import numpy as np

N_TREES, FOREST_DEPTH, FOREST_MIN_LEAF = 40, 7, 8
N_STAGES, GBM_LR, GBM_DEPTH, GBM_MIN_LEAF = 60, 0.15, 4, 16
N_THRESHOLDS = 16
HISTORY_MIN = 3                       # history VMs before percentiles
PERCENTILES = (80, 90, 95, 99)


def _best_split(x, y, feat_ids, min_leaf):
    """Greedy variance-reduction split over quantile thresholds."""
    n = len(y)
    best = (None, None, np.inf)
    parent = np.var(y) * n
    for f in feat_ids:
        xv = x[:, f]
        for t in np.unique(np.quantile(xv, np.linspace(0.05, 0.95,
                                                         N_THRESHOLDS))):
            mask = xv <= t
            nl = int(mask.sum())
            if nl < min_leaf or n - nl < min_leaf:
                continue
            score = np.var(y[mask]) * nl + np.var(y[~mask]) * (n - nl)
            if score < best[2]:
                best = (f, t, score)
    if best[0] is None or best[2] >= parent - 1e-12:
        return None
    return best[0], best[1]


def fit_tree(x, y, max_depth: int, min_leaf: int, max_features: int,
             rng) -> dict:
    nodes = {"feature": [], "threshold": [], "left": [], "right": [],
             "value": []}

    def build(idx, depth):
        nid = len(nodes["feature"])
        for key in nodes:
            nodes[key].append(-1 if key == "feature" else 0)
        ys = y[idx]
        nodes["value"][nid] = float(np.mean(ys)) if len(ys) else 0.0
        if depth >= max_depth or len(idx) < 2 * min_leaf \
                or np.all(ys == ys[0]):
            return nid
        nfeat = x.shape[1]
        feats = rng.choice(nfeat, size=min(max_features, nfeat),
                           replace=False)
        sp = _best_split(x[idx], ys, feats, min_leaf)
        if sp is None:
            return nid
        f, t = sp
        mask = x[idx, f] <= t
        nodes["feature"][nid] = int(f)
        nodes["threshold"][nid] = float(t)
        nodes["left"][nid] = build(idx[mask], depth + 1)
        nodes["right"][nid] = build(idx[~mask], depth + 1)
        return nid

    build(np.arange(len(y)), 0)
    return {"feature": np.array(nodes["feature"], np.int32),
            "threshold": np.array(nodes["threshold"], np.float32),
            "left": np.array(nodes["left"], np.int32),
            "right": np.array(nodes["right"], np.int32),
            "value": np.array(nodes["value"], np.float32),
            "depth": max_depth}


def leaf_index(tree: dict, x: np.ndarray) -> np.ndarray:
    idx = np.zeros(len(x), np.int64)
    rows = np.arange(len(x))
    for _ in range(tree["depth"] + 1):
        f = tree["feature"][idx]
        leaf = f < 0
        left = x[rows, np.maximum(f, 0)] <= tree["threshold"][idx]
        idx = np.where(leaf, idx,
                       np.where(left, tree["left"][idx], tree["right"][idx]))
    return idx


def fit_forest(x, y, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    max_features = max(1, int(np.sqrt(x.shape[1])))
    trees = []
    for i in range(N_TREES):
        idx = rng.integers(0, len(y), len(y))            # bootstrap
        trees.append(fit_tree(x[idx], y[idx].astype(np.float32),
                              FOREST_DEPTH, FOREST_MIN_LEAF, max_features,
                              np.random.default_rng(seed + 100 + i)))
    return trees


def fit_gbm(x, y, tau: float, seed: int) -> dict:
    """Pinball-loss boosting; each stage's leaves take the tau-quantile
    of the residuals inside them."""
    f = np.full(len(y), np.quantile(y, tau), np.float32)
    f0 = float(f[0])
    stages = []
    for s in range(N_STAGES):
        grad = np.where(y < f, tau - 1.0, tau).astype(np.float32)
        tree = fit_tree(x, grad, GBM_DEPTH, GBM_MIN_LEAF, x.shape[1],
                        np.random.default_rng(seed + s))
        leaves = leaf_index(tree, x)
        resid = y - f
        for leaf in np.unique(leaves):
            tree["value"][leaf] = np.quantile(resid[leaves == leaf], tau)
        f = f + GBM_LR * tree["value"][leaves]
        stages.append(tree)
    return {"f0": f0, "lr": GBM_LR, "tau": tau, "stages": stages}


def history_of(customer: np.ndarray, untouched: np.ndarray) -> dict:
    """Untouched fractions per customer, in trace order."""
    return {int(c): untouched[customer == c] for c in np.unique(customer)}


def meta_features(percs: np.ndarray, meta: np.ndarray) -> np.ndarray:
    """UM features: four history percentiles, then the VM's type,
    cores, memory, location and guest OS, as float32."""
    return np.column_stack([percs, meta]).astype(np.float32)


def fit(train, meta: np.ndarray, policy: dict) -> dict:
    """Both models and the customer history they start from, from the
    training trace ``train`` (a ``tracegen.Trace``) and its metadata
    columns ``meta``."""
    sens = (train.slow182 if int(policy["latency_pct"]) == 182
            else train.slow222) >= float(policy["pdm"])
    hist = history_of(train.customer, train.untouched)
    percs = np.array([np.percentile(hist[int(c)], PERCENTILES)
                      if len(hist[int(c)]) >= HISTORY_MIN
                      else [0.5] * len(PERCENTILES)
                      for c in train.customer]).reshape(-1, 4)
    return {"forest": fit_forest(train.pmu, sens.astype(np.float32),
                                 seed=0),
            "gbm": fit_gbm(meta_features(percs, meta), train.untouched,
                           float(policy["um_quantile"]), seed=0),
            "history": hist}


def to_program(models: dict, pdm: float):
    """The models as the program's ``LatencySensitivityModel`` and
    ``UntouchedMemoryModel``, holding the benchmark's weights."""
    from repro.core.predictors.forest import RandomForest
    from repro.core.predictors.gbm import QuantileGBM
    from repro.core.predictors.models import (LatencySensitivityModel,
                                              UntouchedMemoryModel)
    from repro.core.predictors.trees import Tree

    def tree(t):
        return Tree(t["feature"], t["threshold"], t["left"], t["right"],
                    t["value"], t["depth"])

    li = LatencySensitivityModel(pdm=pdm)
    li.forest = RandomForest([tree(t) for t in models["forest"]])
    g = models["gbm"]
    um = UntouchedMemoryModel(g["tau"])
    um.gbm = QuantileGBM(g["f0"], [tree(t) for t in g["stages"]], g["lr"],
                         g["tau"])
    return li, um
