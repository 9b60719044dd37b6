"""Random-forest regression behind ``PREDICT``: scikit-learn fits it, the
benchmark keeps its arrays, and a plain node walk is its reference.

The model the program serves is built from these arrays, never the other
way round, so the reference shares nothing with the code under test.  The
configuration's ``model`` block gives:

- ``features``: the input columns, scaled by ``(x - mean) * (1 / std)`` in
  float32 before the trees see them (the library's ``StandardScaler``);
- ``n_estimators``, ``max_depth``: scikit-learn's ``RandomForestRegressor``
  settings, every other setting at scikit-learn's default;
- ``fit_rows``, ``fit_seed``: the fitting sample, generated by the
  deployment from that seed, so every ``--seed`` scores the same model.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import ml_dtypes
import numpy as np

Arrays = Dict[str, np.ndarray]

# The program picks a tree strategy from a cost model it measures on the
# chip once per process; set-up times that step on its own.
CALIBRATES = True


def fit(model: dict, rows: Dict[str, np.ndarray]) -> Arrays:
    from sklearn.ensemble import RandomForestRegressor

    raw = np.stack([rows[c].astype(np.float32) for c in model["features"]],
                   axis=1)
    mean = raw.astype(np.float64).mean(0).astype(np.float32)
    std = (raw.astype(np.float64).std(0) + 1e-8).astype(np.float32)
    x = (raw - mean) * (np.float32(1.0) / std)
    forest = RandomForestRegressor(
        n_estimators=model["n_estimators"], max_depth=model["max_depth"],
        random_state=model["fit_seed"], n_jobs=os.cpu_count() or 1)
    forest.fit(x, rows[model["label"]])
    trees = [e.tree_ for e in forest.estimators_]
    leaf = [t.children_left < 0 for t in trees]
    return {
        "mean": mean, "std": std,
        "offsets": np.cumsum([0] + [t.node_count for t in trees]),
        "depth": np.asarray([t.max_depth for t in trees], np.int32),
        "feature": np.concatenate([np.where(lf, -1, t.feature)
                                   for t, lf in zip(trees, leaf)]
                                  ).astype(np.int32),
        "threshold": np.concatenate([np.where(lf, 0.0, t.threshold)
                                     for t, lf in zip(trees, leaf)]
                                    ).astype(np.float32),
        "left": np.concatenate([t.children_left for t in trees]
                               ).astype(np.int32),
        "right": np.concatenate([t.children_right for t in trees]
                                ).astype(np.int32),
        "value": np.concatenate([t.value[:, 0, 0] for t in trees]
                                ).astype(np.float32),
    }


def _trees(a: Arrays):
    """Per tree: (feature, threshold, left, right, value, depth)."""
    off = a["offsets"]
    for i in range(len(off) - 1):
        s = slice(int(off[i]), int(off[i + 1]))
        yield (a["feature"][s], a["threshold"][s], a["left"][s],
               a["right"][s], a["value"][s], int(a["depth"][i]))


def summary(a: Arrays) -> dict:
    shapes = [(int((left >= 0).sum()), int((left < 0).sum()))
              for _, _, left, _, _, _ in _trees(a)]
    return {"n_trees": len(shapes), "max_depth": int(a["depth"].max()),
            "max_internal": max(i for i, _ in shapes),
            "max_leaves": max(l for _, l in shapes),
            "mean_leaves": float(np.mean([l for _, l in shapes]))}


def pipeline(model: dict, a: Arrays):
    """The program's fitted pipeline, built from the benchmark's arrays."""
    from repro.ml import (Pipeline, PipelineMetadata, RandomForest,
                          StandardScaler)
    from repro.ml.tree import TreeArrays

    scaler = StandardScaler(list(model["features"]))
    scaler.mean, scaler.std = a["mean"].copy(), a["std"].copy()
    forest = RandomForest(n_trees=len(a["depth"]), task="regression",
                          max_depth=int(a["depth"].max()))
    forest.trees = [
        TreeArrays(feature=f.copy(), threshold=t.copy(), left=lo.copy(),
                   right=r.copy(), value=v[:, None].copy(), depth=d,
                   n_features=len(model["features"]))
        for f, t, lo, r, v, d in _trees(a)]
    return Pipeline([scaler], forest,
                    PipelineMetadata(name=model["name"], task="regression"))


def _walk(x: np.ndarray, tree, dtype) -> np.ndarray:
    """One tree, node by node, over x [F, n]: each row's leaf value."""
    feature, threshold, left, right, value, depth = tree
    leaf = left < 0
    ids = np.arange(len(left), dtype=np.int32)
    feature = np.where(leaf, 0, feature)
    threshold = np.where(leaf, np.inf, threshold).astype(dtype)
    left = np.where(leaf, ids, left)
    right = np.where(leaf, ids, right)
    cols = np.arange(x.shape[1])
    node = np.zeros(x.shape[1], np.int32)
    for _ in range(depth):
        go_left = x[feature[node], cols] <= threshold[node]
        node = np.where(go_left, left[node], right[node])
    return value.astype(dtype)[node]


def scores(a: Arrays, model: dict, rows: Dict[str, np.ndarray],
           dtype=np.float32, block: int = 1 << 15) -> np.ndarray:
    """Each row's score: the scaler in float32, then every tree walked in
    order, leaf values summed in float32 and divided by the tree count.
    ``dtype`` is what features, thresholds and leaf values are held in:
    float32 for the reference, bfloat16 for its control."""
    inv = np.float32(1.0) / a["std"]
    x = np.stack([(rows[c].astype(np.float32) - a["mean"][j]) * inv[j]
                  for j, c in enumerate(model["features"])]).astype(dtype)
    trees = list(_trees(a))

    def part(lo: int) -> np.ndarray:
        xb = x[:, lo:lo + block]
        acc = np.zeros(xb.shape[1], np.float32)
        for tree in trees:
            acc += _walk(xb, tree, dtype).astype(np.float32)
        return acc / np.float32(len(trees))

    n = x.shape[1]
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        parts = list(pool.map(part, range(0, n, block)))
    return np.concatenate(parts) if parts else np.zeros(0, np.float32)


def reference(a: Arrays, model: dict, rows) -> np.ndarray:
    return scores(a, model, rows, np.float32)


def control(a: Arrays, model: dict, rows) -> np.ndarray:
    return scores(a, model, rows, ml_dtypes.bfloat16)


def flops_per_row(a: Arrays, model: dict) -> float:
    """The Hummingbird GEMM formulation (paper section 4.2) on each tree's
    own unpadded sizes: 2 F I + 2 I L + 2 L O per row per tree, O = 1."""
    f = len(model["features"])
    total = 0
    for _, _, left, _, _, _ in _trees(a):
        i, l = int((left >= 0).sum()), int((left < 0).sum())
        total += 2 * f * i + 2 * i * l + 2 * l
    return float(total)


def working_set_bytes_per_row(a: Arrays, model: dict) -> int:
    """The dense strategy's float32 temporaries per scored row (gates [I],
    path counts and matches [L] each) plus the lane-padded input and
    output rows."""
    s = summary(a)
    return 4 * (s["max_internal"] + 2 * s["max_leaves"]) + 2 * 128 * 4
