"""A log-loss boosted ensemble behind ``PREDICT_PROBA``: scikit-learn's
histogram booster fits it, the benchmark keeps its trees, and a plain node
walk is its reference.

The model the program serves is built from these arrays, never the other
way round, so the reference shares nothing with the code under test.  The
configuration's ``model`` block gives:

- ``features``: the input columns, fed to the trees as they are (coded
  categoricals as numbers, as gbm-bench feeds Airline's);
- ``max_iter``, ``max_depth``, ``max_leaf_nodes``, ``learning_rate``:
  ``HistGradientBoostingClassifier``'s settings, with early stopping off
  and every other setting at scikit-learn's default;
- ``fit_rows``, ``fit_seed``: the fitting sample, generated by the
  deployment from that seed, so every ``--seed`` scores the same model.

The answer is ``sigmoid(baseline + sum_t value_t[leaf_t(x)])``: the
library's leaf values already hold the learning rate.  The arrays use the
layout of ``random_forest.py``, whose tree walk, size summary and counts
serve here too.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import ml_dtypes
import numpy as np

from bench.models.random_forest import (_trees, _walk, flops_per_row,
                                        summary, working_set_bytes_per_row)

__all__ = ["fit", "summary", "pipeline", "reference", "control",
           "flops_per_row", "working_set_bytes_per_row"]

Arrays = Dict[str, np.ndarray]

# The program picks a tree strategy from a cost model it measures on the
# chip once per process; set-up times that step on its own.
CALIBRATES = True


def float32_below(t: np.ndarray) -> np.ndarray:
    """The largest float32 not above each float64 threshold, so that
    ``x <= t32`` is the library's ``x <= t`` for every float32 ``x``."""
    t32 = t.astype(np.float32)
    over = t32.astype(np.float64) > t
    return np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32)


def fit(model: dict, rows: Dict[str, np.ndarray]) -> Arrays:
    from sklearn.ensemble import HistGradientBoostingClassifier

    x = np.stack([rows[c].astype(np.float32) for c in model["features"]],
                 axis=1)
    clf = HistGradientBoostingClassifier(
        max_iter=model["max_iter"], max_depth=model["max_depth"],
        max_leaf_nodes=model["max_leaf_nodes"],
        learning_rate=model["learning_rate"], early_stopping=False,
        random_state=model["fit_seed"])
    clf.fit(x, rows[model["label"]])
    # one predictor per iteration for a binary task; private, but the
    # model cache's key holds scikit-learn's version
    nodes = [p[0].nodes for p in clf._predictors]
    leaf = [n["is_leaf"].astype(bool) for n in nodes]
    return {
        "baseline": np.asarray(clf._baseline_prediction, np.float32
                               ).reshape(1),
        "offsets": np.cumsum([0] + [len(n) for n in nodes]),
        "depth": np.asarray([n["depth"].max() for n in nodes], np.int32),
        "feature": np.concatenate([np.where(lf, -1, n["feature_idx"])
                                   for n, lf in zip(nodes, leaf)]
                                  ).astype(np.int32),
        "threshold": np.concatenate([np.where(
            lf, np.float32(0), float32_below(n["num_threshold"]))
            for n, lf in zip(nodes, leaf)]).astype(np.float32),
        "left": np.concatenate([np.where(lf, -1, n["left"])
                                for n, lf in zip(nodes, leaf)]
                               ).astype(np.int32),
        "right": np.concatenate([np.where(lf, -1, n["right"])
                                 for n, lf in zip(nodes, leaf)]
                                ).astype(np.int32),
        "value": np.concatenate([np.where(lf, n["value"], 0.0)
                                 for n, lf in zip(nodes, leaf)]
                                ).astype(np.float32),
    }


def pipeline(model: dict, a: Arrays):
    """The program's fitted pipeline, built from the benchmark's arrays: a
    ``log_loss`` ensemble with the library's baseline and learning rate 1
    (the leaf values hold the rate).  Its featurizer is a scaler of mean 0
    and scale 1, which computes ``(x - 0) * 1 = x`` in float32: the trees
    see the raw columns."""
    from repro.ml import (GradientBoostedTrees, Pipeline, PipelineMetadata,
                          StandardScaler)
    from repro.ml.tree import TreeArrays

    n = len(model["features"])
    raw = StandardScaler(list(model["features"]))
    raw.mean, raw.std = np.zeros(n, np.float32), np.ones(n, np.float32)
    gbt = GradientBoostedTrees(n_trees=len(a["depth"]),
                               max_depth=int(a["depth"].max()),
                               learning_rate=1.0, loss="log_loss")
    gbt.base = float(a["baseline"][0])
    gbt.trees = [
        TreeArrays(feature=f.copy(), threshold=t.copy(), left=lo.copy(),
                   right=r.copy(), value=v[:, None].copy(), depth=d,
                   n_features=n)
        for f, t, lo, r, v, d in _trees(a)]
    return Pipeline([raw], gbt,
                    PipelineMetadata(name=model["name"],
                                     task="classification"))


def probabilities(a: Arrays, model: dict, rows: Dict[str, np.ndarray],
                  dtype=np.float32, block: int = 1 << 15) -> np.ndarray:
    """Each row's P(positive): every tree walked in order, leaf values
    summed in float32, the baseline added, then a float32 sigmoid.
    ``dtype`` is what features, thresholds and leaf values are held in:
    float32 for the reference, bfloat16 for its control."""
    x = np.stack([rows[c].astype(np.float32)
                  for c in model["features"]]).astype(dtype)
    trees = list(_trees(a))
    base = a["baseline"].astype(dtype).astype(np.float32)[0]

    def part(lo: int) -> np.ndarray:
        xb = x[:, lo:lo + block]
        acc = np.zeros(xb.shape[1], np.float32)
        for tree in trees:
            acc += _walk(xb, tree, dtype).astype(np.float32)
        m = acc + base
        e = np.exp(-np.abs(m))
        return np.where(m >= 0, np.float32(1) / (np.float32(1) + e),
                        e / (np.float32(1) + e))

    n = x.shape[1]
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        parts = list(pool.map(part, range(0, n, block)))
    return np.concatenate(parts) if parts else np.zeros(0, np.float32)


def reference(a: Arrays, model: dict, rows) -> np.ndarray:
    return probabilities(a, model, rows, np.float32)


def control(a: Arrays, model: dict, rows) -> np.ndarray:
    return probabilities(a, model, rows, ml_dtypes.bfloat16)
