"""L1 logistic regression behind ``PREDICT_PROBA``: scikit-learn fits it,
the benchmark keeps its weights, and a plain one-hot dot is its reference.

The configuration's ``model`` block gives ``one_hot`` (categorical columns,
one feature per category seen in the fitting sample), ``scaled`` (numeric
columns, ``(x - mean) * (1 / std)``), ``C`` (scikit-learn's inverse L1
strength, chosen for the share of zero weights the paper's sparser model
has) and the fitting sample's ``fit_rows`` and ``fit_seed``.  Features are
ordered one-hot blocks first, column by column and category by category,
then the scaled columns: the order of the library's pipeline.
"""

from __future__ import annotations

from typing import Dict

import ml_dtypes
import numpy as np

Arrays = Dict[str, np.ndarray]


def _design(a: Arrays, model: dict, rows, dtype=np.float32) -> np.ndarray:
    """The full feature matrix [n, D], every weight's feature included."""
    blocks = []
    cats = np.split(a["categories"], a["category_offsets"][1:-1])
    for c, values in zip(model["one_hot"], cats):
        blocks.append((rows[c][:, None] == values[None, :]).astype(dtype))
    inv = np.float32(1.0) / a["std"]
    for j, c in enumerate(model["scaled"]):
        blocks.append((((rows[c].astype(np.float32) - a["mean"][j])
                        * inv[j])[:, None]).astype(dtype))
    return np.concatenate(blocks, axis=1)


def fit(model: dict, rows: Dict[str, np.ndarray]) -> Arrays:
    from sklearn.linear_model import LogisticRegression

    cats = [np.unique(rows[c]).astype(np.int32) for c in model["one_hot"]]
    raw = np.stack([rows[c].astype(np.float32) for c in model["scaled"]],
                   axis=1)
    a = {"categories": np.concatenate(cats),
         "category_offsets": np.cumsum([0] + [len(c) for c in cats]),
         "mean": raw.astype(np.float64).mean(0).astype(np.float32),
         "std": (raw.astype(np.float64).std(0) + 1e-8).astype(np.float32)}
    x = _design(a, model, rows)
    lr = LogisticRegression(l1_ratio=1.0, C=model["C"], solver="liblinear",
                            random_state=model["fit_seed"], max_iter=1000)
    lr.fit(x, rows[model["label"]])
    a["weights"] = lr.coef_[0].astype(np.float32)
    a["bias"] = np.asarray([lr.intercept_[0]], np.float32)
    return a


def summary(a: Arrays) -> dict:
    w = a["weights"]
    return {"features": int(w.size), "zero_weights": int((w == 0).sum()),
            "zero_share": float((w == 0).mean())}


def pipeline(model: dict, a: Arrays):
    """The program's fitted pipeline, built from the benchmark's arrays."""
    from repro.ml import (LogisticRegression, OneHotEncoder, Pipeline,
                          PipelineMetadata, StandardScaler)

    enc = OneHotEncoder(list(model["one_hot"]))
    cats = np.split(a["categories"], a["category_offsets"][1:-1])
    enc.categories = {c: v.copy() for c, v in zip(model["one_hot"], cats)}
    scaler = StandardScaler(list(model["scaled"]))
    scaler.mean, scaler.std = a["mean"].copy(), a["std"].copy()
    lr = LogisticRegression()
    lr.weights = a["weights"].copy()
    lr.bias = float(a["bias"][0])
    return Pipeline([enc, scaler], lr,
                    PipelineMetadata(name=model["name"],
                                     task="classification"))


def _proba(a: Arrays, model: dict, rows, dtype) -> np.ndarray:
    """P(positive) over every weight: a one-hot feature is 1 exactly where
    the row's code equals its category, so its weight is gathered, not
    multiplied.  Weights and scaled features are held in ``dtype`` and
    summed in float64."""
    w = a["weights"].astype(dtype).astype(np.float64)
    cats = np.split(a["categories"], a["category_offsets"][1:-1])
    n = len(rows[model["one_hot"][0]])
    z = np.full(n, float(a["bias"][0]))
    for c, values, lo in zip(model["one_hot"], cats,
                             a["category_offsets"][:-1]):
        pos = np.minimum(np.searchsorted(values, rows[c]), len(values) - 1)
        hit = values[pos] == rows[c]
        z += np.where(hit, w[lo + pos], 0.0)
    inv = np.float32(1.0) / a["std"]
    base = int(a["category_offsets"][-1])
    for j, c in enumerate(model["scaled"]):
        x = ((rows[c].astype(np.float32) - a["mean"][j]) * inv[j]).astype(
            dtype).astype(np.float64)
        z += x * w[base + j]
    return 1.0 / (1.0 + np.exp(-z))


def reference(a: Arrays, model: dict, rows) -> np.ndarray:
    """Float32 features and weights, summed in float64."""
    return _proba(a, model, rows, np.float32)


def control(a: Arrays, model: dict, rows) -> np.ndarray:
    """The same with features and weights held in bfloat16."""
    return _proba(a, model, rows, ml_dtypes.bfloat16)


def flops_per_row(a: Arrays, model: dict) -> float:
    """2 x the featurized width x one output."""
    return float(2 * a["weights"].size)


def working_set_bytes_per_row(a: Arrays, model: dict) -> int:
    """The featurized float32 row and the float32 input columns."""
    return 4 * (a["weights"].size + len(model["one_hot"])
                + len(model["scaled"]))
