"""Boosted ensembles behind ``PREDICT`` and ``PREDICT_PROBA``.

A ``GradientBoostedTrees`` states its loss, and every runtime and tree
strategy maps its margin ``base + learning_rate * sum_t leaf_t(x)`` the
same way (``core.codegen.output_task``): under ``log_loss``
``PREDICT_PROBA`` is the margin's sigmoid and classification ``PREDICT``
is ``margin > 0``; under ``squared_error`` both return the margin.  Each
answer served through ``PredictionService.sql()`` is compared with a plain
numpy float32 node walk on seeded random ensembles whose trees differ in
size, as a leaf-wise booster's do.
"""

import numpy as np
import pytest

from repro.core import ModelStore
from repro.core.optimizer import OptimizerConfig
from repro.ml import (GradientBoostedTrees, Pipeline, PipelineMetadata,
                      StandardScaler)
from repro.ml.tree import TreeArrays
from repro.relational.table import Table
from repro.serve import PredictionService

FEATURES = ["f0", "f1", "f2", "f3"]
N_ROWS = 300


def random_tree(rng, max_depth: int) -> TreeArrays:
    """A tree grown by random splits that stop early at random, so trees of
    one ensemble come out of uneven sizes."""
    feature, threshold, left, right, value = [], [], [], [], []

    def grow(depth: int) -> int:
        node = len(feature)
        feature.append(-1)
        threshold.append(0.0)
        left.append(-1)
        right.append(-1)
        value.append(0.0)
        if depth < max_depth and (depth == 0 or rng.random() < 0.7):
            feature[node] = int(rng.integers(len(FEATURES)))
            threshold[node] = float(rng.normal())
            left[node] = grow(depth + 1)
            right[node] = grow(depth + 1)
        else:
            value[node] = float(rng.normal(scale=0.5))
        return node

    grow(0)

    def depth_of(i: int) -> int:
        if left[i] < 0:
            return 0
        return 1 + max(depth_of(left[i]), depth_of(right[i]))

    return TreeArrays(
        feature=np.asarray(feature, np.int32),
        threshold=np.asarray(threshold, np.float32),
        left=np.asarray(left, np.int32), right=np.asarray(right, np.int32),
        value=np.asarray(value, np.float32)[:, None], depth=depth_of(0),
        n_features=len(FEATURES))


def complete_tree(rng, depth: int) -> TreeArrays:
    """A complete binary tree: every tree of an ensemble the same size."""
    n = 2 ** (depth + 1) - 1
    internal = np.arange(n) < 2 ** depth - 1
    ids = np.arange(n)
    return TreeArrays(
        feature=np.where(internal, rng.integers(len(FEATURES), size=n),
                         -1).astype(np.int32),
        threshold=np.where(internal, rng.normal(size=n), 0.0
                           ).astype(np.float32),
        left=np.where(internal, 2 * ids + 1, -1).astype(np.int32),
        right=np.where(internal, 2 * ids + 2, -1).astype(np.int32),
        value=np.where(internal, 0.0, rng.normal(size=n)
                       ).astype(np.float32)[:, None],
        depth=depth, n_features=len(FEATURES))


def boosted(seed: int, loss: str, trees=None) -> GradientBoostedTrees:
    rng = np.random.default_rng(seed)
    gbt = GradientBoostedTrees(learning_rate=0.3, loss=loss)
    gbt.trees = trees or [random_tree(rng, 5) for _ in range(12)]
    gbt.n_trees = len(gbt.trees)
    gbt.base = -0.4
    return gbt


def raw_features() -> StandardScaler:
    """Mean 0 and scale 1: ``(x - 0) * 1`` is ``x`` in float32."""
    sc = StandardScaler(FEATURES)
    sc.mean = np.zeros(len(FEATURES), np.float32)
    sc.std = np.ones(len(FEATURES), np.float32)
    return sc


def table(seed: int):
    rng = np.random.default_rng(seed + 1)
    cols = {c: rng.normal(size=N_ROWS).astype(np.float32) for c in FEATURES}
    cols["k"] = np.arange(N_ROWS, dtype=np.int32)
    return cols


def margin_reference(gbt: GradientBoostedTrees, cols) -> np.ndarray:
    """Each tree walked node by node in float32, the scaled leaf values
    summed in tree order, then the base."""
    x = np.stack([cols[c] for c in FEATURES], axis=1).astype(np.float32)
    acc = np.zeros(len(x), np.float32)
    for t in gbt.trees:
        leaf = np.empty(len(x), np.float32)
        for r in range(len(x)):
            node = 0
            while t.left[node] >= 0:
                go_left = x[r, t.feature[node]] <= t.threshold[node]
                node = t.left[node] if go_left else t.right[node]
            leaf[r] = t.value[node, 0]
        acc = acc + np.float32(gbt.learning_rate) * leaf
    return acc + np.float32(gbt.base)


def serve(gbt, cols, sql, strategy: str, task="classification", **cfg):
    store = ModelStore()
    store.register_table("t", Table.from_pydict(cols))
    flavor = "external" if strategy == "external" else "repro.native"
    store.register_model("m", Pipeline(
        [raw_features()], gbt,
        PipelineMetadata(name="m", task=task, flavor=flavor)))
    opt = OptimizerConfig(
        tree_strategy="auto" if strategy == "external" else strategy, **cfg)
    svc = PredictionService(store, optimizer_config=opt,
                            enable_result_cache=False)
    try:
        out = svc.sql(sql)
        got = {c: np.asarray(v) for c, v in out.columns.items()}
        return got, svc
    finally:
        svc.close()


STRATEGIES = ["traversal", "gemm", "pallas", "external"]


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("loss,fn", [("log_loss", "PREDICT_PROBA"),
                                     ("log_loss", "PREDICT"),
                                     ("squared_error", "PREDICT_PROBA"),
                                     ("squared_error", "PREDICT")])
def test_every_strategy_maps_the_margin_by_the_loss(strategy, loss, fn):
    """Same answer from traversal, dense GEMM, Pallas (interpret mode on
    the CPU) and the external runtime, each against the numpy reference.
    A squared-error ensemble in a classification pipeline returns its
    margin under both functions: it is the prediction, not a logit."""
    gbt = boosted(3, loss)
    cols = table(3)
    got, _ = serve(gbt, cols, f"SELECT k, {fn}(MODEL='m') AS p FROM t",
                   strategy)
    np.testing.assert_array_equal(got["k"], cols["k"])
    margin = margin_reference(gbt, cols)
    if loss == "squared_error":
        want = margin
    elif fn == "PREDICT_PROBA":
        want = 1.0 / (1.0 + np.exp(-margin.astype(np.float64)))
    else:
        want = (margin > 0).astype(np.float32)
    np.testing.assert_allclose(got["p"], want, rtol=1e-6, atol=1e-6)


def test_dense_strategy_proba_is_the_probability_not_the_margin():
    """Until the loss was stated, the dense strategy forced every boosted
    ensemble to regression and returned the raw margin under
    ``PREDICT_PROBA`` (gap 0.38 to the sigmoid on a 5-tree probe), while
    traversal returned the sigmoid."""
    gbt = boosted(5, "log_loss")
    cols = table(5)
    sql = "SELECT k, PREDICT_PROBA(MODEL='m') AS p FROM t"
    dense, _ = serve(gbt, cols, sql, "gemm")
    walked, _ = serve(gbt, cols, sql, "traversal")
    margin = margin_reference(gbt, cols)
    assert np.all((dense["p"] > 0) & (dense["p"] < 1))
    assert np.abs(dense["p"] - margin).max() > 0.1
    np.testing.assert_allclose(dense["p"], walked["p"], rtol=0, atol=1e-6)


def test_regression_pipeline_returns_the_margin():
    gbt = boosted(7, "squared_error")
    cols = table(7)
    got, _ = serve(gbt, cols, "SELECT k, PREDICT(MODEL='m') AS p FROM t",
                   "gemm", task="regression")
    np.testing.assert_allclose(got["p"], margin_reference(gbt, cols),
                               rtol=1e-6, atol=1e-6)


def test_unknown_loss_and_fitting_a_log_loss_model_refused():
    with pytest.raises(ValueError):
        GradientBoostedTrees(loss="hinge")
    x = np.zeros((10, 2), np.float32)
    with pytest.raises(ValueError):
        GradientBoostedTrees(loss="log_loss").fit(x, np.zeros(10))


def slot_counts(trees):
    real = 0
    for t in trees:
        leaves = int((t.left < 0).sum())
        internal = t.n_nodes - leaves
        real += internal + internal * leaves + leaves
    return real


@pytest.mark.parametrize("shape", ["uneven", "equal"])
def test_tree_slot_counters(shape):
    """``ServiceStats.tree_slots`` counts each executed plan's fitted tree
    slots per row times its rows, ``tree_slots_padded`` the same at the
    padded ensemble's sizes: never fewer, and equal for trees of one size
    when nothing pads them.  EXPLAIN carries both per row; traversal runs
    no tree_gemm and counts nothing.  Pruning is off, so the trees served
    are the trees built here."""
    rng = np.random.default_rng(11)
    cfg = {"enable_model_pruning": False, "enable_stats_pruning": False}
    if shape == "equal":
        trees = [complete_tree(rng, 3) for _ in range(6)]
        cfg["gemm_pad_to"] = 1
    else:
        trees = [random_tree(rng, 5) for _ in range(12)]
    gbt = boosted(11, "log_loss", trees)
    cols = table(11)
    sql = "SELECT k, PREDICT_PROBA(MODEL='m') AS p FROM t"
    _, svc = serve(gbt, cols, sql, "gemm", **cfg)
    real = slot_counts(trees)
    st = svc.stats
    assert st.tree_slots == real * N_ROWS
    if shape == "equal":
        assert st.tree_slots_padded == st.tree_slots
    else:
        n_i = max(t.n_nodes - int((t.left < 0).sum()) for t in trees)
        n_l = max(int((t.left < 0).sum()) for t in trees)
        pad = lambda v: -(-v // 8) * 8                         # noqa: E731
        want = len(trees) * (pad(n_i) + pad(n_i) * pad(n_l) + pad(n_l))
        assert st.tree_slots_padded == want * N_ROWS > st.tree_slots
    assert f"slots {real} real" in svc.explain(sql).pretty()
    _, walked = serve(gbt, cols, sql, "traversal", **cfg)
    assert walked.stats.tree_slots == walked.stats.tree_slots_padded == 0
