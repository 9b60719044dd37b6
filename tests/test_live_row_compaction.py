"""Live-row compaction at the model step.

Where a ``filter`` narrows the table a native model scores, the program
gathers the live rows into chunks of ``codegen.COMPACT_ROWS`` and scores
only those (``codegen._score_live_rows``); the model's output keeps one row
per input row.  Every answer served through ``PredictionService.sql()`` is
compared bit for bit with a whole-table numpy float32 reference (node walks
for trees, with JAX's own sigmoid for probabilities), on the whole-table,
morsel and sharded paths, with live counts around the chunk size and a NaN
feature among the live rows.  The chunk is cut to a few rows here so that
those counts fit a small table.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ModelStore, codegen
from repro.core.codegen import ExecutionConfig, compaction_sites
from repro.core.optimizer import OptimizerConfig
from repro.core.rules.nn_translation import tree_slots
from repro.ml import (GradientBoostedTrees, LogisticRegression, Pipeline,
                      PipelineMetadata, RandomForest, StandardScaler)
from repro.ml.tree import TreeArrays
from repro.relational.table import Table
from repro.serve import PredictionService

C = 32                      # rows a compacted step scores per loop step here
N = 200
FEATURES = ["f0", "f1", "f2"]
LIVE = {"none": 0, "one": 1, "c_minus_1": C - 1, "c": C, "c_plus_1": C + 1,
        "fig1_share": round(0.0667 * N), "all": N}
MODELS = ["forest_gemm", "forest_pallas", "forest_traversal", "gbt_proba",
          "logistic"]
PATHS = ["whole", "morsel", "sharded"]


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(codegen, "COMPACT_ROWS", C)
        yield


def walk(t: TreeArrays, x: np.ndarray) -> np.ndarray:
    """Each row's leaf value [n, outputs], node by node (``NaN <= t`` is
    False: right)."""
    node = np.zeros(len(x), np.int64)
    for _ in range(t.depth):
        inner = t.left[node] >= 0
        left = x[np.arange(len(x)), t.feature[node]] <= t.threshold[node]
        node = np.where(inner, np.where(left, t.left[node], t.right[node]),
                        node)
    return t.value[node]


def complete_tree(rng, depth: int) -> TreeArrays:
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


def data():
    rng = np.random.default_rng(16)
    cols = {c: rng.normal(size=N).astype(np.float32) for c in FEATURES}
    cols["k"] = np.arange(N, dtype=np.int32)
    # a random permutation: ``rank < n`` keeps n rows spread over the table
    cols["rank"] = rng.permutation(N).astype(np.int32)
    # NaN features in rows live from n = 1 and from n = C + 1 on
    cols["f1"][cols["rank"] == 0] = np.nan
    cols["f0"][cols["rank"] == C] = np.nan
    return cols


def model(name: str):
    """(model, task, PREDICT function, numpy [n, F] -> reference)."""
    rng = np.random.default_rng(len(name))
    if name.startswith("forest"):
        rf = RandomForest(n_trees=6, task="regression")
        rf.trees = [complete_tree(rng, 4) for _ in range(6)]

        def ref(x):
            acc = walk(rf.trees[0], x)
            for t in rf.trees[1:]:
                acc = acc + walk(t, x)
            # inside a compiled program the division by the tree count is
            # a multiplication by its float32 reciprocal
            return (acc * (np.float32(1) / np.float32(len(rf.trees))))[:, 0]
        return rf, "regression", "PREDICT", ref
    if name == "gbt_proba":
        gbt = GradientBoostedTrees(learning_rate=0.3, loss="log_loss")
        gbt.trees = [complete_tree(rng, 3) for _ in range(8)]
        gbt.n_trees = len(gbt.trees)
        gbt.base = -0.4

        def ref(x):
            acc = np.zeros(len(x), np.float32)
            for t in gbt.trees:
                acc = acc + np.float32(gbt.learning_rate) * walk(t, x)[:, 0]
            return np.asarray(jax.nn.sigmoid(jnp.asarray(
                acc + np.float32(gbt.base))))
        return gbt, "classification", "PREDICT_PROBA", ref
    lr = LogisticRegression()
    lr.weights = rng.normal(size=len(FEATURES)).astype(np.float32)
    lr.bias = 0.25

    def ref(x):
        margin = jnp.dot(jnp.asarray(x), jnp.asarray(lr.weights),
                         precision=jax.lax.Precision.HIGHEST) + lr.bias
        return np.asarray(jax.nn.sigmoid(margin))
    return lr, "classification", "PREDICT_PROBA", ref


def optimizer_config(name: str) -> OptimizerConfig:
    # pruning off: the trees served are the trees built here
    cfg = {"enable_model_pruning": False, "enable_stats_pruning": False}
    if name.startswith("forest"):
        cfg["tree_strategy"] = name.split("_")[1]
    elif name == "gbt_proba":
        cfg["tree_strategy"] = "gemm"
    else:    # keep the native predict_model: no linear-algebra chain
        cfg["enable_nn_translation"] = False
    return OptimizerConfig(**cfg)


def service(name: str, path: str,
            opt: OptimizerConfig = None) -> PredictionService:
    raw_features = StandardScaler(FEATURES)
    raw_features.mean = np.zeros(len(FEATURES), np.float32)
    raw_features.std = np.ones(len(FEATURES), np.float32)
    m, task, _, _ = model(name)
    store = ModelStore()
    table = Table.from_pydict(data())
    if path == "sharded":
        store.register_table("t", table, partition_rows=50)
    else:
        store.register_table("t", table)
    store.register_model("m", Pipeline(
        [raw_features], m,
        PipelineMetadata(name="m", task=task, flavor="repro.native")))
    knobs = {}
    if path == "morsel":
        knobs["chunk_rows"] = 64
    if path == "sharded":
        knobs["execution_config"] = ExecutionConfig(
            sharded=True, shard_min_bucket_rows=16, shard_morsel_rows=64)
    return PredictionService(store,
                             optimizer_config=opt or optimizer_config(name),
                             enable_result_cache=False, **knobs)


@pytest.fixture(scope="module")
def services():
    made = {}

    def get(name: str, path: str) -> PredictionService:
        if (name, path) not in made:
            made[name, path] = service(name, path)
        return made[name, path]

    yield get
    for svc in made.values():
        svc.close()


def serve(svc: PredictionService, name: str, n: int):
    # a literal bound: the optimizer pushes it below the model (a
    # parameter would leave the filter above it)
    sql = (f"SELECT k, {model(name)[2]}(MODEL='m') AS p FROM t "
           f"WHERE rank < {n}")
    return sql, svc.sql(sql)


@pytest.mark.parametrize("live", list(LIVE), ids=list(LIVE))
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("name", MODELS)
def test_served_scores_equal_the_whole_table_reference(services, name, path,
                                                       live):
    n = LIVE[live]
    svc = services(name, path)
    sql, out = serve(svc, name, n)
    assert compaction_sites(svc.compile(sql).plan)
    cols = data()
    keep = cols["rank"] < n
    x = np.stack([cols[f] for f in FEATURES], axis=1)
    want = model(name)[3](x)[keep]
    valid = np.asarray(out.valid)
    got_k = np.asarray(out.columns["k"])[valid]
    got_p = np.asarray(out.columns["p"])[valid]
    order = np.argsort(got_k)
    np.testing.assert_array_equal(got_k[order], cols["k"][keep])
    np.testing.assert_array_equal(got_p[order], want)
    assert got_p.dtype == want.dtype == np.float32
    if n > C:
        assert np.isnan(x[keep]).any()
    if path == "morsel":
        assert svc.stats.chunks_executed >= 4
    if path == "sharded":
        assert svc.stats.sharded_executions >= 1


@pytest.mark.parametrize("live", list(LIVE), ids=list(LIVE))
def test_rows_scored_over_rows_read(live):
    """``model_rows_scored / model_rows`` is ``ceil(live / C) * C / N``
    on one whole-table execution, and the tree-slot counters multiply by
    the rows scored."""
    n = LIVE[live]
    svc = service("forest_gemm", "whole")
    try:
        sql, _ = serve(svc, "forest_gemm", n)
        st = svc.stats
        assert st.model_rows == N
        assert st.model_rows_scored == math.ceil(n / C) * C
        real = tree_slots(model("forest_gemm")[0].trees)
        node = next(v for v in svc.compile(sql).plan.nodes.values()
                    if v.op == "tree_gemm")
        assert node.attrs["slots"][0] == real
        assert st.tree_slots == real * st.model_rows_scored
        assert st.tree_slots_padded == \
            node.attrs["slots"][1] * st.model_rows_scored
    finally:
        svc.close()


@pytest.mark.parametrize("name", ["logistic_translated", "gbt_proba"])
def test_no_filter_no_compaction(name):
    """The Fig 2a shapes, scan -> featurize -> model with no WHERE, lower
    with no compaction: the program returns its result alone, EXPLAIN says
    the model scores every row, and every row read is scored."""
    if name == "logistic_translated":    # the default plan: matmul chain
        svc = service("logistic", "whole", OptimizerConfig())
    else:
        svc = service(name, "whole")
    try:
        sql = "SELECT k, PREDICT_PROBA(MODEL='m') AS p FROM t"
        out = svc.sql(sql)
        compiled = svc.compile(sql)
        assert compaction_sites(compiled.plan) == {}
        assert not hasattr(compiled.raw_fn, "compacted")
        assert isinstance(out, Table)
        text = svc.explain(sql).pretty()
        assert "every row" in text and "live rows" not in text
        assert svc.stats.model_rows == svc.stats.model_rows_scored == N
    finally:
        svc.close()


def test_explain_names_the_compacted_step():
    svc = service("forest_gemm", "whole")
    try:
        text = svc.explain(
            "SELECT k, PREDICT(MODEL='m') AS p FROM t WHERE rank < 9"
        ).pretty()
        assert f"tree_gemm [strategy=gemm, live rows in chunks of " \
            f"{codegen.COMPACT_ROWS}]" in text
    finally:
        svc.close()
