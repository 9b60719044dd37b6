"""Runtime code generation: optimized Raven IR -> executable JAX (paper §5).

The paper's Runtime Code Generator emits a SQL query whose model invocations
execute in-process (ONNX Runtime inside SQL Server), out-of-process
(``sp_execute_external_script``) or in a container.  Here the three execution
modes map to:

- **native** (in-process): the operator lowers *into the same jitted
  computation* as the relational plan — one fused XLA module.  This is the
  deepest possible integration: XLA fuses across the RA/ML boundary.
- **external** (out-of-process): the operator runs host-side through
  ``jax.pure_callback`` on numpy inputs — a real process/device boundary with
  real transfer costs, mirroring Raven Ext.
- **container**: like external plus a configurable injected latency simulating
  the REST hop of a containerized runtime (paper §5; we do not spin up real
  containers in this offline environment — documented in DESIGN.md §8).

``compile_plan`` returns a callable ``fn(tables) -> Table`` suitable for
``jax.jit``; ``execute`` runs a plan against the catalog's registered tables.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..relational import ops as rel_ops
from ..relational.expr import bind_params, expr_params
from ..relational.table import ColumnSchema, Schema, Table
from .ir import Plan, plan_params

__all__ = ["compile_plan", "execute", "resolve_params", "ExecutionConfig",
           "compile_stats", "reset_compile_stats", "add_compile_listener",
           "add_trace_listener", "pow2_bucket", "count_jit_trace",
           "COMPACT_ROWS", "model_steps", "compaction_sites"]

# XLA's CPU client owns a worker pool sized by the host's core count.  On a
# one-core host that single worker executes the whole computation — including
# any pure_callback, whose argument transfer (jax routes callback operands
# through device_put, so materializing them needs the same worker) then waits
# on the thread it is running on.  The external/container runtime wedges
# exactly there once operands outgrow the inline-copy path.  Synchronous
# dispatch keeps those transfers on the calling thread; with one core the
# async pipeline had nothing to overlap anyway, so this costs nothing.
if os.cpu_count() == 1:
    jax.config.update("jax_cpu_enable_async_dispatch", False)


class ExecutionConfig:
    """Knobs for non-native runtimes and partition-parallel execution.

    Sharded execution (``serve/sharded.py``): ``sharded=True`` routes
    row-local plans over *partitioned* catalog tables through the SPMD
    partition executor — surviving partitions (post zone-map pruning) are
    packed into bucket-shaped morsels and placed across a ``data`` mesh of
    ``shard_devices`` devices (0 = every local device).
    ``shard_morsel_rows`` caps morsel granularity (a huge table on few
    devices runs as multiple same-shaped waves instead of one giant
    executable); ``shard_min_bucket_rows`` floors the pow-2 morsel bucket.

    Exchange execution (``serve/exchange.py``): ``shard_exchange=True``
    lets equi-joins whose sides are *not* co-partitioned shard anyway via
    a hash-repartition shuffle on the join key.
    ``shard_exchange_cost_gate`` keeps the bytes-moved-vs-whole-table
    cost check (``core.cost_model.exchange_beneficial``) in front of the
    shuffle — small tables fall back to whole-table execution where the
    per-bucket dispatch overhead would dominate; tests that must pin the
    exchange path deterministically turn the gate off.
    """

    def __init__(self, container_latency_s: float = 0.05,
                 external_latency_s: float = 0.0,
                 use_pallas_tree_gemm: bool = False,
                 sharded: bool = False,
                 shard_devices: int = 0,
                 shard_morsel_rows: int = 1 << 16,
                 shard_min_bucket_rows: int = 64,
                 shard_exchange: bool = True,
                 shard_exchange_cost_gate: bool = True):
        self.container_latency_s = container_latency_s
        self.external_latency_s = external_latency_s
        self.use_pallas_tree_gemm = use_pallas_tree_gemm
        self.sharded = sharded
        self.shard_devices = shard_devices
        self.shard_morsel_rows = shard_morsel_rows
        self.shard_min_bucket_rows = shard_min_bucket_rows
        self.shard_exchange = shard_exchange
        self.shard_exchange_cost_gate = shard_exchange_cost_gate

    def cache_key(self) -> tuple:
        """Hashable identity for compiled-executable caching: two configs
        with equal knobs produce identical executables."""
        return (self.container_latency_s, self.external_latency_s,
                self.use_pallas_tree_gemm, self.sharded, self.shard_devices,
                self.shard_morsel_rows, self.shard_min_bucket_rows,
                self.shard_exchange, self.shard_exchange_cost_gate)


# Observability hooks: every compile_plan() call counts under
# ``plans_compiled`` and every jit *trace* of a serving executable under
# ``jit_traces`` (the serving layer calls ``count_jit_trace`` from inside
# its jitted closures — the increment is a Python side effect, so it runs
# exactly once per trace, i.e. once per distinct input shape XLA compiles
# for).  Plan compiles measure signature misses; jit traces measure
# shape-driven recompiles.  The two are deliberately separate counters —
# conflating them hides unbounded shape-specialized recompilation behind a
# flat "compiles" number (see ServiceStats.bucket_compiles).
compile_stats: Dict[str, int] = {"plans_compiled": 0, "jit_traces": 0}
_compile_listeners: List[Callable[[Plan], None]] = []
_trace_listeners: List[Callable[[], None]] = []


def reset_compile_stats() -> None:
    compile_stats["plans_compiled"] = 0
    compile_stats["jit_traces"] = 0


def count_jit_trace() -> None:
    """Record one jit trace (one shape-specialized XLA compilation)."""
    compile_stats["jit_traces"] += 1
    for listener in list(_trace_listeners):
        listener()


def pow2_bucket(n: int, min_rows: int = 1, max_rows: int = 0) -> int:
    """Row-count shape bucket: the smallest power-of-two >= ``n`` clamped
    to ``[min_rows, max_rows]``.  Padding batches to bucketed shapes keeps
    the number of distinct executables XLA compiles for a query at
    O(log max_rows/min_rows) no matter how batch sizes vary; beyond
    ``max_rows`` the bucket grows in ``max_rows`` multiples (compile count
    then linear in overflow factor, which bounded queues keep small)."""
    b = max(int(min_rows), 1)
    if max_rows and n > max_rows:
        return ((n + max_rows - 1) // max_rows) * max_rows
    while b < n:
        b <<= 1
    # clamp: with a non-power-of-two max_rows the doubling can overshoot
    # the cap even though n fits under it (still >= n in this branch)
    if max_rows:
        b = min(b, max_rows)
    return b


def add_compile_listener(fn: Callable[[Plan], None]) -> Callable[[], None]:
    """Register a hook fired on every compile_plan; returns an unsubscriber."""
    _compile_listeners.append(fn)
    return lambda: _compile_listeners.remove(fn)


def add_trace_listener(fn: Callable[[], None]) -> Callable[[], None]:
    """Register a hook fired on every ``count_jit_trace`` (i.e. once per
    shape-specialized XLA trace of a serving executable); returns an
    unsubscriber.  The serving layer's MetricsRegistry subscribes here so
    shape-driven recompiles surface as a process metric."""
    _trace_listeners.append(fn)
    return lambda: _trace_listeners.remove(fn)


def _model_scores(model, x: jnp.ndarray) -> jnp.ndarray:
    """Raw scores [n, k] for any supported model kind."""
    kind = getattr(model, "kind", None)
    if kind in ("decision_tree", "random_forest"):
        return model.predict_scores(x)
    if kind == "gbt":
        return model.predict(x)[:, None]
    if kind in ("linear_regression", "logistic_regression"):
        return model.decision_function(x)[:, None]
    if kind == "mlp":
        return model.predict_scores(x)
    raise ValueError(f"unknown model kind {kind}")


# Live-row compaction: rows a compacted model step scores per loop step.
# The middle of 8,192, 16,384 and 32,768; which is the smallest at which
# the dense tree GEMM keeps its per-row time on a v5e is still to be
# measured (PERF.md, section 7).
COMPACT_ROWS = 16384


def model_steps(plan: Plan) -> Dict[str, Optional[str]]:
    """Every model step of ``plan`` (a node that reads a ``featurize``
    output), in execution order, mapped to the table node whose live rows
    are all it scores, or to None where it scores every row.

    A native ``predict_model`` or ``tree_gemm`` is compacted when its
    featurized table has a ``filter`` on its path from the scans: a filter
    only narrows the validity mask, so without compaction the model would
    score every row the filter dropped.  External and container runtimes
    and the linear-algebra chains of translated linear models and MLPs
    score every row."""
    steps: Dict[str, Optional[str]] = {}
    nodes = plan.nodes
    for nid in plan.topo_order():
        n = nodes[nid]
        if not n.inputs or nodes[n.inputs[0]].op != "featurize":
            continue
        table = nodes[n.inputs[0]].inputs[0]
        native = n.op == "tree_gemm" or (n.op == "predict_model"
                                         and n.runtime == "native")
        steps[nid] = table if native and _filtered(plan, table) else None
    return steps


def compaction_sites(plan: Plan) -> Dict[str, str]:
    """The compacted model steps of ``plan``: node id -> the table node
    whose validity gates the rows it scores (:func:`model_steps`)."""
    return {nid: t for nid, t in model_steps(plan).items() if t is not None}


def _filtered(plan: Plan, nid: str) -> bool:
    """Whether a ``filter`` lies on some path from the scans to ``nid``."""
    stack, seen = [nid], set()
    while stack:
        cur = stack.pop()
        if cur in seen:
            continue
        seen.add(cur)
        n = plan.nodes[cur]
        if n.op == "filter":
            return True
        stack.extend(n.inputs)
    return False


def _score_live_rows(score: Callable[[jnp.ndarray], jnp.ndarray],
                     x: jnp.ndarray, valid: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``score`` over only the rows of ``x`` that ``valid`` keeps: their
    indices are compacted, and a loop scores ``ceil(live / C)`` chunks of C
    rows (C is ``COMPACT_ROWS`` clamped to the rows), gathering each chunk
    and scattering its scores back into an ``[n, k]`` result of zeros.
    Rows are scored independently, so every live row's score is the one
    ``score(x)`` gives it.  Returns the scores and the rows scored (chunks
    times C, an int32 scalar)."""
    n = x.shape[0]
    if n == 0:
        return score(x), jnp.int32(0)
    c = min(COMPACT_ROWS, n)
    steps = -(-n // c)
    live = jnp.sum(valid, dtype=jnp.int32)
    idx = jnp.nonzero(valid, size=steps * c, fill_value=n)[0]
    out = jax.eval_shape(score, jax.ShapeDtypeStruct((c,) + x.shape[1:],
                                                     x.dtype))

    def chunk(i, acc):
        rows = jax.lax.dynamic_slice_in_dim(idx, i * c, c)
        part = score(x.at[rows].get(mode="clip", indices_are_sorted=True))
        return acc.at[rows].set(part, mode="drop", indices_are_sorted=True)

    chunks = (live + c - 1) // c
    scores = jax.lax.fori_loop(
        0, chunks, chunk, jnp.zeros((n,) + out.shape[1:], out.dtype))
    return scores, chunks * c


def output_task(task: str, loss: Optional[str] = None) -> str:
    """The task under which a model's scores become its ``PREDICT``
    column (:func:`_scores_to_output`): the pipeline's, except where a
    boosted ensemble's ``loss`` says otherwise.  Every runtime and tree
    strategy maps through here.

    A boosted ensemble's score is one margin.  Under ``log_loss`` it is a
    logit: ``PREDICT_PROBA`` of a classification pipeline is its sigmoid
    and ``PREDICT`` is ``margin > 0``.  Under ``squared_error`` it is the
    prediction itself, returned as it is by ``PREDICT`` and
    ``PREDICT_PROBA`` alike, whatever the pipeline's task (fitted to 0/1
    labels it is a least-squares estimate of P(y = 1), to be thresholded
    by the query).  ``loss`` is None for every other kind."""
    return "regression" if loss == "squared_error" else task


def _scores_to_output(scores: jnp.ndarray, task: str, proba: bool
                      ) -> jnp.ndarray:
    """[n, k] scores -> [n] prediction column."""
    if scores.shape[-1] == 1:
        col = scores[:, 0]
        if task == "classification":
            if proba:
                return jax.nn.sigmoid(col)
            return (col > 0).astype(jnp.float32)
        return col
    if task == "classification":
        if proba:
            return jax.nn.softmax(scores, axis=-1)[:, 1]
        return jnp.argmax(scores, axis=-1).astype(jnp.float32)
    return scores[:, 0]


# ---------------------------------------------------------------------------
# External / container runtime: pure-numpy host evaluation.
#
# The out-of-process runtimes run behind ``jax.pure_callback``, and the
# callback body must not dispatch jax work: callbacks execute on device
# execution threads, and under partition-parallel execution
# (``serve/sharded.py``) every device can sit inside a callback at once —
# a nested jnp op would then queue behind computations that are themselves
# blocked on callbacks (observed as a hard deadlock at 8 simulated
# devices).  It is also the honest simulation: Raven Ext evaluates the
# model in a *separate* runtime (sp_execute_external_script / ONNX in a
# container), not in the database engine's compute stream.  Model
# parameters are snapshotted to host numpy once at closure-build time.
# ---------------------------------------------------------------------------

def _np_sigmoid(x: np.ndarray) -> np.ndarray:
    out = np.empty_like(x, dtype=np.float32)
    pos = x >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-x[pos]))
    ex = np.exp(x[~pos])
    out[~pos] = ex / (1.0 + ex)
    return out


def _tree_scores_np(tree, x: np.ndarray) -> np.ndarray:
    """Vectorized numpy twin of ``TreeArrays.predict_jnp`` (same fixed
    depth-bounded traversal, so identical leaf assignment)."""
    n = x.shape[0]
    node = np.zeros((n,), np.int32)
    rows = np.arange(n)
    for _ in range(max(tree.depth, 1)):
        is_leaf = tree.left[node] < 0
        go_left = x[rows, tree.feature[node]] <= tree.threshold[node]
        nxt = np.where(go_left, tree.left[node], tree.right[node])
        node = np.where(is_leaf, node, nxt).astype(np.int32)
    return tree.value[node]


def _np_model_fn(model):
    """Build a ``numpy [n, d] -> numpy [n, k]`` scorer with every
    parameter already host-resident (no jax objects captured)."""
    kind = getattr(model, "kind", None)
    if kind == "decision_tree":
        tree = model.tree
        return lambda x: _tree_scores_np(tree, x)
    if kind == "random_forest":
        trees = list(model.trees)
        return lambda x: sum(_tree_scores_np(t, x) for t in trees) \
            / len(trees)
    if kind == "gbt":
        trees, base, lr = list(model.trees), model.base, model.learning_rate

        def gbt(x):
            # the float32 order of GradientBoostedTrees.predict
            out = np.zeros((x.shape[0],), np.float32)
            for t in trees:
                out = out + lr * _tree_scores_np(t, x)[:, 0]
            return (out + base)[:, None]
        return gbt
    if kind in ("linear_regression", "logistic_regression"):
        w = np.asarray(model.weights, np.float32)
        b = np.float32(model.bias)
        return lambda x: (x @ w + b)[:, None]
    if kind == "mlp":
        layers = [(np.asarray(p["w"], np.float32),
                   np.asarray(p["b"], np.float32)) for p in model.params]

        def mlp(x):
            h = x
            for i, (w, b) in enumerate(layers):
                h = h @ w + b
                if i < len(layers) - 1:
                    h = np.maximum(h, 0.0)
            return h
        return mlp
    raise ValueError(f"unknown model kind {kind}")


def _scores_to_output_np(scores: np.ndarray, task: str,
                         proba: bool) -> np.ndarray:
    """numpy twin of :func:`_scores_to_output`."""
    if scores.shape[-1] == 1:
        col = scores[:, 0]
        if task == "classification":
            if proba:
                return _np_sigmoid(col)
            return (col > 0).astype(np.float32)
        return col
    if task == "classification":
        if proba:
            e = np.exp(scores - scores.max(axis=-1, keepdims=True))
            return (e / e.sum(axis=-1, keepdims=True))[:, 1]
        return np.argmax(scores, axis=-1).astype(np.float32)
    return scores[:, 0]


def _external_predict(model, task: str, proba: bool, latency_s: float):
    """Host-side (numpy) model evaluation behind a pure_callback — the
    Raven Ext / container execution path."""
    score_fn = _np_model_fn(model)
    task = output_task(task, getattr(model, "loss", None))

    def host_fn(x: np.ndarray) -> np.ndarray:
        if latency_s > 0:
            time.sleep(latency_s)
        scores = score_fn(np.asarray(x, np.float32))
        return np.asarray(_scores_to_output_np(scores, task, proba),
                          np.float32)

    def call(x: jnp.ndarray) -> jnp.ndarray:
        shape = jax.ShapeDtypeStruct((x.shape[0],), jnp.float32)
        return jax.pure_callback(host_fn, shape, x)

    return call


# Name scope of each op's device ops (``jax.named_scope``), so a profiler
# trace attributes device time to the layer that emitted it; every other
# op is ``repro.relational``.  HLO metadata only: fusion is unchanged.
_SCOPES = {"join": "repro.join", "udf": "repro.udf"}
_SCOPES.update(dict.fromkeys(
    ("featurize", "gather_features", "affine"), "repro.featurize"))
_SCOPES.update(dict.fromkeys(
    ("predict_model", "tree_gemm", "matmul_bias", "sigmoid", "relu",
     "softmax", "argmax", "select_column", "threshold", "constant_vector"),
    "repro.model"))


def compile_plan(plan: Plan, catalog,
                 config: Optional[ExecutionConfig] = None,
                 capture: Optional[str] = None,
                 node_hook: Optional[Callable[[str, Any, Any, float],
                                              None]] = None,
                 count_scored: bool = False
                 ) -> Callable[[Dict[str, Table]], Any]:
    """Build the executable closure for ``plan``.

    The returned function is pure in its table inputs (model parameters are
    embedded as constants — they are part of the *compiled query*, which is
    exactly the paper's model+inference-session caching) and is therefore
    jit-compatible as a whole.

    ``capture`` names a node whose intermediate value the caller wants
    alongside the output: the function then returns ``(output, captured)``.
    The serving layer uses this to materialize a sub-plan's result for its
    cross-query result cache *during* normal execution — the first query
    pays nothing beyond returning one extra array from the fused program.

    Plans may contain ``materialized`` nodes (see
    ``serve.prediction_service``): leaves that read a previously captured
    value injected through the tables dict under ``attrs['slot']``.

    ``node_hook(nid, node, value, elapsed_s)`` turns the closure into an
    instrumented op-at-a-time profiler: each node's value is forced with
    ``jax.block_until_ready`` and the hook observes its wall time.  This is
    the EXPLAIN ANALYZE seam — only meaningful *un-jitted* (under jit the
    values are tracers and the timings are trace-time, not run-time), so
    the serving layer runs profiled executions eagerly.

    Model steps whose input a filter narrowed score only the live rows
    (:func:`compaction_sites`, ``_score_live_rows``); their output keeps
    one row per input row.  With ``count_scored`` the function then
    returns ``(result, scored)``, ``scored`` an int32 vector of the rows
    each compacted step scored, and carries ``compacted``: each step's
    ``(real, padded)`` tree slots per row, in the vector's order.  A plan
    with no compacted step returns its result alone either way.
    """
    config = config or ExecutionConfig()
    compile_stats["plans_compiled"] += 1
    for listener in list(_compile_listeners):
        listener(plan)
    order = plan.topo_order()
    nodes = plan.nodes
    # Filter/map nodes holding Param placeholders bind them *inside* the
    # closure, against the reserved ``__params__`` entry of the tables dict:
    # under jit the bound values are tracers, so one traced executable
    # serves every literal binding (the parameterized-plan-reuse contract).
    parametric = {nid for nid in order
                  if nodes[nid].op in ("filter", "map")
                  and plan_params(plan, [nid])}
    sites = compaction_sites(plan)

    def run(tables: Dict[str, Table]) -> Any:
        env: Dict[str, Any] = {}
        scored: List[jnp.ndarray] = []

        def scores_of(nid, score, x):
            if nid not in sites:
                return score(x)
            out, rows = _score_live_rows(score, x, env[sites[nid]].valid)
            scored.append(rows)
            return out

        def bound(expr):
            try:
                return bind_params(expr, tables.get("__params__") or {})
            except KeyError as k:
                raise ValueError(
                    f"unbound query parameter {k.args[0]!r}: pass "
                    f"params= with a value for it") from None

        for nid in order:
            n = nodes[nid]
            op = n.op
            ins = [env[i] for i in n.inputs]
            a = n.attrs
            t0 = time.perf_counter() if node_hook is not None else 0.0
            with jax.named_scope(_SCOPES.get(op, "repro.relational")):
                if op == "scan":
                    env[nid] = tables[a["table"]]
                elif op == "materialized":
                    env[nid] = tables[a["slot"]]
                elif op == "filter":
                    pred = a["predicate"]
                    if nid in parametric:
                        pred = bound(pred)
                    env[nid] = rel_ops.filter_(ins[0], pred)
                elif op == "project":
                    env[nid] = rel_ops.project(ins[0], a["columns"])
                elif op == "rename":
                    t = ins[0]
                    mapping = a["mapping"]
                    cols = {mapping.get(k, k): v for k, v in t.columns.items()}
                    env[nid] = Table(cols, t.valid, t.schema.rename(mapping))
                elif op == "map":
                    expr = a["expr"]
                    if nid in parametric:
                        expr = bound(expr)
                    env[nid] = rel_ops.with_column(ins[0], a["name"], expr)
                elif op == "join":
                    env[nid] = rel_ops.join_unique(ins[0], ins[1], on=a["on"],
                                                   how=a.get("how", "inner"))
                elif op == "group_agg":
                    env[nid] = rel_ops.group_aggregate(
                        ins[0], a["key"], a["aggs"], a.get("num_groups"))
                elif op == "partial_agg":
                    # local phase of a two-phase aggregation: mergeable state
                    # per morsel; `serve/sharded.py` runs the combine stage
                    env[nid] = rel_ops.partial_aggregate(
                        ins[0], a["key"], a["aggs"], a.get("num_groups"))
                elif op == "order_by":
                    env[nid] = rel_ops.order_by(ins[0], a["key"],
                                                a.get("descending", False))
                elif op == "limit":
                    env[nid] = rel_ops.limit(ins[0], a["n"])
                elif op == "union":
                    env[nid] = rel_ops.union_all(ins[0], ins[1])
                elif op == "attach_column":
                    t, vec = ins
                    if vec.ndim == 2:
                        vec = vec[:, 0]
                    env[nid] = t.with_columns({a["name"]: vec})
                elif op == "featurize":
                    table = ins[0]
                    feats = [f.transform(table.columns)
                             for f in a["featurizers"]]
                    env[nid] = jnp.concatenate(feats, axis=1)
                elif op == "gather_features":
                    env[nid] = ins[0][:, jnp.asarray(a["indices"])]
                elif op == "predict_model":
                    x = ins[0]
                    task = a.get("task", "classification")
                    proba = a.get("proba", False)
                    if n.runtime == "native":
                        scores = scores_of(nid, functools.partial(
                            _model_scores, a["model"]), x)
                        env[nid] = _scores_to_output(scores, output_task(
                            task, getattr(a["model"], "loss", None)), proba)
                    elif n.runtime == "external":
                        env[nid] = _external_predict(
                            a["model"], task, proba,
                            config.external_latency_s)(x)
                    else:  # container
                        env[nid] = _external_predict(
                            a["model"], task, proba,
                            config.container_latency_s)(x)
                # ---- LA ops produced by NN-translation / pruning rules ------
                elif op == "affine":
                    env[nid] = ins[0] * jnp.asarray(a["scale"]) \
                        + jnp.asarray(a["offset"])
                elif op == "matmul_bias":
                    env[nid] = jnp.dot(ins[0], jnp.asarray(a["weights"]),
                                       precision=jax.lax.Precision.HIGHEST) \
                        + jnp.asarray(a["bias"])
                elif op == "sigmoid":
                    env[nid] = jax.nn.sigmoid(ins[0])
                elif op == "relu":
                    env[nid] = jax.nn.relu(ins[0])
                elif op == "softmax":
                    env[nid] = jax.nn.softmax(ins[0], axis=-1)
                elif op == "argmax":
                    env[nid] = jnp.argmax(ins[0], axis=-1).astype(jnp.float32)
                elif op == "select_column":
                    env[nid] = ins[0][:, a["index"]]
                elif op == "threshold":
                    env[nid] = (ins[0] > a["value"]).astype(jnp.float32)
                elif op == "tree_gemm":
                    ens = a["ensemble"]
                    # Strategy chosen by the cost-model crossover at plan
                    # time (nn_translation); ``use_pallas_tree_gemm``
                    # force-overrides for benchmarks/back-compat.  The
                    # strategy attr participates in the plan signature, so
                    # differently-lowered plans never share a cached
                    # executable.
                    strategy = a.get("strategy", "gemm")
                    if config.use_pallas_tree_gemm or strategy == "pallas":
                        from ..kernels.tree_gemm.ops import tree_gemm
                    else:
                        from ..ml.hummingbird import \
                            predict_ensemble_gemm as tree_gemm
                    bias = a.get("bias", 0.0)
                    scores = scores_of(
                        nid, lambda v: tree_gemm(ens, v) + bias, ins[0])
                    env[nid] = _scores_to_output(
                        scores, output_task(a.get("task", "classification"),
                                            a.get("loss")),
                        a.get("proba", False))
                elif op == "constant_vector":
                    n_rows = ins[0].shape[0] \
                        if ins and hasattr(ins[0], "shape") \
                        else ins[0].capacity
                    env[nid] = jnp.full((n_rows,), a["value"], jnp.float32)
                elif op == "udf":
                    fn = a["fn"]
                    out_dtype = a.get("dtype", jnp.float32)
                    x = ins[0]
                    rows = x.shape[0] if hasattr(x, "shape") else x.capacity
                    shape = jax.ShapeDtypeStruct((rows,), out_dtype)
                    if hasattr(x, "columns"):   # table input: pass column dict
                        cols = {k: v for k, v in x.columns.items()}
                        env[nid] = jax.pure_callback(
                            lambda **kw: np.asarray(fn(kw), out_dtype), shape,
                            **cols)
                    else:
                        env[nid] = jax.pure_callback(
                            lambda v: np.asarray(fn(v), out_dtype), shape, x)
                else:
                    raise ValueError(f"codegen: unknown op {op}")
            if node_hook is not None:
                env[nid] = jax.block_until_ready(env[nid])
                node_hook(nid, n, env[nid], time.perf_counter() - t0)
        out = env[plan.output] if capture is None \
            else (env[plan.output], env[capture])
        if count_scored and scored:
            return out, jnp.stack(scored)
        return out

    if count_scored and sites:
        run.compacted = tuple(nodes[nid].attrs.get("slots", (0, 0))
                              for nid in sites)
    return run


_STRUCTURAL_PARAM_ATTRS = {"limit": ("n",)}


def bind_structural_params(plan: Plan, bound: Optional[Dict[str, Any]]
                           ) -> Tuple[Plan, Optional[Dict[str, Any]]]:
    """Substitute bindings for *plan-structural* parameters (``LIMIT :n``)
    into a copy of the plan at plan-build time.

    Expression parameters bind inside the jitted closure, so every binding
    shares one plan signature and one executable.  Structural parameters
    shape the plan itself and cannot be traced; they are bound here instead,
    which deliberately gives each distinct value its own plan signature (a
    ``LIMIT 10`` and a ``LIMIT 20`` request compile separately — the
    documented cost of accepting parameters in structural positions).
    Returns ``(plan, residual_bound)`` with consumed names dropped from the
    binding dict; a no-op (same plan object) when nothing is structural.
    """
    from ..relational.expr import Param
    if not bound:
        return plan, bound
    sites = []
    for n in plan.nodes.values():
        for attr in _STRUCTURAL_PARAM_ATTRS.get(n.op, ()):
            v = n.attrs.get(attr)
            if isinstance(v, Param):
                sites.append((n.id, attr, v.name))
    if not sites:
        return plan, bound
    out = plan.copy()
    for nid, attr, name in sites:
        out.nodes[nid].attrs[attr] = int(np.asarray(bound[name]))
    # a name used only structurally is fully consumed; one also referenced
    # by an expression (e.g. WHERE x > :n LIMIT :n) stays bound
    remaining = plan_params(out)
    residual = {k: v for k, v in bound.items() if k in remaining}
    out.param_order = tuple(k for k in getattr(plan, "param_order", ())
                            if k in remaining)
    return out, residual


def resolve_params(plan: Plan, params: Any) -> Dict[str, jnp.ndarray]:
    """Normalize a ``params`` argument (positional sequence or name->value
    mapping) into the ``__params__`` binding dict, validated against the
    plan's unbound placeholders.  Positional sequences follow the parse
    order recorded by the SQL frontend (``plan.param_order``); values are
    canonicalized to jnp scalars so the jitted trace is stable across
    bindings of the same dtype."""
    names = plan_params(plan)
    if params is None:
        params = {}
    if not isinstance(params, dict):
        order = getattr(plan, "param_order", None)
        if order is None:
            raise ValueError(
                "positional params need a plan with recorded parameter "
                "order (parse_query output); pass a {name: value} dict")
        if len(params) != len(order):
            raise ValueError(
                f"expected {len(order)} parameter(s) "
                f"({', '.join(order)}), got {len(params)}")
        params = dict(zip(order, params))
    missing = sorted(names - set(params))
    if missing:
        raise ValueError(f"unbound query parameter(s): {', '.join(missing)}")
    return {k: jnp.asarray(v) for k, v in params.items() if k in names}


def execute(plan: Plan, catalog, config: Optional[ExecutionConfig] = None,
            jit: bool = True, tables: Optional[Dict[str, Table]] = None,
            params: Any = None) -> Any:
    """Execute ``plan`` against catalog tables (or ``tables`` override).

    ``params`` binds query parameters (``?`` / ``:name`` placeholders from
    the SQL frontend): a sequence for positional, a mapping for named."""
    needed = [n.attrs["table"] for n in plan.nodes.values() if n.op == "scan"]
    tabs = dict(tables or {})
    for name in needed:
        if name not in tabs:
            tabs[name] = catalog.get_table(name)
    if params is not None or plan_params(plan):
        bound = resolve_params(plan, params)
        plan, bound = bind_structural_params(plan, bound)
        tabs["__params__"] = bound
    fn = compile_plan(plan, catalog, config)
    if jit:
        fn = jax.jit(fn)
    return fn(tabs)
