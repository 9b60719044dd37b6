#!/usr/bin/env python3
"""Chip smoke test: serve the paper's Fig 1 prediction query on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # only the sharded join, on 4 chips

Deployment: the Fig 1 hospital schema (``repro.data.hospital_tables``,
seeded) with ``--rows`` rows in each of ``patient_info``, ``blood_tests``
and ``prenatal_tests``, resident on the device.  Model: ``StandardScaler``
into a 100-tree, depth-10 regression forest over seven Fig 1 features,
fitted on a seeded sample.  Every query goes through
``PredictionService.sql()`` and every answer is compared with a plain
NumPy reference that walks the same fitted trees node by node in float32.

Each earlier line is ``label: value``.  Timings are smoke timings, not a
benchmark.  The last line is one JSON object, printed only when every
phase passed; with no TPU, or on any failed check, the script exits
non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

ROOT = Path(__file__).resolve().parent

FEATURES = ("age", "gender", "pregnant", "rcount",
            "hematocrit", "neutrophils", "bp")
JOIN_SQL = ("SELECT pid, PREDICT(MODEL='los') AS los FROM patient_info "
            "JOIN blood_tests ON pid WHERE pregnant = 1")
PARAM_SQL = JOIN_SQL + " AND age > :min_age"
WHOLE_SQL = ("SELECT pid, PREDICT(MODEL='los') AS los FROM patient_info "
             "JOIN blood_tests ON pid")
STRATEGIES = ("traversal", "gemm", "pallas")
# Forced traversal serves the join over this prefix of both tables (the
# same query, through sql()'s request tables): on a v5e it gathers at
# about 5.3 s per depth-10 tree per 10M rows, so the whole table would
# take some 530 s of the run's 1200 s.
TRAVERSAL_ROWS = 500_000
# A score is the mean of n_trees leaf values of a few days each.  Every
# strategy and the reference add the same float32 leaf values in the same
# tree order, so they differ only in how the final division by n_trees
# rounds: about 1e-7 relative.  A gate flipped by rounding picks another
# leaf in one tree and moves a score by that leaf gap over n_trees; the
# script prints the median such gap next to this tolerance.
SCORE_RTOL = 1e-5
# Working set per scored row of the dense strategy (gates [I], path
# counts and matches [L] each, float32) plus the kernel's lane-padded
# [rows, F] input and [rows, O] output; a plan runs in morsels when the
# whole table's working set exceeds a quarter of the device memory.
MEMORY_SHARE = 0.25


def log(label: str, value) -> None:
    print(f"{label}: {value}", flush=True)


# -- deployment ---------------------------------------------------------------

def build_deployment(n_rows: int, n_trees: int, max_depth: int,
                     fit_rows: int, seed: int,
                     partitions: int = 0):
    """Register the seeded Fig 1 tables (range-partitioned by pid into
    ``partitions`` when non-zero) and the fitted ``los`` pipeline.
    Returns (store, pipeline, host columns of both join sides)."""
    from repro.core import ModelStore
    from repro.data import hospital_tables
    from repro.ml import (Pipeline, PipelineMetadata, RandomForest,
                          StandardScaler)

    t0 = time.perf_counter()
    tables = hospital_tables(n_rows, seed=seed)
    store = ModelStore()
    for name, table in tables.items():
        if partitions and name in ("patient_info", "blood_tests"):
            bounds = [n_rows * i // partitions for i in range(1, partitions)]
            store.register_table(name, table, partition_by="pid",
                                 partition_bounds=bounds)
        else:
            store.register_table(name, table)
    host = {
        side: {c: np.asarray(tables[side].column(c))
               for c in tables[side].names}
        for side in ("patient_info", "blood_tests")}
    resident = sum(int(a.nbytes) for t in tables.values()
                   for a in list(t.columns.values()) + [t.valid])
    log("tables", {n: t.capacity for n, t in tables.items()})
    log("bytes_resident", resident)
    log("setup_tables_s", time.perf_counter() - t0)

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    sample = np.sort(rng.choice(n_rows, size=min(fit_rows, n_rows),
                                replace=False))
    pi, bt = joined_features(host, sample)
    data = {c: (pi[c] if c in pi else bt[c]) for c in FEATURES}
    pipe = Pipeline([StandardScaler(list(FEATURES))],
                    RandomForest(task="regression", n_trees=n_trees,
                                 max_depth=max_depth, seed=seed),
                    PipelineMetadata(name="los", task="regression"))
    pipe.fit(data, pi["length_of_stay"])
    store.register_model("los", pipe)
    trees = pipe.model.trees
    log("model", {"n_trees": len(trees),
                  "max_depth": max(t.depth for t in trees),
                  "max_leaves": max(len(t.leaf_indices()) for t in trees),
                  "fit_rows": len(sample)})
    log("setup_fit_s", time.perf_counter() - t0)
    return store, pipe, host


def joined_features(host, rows: np.ndarray):
    """patient_info rows ``rows`` and their blood_tests match by pid (the
    query's N:1 join); every generated pid has exactly one match."""
    bpid = host["blood_tests"]["pid"]
    pos = np.full(int(bpid.max()) + 1, -1, np.int64)
    pos[bpid] = np.arange(len(bpid))
    match = pos[host["patient_info"]["pid"][rows]]
    assert (match >= 0).all(), "a patient has no blood_tests row"
    pi = {c: v[rows] for c, v in host["patient_info"].items()}
    bt = {c: v[match] for c, v in host["blood_tests"].items()}
    return pi, bt


# -- NumPy reference ------------------------------------------------------------

def _walk(tree, x: np.ndarray) -> np.ndarray:
    """One tree, node by node: x [F, n] float32 -> leaf values [n].  A
    leaf tests feature 0 against +inf and points left at itself, so a row
    that reached it stays there."""
    leaf = tree.left < 0
    ids = np.arange(tree.n_nodes, dtype=np.int32)
    feature = np.where(leaf, 0, tree.feature)
    threshold = np.where(leaf, np.float32(np.inf), tree.threshold)
    left = np.where(leaf, ids, tree.left)
    rows = np.arange(x.shape[1])
    node = np.zeros(x.shape[1], np.int32)
    for _ in range(tree.depth):
        node = np.where(x[feature[node], rows] <= threshold[node],
                        left[node], tree.right[node])
    return tree.value[node, 0]


def reference_scores(pipe, host, rows: np.ndarray,
                     block: int = 1 << 16) -> np.ndarray:
    """float32 score of each patient_info row in ``rows``: the scaler as
    float32 ``(x - mean) * (1 / std)``, then every tree walked in order and
    summed in float32, divided by the tree count."""
    scaler, trees = pipe.featurizers[0], pipe.model.trees
    inv = np.float32(1.0) / scaler.std
    pi, bt = joined_features(host, rows)
    x = np.stack([((pi[c] if c in pi else bt[c]).astype(np.float32)
                   - scaler.mean[j]) * inv[j]
                  for j, c in enumerate(scaler.columns)])     # [F, n]

    def score(lo: int) -> np.ndarray:
        xb = x[:, lo:lo + block]
        acc = np.zeros(xb.shape[1], np.float32)
        for tree in trees:
            acc += _walk(tree, xb)
        return acc / np.float32(len(trees))

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        parts = list(pool.map(score, range(0, len(rows), block)))
    return np.concatenate(parts) if parts else np.zeros(0, np.float32)


def leaf_gap(pipe) -> float:
    """Median |left - right| payout of sibling leaves, over n_trees: what
    one flipped gate moves a score by."""
    gaps = []
    for t in pipe.model.trees:
        inner = np.nonzero(t.left >= 0)[0]
        both = inner[(t.left[t.left[inner]] < 0)
                     & (t.left[t.right[inner]] < 0)]
        gaps.append(np.abs(t.value[t.left[both], 0]
                           - t.value[t.right[both], 0]))
    return float(np.median(np.concatenate(gaps))) / len(pipe.model.trees)


def check(label: str, out, want_valid: np.ndarray, want_pid: np.ndarray,
          want_score: np.ndarray) -> np.ndarray:
    """Same rows as the reference, bitwise; scores within SCORE_RTOL.
    Returns the served scores of the valid rows."""
    valid = np.asarray(out.valid)
    assert valid.shape == want_valid.shape, \
        f"{label}: {valid.shape[0]} rows, want {want_valid.shape[0]}"
    assert (valid == want_valid).all(), f"{label}: valid rows differ"
    pid = np.asarray(out.columns["pid"])[valid]
    assert (pid == want_pid).all(), f"{label}: pid column differs"
    got = np.asarray(out.columns["los"])[valid]
    err = np.abs(got.astype(np.float64) - want_score)
    bound = SCORE_RTOL * (1.0 + np.abs(want_score))
    bad = int((err > bound).sum())
    log(f"{label}.check", {"rows": int(valid.sum()),
                           "max_abs_err": float(err.max(initial=0.0)),
                           "rows_over_tol": bad})
    assert bad == 0, f"{label}: {bad} scores off the reference"
    return got


# -- compile accounting ---------------------------------------------------------

class CompileMeter:
    """Backend compiles (a persistent-cache hit counts, at its read time)
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.count, self.cache_hits


def timed_sql(meter: CompileMeter, svc, label: str, sql: str,
              params=None, tables=None):
    s0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    out = svc.sql(sql, params=params, tables=tables)
    wall = time.perf_counter() - t0
    s1, c1, h1 = meter.snapshot()
    log(f"{label}.smoke_timing", {"wall_s": wall, "compile_s": s1 - s0,
                                  "compiles": c1 - c0,
                                  "cache_hits": h1 - h0})
    return out, c1 - c0


# -- phases ---------------------------------------------------------------------

def choose_chunk_rows(pipe, n_rows: int) -> int:
    """Rows per morsel: 0 (whole table) when the dense working set of the
    whole table fits MEMORY_SHARE of the device, else the largest power
    of two that does."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    trees = pipe.model.trees
    inner = max(t.n_nodes - len(t.leaf_indices()) for t in trees)
    leaves = max(len(t.leaf_indices()) for t in trees)
    per_row = 4 * (inner + 2 * leaves) + 2 * 128 * 4
    need = per_row * n_rows
    if limit is None:
        log("chunk_rows", {"rows": 0, "reason": "device reports no memory"})
        return 0
    budget = MEMORY_SHARE * limit
    rows = 0 if need <= budget else 1 << int(np.log2(budget / per_row))
    log("chunk_rows", {"rows": rows, "whole_table_bytes": need,
                       "budget_bytes": int(budget)})
    return rows


def one_chip(n_rows: int, n_trees: int, max_depth: int, fit_rows: int,
             seed: int, chunk_rows: Optional[int] = None,
             strategies: Sequence[str] = STRATEGIES,
             traversal_rows: int = TRAVERSAL_ROWS,
             check_kernel: bool = True) -> None:
    """Every one-chip phase; raises on the first failed check."""
    import jax

    from repro.core import OptimizerConfig
    from repro.core.cost_model import calibrated_tree_costs
    from repro.serve import PredictionService

    meter = CompileMeter()
    store, pipe, host = build_deployment(n_rows, n_trees, max_depth,
                                         fit_rows, seed)
    log("leaf_flip_score_change", leaf_gap(pipe))
    log("score_rtol", SCORE_RTOL)
    if chunk_rows is None:
        chunk_rows = choose_chunk_rows(pipe, n_rows)

    t0 = time.perf_counter()
    all_rows = np.arange(n_rows)
    ref = reference_scores(pipe, host, all_rows)
    log("reference_s", time.perf_counter() - t0)
    pi = host["patient_info"]
    pregnant = pi["pregnant"] == 1

    def want(mask, rows: int = n_rows):
        mask = mask[:rows]
        return mask, pi["pid"][:rows][mask], ref[:rows][mask].astype(
            np.float64)

    t0 = time.perf_counter()
    cal = calibrated_tree_costs(catalog=store)
    log("calibration", {"s": time.perf_counter() - t0, **vars(cal)})

    def service(strategy: str = "auto"):
        # the result cache is off so each query executes
        return PredictionService(
            store, optimizer_config=OptimizerConfig(tree_strategy=strategy),
            chunk_rows=chunk_rows, enable_result_cache=False)

    svc = service()
    out, _ = timed_sql(meter, svc, "join", JOIN_SQL)
    verdict = {d for r, d in svc.compile(JOIN_SQL).report.entries
               if r == "tree_strategy"}
    log("join.auto_strategy", sorted(verdict))
    check("join", out, *want(pregnant))

    from repro.core import codegen
    for i, min_age in enumerate((30, 45)):
        before = dict(codegen.compile_stats)
        out, compiles = timed_sql(meter, svc, f"param{i}", PARAM_SQL,
                                  params={"min_age": min_age})
        check(f"param{i}", out, *want(pregnant & (pi["age"] > min_age)))
        if i == 1:
            new = {k: codegen.compile_stats[k] - before[k] for k in before}
            log("param1.compiles", {"xla": compiles, **new})
            assert compiles == 0 and not any(new.values()), \
                "the second parameter binding compiled"

    out, _ = timed_sql(meter, svc, "whole", WHOLE_SQL)
    check("whole", out, *want(np.ones(n_rows, bool)))

    scores = {}
    common = min(n_rows, traversal_rows)
    for strategy in strategies:
        fsvc = service(strategy)
        rows = common if strategy == "traversal" else n_rows
        tables = None if rows == n_rows else {
            n: store.get_table(n).row_slice(0, rows)
            for n in ("patient_info", "blood_tests")}
        out, _ = timed_sql(meter, fsvc, f"forced_{strategy}", JOIN_SQL,
                           tables=tables)
        got = check(f"forced_{strategy}", out, *want(pregnant, rows))
        scores[strategy] = got[:int(pregnant[:common].sum())]
        if strategy == "pallas" and check_kernel:
            compiled = fsvc.compile(JOIN_SQL)
            tabs = {n: store.get_table(n) for n in compiled.scan_tables}
            if chunk_rows:
                name = compiled.morsel_table
                tabs[name] = tabs[name].row_slice(0, chunk_rows)
            hlo = compiled.fn.lower(tabs).compile().as_text()
            has_kernel = "tpu_custom_call" in hlo
            log("pallas.hlo_has_tpu_custom_call", has_kernel)
            assert has_kernel, "the pallas plan runs no compiled kernel"
    first = scores[strategies[0]]
    log("forced.max_abs_diff_between_strategies",
        {s: float(np.abs(v - first).max(initial=0.0))
         for s, v in scores.items()})

    s, c, h = meter.snapshot()
    log("compile_total", {"s": s, "compiles": c, "cache_hits": h})
    stats = jax.devices()[0].memory_stats() or {}
    log("peak_bytes_in_use", stats.get("peak_bytes_in_use"))


def four_chips(n_rows: int, n_trees: int, max_depth: int, fit_rows: int,
               seed: int, partitions: int = 16, n_devices: int = 4) -> None:
    """The Fig 1 join over ``partitions`` co-partitioned pid ranges on
    ``n_devices`` devices, against the same plan on one device."""
    import jax

    from repro.core import ExecutionConfig
    from repro.serve import PredictionService

    meter = CompileMeter()
    store, pipe, host = build_deployment(n_rows, n_trees, max_depth,
                                         fit_rows, seed,
                                         partitions=partitions)
    pi = host["patient_info"]
    mask = pi["pregnant"] == 1
    rows = np.nonzero(mask)[0]
    t0 = time.perf_counter()
    ref = reference_scores(pipe, host, rows).astype(np.float64)
    log("reference_s", time.perf_counter() - t0)

    single = PredictionService(store, chunk_rows=choose_chunk_rows(
        pipe, n_rows), enable_result_cache=False)
    one, _ = timed_sql(meter, single, "single_device", JOIN_SQL)
    check("single_device", one, mask, pi["pid"][mask], ref)

    sharded = PredictionService(
        store, execution_config=ExecutionConfig(sharded=True,
                                                shard_devices=n_devices),
        enable_result_cache=False)
    out, _ = timed_sql(meter, sharded, "sharded", JOIN_SQL)
    got = check("sharded", out, mask, pi["pid"][mask], ref)
    assert np.asarray(out.valid).shape == np.asarray(one.valid).shape
    for k in one.columns:
        if k != "los":
            assert (np.asarray(out.columns[k])
                    == np.asarray(one.columns[k])).all(), k
    single_scores = np.asarray(one.columns["los"])[mask]
    log("sharded_vs_single.max_abs_diff",
        float(np.abs(got - single_scores).max(initial=0.0)))
    info = sharded.shard_info()
    log("shard_info", info)
    assert info["join_executions"] >= 1, "the join did not run sharded"
    placed = info["morsels_per_device"]
    assert len(placed) == n_devices and all(placed), \
        f"morsels not on every device: {placed}"
    s, c, h = meter.snapshot()
    log("compile_total", {"s": s, "compiles": c, "cache_hits": h})
    for d in jax.devices():
        log(f"peak_bytes_in_use.{d.id}",
            (d.memory_stats() or {}).get("peak_bytes_in_use"))


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded join on four chips")
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--depth", type=int, default=10)
    ap.add_argument("--fit-rows", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU device(s), found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 3
    log("device", {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)})
    log("compile_cache_dir", enable_compile_cache())
    shape = dict(n_rows=args.rows, n_trees=args.trees,
                 max_depth=args.depth, fit_rows=args.fit_rows,
                 seed=args.seed)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(**shape, n_devices=want)
    else:
        one_chip(**shape)
    log("total_s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
