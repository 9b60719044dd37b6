"""One run of one cell: set-up, the measured window, the check, the result.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file, ``bench/traffic/<mix>.json``,
``bench/deployments/<schema>.py``, ``bench/models/<kind>.py`` and
``bench/metrics/<metric>.py`` (or, for a metric named ``<quantity>.<part>``,
``bench/metrics/<quantity>.py``).  Adding a cell or a metric adds files and
edits none of these.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib
import importlib.util
import json
import os
import shutil
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

import numpy as np

from bench import check, counts, loads

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
CACHE = HERE / ".cache"
# Fixed paths inside the checkout: the compile cache's path is part of what
# a later process must find again.
JAX_CACHE = CACHE / "jax"
MODEL_CACHE = CACHE / "models"
TRACE_DIR = CACHE / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(label: str, value: Any) -> None:
    print(f"{label}: {value}", flush=True)


# -- finding a cell's parts by name ---------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((ROOT / entry["file"]).read_text()),
        traffic=json.loads((HERE / "traffic"
                            / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def deployment(config: dict):
    return importlib.import_module(f"bench.deployments.{config['schema']}")


def model_kind(config: dict):
    return importlib.import_module(f"bench.models.{config['model']['kind']}")


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r}; the table has "
                       f"{sorted(table['devices'])}")
    return table["devices"][kind]


# -- JAX and the chip -------------------------------------------------------------

def start_jax(chips: int, require_tpu: bool = True):
    """Import JAX with the checkout's compile cache and check the chips.
    Without ``require_tpu`` (CPU tests) the persistent cache stays off."""
    if require_tpu:
        JAX_CACHE.mkdir(parents=True, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
        # libtpu would otherwise log to a fixed path under /tmp
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return jax, devices[:chips]


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- set-up -------------------------------------------------------------------------

def _model_path(config: dict) -> Path:
    from importlib.metadata import version
    key = json.dumps({"model": config["model"], "schema": config["schema"],
                      "schema_params": config["schema_params"],
                      "sklearn": version("scikit-learn"),
                      "numpy": np.__version__}, sort_keys=True)
    digest = hashlib.sha256(key.encode()).hexdigest()[:16]
    return MODEL_CACHE / f"{config['model']['kind']}-{digest}.npz"


def fitted(config: dict) -> Dict[str, np.ndarray]:
    """The configuration's model arrays: fitted once from its own fixed
    seed, then read back from the checkout."""
    path = _model_path(config)
    if path.exists():
        with np.load(path, allow_pickle=False) as z:
            return {k: z[k] for k in z.files}
    m = config["model"]
    dep = deployment(config)
    rows = dep.joined(dep.generate(m["fit_rows"], m["fit_seed"],
                                   **config["schema_params"]))
    arrays = model_kind(config).fit(m, rows)
    MODEL_CACHE.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return arrays


def morsel_rows(per_row_bytes: int, n_rows: int, device) -> int:
    """Rows per morsel: 0 (whole table) when the working set of the whole
    table fits a quarter of the device memory, else the largest power of
    two that does."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        return 0
    budget = 0.25 * limit
    if per_row_bytes * n_rows <= budget:
        return 0
    return 1 << int(np.log2(budget / per_row_bytes))


def register_tables(store, config: dict, tables: Dict[str, dict]) -> None:
    from repro.relational.table import Table

    parts = int(config.get("partitions") or 0)
    key = config.get("key")
    for name, cols in tables.items():
        table = Table.from_pydict(cols)
        if parts and key in cols:
            n = len(cols[key])
            bounds = [n * i // parts for i in range(1, parts)]
            store.register_table(name, table, partition_by=key,
                                 partition_bounds=bounds)
        else:
            store.register_table(name, table)


def service(store, config: dict, traffic: dict, chunk_rows: int):
    from repro.core import ExecutionConfig
    from repro.serve import PredictionService

    return PredictionService(
        store, chunk_rows=chunk_rows,
        execution_config=ExecutionConfig(**config.get("execution", {})),
        **traffic.get("service", {}))


# -- one run -------------------------------------------------------------------------

def _host(out) -> Dict[str, np.ndarray]:
    cols = {k: np.asarray(v) for k, v in out.columns.items()}
    cols["__valid__"] = np.asarray(out.valid)
    return cols


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        scale: Optional[float] = None, peaks_of: Optional[str] = None
        ) -> dict:
    """Everything after argument parsing; returns the result object.
    CPU tests pass ``require_tpu=False``, ``scale`` (a share of the
    configuration's rows) and ``peaks_of`` (a device kind of the table)."""
    cell = load_cell(workload)
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] != "closed" or mix.get("clients", 1) != 1:
        raise ValueError(f"unknown traffic loop {mix['loop']!r}")
    if scale is not None:
        cfg = dict(cfg, rows=max(64, int(cfg["rows"] * scale)))
    jax, devices = start_jax(cell.chips, require_tpu)
    from bench.compile_meter import CompileMeter
    from repro.core import ModelStore

    kind = peaks(peaks_of or devices[0].device_kind)
    log("device", {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)})
    meter = CompileMeter()
    parts: Dict[str, float] = {}
    dep, mk = deployment(cfg), model_kind(cfg)
    model = cfg["model"]

    t0 = time.perf_counter()
    arrays = fitted(cfg)
    parts["model_s"] = time.perf_counter() - t0
    log("model", mk.summary(arrays))
    chunk = morsel_rows(mk.working_set_bytes_per_row(arrays, model),
                        cfg["rows"], devices[0])
    log("morsel_rows", chunk)

    store = ModelStore()
    t0 = time.perf_counter()
    tables = dep.generate(cfg["rows"], seed % (1 << 63),
                          **cfg["schema_params"])
    register_tables(store, cfg, tables)
    store.register_model(model["name"], mk.pipeline(model, arrays))
    parts["tables_s"] = time.perf_counter() - t0
    svc = service(store, cfg, mix, chunk)
    sql = cfg["queries"][mix["query"]]
    log("query", sql)

    if getattr(mk, "CALIBRATES", False):
        # the tree-strategy cost model measures the chip once per process;
        # the service reads it back from the catalog
        from repro.core.cost_model import calibrated_tree_costs
        t0 = time.perf_counter()
        calibrated_tree_costs(catalog=store)
        parts["calibration_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    svc.sql(sql)                                       # compiles
    parts["warm_s"] = time.perf_counter() - t0
    compile_s, compiles, hits = meter.snapshot()
    parts["compile_s"] = compile_s
    log("setup_parts", parts)
    log("setup_compiles", {"count": compiles, "cache_hits": hits,
                           "seconds": compile_s})
    strategies = sorted({d for r, d in svc.compile(sql).report.entries
                         if r == "tree_strategy"})
    if strategies:
        log("tree_strategy", strategies)

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / f"{workload}.{os.getpid()}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans (TraceAnnotation) stay; Python function events, which
        # slow a host-bound cell, are left out
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    kept = {}

    def issue():
        out = svc.sql(sql)
        # the first answer and the newest one are kept for the check; every
        # other answer is dropped as it is replaced
        kept.setdefault("first", out)
        kept["last"] = out
    try:
        with annotate("bench.window"):
            records = loads.closed_loop(issue, seconds, annotate=annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    _, window_compiles, _ = meter.snapshot()
    log("window_compiles", window_compiles - compiles)
    st = svc.stats
    log("service_stats", {k: getattr(st, k) for k in (
        "batch_executions", "chunks_executed", "sharded_executions",
        "shard_waves", "shard_join_executions")})
    if cfg.get("execution", {}).get("sharded"):
        log("shard_info", svc.shard_info())

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    ok = [r for r in records if r.error is None]
    failed = len(records) - len(ok)
    window_s = records[-1].done - records[0].due
    log("window", {"seconds": window_s, "attempted": len(records),
                   "completed": len(ok), "failed": failed})
    errors = sorted({r.error for r in records if r.error})[:3]
    if errors:
        log("errors", errors)

    # free the program's state before the reference runs
    answers = [_host(v) for _, v in sorted(kept.items())]
    svc.close()
    del svc, store, kept

    rows = dep.joined(tables)
    cand = check.where_mask(rows, cfg.get("where", {}))
    numbers = _check(cfg, mk, arrays, rows, cand, answers, failed)
    correct = check.correct(numbers)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    work = SimpleNamespace(
        seconds=window_s, queries=len(ok),
        rows=len(ok) * len(tables[cfg["driving_table"]][cfg["key"]]),
        scored_rows=len(ok) * int(cand.sum()))
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": len(records), "failed": failed}
    if trace:
        from bench import trace as tr
        summary = tr.summarize(tr.load(str(trace_dir)),
                               [d.id for d in devices])
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("idle_share_by_device", summary.idle_share)
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        ctx = SimpleNamespace(
            trace=summary, work=work, chips=len(devices), peaks=kind,
            flops_per_row=mk.flops_per_row(arrays, model),
            bytes_per_query=counts.query_bytes(cfg, tables),
            compile_s=compile_s)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_by_host]}
    else:
        values = {"setup_s": setup_s, "peak_hbm_bytes": float(peak)}
        for m in cell.end_to_end:
            if m["name"].split(".")[0] == "rows_per_s" and window_s > 0:
                values[m["name"]] = work.rows / window_s
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
        result["device"] = device
    result["checks"] = numbers
    return result


def _check(cfg, mk, arrays, rows, cand, answers, failed
           ) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each beside its limit: every answer kept
    against the reference over the same rows."""
    ref = np.full(len(cand), np.nan)
    ref[cand] = mk.reference(arrays, cfg["model"],
                             {c: v[cand] for c, v in rows.items()})
    flt = cfg.get("output_filter")
    counts_ = {"candidates": int(cand.sum()),
               "answer": int(check.output_mask(cand, ref, flt).sum())}
    if flt:
        counts_["near_threshold"] = int(check.near_threshold(
            cand, ref, flt, cfg["limits"]["score_gap"]).sum())
    log("reference_rows", counts_)
    return check.numbers([(cand, rows[cfg["key"]], ref, a) for a in answers],
                         cfg["key"], cfg["output"], cfg["limits"],
                         missing=failed, output_filter=flt)
