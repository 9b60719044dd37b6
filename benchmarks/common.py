"""Shared benchmark utilities: timing, dataset/model setup, CSV output."""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ModelStore
from repro.data import flight_features, hospital_tables
from repro.ml import (DecisionTree, GradientBoostedTrees, LogisticRegression,
                      MLP, OneHotEncoder, Pipeline, PipelineMetadata,
                      RandomForest, StandardScaler)

ROWS = []

# Metrics snapshots benchmarks opt into exporting (``run.py --json``
# embeds them under the top-level ``metrics`` key): benchmark name ->
# ``PredictionService.metrics_snapshot()``.  Histograms make the bucket
# tuples JSON-clean here so the export never trips on them.
METRICS: Dict[str, dict] = {}


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


def record_metrics(name: str, snapshot: dict) -> None:
    """Stash a service's registry snapshot for the ``--json`` export."""
    METRICS[name] = {
        "counters": dict(snapshot.get("counters", {})),
        "gauges": dict(snapshot.get("gauges", {})),
        "histograms": {
            k: {"sum": h["sum"], "count": h["count"],
                "buckets": [[float(b), int(c)] for b, c in h["buckets"]]}
            for k, h in snapshot.get("histograms", {}).items()
        },
    }


def run_sharded(module: str, main: Callable[[int, int], None], rows: int,
                devices: int) -> None:
    """Entry of a sharded benchmark under ``benchmarks.run``.  On the CPU
    the module re-execs with ``devices`` simulated host devices; on an
    accelerator it runs here, on the devices that exist — a child process
    would need the chip this process already holds.  With one accelerator
    there is no sharded-vs-single comparison to make (each module asserts
    a >= 2x speedup), so the module is skipped with a printed reason."""
    if jax.default_backend() == "cpu":
        rerun_with_simulated_devices(module, rows, devices)
    elif len(jax.devices()) < 2:
        print(f"{module}: skipped, its sharded-vs-single-device comparison "
              f"needs >= 2 devices, found {len(jax.devices())}",
              file=sys.stderr)
    else:
        main(rows, len(jax.devices()))


def rerun_with_simulated_devices(module: str, rows: int, devices: int,
                                 timeout: int = 1200) -> None:
    """Re-exec a sharded benchmark module in a child process with
    ``xla_force_host_platform_device_count`` set in its environment (jax
    only honors the flag before import, and the parent driver already
    initialized jax), folding the child's printed CSV rows back into
    ``ROWS`` so ``--json`` exports see them.  CPU only: the child starts
    its own backend."""
    assert jax.default_backend() == "cpu", \
        "simulated devices are a CPU-backend rehearsal"
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={devices}").strip()
    proc = subprocess.run(
        [sys.executable, "-m", module, "--rows", str(rows),
         "--devices", str(devices), "--no-header"],
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), capture_output=True, text=True, timeout=timeout)
    for line in proc.stdout.splitlines():
        parts = line.split(",", 2)
        try:
            emit(parts[0], float(parts[1]),
                 parts[2] if len(parts) > 2 else "")
        except (IndexError, ValueError):
            print(line)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise RuntimeError(
            f"{module} child failed with code {proc.returncode}")


def assert_tables_bit_exact(got, want) -> None:
    """Bit-exact table comparison for benchmark acceptance checks (the test
    suite's twin lives in tests/conftest.py as the assert_tables_equal
    fixture)."""
    vg, vw = np.asarray(got.valid), np.asarray(want.valid)
    assert (vg == vw).all(), "validity mask diverged"
    assert set(got.columns) == set(want.columns), \
        f"columns diverged: {set(got.columns)} vs {set(want.columns)}"
    for k in want.columns:
        assert (np.asarray(got.columns[k])
                == np.asarray(want.columns[k])).all(), \
            f"column {k} not bit-exact"


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall seconds per call (warm)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def hospital_store(n_rows: int) -> Tuple[ModelStore, Dict[str, np.ndarray]]:
    store = ModelStore()
    tables = hospital_tables(n_rows)
    for name, t in tables.items():
        store.register_table(name, t)
    data: Dict[str, np.ndarray] = {}
    for t in tables.values():
        for c in t.names:
            data[c] = np.asarray(t.column(c))
    return store, data


def hospital_tree_pipeline(data, max_depth=8, min_leaf=20,
                           name="los") -> Pipeline:
    feat = ["age", "gender", "pregnant", "rcount", "hematocrit",
            "neutrophils", "bp"]
    sc = StandardScaler(feat).fit(data)
    pipe = Pipeline([sc], DecisionTree(task="regression",
                                       max_depth=max_depth,
                                       min_leaf=min_leaf),
                    PipelineMetadata(name=name, task="regression"))
    pipe.fit({k: data[k] for k in feat}, data["length_of_stay"])
    return pipe


def flights_lr_pipeline(fcols, fy, l1=0.02, steps=300,
                        name="delay") -> Pipeline:
    ohe = OneHotEncoder(["origin", "dest", "carrier", "dow"]).fit(fcols)
    sc = StandardScaler(["distance", "taxi_out", "dep_hour"]).fit(fcols)
    pipe = Pipeline([ohe, sc], LogisticRegression(l1=l1, steps=steps),
                    PipelineMetadata(name=name, task="classification"))
    pipe.fit(fcols, fy)
    return pipe
