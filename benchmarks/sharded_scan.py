"""Partitioned-table sharded scan: data-parallel prediction + zone-map
partition pruning.

The classic DB scaling lever PRs 1-3 had not pulled: *partitioned,
data-parallel scans with statistics-based partition skipping*.  A 64-way
row-range-partitioned table (age-clustered, so zone maps are selective)
serves a scan-heavy prediction query on the external runtime — the
Raven-Ext path whose per-execution out-of-process hop is exactly the fixed
cost partition parallelism amortizes.

Like ``launch/dryrun.py``, devices are simulated:
``--xla_force_host_platform_device_count`` is set **before** importing jax
(so this module must run in its own process — ``run()`` re-execs itself
when the parent already initialized jax).

Reported rows:

- ``sharded_scan/single_device`` — the same morsel schedule executed on a
  1-device mesh (serial waves).
- ``sharded_scan/mesh8`` — surviving partitions placed across 8 simulated
  devices; derived column carries the throughput speedup.
- ``sharded_scan/pruned`` — a selective predicate; derived column carries
  partitions pruned and the speedup vs the unpruned sharded scan.

Acceptance (asserted in ``main()``):

- >= 2x throughput at 8 simulated devices vs single-device;
- bit-exact outputs (full-table equality unpruned; valid-row equality
  under pruning vs the unsharded reference);
- the selective predicate prunes >= half the partitions with a
  proportional (> 1.5x) speedup;
- zero extra compiles on warm repeats (signature misses, sharded twin
  builds and jit traces all flat across the timed windows).
"""

from __future__ import annotations

import argparse
import os
import time

N_PARTITIONS = 64
EXTERNAL_LATENCY_S = 15e-3
SQL_FULL = "SELECT pid, PREDICT(MODEL='delay_lr') AS p FROM flights_part"
SQL_SELECTIVE = SQL_FULL + " WHERE age < 25"


def run(n_rows: int = 200_000, devices: int = 8) -> None:
    """Entry from ``benchmarks.run``: see ``common.run_sharded``."""
    from .common import run_sharded
    run_sharded("benchmarks.sharded_scan", main, n_rows, devices)


def _build_store(n_rows: int):
    import numpy as np

    from repro.core import ModelStore
    from repro.ml import (LogisticRegression, Pipeline, PipelineMetadata,
                          StandardScaler)
    from repro.relational.table import Table

    rng = np.random.RandomState(7)
    age = np.sort(rng.uniform(0.0, 100.0, n_rows)).astype(np.float32)
    cols = {
        "pid": np.arange(n_rows, dtype=np.int32),
        "age": age,                                 # clustered: zone maps bite
        "distance": rng.uniform(50, 3000, n_rows).astype(np.float32),
        "dep_hour": rng.randint(0, 24, n_rows).astype(np.int32),
    }
    y = ((age * 0.02 + cols["distance"] * 1e-3
          + rng.randn(n_rows)) > 2.0).astype(np.int32)
    store = ModelStore()
    store.register_table("flights_part", Table.from_pydict(cols),
                         partition_rows=-(-n_rows // N_PARTITIONS))
    feats = ["age", "distance", "dep_hour"]
    data = {k: cols[k].astype(np.float32) for k in feats}
    sc = StandardScaler(feats).fit(data)
    pipe = Pipeline([sc], LogisticRegression(steps=60),
                    PipelineMetadata(name="delay_lr", task="classification",
                                     flavor="external"))   # Raven-Ext path
    pipe.fit(data, y)
    store.register_model("delay_lr", pipe)
    return store


def _service(store, shard_devices: int, morsel_rows: int):
    from repro.core import ExecutionConfig, OptimizerConfig
    from repro.serve import PredictionService

    # external flavor: keep the model out-of-process (no inlining/GEMM)
    opt = OptimizerConfig(enable_model_inlining=False,
                          enable_nn_translation=False)
    return PredictionService(store, optimizer_config=opt,
                             execution_config=ExecutionConfig(
                                 external_latency_s=EXTERNAL_LATENCY_S,
                                 sharded=True,
                                 shard_devices=shard_devices,
                                 shard_morsel_rows=morsel_rows))


def _timed(svc, sql: str, iters: int = 5) -> float:
    """Median warm wall-seconds per serve (the service was already warmed:
    the timed window must observe zero compiles)."""
    import numpy as np
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        svc.run(sql)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _assert_flat_compiles(svc, before, label: str):
    after = (svc.stats.cache_misses, svc.stats.shard_compiles,
             svc.stats.jit_traces)
    assert after == before, \
        f"{label}: compiles moved during warm repeats {before} -> {after}"


def main(n_rows: int, devices: int) -> None:
    import numpy as np

    from repro.core.codegen import pow2_bucket

    from .common import emit

    store = _build_store(n_rows)
    # morsel granularity = one partition: every partition scan pays its
    # fixed out-of-process hop, the 1-device mesh runs all 64 serially and
    # the 8-way mesh runs 8 concurrent streams of 8 — same morsels, same
    # shapes, different parallelism (and pruning removes whole hops)
    morsel_rows = pow2_bucket(-(-n_rows // N_PARTITIONS))
    import jax
    assert len(jax.devices()) >= devices, \
        f"need {devices} devices, found {len(jax.devices())}"

    from repro.serve import PredictionService
    from repro.core import OptimizerConfig, ExecutionConfig

    # unsharded reference for bit-exactness
    ref = PredictionService(store, optimizer_config=OptimizerConfig(
        enable_model_inlining=False, enable_nn_translation=False),
        execution_config=ExecutionConfig(
            external_latency_s=EXTERNAL_LATENCY_S))
    want_full = ref.run(SQL_FULL)
    want_sel = ref.run(SQL_SELECTIVE)
    ref.close()

    single = _service(store, shard_devices=1, morsel_rows=morsel_rows)
    mesh = _service(store, shard_devices=devices, morsel_rows=morsel_rows)

    got_single = single.run(SQL_FULL)                      # warm + check
    got_mesh = mesh.run(SQL_FULL)
    for got in (got_single, got_mesh):                     # bit-exact, full
        assert got.capacity == want_full.capacity
        assert (np.asarray(got.valid) == np.asarray(want_full.valid)).all()
        for k in want_full.columns:
            assert (np.asarray(got.columns[k])
                    == np.asarray(want_full.columns[k])).all(), k

    flat_single = (single.stats.cache_misses, single.stats.shard_compiles,
                   single.stats.jit_traces)
    flat_mesh = (mesh.stats.cache_misses, mesh.stats.shard_compiles,
                 mesh.stats.jit_traces)
    t_single = _timed(single, SQL_FULL)
    t_mesh = _timed(mesh, SQL_FULL)
    _assert_flat_compiles(single, flat_single, "single-device")
    _assert_flat_compiles(mesh, flat_mesh, "mesh")
    speedup = t_single / t_mesh
    emit("sharded_scan/single_device", t_single * 1e6,
         f"rows_per_s={n_rows / t_single:.0f} "
         f"waves={single.shard_info()['shard_waves']}")
    emit("sharded_scan/mesh8", t_mesh * 1e6,
         f"rows_per_s={n_rows / t_mesh:.0f} speedup={speedup:.2f}x "
         f"devices={mesh.shard_info()['devices']}")

    # -- zone-map pruning: selective predicate over the age-clustered table
    got_sel = mesh.run(SQL_SELECTIVE)                      # warm + check
    vg, vw = np.asarray(got_sel.valid), np.asarray(want_sel.valid)
    for k in want_sel.columns:                             # valid-row exact
        a = np.asarray(got_sel.columns[k])[vg]
        b = np.asarray(want_sel.columns[k])[vw]
        assert a.shape == b.shape and (a == b).all(), k
    report = mesh.compile(SQL_SELECTIVE).report
    surviving, total = report.partitions["flights_part"]
    pruned = total - surviving
    flat_mesh = (mesh.stats.cache_misses, mesh.stats.shard_compiles,
                 mesh.stats.jit_traces)
    t_sel = _timed(mesh, SQL_SELECTIVE)
    _assert_flat_compiles(mesh, flat_mesh, "pruned")
    prune_speedup = t_mesh / t_sel
    emit("sharded_scan/pruned", t_sel * 1e6,
         f"pruned={pruned}/{total} speedup_vs_full={prune_speedup:.2f}x "
         f"prune_rate={mesh.shard_info()['prune_rate']:.2f}")

    single.close()
    mesh.close()

    assert speedup >= 2.0, \
        f"sharded scan only {speedup:.2f}x at {devices} devices (need >=2x)"
    assert pruned >= total / 2, \
        f"selective predicate pruned only {pruned}/{total} partitions"
    assert prune_speedup >= 1.5, \
        f"pruning {pruned}/{total} partitions sped up only " \
        f"{prune_speedup:.2f}x (want proportional, >=1.5x)"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--no-header", action="store_true")
    args = ap.parse_args()
    # simulated devices must exist before jax initializes (dryrun-style);
    # a no-op when run() already set the flag in our environment
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    if not args.no_header:
        print("name,us_per_call,derived")
    main(args.rows, args.devices)
