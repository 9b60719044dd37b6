"""Partition-wise sharded FK join + two-phase aggregation over predictions.

The workload shape PR 4's sharded scans could not touch: a co-partitioned
FK join (fact ``visits`` ⋈ dim ``patients`` ON pid, both range-partitioned
on ``pid`` with identical bounds into 64 partitions) feeding an
external-runtime model, with a grouped aggregate over the predictions on
top.  The ``distributed_plan`` rule rewrites the whole query into
per-partition local joins + per-morsel partial aggregates + a host-side
combine, so every partition pays its out-of-process model hop
independently — the fixed cost the data mesh then amortizes across
devices.

Like ``sharded_scan``, devices are simulated:
``--xla_force_host_platform_device_count`` must be set before importing
jax, so ``run()`` re-execs this module in a child process.

Reported rows:

- ``sharded_join_agg/single_device`` — the same morsel schedule executed
  on a 1-device mesh (serial waves).
- ``sharded_join_agg/mesh8`` — aligned morsel pairs placed across 8
  simulated devices; derived column carries the throughput speedup and
  the (asserted-zero) warm compile count.

Acceptance (asserted in ``main()``):

- >= 2x throughput at 8 simulated devices vs single-device;
- mesh output bit-identical to single-device (same partials, same
  partition-ordered combine) and matching the unsharded reference
  (count/min/max bitwise; mean within float tolerance — partial sums
  reassociate float addition, the standard parallel-aggregation caveat);
- zero extra compiles across every timed window (signature misses,
  sharded twin builds and jit traces all flat).
"""

from __future__ import annotations

import argparse
import os
import time

N_PARTITIONS = 64
N_REGIONS = 8
FACT_PER_PID = 4
EXTERNAL_LATENCY_S = 15e-3


def run(n_rows: int = 200_000, devices: int = 8) -> None:
    """Entry from ``benchmarks.run``: see ``common.run_sharded``."""
    from .common import run_sharded
    run_sharded("benchmarks.sharded_join_agg", main, n_rows, devices)


def _build_store(n_rows: int):
    import numpy as np

    from repro.core import ModelStore
    from repro.ml import (LogisticRegression, Pipeline, PipelineMetadata,
                          StandardScaler)
    from repro.relational.table import Table

    rng = np.random.RandomState(13)
    n_pids = max(N_PARTITIONS, n_rows // FACT_PER_PID)
    n_rows = n_pids * FACT_PER_PID
    # fact side: FACT_PER_PID visits per patient, sorted by pid
    pid_f = np.repeat(np.arange(n_pids, dtype=np.int32), FACT_PER_PID)
    visits = Table.from_pydict({
        "pid": pid_f,
        "amount": rng.uniform(1.0, 500.0, n_rows).astype(np.float32),
        "dep_hour": rng.randint(0, 24, n_rows).astype(np.int32),
    })
    age = rng.uniform(0.0, 100.0, n_pids).astype(np.float32)
    patients = Table.from_pydict({
        "pid": np.arange(n_pids, dtype=np.int32),
        "age": age,
        "region": rng.randint(0, N_REGIONS, n_pids).astype(np.int32),
    })
    # identical pid split points -> co-partitioned by construction
    step = n_pids // N_PARTITIONS
    bounds = [k * step for k in range(1, N_PARTITIONS)]
    store = ModelStore()
    store.register_table("visits", visits, partition_by="pid",
                         partition_bounds=bounds)
    store.register_table("patients", patients, partition_by="pid",
                         partition_bounds=bounds)

    feats = ["age", "amount", "dep_hour"]
    data = {"age": np.repeat(age, FACT_PER_PID),
            "amount": np.asarray(visits.column("amount")),
            "dep_hour": np.asarray(visits.column("dep_hour"),
                                   np.float32)}
    y = ((data["age"] * 0.02 + data["amount"] * 1e-3
          + rng.randn(n_rows)) > 1.5).astype(np.int32)
    sc = StandardScaler(feats).fit(data)
    pipe = Pipeline([sc], LogisticRegression(steps=60),
                    PipelineMetadata(name="risk_lr", task="classification",
                                     flavor="external"))  # Raven-Ext path
    pipe.fit(data, y)
    store.register_model("risk_lr", pipe)
    return store, pipe, n_rows


def _plan(pipe):
    """visits ⋈ patients ON pid -> featurize -> predict (external) ->
    grouped aggregate of the prediction by region.  Built as IR (SQL has
    no AVG(PREDICT(...)) spelling)."""
    from repro.core.ir import Plan

    plan = Plan()
    v = plan.emit("scan", "RA", [], "table", table="visits")
    p = plan.emit("scan", "RA", [], "table", table="patients")
    j = plan.emit("join", "RA", [v, p], "table", on="pid", how="inner")
    f = plan.emit("featurize", "MLD", [j], "matrix",
                  pipeline_name="risk_lr", featurizers=pipe.featurizers,
                  input_columns=pipe.input_columns())
    m = plan.emit("predict_model", "MLD", [f], "matrix", model=pipe.model,
                  model_name="risk_lr", proba=True, task="classification",
                  flavor="external")
    a = plan.emit("attach_column", "RA", [j, m], "table", name="p")
    plan.output = plan.emit(
        "group_agg", "RA", [a], "table", key="region",
        aggs={"avg_p": ("avg", "p"), "n": ("count", None),
              "max_p": ("max", "p")},
        num_groups=N_REGIONS)
    return plan


def _service(store, shard_devices: int, morsel_rows: int, sharded=True):
    from repro.core import ExecutionConfig, OptimizerConfig
    from repro.serve import PredictionService

    # external flavor: keep the model out-of-process (no inlining/GEMM)
    opt = OptimizerConfig(enable_model_inlining=False,
                          enable_nn_translation=False)
    return PredictionService(store, optimizer_config=opt,
                             execution_config=ExecutionConfig(
                                 external_latency_s=EXTERNAL_LATENCY_S,
                                 sharded=sharded,
                                 shard_devices=shard_devices,
                                 shard_morsel_rows=morsel_rows))


def _timed(svc, plan, iters: int = 5) -> float:
    import numpy as np
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        svc.run(plan.copy())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _flat(svc):
    return (svc.stats.cache_misses, svc.stats.shard_compiles,
            svc.stats.jit_traces)


def main(n_rows: int, devices: int) -> None:
    import numpy as np

    from repro.core.codegen import pow2_bucket

    from .common import emit

    store, pipe, n_rows = _build_store(n_rows)
    plan = _plan(pipe)
    # morsel granularity = one partition on either mesh: identical morsels
    # (and identical partial-combine order) at 1 and 8 devices, so the
    # comparison is pure parallelism — and the outputs are bit-identical
    morsel_rows = pow2_bucket(FACT_PER_PID * -(-n_rows
                                               // (FACT_PER_PID
                                                   * N_PARTITIONS)))
    import jax
    assert len(jax.devices()) >= devices, \
        f"need {devices} devices, found {len(jax.devices())}"

    # unsharded reference (one whole-table execution, single model hop)
    ref = _service(store, 1, morsel_rows, sharded=False)
    want = ref.run(plan.copy())
    ref.close()

    single = _service(store, shard_devices=1, morsel_rows=morsel_rows)
    mesh = _service(store, shard_devices=devices, morsel_rows=morsel_rows)
    got_single = single.run(plan.copy())               # warm + check
    got_mesh = mesh.run(plan.copy())

    assert mesh.compile(plan.copy()).dist is not None, \
        "plan was not distributed-rewritten"
    info = mesh.shard_info()
    assert info["join_executions"] >= 1 and info["agg_combines"] >= 1

    # mesh == single-device bitwise (same partials, same combine order)
    for k in got_single.columns:
        assert (np.asarray(got_mesh.columns[k])
                == np.asarray(got_single.columns[k])).all(), k
    assert (np.asarray(got_mesh.valid)
            == np.asarray(got_single.valid)).all()
    # vs the unsharded reference: exact where exact is possible
    assert (np.asarray(got_mesh.valid) == np.asarray(want.valid)).all()
    for k in ("region", "n", "max_p"):
        assert (np.asarray(got_mesh.columns[k])
                == np.asarray(want.columns[k])).all(), k
    np.testing.assert_allclose(                  # reassociated float sums
        np.asarray(got_mesh.columns["avg_p"]),
        np.asarray(want.columns["avg_p"]), rtol=1e-5)

    flat_single, flat_mesh = _flat(single), _flat(mesh)
    t_single = _timed(single, plan)
    t_mesh = _timed(mesh, plan)
    assert _flat(single) == flat_single, "single-device warm compiles"
    assert _flat(mesh) == flat_mesh, "mesh warm compiles"
    speedup = t_single / t_mesh
    emit("sharded_join_agg/single_device", t_single * 1e6,
         f"rows_per_s={n_rows / t_single:.0f} "
         f"waves={single.shard_info()['shard_waves']}")
    emit("sharded_join_agg/mesh8", t_mesh * 1e6,
         f"rows_per_s={n_rows / t_mesh:.0f} speedup={speedup:.2f}x "
         f"devices={mesh.shard_info()['devices']} warm_compiles=0 "
         f"partials={mesh.shard_info()['partial_aggs']}")

    single.close()
    mesh.close()

    assert speedup >= 2.0, \
        f"sharded join+agg only {speedup:.2f}x at {devices} devices " \
        f"(need >=2x)"


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--no-header", action="store_true")
    args = ap.parse_args()
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    if not args.no_header:
        print("name,us_per_call,derived")
    main(args.rows, args.devices)
