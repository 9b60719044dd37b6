"""Hash-repartition shuffle exchange: sharding a *non*-co-partitioned join.

The workload shape ``sharded_join_agg`` could not touch: fact ``visits``
is range-partitioned on ``oid`` (order id — its natural ingest order),
dim ``patients`` on ``pid``, and the query joins ON ``pid`` — the join
key does not align with the fact table's partitioning, so the
partition-wise rewrite is impossible.  The ``distributed_plan`` rule
marks the join ``exchange`` and ``serve/exchange.py`` hash-buckets both
sides on the join key host-side, placing each bucket's local join (+
external-runtime model hop) on its own device.

Like the other sharded benchmarks, devices are simulated:
``--xla_force_host_platform_device_count`` must be set before importing
jax, so ``run()`` re-execs this module in a child process.

Reported rows:

- ``shuffle_join/single_device`` — the same bucket split executed on a
  1-device mesh (serial waves; the cost gate is forced open — left to
  itself it would rightly refuse a 1-device shuffle).
- ``shuffle_join/mesh8`` — buckets placed across 8 simulated devices;
  derived column carries the throughput speedup and the (asserted-zero)
  warm compile count.
- ``shuffle_join/bitwise`` — derived ``agree=1.0`` only when the mesh
  output is bit-identical to the single-device run in full AND matches
  the whole-table reference bitwise on every valid relational column
  (the model score is allclose — XLA reduces differently-padded matmuls
  in different orders): the scatter-back determinism contract as a
  tracked hard floor.
- ``shuffle_join/cost_gate_fallback`` — the same query on 1 device with
  the gate *on*: the shuffle is refused (``exchange_fallbacks=1``) and
  execution falls back to whole-table, automatically.

Acceptance (asserted in ``main()``):

- >= 2x throughput at 8 simulated devices vs single-device waves;
- mesh output bit-identical to single-device (same data-determined
  bucket split, same scatter-back) and to the unsharded reference on
  valid rows;
- zero extra compiles across every timed window;
- the cost gate falls back to whole-table execution where the shuffle
  cannot pay.
"""

from __future__ import annotations

import argparse
import os
import time

N_PARTITIONS = 32
FACT_PER_PID = 4
EXTERNAL_LATENCY_S = 25e-3


def run(n_rows: int = 200_000, devices: int = 8) -> None:
    """Entry from ``benchmarks.run``: see ``common.run_sharded``."""
    from .common import run_sharded
    run_sharded("benchmarks.shuffle_join", main, n_rows, devices)


def _build_store(n_rows: int):
    import numpy as np

    from repro.core import ModelStore
    from repro.ml import (LogisticRegression, Pipeline, PipelineMetadata,
                          StandardScaler)
    from repro.relational.table import Table

    rng = np.random.RandomState(29)
    n_pids = max(N_PARTITIONS, n_rows // FACT_PER_PID)
    n_rows = n_pids * FACT_PER_PID
    # fact side: ordered by oid (ingest order); pids arrive shuffled, so
    # the table cannot be range-partitioned on the join key
    visits = Table.from_pydict({
        "oid": np.arange(n_rows, dtype=np.int64),
        "pid": rng.permutation(np.repeat(
            np.arange(n_pids, dtype=np.int32), FACT_PER_PID)),
        "amount": rng.uniform(1.0, 500.0, n_rows).astype(np.float32),
        "dep_hour": rng.randint(0, 24, n_rows).astype(np.int32),
    })
    age = rng.uniform(0.0, 100.0, n_pids).astype(np.float32)
    patients = Table.from_pydict({
        "pid": np.arange(n_pids, dtype=np.int32),
        "age": age,
        "region": rng.randint(0, 8, n_pids).astype(np.int32),
    })
    fact_step = n_rows // N_PARTITIONS
    dim_step = n_pids // N_PARTITIONS
    store = ModelStore()
    store.register_table(
        "visits", visits, partition_by="oid",
        partition_bounds=[k * fact_step for k in range(1, N_PARTITIONS)])
    store.register_table(
        "patients", patients, partition_by="pid",
        partition_bounds=[k * dim_step for k in range(1, N_PARTITIONS)])

    feats = ["age", "amount", "dep_hour"]
    data = {"age": age[np.asarray(visits.column("pid"))],
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
    attach the prediction: row-local over the fact side, so the exchange
    scatter-back must reproduce the whole-table row order bit-for-bit."""
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
    plan.output = plan.emit("attach_column", "RA", [j, m], "table",
                            name="p")
    return plan


def _service(store, shard_devices: int, morsel_rows: int, sharded=True,
             cost_gate=False):
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
                                 shard_morsel_rows=morsel_rows,
                                 shard_exchange_cost_gate=cost_gate))


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
    # pin the bucket count to ``devices`` on both meshes: pow2 morsel cap
    # in (rows/8, rows/4] makes choose_bucket_count land on 8 whether it
    # starts from 1 device (doubling past the cap) or 8 — identical
    # data-determined split, so the two runs are bitwise comparable and
    # the speedup is pure parallelism
    morsel_rows = pow2_bucket(-(-n_rows // devices))
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

    compiled = mesh.compile(plan.copy())
    assert compiled.dist is not None, "plan was not distributed-rewritten"
    assert compiled.dist.exchange is not None, \
        "non-co-partitioned join did not plan an exchange"
    info = mesh.shard_info()
    assert info["exchange_executions"] >= 1
    assert info["exchange_fallbacks"] == 0
    assert single.shard_info()["exchange_executions"] >= 1

    # mesh == single-device bitwise in full (same bucket split, same
    # scatter-back — placement is unobservable)
    for k in got_single.columns:
        assert (np.asarray(got_mesh.columns[k])
                == np.asarray(got_single.columns[k])).all(), k
    assert (np.asarray(got_mesh.valid)
            == np.asarray(got_single.valid)).all()
    # vs the unsharded reference: bitwise on the mask and the valid rows
    # of every relational column (unmatched inner-join rows carry
    # garbage-but-masked right columns); the model score is allclose —
    # XLA reduces a [32k, f] and a [4k, f] matmul in different orders,
    # the standard shape-dependent float caveat
    vm, vw = np.asarray(got_mesh.valid), np.asarray(want.valid)
    assert (vm == vw).all()
    for k in want.columns:
        if k == "p":
            np.testing.assert_allclose(
                np.asarray(got_mesh.columns[k])[vm],
                np.asarray(want.columns[k])[vw], rtol=1e-5, atol=1e-6)
        else:
            assert (np.asarray(got_mesh.columns[k])[vm]
                    == np.asarray(want.columns[k])[vw]).all(), k

    flat_single, flat_mesh = _flat(single), _flat(mesh)
    t_single = _timed(single, plan)
    t_mesh = _timed(mesh, plan)
    assert _flat(single) == flat_single, "single-device warm compiles"
    assert _flat(mesh) == flat_mesh, "mesh warm compiles"
    speedup = t_single / t_mesh
    emit("shuffle_join/single_device", t_single * 1e6,
         f"rows_per_s={n_rows / t_single:.0f} "
         f"waves={single.shard_info()['shard_waves']}")
    emit("shuffle_join/mesh8", t_mesh * 1e6,
         f"rows_per_s={n_rows / t_mesh:.0f} speedup={speedup:.2f}x "
         f"devices={mesh.shard_info()['devices']} warm_compiles=0 "
         f"bytes_moved={mesh.shard_info()['exchange_bytes_moved']}")
    emit("shuffle_join/bitwise", 0.0, "agree=1.0")

    single.close()
    mesh.close()

    # cost gate on, 1 device: a shuffle moves every row to buy zero
    # parallelism — the gate must refuse it and fall back to whole-table
    gated = _service(store, shard_devices=1, morsel_rows=morsel_rows,
                     cost_gate=True)
    got_gated = gated.run(plan.copy())
    ginfo = gated.shard_info()
    assert ginfo["exchange_fallbacks"] >= 1
    assert ginfo["exchange_executions"] == 0
    assert gated.stats.sharded_executions == 0
    vg = np.asarray(got_gated.valid)
    assert (vg == vw).all()
    emit("shuffle_join/cost_gate_fallback", 0.0,
         f"fallbacks={ginfo['exchange_fallbacks']}")
    gated.close()

    assert speedup >= 2.0, \
        f"shuffle join only {speedup:.2f}x at {devices} devices " \
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
