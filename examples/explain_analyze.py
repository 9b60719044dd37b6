"""Observability tour: request traces, EXPLAIN ANALYZE, and the metrics
registry (ISSUE 9).

Runs a shared-prefix query pair (the result-cache splice demo) and a
partitioned scan through one :class:`PredictionService`, then shows:

1. ``service.explain(sql)`` — the optimized plan tree with partition
   pruning, strategy and splice annotations; ``analyze=True`` re-runs
   the exact compiled plan un-jitted with per-operator timing, so every
   row of the tree carries ``actual time=... rows=...``.
2. Request traces: the cold trace (optimize/codegen/execute spans), the
   warm trace (executable-cache hit), and the second query of the
   shared-prefix pair whose trace visibly contains the
   ``result_cache_splice`` span — the cross-query cache at work.
3. A profiler trace (``jax.profiler.trace``): the same spans, named
   ``repro.<phase>``, on the profiler's host timeline beside the device
   ops; open the ``.xplane.pb`` it writes in TensorBoard's profiler, or
   its ``perfetto_trace.json.gz`` in https://ui.perfetto.dev.
4. ``service.metrics_text()`` — the Prometheus exposition unifying
   ServiceStats counters, cache gauges and latency histograms.

Run:  PYTHONPATH=src python examples/explain_analyze.py
"""

import tempfile

import jax
import numpy as np

from repro.core import ModelStore
from repro.data import hospital_tables
from repro.ml import (DecisionTree, Pipeline, PipelineMetadata,
                      StandardScaler)
from repro.serve import PredictionService

SQL_A = "SELECT pid, PREDICT(MODEL='los') AS score FROM patient_info"
# same inference prefix as SQL_A (no WHERE — a filter below the featurizer
# would change the subtree signature), one extra projected column: the
# shared prefix splices from the result cache
SQL_B = "SELECT pid, age, PREDICT(MODEL='los') AS score FROM patient_info"
# the EXPLAIN showcase query: the WHERE drives zone-map partition pruning
SQL_EXPLAIN = ("SELECT pid, age, PREDICT(MODEL='los') AS score "
               "FROM patient_info WHERE age > 40")


def build_store(n_rows: int = 20_000) -> ModelStore:
    store = ModelStore(principal="explain_demo")
    tables = hospital_tables(n_rows)
    pi = tables["patient_info"]
    # partitioned registration: zone maps feed the pruning annotations
    store.register_table("patient_info", pi, partition_rows=2_000)
    for name, t in tables.items():
        if name != "patient_info":
            store.register_table(name, t)
    feats = ["age", "gender", "pregnant", "rcount"]
    data = {c: np.asarray(pi.column(c)) for c in pi.names}
    sc = StandardScaler(feats).fit(data)
    pipe = Pipeline([sc], DecisionTree(task="regression", max_depth=6),
                    PipelineMetadata(name="los", task="regression"))
    pipe.fit({k: data[k] for k in feats}, data["length_of_stay"])
    store.register_model("los", pipe)
    return store


def main():
    store = build_store()
    service = PredictionService(store)

    # -- 1. EXPLAIN / EXPLAIN ANALYZE ------------------------------------
    print("=" * 72)
    print("EXPLAIN (no execution):\n")
    print(service.explain(SQL_EXPLAIN).pretty())

    print("\n" + "=" * 72)
    print("EXPLAIN ANALYZE (per-operator measured wall time):\n")
    print(service.explain(SQL_EXPLAIN, analyze=True).pretty())

    # -- 2. request traces: cold, warm, and the splice -------------------
    print("\n" + "=" * 72)
    print("Cold vs warm trace for the same query:\n")
    service.run(SQL_A)            # cold: optimize + codegen + execute
    service.run(SQL_A)            # warm: executable-cache hit
    cold, warm = service.traces()
    print(cold.pretty())
    print()
    print(warm.pretty())

    print("\n" + "=" * 72)
    print("Shared-prefix pair: the second query's trace shows the "
          "result-cache splice\n")
    out = service.run(SQL_B)      # splices SQL_A's materialized prefix
    spliced_trace = service.traces()[-1]
    print(spliced_trace.pretty())
    splice = spliced_trace.find("result_cache_splice")
    assert splice is not None and splice.attrs["hit"], \
        "expected the shared inference prefix to be served from cache"
    print(f"\nspliced rows: {int(np.asarray(out.valid).sum())} "
          f"(result_hits={service.stats.result_hits}, "
          f"spliced_executions={service.stats.spliced_executions})")

    # -- 3. the same spans on the profiler's timeline ---------------------
    log_dir = tempfile.mkdtemp(prefix="repro_profile_")
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        service.run(SQL_B)
    print(f"\nwrote a profiler trace under {log_dir} (repro.* host spans "
          "beside the device ops; open perfetto_trace.json.gz in "
          "https://ui.perfetto.dev)")

    # -- 4. the metrics registry -----------------------------------------
    print("\n" + "=" * 72)
    print("Prometheus exposition (excerpt):\n")
    for line in service.metrics_text().splitlines():
        if any(k in line for k in ("exec_seconds", "cache_hits",
                                   "result_hits", "queue_depth")):
            print(line)

    service.close()


if __name__ == "__main__":
    main()
