"""End-to-end request tracing + unified metrics registry (ISSUE 9).

Four layers of guarantees:

1. **Span mechanics are exact** (ManualClock, no threads): durations,
   nesting, worker ``add_span`` records and events are pinned to
   deterministic clock readings.
2. **MetricsRegistry semantics**: counter/gauge/histogram keying by
   ``(name, labels)``, pull-time collectors sampled at read time, and
   Prometheus text rendering (TYPE lines, cumulative ``le`` buckets).
3. **Trace completeness per serving path**: cold compile, warm hit,
   coalesced groups, result-cache splice, sharded morsels, and the
   shuffle exchange each leave their signature spans in the request's
   trace — the observability contract the EXPLAIN/trace tooling reads.
4. **Off is free**: ``telemetry=False`` yields a null trace (zero spans
   retained, ``ticket.trace()`` is None) and zero hot-path registry
   writes, while pull-time collectors keep working.
5. **Profiler spans**: under a profiler session the same spans, and the
   executor's own, reach the profiler's host timeline as ``repro.*``
   annotations carrying the request's trace id, with telemetry on or
   off; the device ops carry ``repro.<layer>`` name scopes; and
   ``ServiceStats.launches`` counts the device programs issued.

Plus the operator-level EXPLAIN ANALYZE contract: on an external-model
shuffle-join query (known per-operator latency floor) the per-operator
measured times must sum to within 20% of the measured end-to-end wall
time.
"""

import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core import ExecutionConfig, ModelStore, OptimizerConfig
from repro.core.ir import Plan
from repro.data import hospital_tables
from repro.ml import (DecisionTree, LogisticRegression, Pipeline,
                      PipelineMetadata, RandomForest, StandardScaler)
from repro.relational.table import Table
from repro.serve import (NULL_TRACE, AdmissionConfig, ManualClock,
                         MetricsRegistry, PredictionService, Span, Trace)

pytestmark = pytest.mark.tier1

FEATS = ["age", "gender", "pregnant", "rcount"]
SQL = "SELECT pid, age FROM patient_info WHERE age > 30"
SQL_A = "SELECT pid, PREDICT(MODEL='m') AS score FROM patient_info"
SQL_B = "SELECT pid, age, PREDICT(MODEL='m') AS score FROM patient_info"


def _make_store(n_rows=300, seed=7):
    store = ModelStore()
    for n, t in hospital_tables(n_rows, seed=seed).items():
        store.register_table(n, t)
    pi = store.get_table("patient_info")
    data = {c: np.asarray(pi.column(c)) for c in pi.names}
    sc = StandardScaler(FEATS).fit(data)
    # depth 6: > inline_max_nodes, so the predict subtree stays cacheable
    pipe = Pipeline([sc], DecisionTree(task="regression", max_depth=6),
                    PipelineMetadata(name="m", task="regression"))
    pipe.fit({k: data[k] for k in FEATS}, data["length_of_stay"])
    store.register_model("m", pipe)
    return store


@pytest.fixture(scope="module")
def store():
    return _make_store()


def _sub(full: Table, lo: int, n: int) -> Table:
    return Table({k: v[lo:lo + n] for k, v in full.columns.items()},
                 full.valid[lo:lo + n], full.schema)


# ---------------------------------------------------------------------------
# 1. Span mechanics (ManualClock — exact durations)
# ---------------------------------------------------------------------------

def test_span_durations_exact_on_manual_clock():
    clock = ManualClock()
    tr = Trace(clock, trace_id=7, name="q")
    with tr.span("parse"):
        clock.advance(0.25)
    with tr.span("execute", rows=10) as ex:
        clock.advance(1.5)
        with tr.span("inner"):
            clock.advance(0.5)
    clock.advance(0.125)
    tr.finish()
    tr.finish()                             # idempotent: first stamp wins

    parse, execute = tr.roots
    assert parse.duration == 0.25
    assert execute is ex and execute.duration == 2.0
    assert execute.attrs == {"rows": 10}
    (inner,) = execute.children
    assert inner.duration == 0.5
    assert tr.total_s == 2.375
    assert tr.span_names() == ["parse", "execute", "inner"]
    assert tr.find("inner").duration == 0.5
    assert "execute 2000.000ms" in tr.pretty()


def test_worker_add_span_and_events():
    clock = ManualClock()
    tr = Trace(clock)
    tr.event("cache", result="hit")
    with tr.span("execute"):
        # overlapping worker spans, recorded out-of-band per device
        tr.add_span("shard_wave", 0.0, 0.5, device=0)
        tr.add_span("shard_wave", 0.0, 0.75, device=1)
        clock.advance(0.75)
    ev = tr.find("cache")
    assert ev.duration == 0.0 and ev.attrs == {"result": "hit"}
    waves = [s for s in tr.spans() if s.name == "shard_wave"]
    assert [w.attrs["device"] for w in waves] == [0, 1]
    assert [w.duration for w in waves] == [0.5, 0.75]
    # workers parent under the phase span that was open when they recorded
    assert all(w in tr.find("execute").children for w in waves)


def test_null_trace_is_inert():
    with NULL_TRACE.span("anything", x=1) as s:
        # the profiler annotation alone: no Span is recorded
        assert not isinstance(s, Span)
    assert NULL_TRACE.event("e") is None
    assert NULL_TRACE.add_span("w", 0.0, 1.0) is None
    assert not NULL_TRACE.enabled
    assert NULL_TRACE.span_names() == []
    assert list(NULL_TRACE.spans()) == []


# ---------------------------------------------------------------------------
# 2. MetricsRegistry semantics
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_labels():
    reg = MetricsRegistry()
    reg.inc("req_total")
    reg.inc("req_total", 2.0)
    reg.inc("req_total", labels={"tenant": "a"})
    reg.set_gauge("depth", 4)
    snap = reg.snapshot()
    assert snap["counters"]["req_total"] == 3.0
    assert snap["counters"]["req_total{tenant=a}"] == 1.0
    assert snap["gauges"]["depth"] == 4.0
    assert reg.writes == 4


def test_registry_histogram_render_cumulative():
    reg = MetricsRegistry()
    for v in (0.3, 0.4, 99.0):
        reg.observe("lat_seconds", v, buckets=(0.5, 1.0))
    text = reg.render()
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.5"} 2' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert "lat_seconds_sum 99.7" in text


def test_registry_collectors_sampled_at_read_time():
    reg = MetricsRegistry()
    state = {"n": 1}
    unsub = reg.add_collector(
        lambda: [("live_total", "counter", state["n"], None),
                 ("live_depth", "gauge", 2.0, {"q": "x"})])
    assert reg.snapshot()["counters"]["live_total"] == 1.0
    state["n"] = 5
    snap = reg.snapshot()
    assert snap["counters"]["live_total"] == 5.0     # re-sampled, not cached
    assert snap["gauges"]["live_depth{q=x}"] == 2.0
    assert reg.writes == 0                           # collection is a read
    unsub()
    assert "live_total" not in reg.snapshot()["counters"]


# ---------------------------------------------------------------------------
# 3. Trace completeness per serving path
# ---------------------------------------------------------------------------

def test_queue_wait_span_is_exact_on_manual_clock(store):
    clock = ManualClock()
    svc = PredictionService(store, clock=clock, admission=AdmissionConfig(
        latency_budget_s=1.0, background=False))
    ticket = svc.submit(SQL)
    clock.advance(1.1)
    assert svc.admission_tick() == 1
    ticket.result(timeout=0)
    tr = ticket.trace()
    assert tr is not None and tr.finished is not None
    qw = tr.find("queue_wait")
    assert qw.duration == pytest.approx(1.1)
    assert qw.attrs["reason"] == "deadline"
    assert svc.traces()[-1] is tr
    svc.close()


def test_cold_then_warm_trace_spans(store):
    svc = PredictionService(store)
    svc.run(SQL)
    svc.run(SQL)
    cold, warm = svc.traces()
    assert cold.name == SQL
    for name in ("parse", "queue_wait", "optimize", "codegen", "execute"):
        assert cold.find(name) is not None, name
    assert cold.find("executable_cache").attrs["result"] == "miss"
    warm_names = warm.span_names()
    assert warm.find("executable_cache").attrs["result"] == "hit"
    assert "optimize" not in warm_names and "codegen" not in warm_names
    assert warm.find("execute") is not None
    svc.close()


def test_coalesced_member_gets_event_head_gets_execute(store):
    clock = ManualClock()
    svc = PredictionService(store, clock=clock, admission=AdmissionConfig(
        latency_budget_s=1.0, background=False))
    t1 = svc.submit(SQL)
    t2 = svc.submit(SQL)
    clock.advance(1.5)
    assert svc.admission_tick() == 2
    head, rider = t1.trace(), t2.trace()
    assert head.find("execute").attrs["coalesced"] == 1
    assert rider.find("coalesced").attrs["group"] == 2
    assert rider.find("execute") is None
    assert len(svc.traces()) == 2
    svc.close()


def test_splice_trace_visible_in_second_query(store):
    svc = PredictionService(store)
    svc.run(SQL_A)
    svc.run(SQL_B)
    assert svc.stats.spliced_executions == 1
    first, second = svc.traces()
    assert first.find("result_cache_splice") is None
    splice = second.find("result_cache_splice")
    assert splice is not None and splice.attrs["hit"] is True
    assert "patient_info" in splice.attrs["subtree"]
    svc.close()


def test_sharded_trace_carries_shard_waves():
    rng = np.random.RandomState(0)
    n = 1200
    t = Table.from_pydict({
        "pid": np.arange(n),
        "age": np.sort(rng.randint(0, 100, n)).astype(np.int32)})
    store = ModelStore()
    store.register_table("people", t, partition_rows=200)
    svc = PredictionService(store, execution_config=ExecutionConfig(
        sharded=True, shard_min_bucket_rows=32))
    svc.run("SELECT pid FROM people WHERE age < 30")
    assert svc.stats.sharded_executions == 1
    (tr,) = svc.traces()
    waves = [s for s in tr.spans() if s.name == "shard_wave"]
    n_dev = svc.shard_info()["devices"]
    assert waves and all(0 <= w.attrs["device"] < n_dev for w in waves)
    assert sum(w.attrs["partitions"] for w in waves) \
        == svc.stats.partitions_scanned
    svc.close()


def _exchange_store(n_pids=48, per_pid=4, seed=3):
    """Fact/dim pair partitioned on *different* keys, so the join can only
    shard through the hash-repartition exchange (test_exchange idiom)."""
    rng = np.random.RandomState(seed)
    n_rows = n_pids * per_pid
    visits = Table.from_pydict({
        "oid": np.arange(n_rows, dtype=np.int64),
        "pid": rng.permutation(np.repeat(
            np.arange(n_pids, dtype=np.int32), per_pid)),
        "amount": rng.uniform(0.0, 9.0, n_rows).astype(np.float32)})
    patients = Table.from_pydict({
        "pid": np.arange(n_pids, dtype=np.int32),
        "age": rng.uniform(0.0, 99.0, n_pids).astype(np.float32)})
    store = ModelStore()
    store.register_table("visits", visits, partition_by="oid",
                         partition_bounds=[n_rows // 2])
    store.register_table("patients", patients, partition_by="pid",
                         partition_bounds=[n_pids // 2])
    return store


def _join_plan():
    plan = Plan()
    v = plan.emit("scan", "RA", [], "table", table="visits")
    p = plan.emit("scan", "RA", [], "table", table="patients")
    plan.output = plan.emit("join", "RA", [v, p], "table", on="pid",
                            how="inner")
    return plan


def test_exchange_trace_spans_and_placement_attrs():
    svc = PredictionService(_exchange_store(), execution_config=
        ExecutionConfig(
            sharded=True, shard_min_bucket_rows=4, shard_morsel_rows=16,
            shard_exchange_cost_gate=False))
    svc.run(_join_plan())
    assert svc.stats.exchange_executions == 1
    (tr,) = svc.traces()
    build = tr.find("exchange_build")
    assert build.attrs["on"] == "pid"
    assert build.attrs["n_buckets"] >= 1          # ExchangePlacement.describe
    assert build.attrs["anchor_rows_total"] == 192
    buckets = [s for s in tr.spans() if s.name == "exchange_bucket"]
    n_dev = svc.shard_info()["devices"]
    assert buckets and all(0 <= b.attrs["device"] < n_dev for b in buckets)
    scatter = tr.find("exchange_scatter")
    assert scatter is not None and scatter.attrs["rows"] == 192
    svc.close()


def test_trace_ring_capacity_bounds_retention(store):
    svc = PredictionService(store, trace_capacity=2)
    for _ in range(5):
        svc.run(SQL)
    assert len(svc.traces()) == 2
    assert len(svc.traces(1)) == 1
    svc.close()


# ---------------------------------------------------------------------------
# 4. telemetry=False is free
# ---------------------------------------------------------------------------

def test_telemetry_off_zero_spans_zero_writes(store):
    svc = PredictionService(store, telemetry=False)
    ticket = svc.submit(SQL)
    svc.flush()
    ticket.result(timeout=5)
    svc.run(SQL)
    assert svc.traces() == []
    assert ticket.trace() is None
    assert svc.metrics.writes == 0                # no hot-path mutations
    # pull-time collectors still work: stats stay the source of truth
    snap = svc.metrics_snapshot()
    assert snap["counters"]["repro_submitted_total"] == 2.0
    assert snap["counters"]["repro_cache_hits_total"] == 1.0
    svc.close()


def test_telemetry_on_writes_and_prometheus_text(store):
    svc = PredictionService(store)
    svc.run(SQL)
    assert svc.metrics.writes >= 3      # queue wait + exec + compile observes
    text = svc.metrics_text()
    assert "# TYPE repro_queue_wait_seconds histogram" in text
    assert "repro_exec_seconds_count 1" in text
    assert "repro_compile_seconds_count 1" in text
    assert "repro_plans_compiled_total 1" in text
    assert "repro_batch_executions_total 1" in text
    assert "repro_admission_queue_depth_high_water 1" in text
    svc.close()


# ---------------------------------------------------------------------------
# 5. EXPLAIN ANALYZE
# ---------------------------------------------------------------------------

EXTERNAL_LATENCY_S = 20e-3


def _explain_store(n_pids=64, per_pid=4, seed=11):
    """Shuffle-join-shaped store with an *external*-flavor model: every
    operator above the scans costs real wall time (the external hop has a
    simulated 20ms floor), so per-operator times must account for the
    end-to-end measurement."""
    rng = np.random.RandomState(seed)
    store = _exchange_store(n_pids=n_pids, per_pid=per_pid, seed=seed)
    visits = store.get_table("visits")
    patients = store.get_table("patients")
    age = np.asarray(patients.column("age"))
    feats = ["age", "amount"]
    data = {"age": age[np.asarray(visits.column("pid"))],
            "amount": np.asarray(visits.column("amount"))}
    y = (data["age"] * 0.02 + data["amount"] * 0.1
         + rng.randn(len(data["age"])) > 1.0).astype(np.int32)
    sc = StandardScaler(feats).fit(data)
    pipe = Pipeline([sc], LogisticRegression(steps=25),
                    PipelineMetadata(name="risk", task="classification",
                                     flavor="external"))
    pipe.fit(data, y)
    store.register_model("risk", pipe)
    return store, pipe


def _predict_join_plan(pipe):
    plan = _join_plan()
    j = plan.output
    f = plan.emit("featurize", "MLD", [j], "matrix", pipeline_name="risk",
                  featurizers=pipe.featurizers,
                  input_columns=pipe.input_columns())
    m = plan.emit("predict_model", "MLD", [f], "matrix", model=pipe.model,
                  model_name="risk", proba=True, task="classification",
                  flavor="external")
    plan.output = plan.emit("attach_column", "RA", [j, m], "table", name="p")
    return plan


def test_explain_analyze_operator_times_account_for_e2e():
    store, pipe = _explain_store()
    svc = PredictionService(
        store,
        optimizer_config=OptimizerConfig(enable_model_inlining=False,
                                         enable_nn_translation=False),
        execution_config=ExecutionConfig(
            external_latency_s=EXTERNAL_LATENCY_S))
    ex = svc.explain(_predict_join_plan(pipe), analyze=True)
    assert ex.analyze and ex.total_s > 0
    op_names = [n.op for _, n in ex.operators()]
    assert "join" in op_names and "predict_model" in op_names
    measured = ex.measured_s
    # the acceptance bound: per-operator sum within 20% of end-to-end
    assert measured == pytest.approx(ex.total_s, rel=0.2)
    # the external hop's 20ms floor is visible on its operator
    pm = [nid for nid, n in ex.plan.nodes.items()
          if n.op == "predict_model"]
    assert pm and ex.samples[pm[0]][0] >= EXTERNAL_LATENCY_S * 0.5
    text = ex.pretty()
    assert "predict_model" in text and "actual time=" in text
    assert "end-to-end" in text
    svc.close()


def test_explain_without_analyze_renders_plan_only(store):
    svc = PredictionService(store)
    ex = svc.explain(SQL)
    assert not ex.analyze and ex.samples == {}
    text = ex.pretty()
    assert "scan [patient_info]" in text
    assert "actual time=" not in text
    svc.close()


# ---------------------------------------------------------------------------
# 6. Profiler spans, device-op scopes and the launch counter
# ---------------------------------------------------------------------------

CHUNK = 64                           # 300 rows: 5 morsels, the tail padded


def _profile_events(log_dir):
    """(start, end, name, trace_id, line) of every ``repro.*`` event on the
    host planes of the newest profile under ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name[len("repro."):],
                                dict(e.stats).get("trace_id"), i))
    return out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1] \
        and inner[4] == outer[4]


@pytest.mark.parametrize("telemetry", [True, False])
def test_profiler_spans_of_a_chunked_query(store, tmp_path, telemetry):
    """A cold then a warm chunked ``sql()`` under a CPU profiler session:
    every phase reaches the host timeline as ``repro.<name>``, nested as
    the executor runs them, tagged with its request's trace id; with
    telemetry off too, and then with no registry write."""
    svc = PredictionService(store, chunk_rows=CHUNK, telemetry=telemetry)
    n_morsels = -(-store.get_table("patient_info").capacity // CHUNK)
    with jax.profiler.trace(str(tmp_path)):
        svc.sql(SQL)
        svc.sql(SQL)
    events = _profile_events(str(tmp_path))
    ids = sorted({e[3] for e in events} - {0})
    assert len(ids) == 2                      # one id per request
    if telemetry:
        assert ids == [t.trace_id for t in svc.traces()]
    else:
        assert svc.metrics.writes == 0 and svc.traces() == []
    # the only span outside any request: the admission queue's drain
    assert {e[2] for e in events if e[3] == 0} == {"admit"}
    for i, rid in enumerate(ids):
        mine = [e for e in events if e[3] == rid]
        count = Counter(e[2] for e in mine)
        assert count["parse"] == 1 and count["compile"] == 1
        assert count["admit"] == 2            # offer, then the serve
        assert count["execute"] == 1 and count["assemble"] == 1
        assert count["morsel.slice"] == count["morsel.launch"] == n_morsels
        # the loop waits on the morsel two back before each later slice,
        # then the served result is waited on once
        assert count["device_wait"] == max(n_morsels - 2, 0) + 1
        # the cold request optimizes and generates code inside `compile`
        assert count["optimize"] == count["codegen"] == (1 - i)
        (compile_,) = [e for e in mine if e[2] == "compile"]
        (execute,) = [e for e in mine if e[2] == "execute"]
        for e in mine:
            if e[2] in ("optimize", "codegen"):
                assert _inside(e, compile_)
            if e[2].startswith("morsel.") or e[2] in ("assemble",
                                                      "device_wait"):
                assert _inside(e, execute)
        assert compile_[1] <= execute[0]
    svc.close()


def _forest_join_store(n_rows=400, seed=5):
    store = ModelStore()
    for n, t in hospital_tables(n_rows, seed=seed).items():
        store.register_table(n, t)
    pi = store.get_table("patient_info")
    bt = store.get_table("blood_tests")
    feats = FEATS + ["hematocrit"]
    order = np.argsort(np.asarray(bt.column("pid")))
    data = {c: np.asarray(pi.column(c)) for c in FEATS}
    data["hematocrit"] = np.asarray(bt.column("hematocrit"))[order]
    pipe = Pipeline([StandardScaler(feats).fit(data)],
                    RandomForest(n_trees=4, task="regression", max_depth=5),
                    PipelineMetadata(name="rf", task="regression"))
    pipe.fit(data, np.asarray(pi.column("length_of_stay")))
    store.register_model("rf", pipe)
    return store


def test_device_ops_named_by_layer_in_hlo():
    """The compiled join + forest plan's HLO metadata names each op's
    layer: the join, the featurizer and the model step."""
    store = _forest_join_store()
    svc = PredictionService(store)
    sql = ("SELECT pid, PREDICT(MODEL='rf') AS los FROM patient_info "
           "JOIN blood_tests ON pid")
    compiled = svc.compile(sql)
    tabs = {t: store.get_table(t) for t in compiled.scan_tables}
    hlo = compiled.fn.lower(tabs).compile().as_text()
    for scope in ("repro.join", "repro.featurize", "repro.model"):
        assert f"/{scope}/" in hlo, scope
    svc.close()


@pytest.mark.parametrize("n_rows", [300, 256])
def test_launches_count_a_chunked_plan_by_hand(n_rows):
    """Each morsel of patient_info is cut by one slicer call (the short
    tail too) and run by one program call; one assembler call then
    concatenates and trims the outputs."""
    store = _make_store(n_rows=n_rows)
    svc = PredictionService(store, chunk_rows=CHUNK)
    svc.sql(SQL)
    morsels = n_rows // CHUNK + (n_rows == 300)   # 300: 5, the fifth short
    assert svc.stats.chunks_executed == morsels
    assert svc.stats.launches == 2 * morsels + 1
    svc.close()


def test_launches_of_the_whole_path_is_one_program(store):
    svc = PredictionService(store)
    svc.sql(SQL)
    svc.sql(SQL)
    assert svc.stats.launches == 2
    snap = svc.metrics_snapshot()
    assert snap["counters"]["repro_launches_total"] == 2.0
    svc.close()
