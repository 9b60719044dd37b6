"""Prediction-query serving layer: plan-signature cache, chunked execution,
micro-batch coalescing.

Key guarantees under test:
- a repeat of an identical query performs ZERO plan compilations (asserted
  through the ``codegen`` compile-counter hook);
- the plan signature is invariant to node-id aliasing and table column
  order, but sensitive to model *content* (retrained weights miss the cache);
- chunked (morsel) execution is bit-exact vs whole-table execution,
  including ragged tails;
- concurrent requests sharing a signature coalesce into one execution.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import ModelStore, parse_query
from repro.core import codegen
from repro.core.codegen import add_compile_listener
from repro.core.ir import Category, Node, Plan, plan_signature
from repro.core.model_store import content_fingerprint
from repro.data import hospital_tables
from repro.ml import DecisionTree, Pipeline, PipelineMetadata, StandardScaler
from repro.relational.table import Table
from repro.relational.expr import col
from repro.serve import PredictionService
from repro.serve.prediction_service import (_assemble_jit, _morsel,
                                            _morsel_jit, _morsel_table)

N_ROWS = 600
FEATS = ["age", "gender", "pregnant", "rcount"]
SQL = ("SELECT pid, age, PREDICT(MODEL='los_pi') AS los "
       "FROM patient_info WHERE age > 30")


def _pipeline(data, max_depth=6):
    sc = StandardScaler(FEATS).fit(data)
    pipe = Pipeline([sc], DecisionTree(task="regression",
                                       max_depth=max_depth),
                    PipelineMetadata(name="los_pi", task="regression"))
    pipe.fit({k: data[k] for k in FEATS}, data["length_of_stay"])
    return pipe


@pytest.fixture(scope="module")
def store():
    store = ModelStore()
    for n, t in hospital_tables(N_ROWS, seed=7).items():
        store.register_table(n, t)
    pi = store.get_table("patient_info")
    data = {c: np.asarray(pi.column(c)) for c in pi.names}
    store.register_model("los_pi", _pipeline(data))
    return store


def _sub_table(table: Table, lo: int, hi: int) -> Table:
    return Table({k: v[lo:hi] for k, v in table.columns.items()},
                 table.valid[lo:hi], table.schema)


def _table_arrays(t: Table):
    return ({k: np.asarray(v) for k, v in t.columns.items()},
            np.asarray(t.valid))


# ---------------------------------------------------------------------------
# Cache behaviour
# ---------------------------------------------------------------------------

def test_second_run_zero_plan_compiles(store):
    service = PredictionService(store)
    compiled_plans = []
    unsubscribe = add_compile_listener(compiled_plans.append)
    try:
        out1 = service.run(SQL)
        assert len(compiled_plans) == 1
        assert service.stats.cache_misses == 1
        out2 = service.run(SQL)                # warm: zero compilations
        assert len(compiled_plans) == 1
        assert service.stats.cache_hits == 1
    finally:
        unsubscribe()
    c1, v1 = _table_arrays(out1)
    c2, v2 = _table_arrays(out2)
    assert (v1 == v2).all()
    for k in c1:
        assert (c1[k] == c2[k]).all()


def test_compile_counter_counts(store):
    before = codegen.compile_stats["plans_compiled"]
    service = PredictionService(store)
    service.run(SQL)
    service.run(SQL)
    service.run(SQL)
    assert codegen.compile_stats["plans_compiled"] == before + 1


def test_lru_eviction(store):
    service = PredictionService(store, max_cache_entries=2)
    service.run("SELECT pid FROM patient_info WHERE age > 10")
    service.run("SELECT pid FROM patient_info WHERE age > 20")
    service.run("SELECT pid FROM patient_info WHERE age > 30")
    info = service.cache_info()
    assert info["entries"] == 2
    assert info["evictions"] == 1


# ---------------------------------------------------------------------------
# Signature semantics
# ---------------------------------------------------------------------------

def test_signature_invariant_to_node_id_aliases(store):
    """The same logical plan built under different node ids (the SQL
    frontend's fresh-id counter, or hand-chosen aliases) hashes identically."""
    p1 = parse_query(SQL, store)
    p2 = parse_query(SQL, store)        # fresh auto-generated ids
    assert plan_signature(p1) == plan_signature(p2)

    def hand_built(alias: str) -> Plan:
        plan = Plan()
        scan = plan.add(Node("scan", Category.RA, [], {"table": "patient_info"},
                             "table", id=f"{alias}_scan"))
        filt = plan.add(Node("filter", Category.RA, [scan],
                             {"predicate": col("age") > 30}, "table",
                             id=f"{alias}_filter"))
        plan.output = filt
        return plan

    assert plan_signature(hand_built("a")) == plan_signature(hand_built("zz"))


def test_signature_invariant_to_column_order(store):
    """Cache keys hash table schemas sorted by column name, so two catalogs
    whose tables declare the same columns in different order share keys."""
    pi = store.get_table("patient_info")
    names = list(pi.names)
    reordered = Table({n: pi.columns[n] for n in reversed(names)},
                      pi.valid, pi.schema.select(list(reversed(names))))
    other = ModelStore()
    other.register_table("patient_info", reordered)
    other.register_model("los_pi", store.get_model("los_pi"))

    s1 = PredictionService(store)
    s2 = PredictionService(other)
    k1, _ = s1._cache_key(parse_query(SQL, store), None)
    k2, _ = s2._cache_key(parse_query(SQL, other), None)
    assert k1 == k2


def test_signature_sensitive_to_model_content(store):
    pi = store.get_table("patient_info")
    data = {c: np.asarray(pi.column(c)) for c in pi.names}
    retrained = _pipeline(data, max_depth=3)

    other = ModelStore()
    other.register_table("patient_info", pi)
    other.register_model("los_pi", retrained)

    sig_orig = plan_signature(parse_query(SQL, store))
    sig_new = plan_signature(parse_query(SQL, other))
    assert sig_orig != sig_new
    assert content_fingerprint(store.get_model("los_pi")) \
        != content_fingerprint(retrained)
    # byte-identical re-registration digests identically
    v2 = other.register_model("los_pi", retrained)
    assert other.model_digest("los_pi", 1) == other.model_digest("los_pi", v2)


def test_udf_signature_sensitive_to_constants_and_closures(store):
    """co_code alone cannot distinguish `+1` from `+2` (the constant lives
    in co_consts) — the signature must."""
    def build(fn):
        plan = Plan()
        scan = plan.emit("scan", Category.RA, [], "table",
                         table="patient_info")
        plan.output = plan.emit("udf", Category.UDF, [scan], "vector", fn=fn)
        return plan

    s_plus1 = plan_signature(build(lambda cols: cols["age"] + 1))
    s_plus2 = plan_signature(build(lambda cols: cols["age"] + 2))
    assert s_plus1 != s_plus2

    def closed_over(k):
        return lambda cols: cols["age"] + k

    assert plan_signature(build(closed_over(3))) \
        != plan_signature(build(closed_over(4)))


def test_fingerprint_covers_globals_and_private_attrs():
    """Identical bytecode must not collide: the referenced global name
    (abs vs len, np.log vs np.exp) and underscored fitted state (e.g.
    Bucketizer._kept) are part of an artifact's content."""
    assert content_fingerprint(lambda x: abs(x)) \
        != content_fingerprint(lambda x: len(x))

    def log_udf(cols):
        return np.log(cols["age"])

    def exp_udf(cols):
        return np.exp(cols["age"])

    assert content_fingerprint(log_udf) != content_fingerprint(exp_udf)

    class Fitted:
        def __init__(self, w):
            self._w = w

    assert content_fingerprint(Fitted(1)) != content_fingerprint(Fitted(2))
    # ...and constants inside *nested* functions
    assert content_fingerprint(lambda cols: (lambda v: v + 1)(cols)) \
        != content_fingerprint(lambda cols: (lambda v: v + 2)(cols))


def test_zero_cache_entries_disables_caching(store):
    service = PredictionService(store, max_cache_entries=0)
    sql = "SELECT pid FROM patient_info WHERE age > 10"
    out1 = service.run(sql)
    out2 = service.run(sql)
    assert service.cache_info()["entries"] == 0
    assert (np.asarray(out1.valid) == np.asarray(out2.valid)).all()


def test_stats_update_invalidates_cache_key(store):
    """Stats-based pruning bakes catalog stats into the executable, so
    re-registering a table with different stats must miss the cache."""
    other = ModelStore()
    pi = store.get_table("patient_info")
    other.register_table("patient_info", pi)
    other.register_model("los_pi", store.get_model("los_pi"))
    service = PredictionService(other)
    k1, _ = service._cache_key(parse_query(SQL, other), None)
    wider = pi.with_columns({"age": np.asarray(pi.column("age")) + 100})
    other.register_table("patient_info", wider)
    k2, _ = service._cache_key(parse_query(SQL, other), None)
    assert k1 != k2


def test_override_tables_bypass_stats_pruning(store):
    """Caller-supplied tables may violate catalog stats; predictions must
    match an unpruned execution even for out-of-range rows."""
    from repro.core import OptimizerConfig
    pi = store.get_table("patient_info")
    out_of_range = pi.with_columns(
        {"age": np.asarray(pi.column("age"), np.float32) + 500.0})
    service = PredictionService(store)
    sql = "SELECT pid, PREDICT(MODEL='los_pi') AS los FROM patient_info"
    got = service.run(sql, {"patient_info": out_of_range})

    unpruned = PredictionService(
        store, optimizer_config=OptimizerConfig(enable_model_pruning=False))
    want = unpruned.run(sql, {"patient_info": out_of_range})
    cg, vg = _table_arrays(got)
    cw, vw = _table_arrays(want)
    assert (vg == vw).all()
    for k in cw:
        np.testing.assert_allclose(cg[k], cw[k], rtol=1e-6)


def test_optimizer_report_carries_signatures(store):
    from repro.core import CrossOptimizer
    plan = parse_query(SQL, store)
    _, report = CrossOptimizer(store).optimize(plan)
    assert report.input_signature == plan_signature(plan)
    assert report.plan_signature is not None
    assert report.referenced_models == ("los_pi",)


# ---------------------------------------------------------------------------
# Chunked (morsel) execution
# ---------------------------------------------------------------------------

def test_chunked_bit_exact_with_ragged_tail(store):
    whole = PredictionService(store)
    chunked = PredictionService(store, chunk_rows=128)   # 600 -> 4 + tail 88
    o1, o2 = whole.run(SQL), chunked.run(SQL)
    assert chunked.stats.chunks_executed == 5
    c1, v1 = _table_arrays(o1)
    c2, v2 = _table_arrays(o2)
    assert (v1 == v2).all()
    for k in c1:
        assert (c1[k] == c2[k]).all(), f"column {k} diverged under chunking"


def test_chunked_single_plan_compile(store):
    before = codegen.compile_stats["plans_compiled"]
    service = PredictionService(store, chunk_rows=100)
    service.run(SQL)
    service.run(SQL)
    assert codegen.compile_stats["plans_compiled"] == before + 1


def test_join_query_falls_back_to_whole_table(store):
    """A join whose build side scans the probe table too has no morsel
    table (a morsel would replace both sides), so it runs whole."""
    plan = Plan()
    probe = plan.add(Node("scan", Category.RA, [], {"table": "patient_info"},
                          "table"))
    build = plan.add(Node("scan", Category.RA, [], {"table": "patient_info"},
                          "table"))
    plan.output = plan.add(Node("join", Category.RA, [probe, build],
                                {"on": "pid"}, "table"))
    assert _morsel_table(plan, []) is None
    service = PredictionService(store, chunk_rows=64)
    compiled = service.compile(plan)
    assert compiled.morsel_table is None and compiled.chunk_table is None
    out = service.run(plan)
    assert service.stats.chunks_executed == 0
    assert np.asarray(out.valid).all()


def test_lookup_join_runs_in_morsels_bitwise(store):
    """A lookup join keeps its probe side's rows, so it runs in morsels of
    the probe table with the build table whole, and matches the
    whole-table answer bitwise."""
    # hematocrit keeps the join alive through join-elimination
    sql = ("SELECT pid, hematocrit FROM patient_info JOIN blood_tests ON pid "
           "WHERE age > 30")
    service = PredictionService(store, chunk_rows=64)
    compiled = service.compile(sql)
    assert compiled.chunk_table is None      # join is not row-local
    assert compiled.morsel_table == "patient_info"
    out = service.run(sql)
    assert service.stats.chunks_executed == -(-N_ROWS // 64)
    assert np.asarray(out.valid).any()
    want = PredictionService(store).run(sql)
    c1, v1 = _table_arrays(out)
    c2, v2 = _table_arrays(want)
    assert (v1 == v2).all()
    for k in c2:
        assert (c1[k] == c2[k]).all(), k


MORSEL = 64


def _store_of(n_rows):
    store = ModelStore()
    for n, t in hospital_tables(n_rows, seed=7).items():
        store.register_table(n, t)
    pi = store.get_table("patient_info")
    data = {c: np.asarray(pi.column(c)) for c in pi.names}
    store.register_model("los_pi", _pipeline(data))
    return store


@pytest.mark.parametrize("tail,result_cache,jit", [
    (0, False, True),
    (1, False, True),
    (MORSEL - 1, False, True),
    (1, True, True),                 # the capture branch assembles twice
    (MORSEL - 1, False, False),      # eager: the same helpers, unjitted
])
def test_morsel_slicer_and_assembler(tail, result_cache, jit,
                                     assert_tables_equal):
    """Eight whole morsels and a ``tail``-row one: the chunked answer is
    the whole-table answer bitwise, the tail's pad rows are zero with
    valid False, and a second query traces, compiles and builds nothing
    new, neither a plan nor a slicer or assembler executable."""
    n_rows = 8 * MORSEL + tail
    store = _store_of(n_rows)
    chunked = PredictionService(store, jit=jit, chunk_rows=MORSEL,
                                enable_result_cache=result_cache)
    whole = PredictionService(store, jit=jit,
                              enable_result_cache=result_cache)
    out = chunked.run(SQL)
    n_morsels = 8 + (tail > 0)
    assert chunked.stats.chunks_executed == n_morsels
    # a slicer and a program call a morsel, then one assembler call for
    # the answer and one for the captured subtree
    assert chunked.stats.launches == 2 * n_morsels + 1 + result_cache
    assert (chunked.compile(SQL).capture is not None) == result_cache
    if result_cache:
        assert chunked.stats.result_puts == 1
    assert_tables_equal(out, whole.run(SQL))

    plans = codegen.compile_stats["plans_compiled"]
    traces = chunked.stats.jit_traces
    sizes = (_morsel_jit._cache_size(), _assemble_jit._cache_size())
    again = chunked.run(SQL)
    assert codegen.compile_stats["plans_compiled"] == plans
    assert chunked.stats.jit_traces == traces
    assert (_morsel_jit._cache_size(), _assemble_jit._cache_size()) == sizes
    assert chunked.stats.chunks_executed == 2 * n_morsels
    assert_tables_equal(again, out)

    if tail:
        pi = store.get_table("patient_info")
        cut = _morsel_jit if jit else _morsel
        last = cut(pi, 8 * MORSEL, size=MORSEL, rows=tail)
        assert last.capacity == MORSEL
        valid = np.asarray(last.valid)
        assert valid[:tail].tolist() == np.asarray(pi.valid)[-tail:].tolist()
        assert not valid[tail:].any()
        for k, v in last.columns.items():
            v = np.asarray(v)
            assert (v[:tail] == np.asarray(pi.columns[k])[-tail:]).all(), k
            assert (v[tail:] == 0).all(), k
    chunked.close()
    whole.close()


# ---------------------------------------------------------------------------
# Micro-batch admission
# ---------------------------------------------------------------------------

def test_coalesced_requests_single_execution(store):
    pi = store.get_table("patient_info")
    service = PredictionService(store)
    parts = [(0, 100), (100, 350), (350, 600)]
    tickets = [service.submit(SQL, {"patient_info": _sub_table(pi, lo, hi)})
               for lo, hi in parts]
    assert service.flush() == 3
    assert service.stats.batch_executions == 1
    assert service.stats.coalesced_requests == 2

    reference = PredictionService(store)
    for ticket, (lo, hi) in zip(tickets, parts):
        got = ticket.result()
        want = reference.run(SQL, {"patient_info": _sub_table(pi, lo, hi)})
        cg, vg = _table_arrays(got)
        cw, vw = _table_arrays(want)
        assert (vg == vw).all()
        for k in cw:
            assert (cg[k] == cw[k]).all()


def test_identical_catalog_requests_share_one_execution(store):
    service = PredictionService(store)
    t1 = service.submit(SQL)
    t2 = service.submit(SQL)
    t3 = service.submit(SQL)
    assert service.flush() == 3
    assert service.stats.batch_executions == 1
    assert service.stats.coalesced_requests == 2
    v1 = np.asarray(t1.result().valid)
    assert (v1 == np.asarray(t3.result().valid)).all()
    assert t2.done


@pytest.mark.timeout_guard(300)
def test_concurrent_run_threads(store):
    pi = store.get_table("patient_info")
    service = PredictionService(store)
    service.run(SQL)                         # warm the cache
    results = {}
    errors = []

    def worker(i, lo, hi):
        try:
            results[i] = service.run(
                SQL, {"patient_info": _sub_table(pi, lo, hi)})
        except Exception as e:               # pragma: no cover
            errors.append(e)

    spans = [(0, 200), (200, 400), (400, 600), (0, 600)]
    threads = [threading.Thread(target=worker, args=(i, lo, hi))
               for i, (lo, hi) in enumerate(spans)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 4
    reference = PredictionService(store)
    for i, (lo, hi) in enumerate(spans):
        want = reference.run(SQL, {"patient_info": _sub_table(pi, lo, hi)})
        cg, vg = _table_arrays(results[i])
        cw, vw = _table_arrays(want)
        assert (vg == vw).all()
        for k in cw:
            assert (cg[k] == cw[k]).all()


def test_failed_request_reports_error(store):
    service = PredictionService(store)
    ticket = service.submit("SELECT pid FROM no_such_table")
    service.flush()
    with pytest.raises(KeyError):
        ticket.result()


def test_ticket_result_timeout_raises(store):
    """Regression: an unserved ticket must raise TimeoutError on expiry,
    never silently return None (indistinguishable from a null result)."""
    service = PredictionService(store)
    ticket = service.submit(SQL)          # queued, deliberately not flushed
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        ticket.result(timeout=0.05)
    assert time.perf_counter() - t0 < 5.0
    assert not ticket.done
    service.flush()                       # same ticket still serveable after
    out = ticket.result(timeout=30.0)
    assert np.asarray(out.valid).any()


@pytest.mark.timeout_guard(300)
def test_concurrent_submit_flush_stress(store):
    """N threads submitting and flushing against one service: no deadlock,
    every ticket resolves, and the stats ledger balances —
    hits + misses == compile-cache lookups == executions issued, and
    executions + coalesced == requests served."""
    service = PredictionService(store)
    queries = [
        SQL,
        "SELECT pid, age, PREDICT(MODEL='los_pi') AS los "
        "FROM patient_info WHERE age > 45",
        "SELECT pid, PREDICT(MODEL='los_pi') AS los FROM patient_info",
    ]
    n_threads, per_thread = 8, 6
    before_compiles = codegen.compile_stats["plans_compiled"]
    results, errors = {}, []
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        try:
            barrier.wait(timeout=30)
            for i in range(per_thread):
                ticket = service.submit(queries[(tid + i) % len(queries)])
                service.flush()
                results[(tid, i)] = ticket.result(timeout=60.0)
        except Exception as e:            # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "worker deadlocked"
    assert not errors
    assert len(results) == n_threads * per_thread
    for out in results.values():
        assert np.asarray(out.valid).any()

    s = service.stats
    # every group serve performs exactly one cache lookup and one execution
    assert s.cache_hits + s.cache_misses == s.batch_executions
    assert s.batch_executions + s.coalesced_requests \
        == n_threads * per_thread
    # every plan compile is accounted for: one per miss, plus any splice
    # upgrades / rematerializations (none expected for disjoint prefixes)
    assert codegen.compile_stats["plans_compiled"] - before_compiles \
        == s.cache_misses + s.splice_upgrades + s.rematerializations
    assert s.rematerializations == 0
