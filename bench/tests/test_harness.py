"""CPU tests of the benchmark's own parts: the trace reduction, the counts,
the traffic generator, discovery by name, the contract's shape of
``BENCHMARK.json``, the control, and the refusal of a host with no TPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, counts, harness, loads  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.models import logistic_regression, random_forest  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


# -- trace reduction --------------------------------------------------------------

def synthetic_trace():
    """Two devices over a 10 s window; device 0 busy 1-3 and 2-4 (union
    1-4) and 6-7, device 1 busy 0-10; host spans: the window, one query
    0-5 and a fetch 5-8."""
    return tr.Trace(
        ops={0: [(1.0, 3.0, "fusion.1"), (2.0, 4.0, "dot.2"),
                 (6.0, 7.0, "fusion.1"), (11.0, 12.0, "fusion.1")],
             1: [(0.0, 10.0, "while.3")]},
        spans=[(0.0, 10.0, "bench.window"), (0.0, 5.0, "bench.query"),
               (5.0, 8.0, "bench.fetch")])


def test_union_and_gaps():
    busy = tr.union([(1, 3), (2, 4), (6, 7), (11, 12)], 0, 10)
    assert busy == [(1, 4), (6, 7)]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (4, 6), (7, 10)]


def test_summary_busy_idle_and_top_ops():
    s = tr.summarize(synthetic_trace(), [0, 1])
    assert s.window == (0.0, 10.0)
    assert s.busy_s == {0: 4.0, 1: 10.0}
    assert s.idle_share == pytest.approx({0: 0.6, 1: 0.0})
    assert s.mean_idle_share == pytest.approx(0.3)
    assert s.mean_busy_s == pytest.approx(7.0)
    assert s.top_ops[0] == ("while.3", 10.0)
    assert dict(s.top_ops) == {"while.3": 10.0, "fusion.1": 3.0,
                               "dot.2": 2.0}


def test_idle_gaps_named_by_host_spans():
    s = tr.summarize(synthetic_trace(), [0])
    # gaps 0-1 and 4-5 under the query, 5-6 and 7-8 under the fetch,
    # 8-10 under no bench span
    assert dict(s.idle_by_host) == pytest.approx(
        {"query": 2.0, "fetch": 2.0, "none": 2.0})


def test_summary_needs_a_window():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(ops={0: []}, spans=[]), [0])


# -- counts -------------------------------------------------------------------------

def two_tree_forest():
    """Tree 0: root splits feature 0, its left child feature 1 (I = 2,
    L = 3).  Tree 1: a stump on feature 2 (I = 1, L = 2)."""
    return {
        "mean": np.zeros(3, np.float32), "std": np.ones(3, np.float32),
        "offsets": np.array([0, 5, 8]),
        "depth": np.array([2, 1], np.int32),
        "feature": np.array([0, 1, -1, -1, -1, 2, -1, -1], np.int32),
        "threshold": np.array([0.5, 1.5, 0, 0, 0, 2.5, 0, 0], np.float32),
        "left": np.array([1, 3, -1, -1, -1, 1, -1, -1], np.int32),
        "right": np.array([2, 4, -1, -1, -1, 2, -1, -1], np.int32),
        "value": np.array([0, 0, 10, 20, 30, 0, 1, 2], np.float32),
    }


FOREST_MODEL = {"features": ["a", "b", "c"]}


def test_forest_flops_by_hand():
    # per tree 2 F I + 2 I L + 2 L O with F = 3, O = 1
    want = (2 * 3 * 2 + 2 * 2 * 3 + 2 * 3) + (2 * 3 * 1 + 2 * 1 * 2 + 2 * 2)
    got = random_forest.flops_per_row(two_tree_forest(), FOREST_MODEL)
    assert got == want == 44


def test_forest_reference_walks_each_tree():
    rows = {"a": np.array([0.0, 1.0, 1.0]), "b": np.array([1.0, 2.0, 0.0]),
            "c": np.array([3.0, 0.0, 3.0])}
    # row 0: a <= .5, b <= 1.5 -> 20; c > 2.5 -> 2; mean 11
    # row 1: a > .5 -> 10; c <= 2.5 -> 1; mean 5.5
    # row 2: a > .5 -> 10; c > 2.5 -> 2; mean 6
    got = random_forest.reference(two_tree_forest(), FOREST_MODEL, rows)
    np.testing.assert_array_equal(got, np.float32([11.0, 5.5, 6.0]))


def one_hot_lr():
    """One-hot over two colours and three sizes, one scaled column."""
    return {"categories": np.array([0, 1, 5, 6, 7], np.int32),
            "category_offsets": np.array([0, 2, 5]),
            "mean": np.array([1.0], np.float32),
            "std": np.array([2.0], np.float32),
            "weights": np.array([0.5, 0.0, 0.0, -1.0, 2.0, 0.25],
                                np.float32),
            "bias": np.array([0.1], np.float32)}


LR_MODEL = {"one_hot": ["colour", "size"], "scaled": ["x"]}


def test_lr_flops_and_reference_by_hand():
    a = one_hot_lr()
    assert logistic_regression.flops_per_row(a, LR_MODEL) == 12.0
    rows = {"colour": np.array([0, 1]), "size": np.array([6, 7]),
            "x": np.array([3.0, 1.0], np.float32)}
    z = np.array([0.1 + 0.5 - 1.0 + 0.25, 0.1 + 0.0 + 2.0 + 0.0])
    np.testing.assert_allclose(
        logistic_regression.reference(a, LR_MODEL, rows),
        1 / (1 + np.exp(-z)), rtol=1e-7)


def test_query_bytes_by_hand():
    tables = {"t": {"k": np.zeros(10, np.int32), "v": np.zeros(10,
                                                               np.float32)},
              "u": {"k": np.zeros(10, np.int32)}}
    cfg = {"reads": {"t": ["k", "v"], "u": ["k"]}, "writes": ["k", "p"],
           "driving_table": "t", "key": "k"}
    # t: 2 x 4 B + mask, u: 4 B + mask, output: 2 x 4 B + mask
    assert counts.query_bytes(cfg, tables) == 10 * (9 + 5 + 9)


# -- traffic ------------------------------------------------------------------------

def test_closed_loop_window():
    calls = []
    recs = loads.closed_loop(lambda: calls.append(1), 0.05)
    assert len(recs) == len(calls) and recs[-1].done - recs[0].due >= 0.05


def test_deployments_are_seeded():
    from bench.deployments import flights, hospital
    a = hospital.generate(100, 5)
    b = hospital.generate(100, 5)
    for t in a:
        for c in a[t]:
            np.testing.assert_array_equal(a[t][c], b[t][c])
    f = flights.generate(100, 5, n_airports=320, n_carriers=14, n_days=7,
                         n_regions=5)["flights"]
    assert f["origin"].max() < 320 and f["carrier"].max() < 14


# -- discovery by name -----------------------------------------------------------------

@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_cell_parts_found_by_name(workload):
    cell = harness.load_cell(workload)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    harness.deployment(cell.config)
    kind = harness.model_kind(cell.config)
    for fn in ("fit", "pipeline", "reference", "control", "flops_per_row",
               "working_set_bytes_per_row", "summary"):
        assert callable(getattr(kind, fn))
    assert cell.config["queries"][cell.traffic["query"]]
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert {"setup_s"} <= {m["name"] for m in cell.end_to_end}


def test_unknown_device_kind_fails():
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


# -- the contract's shape of BENCHMARK.json ---------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for item in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
            + SPEC["per_layer"]:
        assert NAME.match(item["name"]), item["name"]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            mv = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
            assert "workloads" not in mv or w in mv["workloads"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert len(c["source"]) <= 200
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


# -- the check and its control ------------------------------------------------------------

def test_check_numbers_exact_and_gap():
    cand = np.array([True, False, True])
    keys = np.array([1, 2, 3])
    ref = np.array([1.0, np.nan, 3.0])
    good = {"k": keys, "s": np.array([1.0, 9.0, 3.0]), "__valid__": cand}

    def numbers(got, missing=0):
        return check.numbers([(cand, keys, ref, got)], "k", "s",
                             {"score_gap": 1e-5}, missing=missing)
    assert check.correct(numbers(good))
    n = numbers(dict(good, __valid__=np.array([True, True, True])))
    assert n["rows_wrong"]["value"] == 1 and not check.correct(n)
    n = numbers(dict(good, k=np.array([1, 2, 4])))
    assert n["rows_wrong"]["value"] == 1 and not check.correct(n)
    n = numbers(dict(good, s=np.array([1.0, 9.0, 3.01])))
    assert n["score_gap"]["value"] == pytest.approx(0.0025)
    assert not check.correct(n)
    assert not check.correct(numbers(good, missing=3))
    n = check.numbers([], "k", "s", {"score_gap": 1e-5}, missing=0)
    assert not check.correct(n)


def test_check_output_filter_and_its_threshold():
    """``PREDICT(...) > 7``: rows under the threshold are not in the
    answer; a row whose reference lies within the limit of it may fall on
    either side, and its score is still compared."""
    flt = {"op": ">", "value": 7}
    cand = np.array([True, True, True, False])
    keys = np.arange(4)
    ref = np.array([8.0, 6.0, 7.0 + 1e-6, np.nan])
    want = check.output_mask(cand, ref, flt)
    np.testing.assert_array_equal(want, [True, False, True, False])

    def numbers(valid, scores):
        got = {"k": keys, "s": np.asarray(scores), "__valid__":
               np.asarray(valid)}
        return check.numbers([(cand, keys, ref, got)], "k", "s",
                             {"score_gap": 1e-5}, missing=0,
                             output_filter=flt)
    assert check.correct(numbers(want, [8.0, 6.0, 7.0 + 1e-6, 0.0]))
    # the row at the threshold served just under it and left out: sound
    n = numbers([True, False, False, False], [8.0, 6.0, 7.0, 0.0])
    assert check.correct(n), n
    # the filter dropped, or a row well under the threshold kept
    n = numbers([True, True, True, False], [8.0, 6.0, 7.0, 0.0])
    assert n["rows_wrong"]["value"] == 1
    # a row well over the threshold left out
    n = numbers([False, False, True, False], [8.0, 6.0, 7.0, 0.0])
    assert n["rows_wrong"]["value"] == 1


@pytest.mark.parametrize("workload", ["los_rf.batch", "flights_lr.batch"])
def test_control_fails_where_reference_passes(workload):
    """The bfloat16 control in the program's place fails the check at a
    size a test can hold; the float32 reference itself passes."""
    cell = harness.load_cell(workload)
    cfg = dict(cell.config, rows=20000)
    dep, mk = harness.deployment(cfg), harness.model_kind(cfg)
    arrays = harness.fitted(cfg)
    flt = cfg.get("output_filter")
    rows = dep.joined(dep.generate(cfg["rows"], 9, **cfg["schema_params"]))
    cand = check.where_mask(rows, cfg.get("where", {}))
    want = {c: v[cand] for c, v in rows.items()}
    ref = np.full(len(cand), np.nan)
    ref[cand] = mk.reference(arrays, cfg["model"], want)
    for answer, ok in ((ref[cand], True),
                       (mk.control(arrays, cfg["model"], want), False)):
        got = np.full(len(cand), np.nan)
        got[cand] = answer
        served = {cfg["key"]: rows[cfg["key"]], cfg["output"]: got,
                  "__valid__": check.output_mask(cand, got, flt)}
        n = check.numbers([(cand, rows[cfg["key"]], ref, served)],
                          cfg["key"], cfg["output"], cfg["limits"],
                          missing=0, output_filter=flt)
        assert check.correct(n) is ok, n


# -- per-layer readers ---------------------------------------------------------------------

def reader_context(busy_s=2.0, chips=1):
    from types import SimpleNamespace
    return SimpleNamespace(
        trace=SimpleNamespace(mean_busy_s=busy_s, mean_idle_share=0.25),
        work=SimpleNamespace(seconds=10.0, queries=4, rows=4000,
                             scored_rows=400),
        chips=chips, peaks={"flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
        flops_per_row=50.0, bytes_per_query=10000, compile_s=3.5)


def test_readers_found_by_quantity():
    assert harness.metric_reader("device_idle_share.any_config")(
        reader_context()) == 25.0
    assert harness.metric_reader("compile_s")(reader_context()) == 3.5


def test_shares_of_peak_are_over_device_busy_time():
    # 400 rows x 50 FLOP over 2 busy seconds of a 1e6 FLOP/s chip: 1%;
    # 4 queries x 10 kB over 2 busy seconds of 1e5 B/s: 20%
    ctx = reader_context()
    assert harness.metric_reader("mfu.x")(ctx) == pytest.approx(1.0)
    assert harness.metric_reader("hbm_roofline.x")(ctx) == \
        pytest.approx(20.0)
    # on two chips, each busy 2 s: half of that
    ctx = reader_context(chips=2)
    assert harness.metric_reader("mfu.x")(ctx) == pytest.approx(0.5)
    assert harness.metric_reader("mfu.x")(reader_context(busy_s=0)) is None


# -- no TPU, no result ------------------------------------------------------------------

def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "los_rf.batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == "" or "{" not in p.stdout.splitlines()[-1]
    assert "TPU" in p.stderr
