"""CPU tests of the boosted-ensemble model kind (``bench/models/
gradient_boosting.py``), its cell ``flights_gbt.batch`` and
``bench/tree_layers.py``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_gradient_boosting.py
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, loads, tree_layers  # noqa: E402
from bench.models import gradient_boosting as gb  # noqa: E402

CELL = "flights_gbt.batch"
SCALE = 0.002


def two_tree_ensemble():
    """Tree 0: root splits feature 0, its left child feature 1 (I = 2,
    L = 3).  Tree 1: a stump on feature 2 (I = 1, L = 2)."""
    return {
        "baseline": np.array([-0.5], np.float32),
        "offsets": np.array([0, 5, 8]),
        "depth": np.array([2, 1], np.int32),
        "feature": np.array([0, 1, -1, -1, -1, 2, -1, -1], np.int32),
        "threshold": np.array([0.5, 1.5, 0, 0, 0, 2.5, 0, 0], np.float32),
        "left": np.array([1, 3, -1, -1, -1, 1, -1, -1], np.int32),
        "right": np.array([2, 4, -1, -1, -1, 2, -1, -1], np.int32),
        "value": np.array([0, 0, 1.0, -2.0, 0.25, 0, 0.5, -0.75],
                          np.float32),
    }


TOY = {"features": ["a", "b", "c"]}


def test_flops_by_hand():
    # per tree 2 F I + 2 I L + 2 L with F = 3: Hummingbird's unpadded count
    want = (2 * 3 * 2 + 2 * 2 * 3 + 2 * 3) + (2 * 3 * 1 + 2 * 1 * 2 + 2 * 2)
    assert gb.flops_per_row(two_tree_ensemble(), TOY) == want == 44


def test_reference_by_hand():
    rows = {"a": np.array([0.0, 1.0, 1.0]), "b": np.array([1.0, 2.0, 0.0]),
            "c": np.array([3.0, 0.0, 3.0])}
    # row 0: a <= .5, b <= 1.5 -> -2; c > 2.5 -> -0.75
    # row 1: a > .5 -> 1; c <= 2.5 -> 0.5
    # row 2: a > .5 -> 1; c > 2.5 -> -0.75
    margin = np.array([-0.5 - 2.0 - 0.75, -0.5 + 1.0 + 0.5,
                       -0.5 + 1.0 - 0.75])
    got = gb.reference(two_tree_ensemble(), TOY, rows)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, 1 / (1 + np.exp(-margin)), rtol=1e-6)


def test_float32_thresholds_cut_where_the_float64_ones_do():
    """``x <= t32`` is ``x <= t64`` for every float32 ``x``: checked on the
    float32 neighbours of each threshold, midpoints of float32 values as
    the library's bins make them and random float64 values."""
    rng = np.random.default_rng(3)
    a = rng.normal(size=2000).astype(np.float32).astype(np.float64)
    b = np.nextafter(a.astype(np.float32), np.float32(np.inf)
                     ).astype(np.float64)
    t64 = np.concatenate([(a + b) / 2, rng.normal(size=2000) * 1e3,
                          np.array([0.0, 1.0, 2.5, -7.25])])
    t32 = gb.float32_below(t64)
    assert t32.dtype == np.float32
    near = t64.astype(np.float32)
    x = np.concatenate([near, np.nextafter(near, np.float32(np.inf)),
                        np.nextafter(near, np.float32(-np.inf))])
    tt64, tt32 = np.tile(t64, 3), np.tile(t32, 3)
    np.testing.assert_array_equal(x <= tt32, x.astype(np.float64) <= tt64)
    # a plain cast rounds some of these the wrong way
    assert (near.astype(np.float64) > t64).any()


@pytest.fixture(scope="module")
def small_fit():
    """A 20-tree fit of the cell's settings on a sample of its rows."""
    cfg = harness.load_cell(CELL).config
    model = dict(cfg["model"], max_iter=20)
    dep = harness.deployment(cfg)
    rows = dep.joined(dep.generate(20000, model["fit_seed"],
                                   **cfg["schema_params"]))
    return cfg, model, gb.fit(model, rows)


def test_reference_matches_the_library(small_fit):
    from sklearn.ensemble import HistGradientBoostingClassifier

    cfg, model, arrays = small_fit
    dep = harness.deployment(cfg)
    fit_rows = dep.joined(dep.generate(20000, model["fit_seed"],
                                       **cfg["schema_params"]))
    x = np.stack([fit_rows[c].astype(np.float32)
                  for c in model["features"]], axis=1)
    clf = HistGradientBoostingClassifier(
        max_iter=20, max_depth=model["max_depth"],
        max_leaf_nodes=model["max_leaf_nodes"],
        learning_rate=model["learning_rate"], early_stopping=False,
        random_state=model["fit_seed"]).fit(x, fit_rows[model["label"]])
    rows = dep.joined(dep.generate(5000, 77, **cfg["schema_params"]))
    xs = np.stack([rows[c].astype(np.float32) for c in model["features"]],
                  axis=1)
    want = clf.predict_proba(xs)[:, 1]
    got = gb.reference(arrays, model, rows)
    assert np.abs(got - want).max() <= 1e-6
    assert gb.summary(arrays)["n_trees"] == 20


def test_pipeline_serves_the_reference(small_fit):
    """The program's pipeline built from the arrays, through ``sql()``."""
    from repro.core import ModelStore
    from repro.relational.table import Table
    from repro.serve import PredictionService

    cfg, model, arrays = small_fit
    dep = harness.deployment(cfg)
    tables = dep.generate(3000, 11, **cfg["schema_params"])
    store = ModelStore()
    store.register_table("flights", Table.from_pydict(tables["flights"]))
    store.register_model(model["name"], gb.pipeline(model, arrays))
    svc = PredictionService(store, enable_result_cache=False)
    try:
        out = svc.sql(cfg["queries"]["batch"].replace("delay_gbt",
                                                      model["name"]))
        got = np.asarray(out.columns["p"])
    finally:
        svc.close()
    want = gb.reference(arrays, model, dep.joined(tables))
    assert np.abs(got - want).max() <= 1e-6


def test_control_fails_where_reference_passes():
    """As ``test_harness.py`` checks each older cell: the bfloat16 control
    in the program's place fails the check, the float32 reference
    passes."""
    cfg = dict(harness.load_cell(CELL).config, rows=20000)
    dep, mk = harness.deployment(cfg), harness.model_kind(cfg)
    arrays = harness.fitted(cfg)
    rows = dep.joined(dep.generate(cfg["rows"], 9, **cfg["schema_params"]))
    cand = check.where_mask(rows, cfg.get("where", {}))
    ref = mk.reference(arrays, cfg["model"], rows)
    for answer, ok in ((ref, True),
                       (mk.control(arrays, cfg["model"], rows), False)):
        served = {cfg["key"]: rows[cfg["key"]], cfg["output"]: answer,
                  "__valid__": cand}
        n = check.numbers([(cand, rows[cfg["key"]], ref, served)],
                          cfg["key"], cfg["output"], cfg["limits"],
                          missing=0)
        assert check.correct(n) is ok, n


def run_cell(seed: int = 5) -> dict:
    return harness.run(CELL, seed, 1.0, False, time.perf_counter(),
                       require_tpu=False, scale=SCALE,
                       peaks_of="TPU v5 lite")


def test_sound_run_is_correct_and_an_altered_one_is_not(monkeypatch):
    r = run_cell()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert "rows_per_s.flights_lr" in r["metrics"]

    from repro.core import codegen
    produce = codegen._scores_to_output

    def altered(scores, task, proba):
        out = produce(scores, task, proba)
        return out + 1e-3 * (1.0 + abs(out))

    monkeypatch.setattr(codegen, "_scores_to_output", altered)
    r = run_cell()
    assert not r["correct"]
    assert r["checks"]["score_gap"]["value"] > \
        r["checks"]["score_gap"]["limit"]


def test_tree_numbers_by_hand():
    assert tree_layers.tree_numbers({}, 10) == {}
    assert tree_layers.tree_numbers({"tree_slots": 0,
                                     "tree_slots_padded": 0}, 10) == {}
    got = tree_layers.tree_numbers({"tree_slots": 200,
                                    "tree_slots_padded": 614}, 10)
    assert got == {"tree_pad_share": 3.07, "tree_slots_per_row": 20.0}


def test_tree_layers_traced_run_with_the_dense_strategy(monkeypatch):
    """One traced run on the CPU with the dense strategy forced: the pad
    share comes back above 1, and the harness is left as it was."""
    from repro.core.optimizer import OptimizerConfig

    service = harness.service

    def dense(store, config, traffic, chunk_rows):
        svc = service(store, config, traffic, chunk_rows)
        svc.optimizer_config = OptimizerConfig(tree_strategy="gemm")
        return svc

    monkeypatch.setattr(harness, "service", dense)
    before = (loads.closed_loop, harness.service)
    out = tree_layers.traced_run(CELL, 5, 1.0, require_tpu=False,
                                 scale=SCALE, peaks_of="TPU v5 lite")
    assert (loads.closed_loop, harness.service) == before
    assert out["result"]["correct"]
    lay = out["layers"]
    assert lay["tree_pad_share"] > 1.0
    # pruning by the table's statistics leaves at most the fitted trees
    arrays = harness.fitted(harness.load_cell(CELL).config)
    off, left = arrays["offsets"], arrays["left"]
    fitted = 0
    for i in range(len(off) - 1):
        leaves = int((left[off[i]:off[i + 1]] < 0).sum())
        fitted += (leaves - 1) + (leaves - 1) * leaves + leaves
    assert 0 < lay["tree_slots_per_row"] <= fitted
