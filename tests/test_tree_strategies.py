"""Tree-inference strategy equivalence and crossover sanity (Fig 2d).

The plan path may serve a tree ensemble three ways — native traversal,
the gather-gated dense GEMM lowering, or the Pallas MXU kernel — chosen
by a *measured* cost-model crossover.  The strategies must be freely
interchangeable, which here means **bitwise identical** predictions:

- gather gating ``x[:, feat[t]] <= b[t]`` reproduces traversal's exact
  per-node comparisons (NaN compares False -> right child, same as
  traversal);
- path-count sums are exact small integers (products of {-1, 0, +1}),
  so the ``S == D`` match is reduction-order independent;
- per-tree accumulation is sequential (``fori_loop``), matching
  ``predict_scores``'s left-to-right sum, and padding contributes exact
  zeros.

The property test drives random forests, feature dtypes and NaN/±inf
features through all three strategies; the crossover test checks the
estimator never picks a strategy that measures much slower than its
runner-up on the calibration workload.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.cost_model import (calibrated_tree_costs,
                                   choose_tree_strategy,
                                   tree_strategy_costs)
from repro.core.model_store import ModelStore
from repro.kernels.tree_gemm import ops as tg_ops
from repro.ml import RandomForest, ensemble_to_gemm, predict_ensemble_gemm
from repro.ml.hummingbird import ensemble_to_gemm_mxu

try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("tree_strategies", max_examples=12,
                              deadline=None)
    settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "tree_strategies"))
    HAVE_HYPOTHESIS = True
except ImportError:                     # property test degrades to the
    HAVE_HYPOTHESIS = False             # deterministic grid below


def _forest_and_x(seed, n_trees, depth, n_features, n_rows, dtype_kind,
                  nan_frac):
    rng = np.random.default_rng(seed)
    if dtype_kind == "int":
        xf = rng.integers(-8, 8, size=(256, n_features)).astype(np.float32)
    else:
        xf = rng.normal(size=(256, n_features)).astype(np.float32)
    y = (xf[:, 0] > xf[:, -1]).astype(np.int32)
    rf = RandomForest(n_trees=n_trees, max_depth=depth, min_leaf=2,
                      seed=seed).fit(xf, y)
    if dtype_kind == "int":
        x = rng.integers(-10, 10, size=(n_rows, n_features)) \
            .astype(np.float32)
    else:
        x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    if nan_frac:
        mask = rng.random(x.shape) < nan_frac
        x[mask] = np.nan
        x[rng.random(x.shape) < nan_frac / 2] = np.inf
        x[rng.random(x.shape) < nan_frac / 2] = -np.inf
    return rf, x


def _assert_bitwise(seed, n_trees, depth, n_features, dtype_kind, nan_frac):
    rf, x = _forest_and_x(seed, n_trees, depth, n_features, n_rows=48,
                          dtype_kind=dtype_kind, nan_frac=nan_frac)
    xj = jnp.asarray(x)
    # All strategies jitted, as the plan path runs them: XLA rewrites the
    # final divide-by-n_trees into multiply-by-reciprocal, so an eager
    # reference would differ by 1 ulp whenever n_trees isn't a power of 2.
    want = np.asarray(jax.jit(rf.predict_scores)(xj))

    ens8 = ensemble_to_gemm(rf.trees, pad_to=8)
    ens128 = ensemble_to_gemm_mxu(rf.trees)
    dense = np.asarray(jax.jit(
        lambda v: predict_ensemble_gemm(ens8, v))(xj))
    mxu = np.asarray(jax.jit(
        lambda v: predict_ensemble_gemm(ens128, v))(xj))
    pallas = np.asarray(tg_ops.tree_gemm(ens128, xj))

    np.testing.assert_array_equal(want, dense)
    np.testing.assert_array_equal(want, mxu)
    np.testing.assert_array_equal(want, pallas)


_GRID = [  # (seed, n_trees, depth, n_features, dtype_kind, nan_frac)
    (0, 1, 2, 2, "float", 0.0),
    (1, 6, 6, 9, "float", 0.0),
    (2, 4, 5, 5, "float", 0.05),
    (3, 3, 4, 3, "float", 0.25),
    (4, 5, 6, 7, "int", 0.0),
    (5, 2, 3, 4, "int", 0.05),
    (6, 6, 4, 8, "int", 0.25),
    (7, 1, 6, 6, "float", 0.25),
]


@pytest.mark.parametrize("case", _GRID, ids=lambda c: f"seed{c[0]}")
def test_traversal_gemm_pallas_bitwise(case):
    """traversal == dense GEMM (any pad) == Pallas(interpret), bitwise,
    including NaN/±inf features (deterministic grid)."""
    _assert_bitwise(*case)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 2**31 - 1),
           n_trees=st.integers(1, 6),
           depth=st.integers(2, 6),
           n_features=st.integers(2, 9),
           dtype_kind=st.sampled_from(["float", "int"]),
           nan_frac=st.sampled_from([0.0, 0.05, 0.25]))
    def test_traversal_gemm_pallas_bitwise_fuzz(seed, n_trees, depth,
                                                n_features, dtype_kind,
                                                nan_frac):
        """Same property, hypothesis-driven when the library is present."""
        _assert_bitwise(seed, n_trees, depth, n_features, dtype_kind,
                        nan_frac)


def test_crossover_not_worse_than_runner_up():
    """On the calibration workload itself, the chosen strategy's *measured*
    time is never more than 2x the measured runner-up — i.e. the estimator
    can mis-rank close calls but not pick a blowout loser."""
    cal = calibrated_tree_costs()
    rng = np.random.default_rng(3)
    xf = rng.normal(size=(512, 8)).astype(np.float32)
    y = (xf[:, 0] + xf[:, 1] > 0).astype(np.int32)
    rf = RandomForest(n_trees=8, max_depth=6).fit(xf, y)
    ens = ensemble_to_gemm(rf.trees, pad_to=8)
    n = 2048
    x = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)

    import time

    def best_of(fn):
        jax.block_until_ready(fn(x))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x))
            best = min(best, time.perf_counter() - t0)
        return best

    fns = {
        "traversal": jax.jit(rf.predict_scores),
        "gemm": jax.jit(lambda v: predict_ensemble_gemm(ens, v)),
    }
    chosen, costs = choose_tree_strategy(rf, n, 8)
    if chosen == "pallas":              # only chosen on a real TPU
        fns["pallas"] = lambda v: tg_ops.tree_gemm(ens, v)
    # a single noisy sample (GC pause, CI neighbor) shouldn't fail the
    # build: re-measure up to 3 times and accept any clean round
    for attempt in range(3):
        measured = {k: best_of(fn) for k, fn in fns.items()}
        runner_up = min((k for k in measured if k != chosen),
                        key=measured.get)
        if measured[chosen] <= 2.0 * measured[runner_up]:
            break
    else:
        raise AssertionError((chosen, measured, costs))
    # and the estimator's own ranking agrees with itself: chosen is either
    # the outright cheapest, or traversal retained because no translated
    # strategy beat it by more than the calibration-noise margin
    from repro.core.cost_model import _STRATEGY_MARGIN
    if chosen == "traversal":
        assert min(costs.values()) > _STRATEGY_MARGIN * costs["traversal"]
    else:
        assert costs[chosen] == min(costs.values())
        assert costs[chosen] <= _STRATEGY_MARGIN * costs["traversal"]


def test_strategy_costs_monotone_in_rows():
    """Estimated cost is monotone non-decreasing in n_rows for every
    strategy, and traversal wins tiny batches (its per-call setup is the
    smallest term)."""
    cal = calibrated_tree_costs()
    rng = np.random.default_rng(5)
    xf = rng.normal(size=(256, 8)).astype(np.float32)
    rf = RandomForest(n_trees=8, max_depth=6).fit(
        xf, (xf[:, 0] > 0).astype(np.int32))
    prev = None
    for n in (1, 32, 1024, 32768, 1 << 20):
        costs = tree_strategy_costs(rf, n, 8, cal)
        if prev is not None:
            for k in ("traversal", "gemm"):
                assert costs[k] >= prev[k]
        prev = costs


def test_calibration_cached_in_model_store():
    """calibrated_tree_costs measures once and caches in the catalog, so a
    fresh optimizer run against the same ModelStore never re-times."""
    store = ModelStore()
    cal1 = calibrated_tree_costs(catalog=store)
    assert store.get_calibration(("tree_strategy", cal1.backend)) is cal1
    cal2 = calibrated_tree_costs(catalog=store)
    assert cal2 is cal1
    assert cal1.trav_step > 0 and cal1.gemm_flop > 0
    if cal1.backend != "tpu":
        assert cal1.pallas_flop is None
