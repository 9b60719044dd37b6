"""Jit wrapper for the tree-GEMM kernel, consuming EnsembleGemm artifacts."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .tree_gemm import tree_gemm_pallas

__all__ = ["tree_gemm"]


@functools.partial(jax.jit, static_argnames=("average", "n_trees",
                                             "interpret"))
def _run(x, a, b, c, d, e, n_trees: int, average: bool, interpret: bool):
    x = jnp.asarray(x, jnp.float32)
    # The kernel gates via X @ A, and NaN/±inf would poison every gate column
    # through 0 * NaN = NaN.  Mapping NaN/+inf -> fmax and -inf -> -fmax keeps
    # the gate booleans identical to traversal's per-node comparisons: every
    # real threshold is a finite data midpoint, so fmax <= t is False (like
    # NaN <= t and inf <= t) and -fmax <= t is True (like -inf <= t).
    fmax = float(jnp.finfo(jnp.float32).max)
    x = jnp.nan_to_num(x, nan=fmax, posinf=fmax, neginf=-fmax)
    out = tree_gemm_pallas(x, a, b, c, d, e, interpret=interpret)
    return out / n_trees if average else out


def tree_gemm(ensemble, x: jnp.ndarray) -> jnp.ndarray:
    """Score an ``repro.ml.hummingbird.EnsembleGemm`` with the Pallas kernel.

    Compiled by Mosaic on every backend but the CPU, where the kernel body
    runs in interpret mode (same results; what the tests exercise).
    """
    interpret = jax.default_backend() == "cpu"
    return _run(x, jnp.asarray(ensemble.a), jnp.asarray(ensemble.b),
                jnp.asarray(ensemble.c), jnp.asarray(ensemble.d),
                jnp.asarray(ensemble.e), n_trees=ensemble.n_trees,
                average=ensemble.average, interpret=interpret)
