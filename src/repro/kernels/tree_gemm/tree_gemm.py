"""Pallas TPU kernel: Hummingbird-style tree-ensemble GEMM inference.

The paper's NN translation (§4.2) compiles trees to GEMMs so a tensor runtime
executes them.  On TPU the natural shape is an MXU pipeline over
(row-block x tree): for each grid cell we keep one tree's matrices resident
in VMEM and stream a row-block of the feature matrix through

    T = (X A <= B);  S = T C;  leaf = argmax(S == D);  out += onehot(leaf) E

All matmul dims are padded to 128 at translation time
(``repro.ml.hummingbird.ensemble_to_gemm(pad_to=128)``), so every dot hits
the MXU with aligned tiles.  The ensemble sum accumulates in the output block
across the tree axis of the grid (output revisiting), which Pallas expresses
by giving the out BlockSpec an index map that ignores the tree index.

Grid: (n_row_blocks, n_trees).  VMEM per cell (defaults, F<=512, I=L=128,
O<=128): X block 128xF (256 KB) + A Fx128 + C 128x128 + E 128xO + scratch
(~0.5 MB total) — comfortably inside the ~16 MB v5e VMEM budget even with
double buffering.

The per-tree vectors ``b [T, I]`` and ``d [T, L]`` enter as ``[T, 1, I]``
and ``[T, 1, L]``: Mosaic requires a block's last two dims to be multiples
of (8, 128) or the full array dims, and a ``(1, I)`` block of a ``[T, I]``
array is neither, while ``(1, 1, I)`` of ``[T, 1, I]`` is.

Precision: at the default precision the MXU rounds f32 operands to bf16.
``X A`` must reproduce each feature exactly (a rounded feature can flip a
gate and pick another leaf) and ``match E`` must return the exact leaf
value, so both run at ``HIGHEST``.  ``gates C`` multiplies {0, 1} by
{-1, 0, +1}, which bf16 holds exactly, so it stays at the default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tree_gemm_kernel", "tree_gemm_pallas"]


def tree_gemm_kernel(x_ref, a_ref, b_ref, c_ref, d_ref, e_ref, o_ref):
    """One (row-block, tree) grid cell.

    x [BR, F] • a [F, I] -> gate vs b [1, I]; @ c [I, L] -> match vs
    d [1, L]; select e [L, O] row; accumulate into o [BR, O].
    """
    t_idx = pl.program_id(1)
    exact = jax.lax.Precision.HIGHEST

    x = x_ref[...]
    a = a_ref[0]                                            # [F, I]
    xa = jax.lax.dot(x, a, precision=exact,
                     preferred_element_type=jnp.float32)
    gates = (xa <= b_ref[0]).astype(jnp.float32)            # [BR, I]
    s = jax.lax.dot(gates, c_ref[0],
                    preferred_element_type=jnp.float32)     # [BR, L]
    match = (s == d_ref[0]).astype(jnp.float32)             # [BR, L]
    # onehot(argmax(match)) == match when exactly one leaf matches (padded
    # leaves carry D=+inf so they never match): the select is one more GEMM.
    out = jax.lax.dot(match, e_ref[0], precision=exact,
                      preferred_element_type=jnp.float32)   # [BR, O]

    @pl.when(t_idx == 0)
    def _init():
        o_ref[...] = out

    @pl.when(t_idx > 0)
    def _acc():
        o_ref[...] += out


def tree_gemm_pallas(x, a, b, c, d, e, *, block_rows: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """x [N, F]; a [T, F, I]; b [T, I]; c [T, I, L]; d [T, L]; e [T, L, O]
    -> summed ensemble scores [N, O]."""
    n, f = x.shape
    t, _, i = a.shape
    l = c.shape[2]
    o = e.shape[2]
    n_pad = ((n + block_rows - 1) // block_rows) * block_rows
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    grid = (n_pad // block_rows, t)

    out = pl.pallas_call(
        tree_gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda r, ti: (r, 0)),
            pl.BlockSpec((1, f, i), lambda r, ti: (ti, 0, 0)),
            pl.BlockSpec((1, 1, i), lambda r, ti: (ti, 0, 0)),
            pl.BlockSpec((1, i, l), lambda r, ti: (ti, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda r, ti: (ti, 0, 0)),
            pl.BlockSpec((1, l, o), lambda r, ti: (ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, o), lambda r, ti: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, o), jnp.float32),
        interpret=interpret,
    )(x, a, b[:, None, :], c, d[:, None, :], e)
    return out[:n]
