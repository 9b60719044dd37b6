"""Pure-jnp oracle for the tree-GEMM kernel (same math as
repro.ml.hummingbird.predict_ensemble_gemm, summed not averaged)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_gemm_ref(x, a, b, c, d, e) -> jnp.ndarray:
    """x [N,F]; a [T,F,I]; b [T,I]; c [T,I,L]; d [T,L]; e [T,L,O]
    -> sum over trees of leaf payouts [N, O]."""
    exact = jax.lax.Precision.HIGHEST
    t = (jnp.einsum("nf,tfi->tni", x, a, precision=exact)
         <= b[:, None, :]).astype(jnp.float32)
    s = jnp.einsum("tni,til->tnl", t, c)
    match = (s == d[:, None, :]).astype(jnp.float32)
    out = jnp.einsum("tnl,tlo->no", match, e, precision=exact)
    return out
