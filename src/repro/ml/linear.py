"""Linear / logistic regression with L1 (proximal) training, in JAX.

The paper's model-projection-pushdown experiments (Fig 2a) rely on
L1-regularized logistic regression whose zero weights let features be
projected out early.  We train with proximal gradient descent (ISTA) so the
solution is *exactly* sparse, then expose ``zero_weight_features()`` to the
optimizer rule.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# On the TPU an f32 matmul at default precision rounds its operands to bf16.
# A v5e computed this model's matrix-vector dots exactly at the default as
# well (one output column, no bf16 pass), so HIGHEST changes no answer
# today; it keeps the f32 answer should the dot ever go through the MXU.
_EXACT = jax.lax.Precision.HIGHEST

__all__ = ["LinearRegression", "LogisticRegression"]


def _soft_threshold(w: jnp.ndarray, lam: float) -> jnp.ndarray:
    return jnp.sign(w) * jnp.maximum(jnp.abs(w) - lam, 0.0)


class _LinearBase:
    def __init__(self, l1: float = 0.0, lr: float = 0.1, steps: int = 400,
                 seed: int = 0):
        self.l1 = l1
        self.lr = lr
        self.steps = steps
        self.seed = seed
        self.weights: Optional[np.ndarray] = None   # [d]
        self.bias: float = 0.0
        self.feature_names: Optional[List[str]] = None

    def _loss_grad(self, w, b, x, y):
        raise NotImplementedError

    def fit(self, x: np.ndarray, y: np.ndarray,
            feature_names: Optional[Sequence[str]] = None):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        # Standardize for conditioning; fold scales back into weights after.
        mu = jnp.mean(x, axis=0)
        sd = jnp.std(x, axis=0) + 1e-6
        xs = (x - mu) / sd
        w = jnp.zeros((x.shape[1],), jnp.float32)
        b = jnp.asarray(0.0, jnp.float32)
        grad_fn = jax.jit(jax.grad(self._objective, argnums=(0, 1)))
        lam = self.l1 * self.lr
        for _ in range(self.steps):
            gw, gb = grad_fn(w, b, xs, y)
            w = _soft_threshold(w - self.lr * gw, lam)
            b = b - self.lr * gb
        w = np.asarray(w) / np.asarray(sd)
        b = float(b - np.dot(w, np.asarray(mu)))
        self.weights = w.astype(np.float32)
        self.bias = b
        self.feature_names = list(feature_names) if feature_names else None
        return self

    def zero_weight_features(self, tol: float = 1e-8) -> np.ndarray:
        return np.nonzero(np.abs(self.weights) <= tol)[0]

    def nonzero_weight_features(self, tol: float = 1e-8) -> np.ndarray:
        return np.nonzero(np.abs(self.weights) > tol)[0]

    def sparsity(self) -> float:
        return float((np.abs(self.weights) <= 1e-8).mean())

    def restrict_features(self, keep: np.ndarray):
        """Return a copy using only ``keep`` features (projection pushdown)."""
        clone = self.__class__(self.l1, self.lr, self.steps, self.seed)
        clone.weights = self.weights[keep]
        clone.bias = self.bias
        if self.feature_names:
            clone.feature_names = [self.feature_names[i] for i in keep]
        return clone

    def decision_function(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.dot(jnp.asarray(x, jnp.float32), jnp.asarray(self.weights),
                       precision=_EXACT) + self.bias


class LinearRegression(_LinearBase):
    kind = "linear_regression"

    def _objective(self, w, b, x, y):
        pred = jnp.dot(x, w, precision=_EXACT) + b
        return jnp.mean((pred - y) ** 2)

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.decision_function(x)


class LogisticRegression(_LinearBase):
    kind = "logistic_regression"

    def _objective(self, w, b, x, y):
        logits = jnp.dot(x, w, precision=_EXACT) + b
        return jnp.mean(jnp.maximum(logits, 0) - logits * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    def predict_proba(self, x: jnp.ndarray) -> jnp.ndarray:
        return jax.nn.sigmoid(self.decision_function(x))

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        return (self.decision_function(x) > 0).astype(jnp.int32)
