"""Small MLP model (the paper's Fig 3 uses an MLP pipeline)."""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# On the TPU an f32 matmul at default precision rounds its operands to bf16,
# which moves scores off the f32 reference; model dots run at HIGHEST.
_EXACT = jax.lax.Precision.HIGHEST

__all__ = ["MLP"]


class MLP:
    kind = "mlp"

    def __init__(self, hidden: Sequence[int] = (64, 32), n_outputs: int = 2,
                 task: str = "classification", lr: float = 1e-2,
                 steps: int = 300, seed: int = 0):
        self.hidden = list(hidden)
        self.n_outputs = n_outputs
        self.task = task
        self.lr = lr
        self.steps = steps
        self.seed = seed
        self.params: Optional[List] = None
        self.feature_names: Optional[List[str]] = None

    def _init(self, d_in: int):
        key = jax.random.PRNGKey(self.seed)
        dims = [d_in] + self.hidden + [self.n_outputs]
        params = []
        for i in range(len(dims) - 1):
            key, sub = jax.random.split(key)
            w = jax.random.normal(sub, (dims[i], dims[i + 1]),
                                  jnp.float32) * np.sqrt(2.0 / dims[i])
            params.append({"w": w, "b": jnp.zeros((dims[i + 1],), jnp.float32)})
        return params

    @staticmethod
    def apply(params, x):
        h = x
        for i, layer in enumerate(params):
            h = jnp.dot(h, layer["w"], precision=_EXACT) + layer["b"]
            if i < len(params) - 1:
                h = jax.nn.relu(h)
        return h

    def fit(self, x: np.ndarray, y: np.ndarray,
            feature_names: Optional[Sequence[str]] = None) -> "MLP":
        x = jnp.asarray(x, jnp.float32)
        if self.task == "classification":
            y = jnp.asarray(y, jnp.int32)

            def loss(params):
                logits = self.apply(params, x)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(logp[jnp.arange(x.shape[0]), y])
        else:
            y = jnp.asarray(y, jnp.float32)

            def loss(params):
                pred = self.apply(params, x)[:, 0]
                return jnp.mean((pred - y) ** 2)

        params = self._init(x.shape[1])
        grad_fn = jax.jit(jax.grad(loss))
        for _ in range(self.steps):
            grads = grad_fn(params)
            params = jax.tree_util.tree_map(
                lambda p, g: p - self.lr * g, params, grads)
        self.params = params
        self.feature_names = list(feature_names) if feature_names else None
        return self

    def predict_scores(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.apply(self.params, jnp.asarray(x, jnp.float32))

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        scores = self.predict_scores(x)
        if self.task == "classification":
            return jnp.argmax(scores, axis=-1)
        return scores[:, 0]

    def first_layer_weights(self) -> np.ndarray:
        return np.asarray(self.params[0]["w"])

    def restrict_features(self, keep: np.ndarray) -> "MLP":
        clone = MLP(self.hidden, self.n_outputs, self.task, self.lr,
                    self.steps, self.seed)
        params = [dict(p) for p in self.params]
        params[0] = {"w": self.params[0]["w"][jnp.asarray(keep)],
                     "b": self.params[0]["b"]}
        clone.params = params
        if self.feature_names:
            clone.feature_names = [self.feature_names[i] for i in keep]
        return clone
