"""Data featurizers (MLD operators): one-hot, scaler, imputer, bucketizer.

Featurizers are first-class Raven IR operators: the static analyzer maps
sklearn-style preprocessing onto these, the optimizer reasons about them
(predicate-based pruning constant-folds one-hot groups; NN translation turns
them into LA ops), and codegen executes them inside the fused XLA plan.

Each featurizer knows (a) how to fit on host data, (b) how to apply in jnp,
(c) its feature mapping: input column -> output feature slice (needed by
projection pushdown to trace zero weights back to source columns).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["OneHotEncoder", "StandardScaler", "Imputer", "Bucketizer",
           "FeatureMapping"]


@dataclasses.dataclass
class FeatureMapping:
    """Output feature i comes from input column ``source[i]``; for one-hot
    features ``category[i]`` holds the matching category code, else -1."""

    names: List[str]
    source: List[str]
    category: List[int]

    @property
    def n_features(self) -> int:
        return len(self.names)


class OneHotEncoder:
    kind = "one_hot"

    def __init__(self, columns: Sequence[str]):
        self.columns = list(columns)
        self.categories: Dict[str, np.ndarray] = {}

    def fit(self, data: Dict[str, np.ndarray]) -> "OneHotEncoder":
        for c in self.columns:
            self.categories[c] = np.unique(np.asarray(data[c]))
        return self

    def mapping(self) -> FeatureMapping:
        names, source, cat = [], [], []
        for c in self.columns:
            for v in self.categories[c]:
                names.append(f"{c}={v}")
                source.append(c)
                cat.append(int(v))
        return FeatureMapping(names, source, cat)

    def transform(self, columns: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        blocks = []
        for c in self.columns:
            cats = jnp.asarray(self.categories[c])
            codes = jnp.asarray(columns[c])
            blocks.append((codes[:, None] == cats[None, :]).astype(jnp.float32))
        return jnp.concatenate(blocks, axis=1)

    def restrict(self, keep: Sequence[int]) -> Optional["OneHotEncoder"]:
        """Keep only the given local output-feature indices (projection
        pushdown).  Returns None if nothing survives."""
        keep = set(keep)
        new_cols: List[str] = []
        new_cats: Dict[str, np.ndarray] = {}
        offset = 0
        for c in self.columns:
            cats = self.categories[c]
            kept = [v for i, v in enumerate(cats) if offset + i in keep]
            offset += len(cats)
            if kept:
                new_cols.append(c)
                new_cats[c] = np.asarray(kept)
        if not new_cols:
            return None
        enc = OneHotEncoder(new_cols)
        enc.categories = new_cats
        return enc


class StandardScaler:
    kind = "scaler"

    def __init__(self, columns: Sequence[str]):
        self.columns = list(columns)
        self.mean: Optional[np.ndarray] = None
        self.std: Optional[np.ndarray] = None

    def fit(self, data: Dict[str, np.ndarray]) -> "StandardScaler":
        mat = np.stack([np.asarray(data[c], np.float64) for c in self.columns],
                       axis=1)
        self.mean = mat.mean(0).astype(np.float32)
        self.std = (mat.std(0) + 1e-8).astype(np.float32)
        return self

    def mapping(self) -> FeatureMapping:
        return FeatureMapping(list(self.columns), list(self.columns),
                              [-1] * len(self.columns))

    def transform(self, columns: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        mat = jnp.stack([jnp.asarray(columns[c], jnp.float32)
                         for c in self.columns], axis=1)
        # Multiply by the float32 reciprocal rather than divide: XLA turns a
        # divide by a constant into exactly this multiply inside a jitted
        # plan but not in an eager call, so a divide would give features
        # that differ by an ulp between fit and serve and from a host
        # reference.  The multiply is the same float32 operation everywhere.
        inv = np.float32(1.0) / self.std
        return (mat - jnp.asarray(self.mean)) * jnp.asarray(inv)

    # LA form (for NN translation): x*a + b
    def affine(self) -> Tuple[np.ndarray, np.ndarray]:
        return (1.0 / self.std).astype(np.float32), \
            (-self.mean / self.std).astype(np.float32)

    def restrict(self, keep: Sequence[int]) -> Optional["StandardScaler"]:
        keep = sorted(set(keep))
        if not keep:
            return None
        sc = StandardScaler([self.columns[i] for i in keep])
        sc.mean = self.mean[keep]
        sc.std = self.std[keep]
        return sc


class Imputer:
    kind = "imputer"

    def __init__(self, columns: Sequence[str], strategy: str = "mean"):
        self.columns = list(columns)
        self.strategy = strategy
        self.fill: Optional[np.ndarray] = None

    def fit(self, data: Dict[str, np.ndarray]) -> "Imputer":
        fills = []
        for c in self.columns:
            arr = np.asarray(data[c], np.float64)
            ok = arr[~np.isnan(arr)]
            fills.append(np.mean(ok) if self.strategy == "mean"
                         else np.median(ok))
        self.fill = np.asarray(fills, np.float32)
        return self

    def mapping(self) -> FeatureMapping:
        return FeatureMapping(list(self.columns), list(self.columns),
                              [-1] * len(self.columns))

    def transform(self, columns: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        mat = jnp.stack([jnp.asarray(columns[c], jnp.float32)
                         for c in self.columns], axis=1)
        return jnp.where(jnp.isnan(mat), jnp.asarray(self.fill), mat)

    def restrict(self, keep: Sequence[int]) -> Optional["Imputer"]:
        keep = sorted(set(keep))
        if not keep:
            return None
        im = Imputer([self.columns[i] for i in keep], self.strategy)
        im.fill = self.fill[keep]
        return im


class Bucketizer:
    kind = "bucketizer"

    def __init__(self, column: str, boundaries: Sequence[float]):
        self.column = column
        self.boundaries = np.asarray(sorted(boundaries), np.float32)

    def fit(self, data) -> "Bucketizer":
        return self

    def mapping(self) -> FeatureMapping:
        ids = (self._kept if self._kept is not None
               else np.arange(len(self.boundaries) + 1))
        return FeatureMapping([f"{self.column}_bucket{int(i)}" for i in ids],
                              [self.column] * len(ids),
                              [int(i) for i in ids])

    def transform(self, columns: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        x = jnp.asarray(columns[self.column], jnp.float32)
        bucket = jnp.searchsorted(jnp.asarray(self.boundaries), x)
        ids = jnp.asarray(self._kept if self._kept is not None
                          else np.arange(len(self.boundaries) + 1))
        return (bucket[:, None] == ids[None, :]).astype(jnp.float32)

    _kept: Optional[np.ndarray] = None

    def restrict(self, keep: Sequence[int]) -> Optional["Bucketizer"]:
        keep = sorted(set(keep))
        if not keep:
            return None
        base = self._kept if self._kept is not None \
            else np.arange(len(self.boundaries) + 1)
        b = Bucketizer(self.column, self.boundaries.tolist())
        b._kept = np.asarray([base[i] for i in keep])
        return b
