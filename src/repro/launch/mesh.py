"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods x 256 as
(pod=2, data=16, model=16) — the ``pod`` axis composes with ``data`` for
FSDP/batch sharding, so the same rules scale to N pods (DCN traffic stays on
the pod axis: gradient/weight-gather collectives only).

Defined as functions (never module-level) so importing this module touches no
jax device state; the dry-run sets XLA_FLAGS for 512 host devices *before*
any jax import.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_data_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _make_mesh((data, model), ("data", "model"))


def make_data_mesh(devices: int = 0):
    """1-D pure data-parallel mesh for partition-parallel scans
    (``serve/sharded.py``).  ``devices=0`` takes every local device;
    otherwise clamped to what exists (simulated host devices included —
    the sharded-scan benchmark sets ``xla_force_host_platform_device_count``
    before importing jax, exactly like the dry-run)."""
    n = len(jax.devices())
    d = n if devices in (0, None) else max(1, min(int(devices), n))
    return _make_mesh((d,), ("data",))
