"""A run of each cell, past the look for a chip, at a size a test can hold:
a sound program comes out ``correct``, and a program broken underneath the
timed path does not.  The faults planted are those the cells can have:

- an answer altered where it is produced;
- half of the rows left out of the answer.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_faults.py
"""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SCALE = {"los_rf.batch": 0.005, "flights_lr.batch": 0.002}


def run_cell(workload: str, seed: int = 5) -> dict:
    return harness.run(workload, seed, 1.0, False, time.perf_counter(),
                       require_tpu=False, scale=SCALE[workload],
                       peaks_of="TPU v5 lite")


def alter_answers(monkeypatch):
    """Every served score moved by 0.1% where the model produces it: the
    model's output column, or the logistic function of a model the
    optimizer lowered to linear algebra."""
    import jax

    from repro.core import codegen

    produce, sigmoid = codegen._scores_to_output, jax.nn.sigmoid

    def altered(scores, task, proba):
        out = produce(scores, task, proba)
        return out + 1e-3 * (1.0 + abs(out))

    monkeypatch.setattr(codegen, "_scores_to_output", altered)
    monkeypatch.setattr(jax.nn, "sigmoid",
                        lambda x: sigmoid(x) * (1.0 + 1e-3))


def drop_half_the_rows(monkeypatch):
    """Every executed plan's answer keeps only its first half of rows."""
    import jax.numpy as jnp

    from repro.relational.table import Table
    from repro.serve import prediction_service as ps

    execute = ps.PredictionService._execute

    def halved(self, *args, **kwargs):
        out = execute(self, *args, **kwargs)
        n = out.valid.shape[0]
        return Table(dict(out.columns),
                     out.valid & (jnp.arange(n) < n // 2), out.schema)

    monkeypatch.setattr(ps.PredictionService, "_execute", halved)


@pytest.mark.parametrize("workload", sorted(SCALE))
def test_sound_run_is_correct(workload):
    r = run_cell(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("workload", sorted(SCALE))
def test_altered_answer_is_not_correct(workload, monkeypatch):
    alter_answers(monkeypatch)
    r = run_cell(workload)
    assert not r["correct"]
    assert r["checks"]["score_gap"]["value"] > \
        r["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("workload", sorted(SCALE))
def test_half_the_rows_left_out_is_not_correct(workload, monkeypatch):
    drop_half_the_rows(monkeypatch)
    r = run_cell(workload)
    assert not r["correct"]
    assert r["checks"]["rows_wrong"]["value"] > 0
