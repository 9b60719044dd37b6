"""The paper's Fig 1 hospital length-of-stay schema, generated from a seed.

A copy of the generator the program ships (``hospital_tables``), kept here
so that the data a cell scores cannot change when the program does.
Columns are host NumPy arrays; the harness hands them to the program.

patient_info(pid, age, gender, pregnant, rcount, length_of_stay),
blood_tests(pid, hematocrit, neutrophils, bp) and
prenatal_tests(pid, gestation, fetal_hr), joined on ``pid``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

Columns = Dict[str, np.ndarray]


def generate(n: int, seed: int) -> Dict[str, Columns]:
    """The three tables, ``n`` rows each, with pids ``0 .. n-1``."""
    rng = np.random.default_rng(seed)
    pid = np.arange(n, dtype=np.int32)
    age = rng.integers(18, 90, n).astype(np.int32)
    gender = rng.integers(0, 2, n).astype(np.int32)          # 1 = female
    pregnant = ((gender == 1) & (age < 50)
                & (rng.random(n) < 0.3)).astype(np.int32)
    rcount = rng.poisson(1.2, n).astype(np.int32)
    hematocrit = rng.normal(42, 5, n).astype(np.float32)
    neutrophils = rng.normal(60, 10, n).astype(np.float32)
    bp = rng.normal(120, 18, n).astype(np.float32)
    gestation = np.where(pregnant == 1, rng.integers(8, 40, n), 0).astype(
        np.int32)
    fetal_hr = np.where(pregnant == 1, rng.normal(140, 12, n), 0).astype(
        np.float32)
    los = (2.0
           + 0.06 * np.maximum(age - 35, 0)
           + 1.5 * rcount
           + 0.04 * np.maximum(bp - 140, 0)
           + np.where(pregnant == 1, 1.0 + 0.05 * gestation, 0.0)
           + 0.03 * np.maximum(55 - hematocrit, 0)
           + rng.normal(0, 0.8, n))
    length_of_stay = np.maximum(los, 0.5).astype(np.float32)
    return {
        "patient_info": {"pid": pid, "age": age, "gender": gender,
                         "pregnant": pregnant, "rcount": rcount,
                         "length_of_stay": length_of_stay},
        "blood_tests": {"pid": pid, "hematocrit": hematocrit,
                        "neutrophils": neutrophils, "bp": bp},
        "prenatal_tests": {"pid": pid, "gestation": gestation,
                           "fetal_hr": fetal_hr},
    }


def joined(tables: Dict[str, Columns]) -> Columns:
    """patient_info joined N:1 with blood_tests and then prenatal_tests by
    pid, in patient_info's row order: the flat rows the Fig 1 query
    scores."""
    pi = tables["patient_info"]
    out = dict(pi)
    for name in ("blood_tests", "prenatal_tests"):
        other = tables[name]
        pos = np.full(int(other["pid"].max()) + 1, -1, np.int64)
        pos[other["pid"]] = np.arange(len(other["pid"]))
        match = pos[pi["pid"]]
        if (match < 0).any():
            raise ValueError(f"a patient has no {name} row")
        out.update({c: v[match] for c, v in other.items() if c != "pid"})
    return out
