"""The paper's Fig 2a flight-delay schema, generated from a seed.

A copy of the generator the program ships (``flight_features``), kept here
so that the data a cell scores cannot change when the program does.  One
table, ``flights(origin, dest, carrier, dow, dep_hour, distance, taxi_out,
delayed)``: airports and carriers are categorical codes that one-hot into
wide, sparse features.  Traffic is regional: airports belong to regions
(contiguous code ranges), most flights stay in their region, and carriers
are region-dominant.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

Columns = Dict[str, np.ndarray]


def generate(n: int, seed: int, n_airports: int, n_carriers: int,
             n_days: int, n_regions: int) -> Dict[str, Columns]:
    rng = np.random.default_rng(seed)
    per_region = n_airports // n_regions
    region = rng.integers(0, n_regions, n)
    origin = (region * per_region
              + rng.integers(0, per_region, n)).astype(np.int32)
    same = rng.random(n) < 0.85
    dest_region = np.where(same, region, rng.integers(0, n_regions, n))
    dest = (dest_region * per_region
            + rng.integers(0, per_region, n)).astype(np.int32)
    carriers_per_region = max(n_carriers // n_regions, 1)
    regional_carrier = rng.random(n) < 0.8
    carrier = np.where(
        regional_carrier,
        region * carriers_per_region
        + rng.integers(0, carriers_per_region, n),
        rng.integers(0, n_carriers, n)).astype(np.int32)
    dow = rng.integers(0, n_days, n).astype(np.int32)
    dep_hour = rng.integers(0, 24, n).astype(np.int32)
    distance = rng.uniform(100, 3000, n).astype(np.float32)
    taxi_out = rng.normal(15, 5, n).astype(np.float32)

    # A few airports and carriers are chronically late; evening departures
    # and long taxi-outs add risk.  Most one-hot features are irrelevant,
    # so an L1 model comes out sparse (the paper's Fig 2a setting).
    airport_effect = np.zeros(n_airports)
    airport_effect[: n_airports // 8] = 1.5
    carrier_effect = np.zeros(n_carriers)
    carrier_effect[:2] = 1.0
    logit = (-2.0
             + airport_effect[origin] + 0.5 * airport_effect[dest]
             + carrier_effect[carrier]
             + 0.08 * np.maximum(dep_hour - 15, 0)
             + 0.05 * np.maximum(taxi_out - 20, 0))
    delayed = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    return {"flights": {"origin": origin, "dest": dest, "carrier": carrier,
                        "dow": dow, "dep_hour": dep_hour,
                        "distance": distance, "taxi_out": taxi_out,
                        "delayed": delayed}}


def joined(tables: Dict[str, Columns]) -> Columns:
    """The rows the Fig 2a query scores: the flights table itself."""
    return tables["flights"]
