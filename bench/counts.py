"""Bytes a query has to move at the least, for the HBM roofline share.

Each input column the query reads is read once, with its table's validity
mask, and each output column is written once with the output's mask.  The
operations per row are the model kind's (``bench/models/<kind>.py``,
``flops_per_row``).
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def query_bytes(config: dict, tables: Dict[str, Dict[str, np.ndarray]]
                ) -> int:
    total = 0
    for name, cols in config["reads"].items():
        t = tables[name]
        n = len(t[cols[0]])
        total += sum(int(t[c].itemsize) for c in cols) * n + n
    out = tables[config["driving_table"]]
    n = len(out[config["key"]])
    total += (4 * len(config["writes"]) + 1) * n
    return total
