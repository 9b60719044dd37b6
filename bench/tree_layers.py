#!/usr/bin/env python3
"""``bench/layers.py`` with the tree layer's padding beside it.

    python3 bench/tree_layers.py --workload flights_gbt.batch --seed 7 --seconds 51

Runs one traced run of the cell through ``layers.traced_run`` and prints
its last line with, under ``layers``, the window's tree-layer counters
(``ServiceStats.tree_slots`` and ``tree_slots_padded``: each executed
plan's tree slots per row, fitted and as the padded ensemble computes
them, times its rows):

- ``tree_pad_share``: padded slots over fitted slots, 1 where nothing is
  padded;
- ``tree_slots_per_row``: fitted slots per row the window's queries
  covered.

A program without these counters, or a window that ran no ``tree_gemm``,
prints neither.  Not a metric of ``BENCHMARK.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, layers, loads  # noqa: E402

COUNTERS = ("tree_slots", "tree_slots_padded")


def tree_numbers(counts: dict, rows: int) -> dict:
    """The numbers printed from the window's counter deltas and the rows
    its queries covered."""
    real, padded = counts.get("tree_slots"), counts.get("tree_slots_padded")
    if not real or padded is None:
        return {}
    return {"tree_pad_share": padded / real,
            "tree_slots_per_row": real / rows if rows else None}


def traced_run(workload: str, seed: int, seconds: float, **kw) -> dict:
    """``layers.traced_run`` with the service's tree counters read over
    the window; the harness is wrapped, not changed."""
    seen: dict = {}
    service, closed_loop = harness.service, loads.closed_loop

    def keep_service(*a, **k):
        seen["svc"] = service(*a, **k)
        return seen["svc"]

    def count_window(*a, **k):
        stats = seen["svc"].stats
        before = {c: getattr(stats, c, None) for c in COUNTERS}
        records = closed_loop(*a, **k)
        seen["counts"] = {c: getattr(stats, c) - b
                          for c, b in before.items() if b is not None}
        return records

    harness.service, loads.closed_loop = keep_service, count_window
    try:
        out = layers.traced_run(workload, seed, seconds, **kw)
    finally:
        harness.service, loads.closed_loop = service, closed_loop
    lay = out["layers"]
    cfg = harness.load_cell(workload).config
    if kw.get("scale") is not None:
        cfg = dict(cfg, rows=max(64, int(cfg["rows"] * kw["scale"])))
    rows = lay.get("queries", 0) * cfg["rows"]
    lay.update(tree_numbers(seen.get("counts", {}), rows))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = traced_run(args.workload, args.seed, args.seconds)
    except harness.NoChip as err:
        print(f"tree_layers: {err}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
