#!/usr/bin/env python3
"""Readings that the limits of ``bench/check.py`` are set from.

    python3 bench/calibrate.py --workload los_rf.batch --seeds 1,2,3 --seconds 3

For each seed, in one process so that set-up compiles once: a run of the
cell with a short window (the program's numbers, the lower readings), and
the control, the plain reference computed with features, thresholds or
weights held in bfloat16 and put in the program's place, compared against
the float32 reference on the same rows (the upper readings).  The
benchmark's own runs never run this.  Prints one JSON line per seed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def control_numbers(cell, seed: int) -> dict:
    """The check's numbers with the bfloat16 control as the answer, over
    the rows a run of ``seed`` compares."""
    from bench import check, harness

    cfg = cell.config
    dep, mk = harness.deployment(cfg), harness.model_kind(cfg)
    arrays = harness.fitted(cfg)
    rows = dep.joined(dep.generate(cfg["rows"], seed % (1 << 63),
                                   **cfg["schema_params"]))
    cand = check.where_mask(rows, cfg.get("where", {}))
    want = {c: v[cand] for c, v in rows.items()}
    ref = np.full(len(cand), np.nan)
    ref[cand] = mk.reference(arrays, cfg["model"], want)
    ctl = np.full(len(cand), np.nan)
    ctl[cand] = mk.control(arrays, cfg["model"], want)
    answer = {cfg["key"]: rows[cfg["key"]], cfg["output"]: ctl,
              "__valid__": check.output_mask(cand, ctl,
                                             cfg.get("output_filter"))}
    return check.numbers([(cand, rows[cfg["key"]], ref, answer)],
                         cfg["key"], cfg["output"], cfg["limits"], missing=0,
                         output_filter=cfg.get("output_filter"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--no-program", action="store_true",
                    help="read the control only")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"seed": seed}
        if not args.no_program:
            try:
                r = harness.run(args.workload, seed, args.seconds, False,
                                time.perf_counter())
            except harness.NoChip as err:
                print(f"calibrate: {err}", file=sys.stderr)
                return 3
            line["program"] = {k: v["value"] for k, v in r["checks"].items()}
            line["correct"] = r["correct"]
        else:
            harness.start_jax(cell.chips)
        line["control"] = {k: v["value"] for k, v in
                           control_numbers(cell, seed).items()}
        print("calibration: " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
