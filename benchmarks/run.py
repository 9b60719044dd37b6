"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows (collected in common.ROWS).
``--json PATH`` additionally writes a machine-readable export of every
row — throughput, speedups and compile counts parsed out of the derived
column — which the ``bench-trajectory`` CI job uploads as an artifact and
checks against ``benchmarks/baseline.json`` (see
``benchmarks.check_trajectory``).  The roofline table (§Roofline) is
separate: ``python -m benchmarks.roofline`` reads the dry-run artifacts.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import traceback

_NUM_RE = re.compile(r"^-?\d+(\.\d+)?x?$")


def parse_derived(derived: str) -> dict:
    """``key=value`` tokens from a derived column; numeric values (incl.
    the ``4.71x`` speedup spelling) become floats, the rest stay strings
    (e.g. ``pruned=48/64``)."""
    out = {}
    for token in derived.split():
        if "=" not in token:
            continue
        key, _, value = token.partition("=")
        if _NUM_RE.match(value):
            out[key] = float(value.rstrip("x"))
        else:
            out[key] = value
    return out


def write_json(path: str, quick: bool, failures: int) -> None:
    from .common import METRICS, ROWS
    payload = {
        "schema": 2,
        "quick": quick,
        "failures": failures,
        "benchmarks": {
            name: {"us_per_call": us, "derived": parse_derived(derived),
                   "raw_derived": derived}
            for name, us, derived in ROWS
        },
        # registry snapshots from benchmarks that opted in via
        # common.record_metrics — the trajectory artifacts double as a
        # metrics history (scripts/plot_trajectory.py folds them)
        "metrics": METRICS,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"wrote {len(payload['benchmarks'])} benchmark rows to {path}",
          file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller row counts (CI-sized)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write a machine-readable row export "
                         "(bench-trajectory CI artifact)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (continuous_batching, fig2a_projection_pushdown,
                   fig2b_clustering, fig2c_inlining, fig2d_nn_translation,
                   fig2d_tree_gemm, fig3_integration, lossy_pushdown,
                   multi_tenant_saturation, plan_cache, pruning,
                   sharded_join_agg, sharded_scan, shuffle_join,
                   streaming_ingest, subplan_reuse, telemetry_overhead)

    n = 30_000 if args.quick else 200_000
    print("name,us_per_call,derived")
    jobs = [
        # on the CPU the sharded benchmarks re-exec themselves with 8
        # simulated devices; run them FIRST, while this parent process is
        # still small — their child processes assert wall-clock speedups, and
        # a parent bloated by the earlier benchmarks' jax allocations
        # steals enough of a small CI machine to flake those asserts
        ("sharded_scan", lambda: sharded_scan.run(n_rows=n)),
        ("sharded_join_agg", lambda: sharded_join_agg.run(n_rows=n)),
        ("shuffle_join", lambda: shuffle_join.run(n_rows=n)),
        ("pruning", lambda: pruning.run(n_rows=n)),
        ("fig2a", lambda: fig2a_projection_pushdown.run(n_rows=n)),
        ("fig2b", lambda: fig2b_clustering.run(n_rows=n)),
        ("fig2c", lambda: fig2c_inlining.run(
            n_rows=min(n, 300_000) if not args.quick else 30_000)),
        ("fig2d", lambda: fig2d_nn_translation.run()),
        ("fig2d_tree_gemm", lambda: fig2d_tree_gemm.run(
            sizes=(1_000, 10_000) if args.quick
            else (1_000, 10_000, 50_000))),
        ("fig3", lambda: fig3_integration.run(
            sizes=(1_000, 10_000) if args.quick
            else (1_000, 10_000, 100_000), per_tuple=True)),
        # beyond-paper: the paper's §4.1 open question
        ("lossy_pushdown", lambda: lossy_pushdown.run(
            n_rows=min(n, 100_000))),
        ("plan_cache", lambda: plan_cache.run(
            n_rows=10_000 if args.quick else 50_000)),
        ("subplan_reuse", lambda: subplan_reuse.run(
            n_rows=20_000 if args.quick else 100_000)),
        ("continuous_batching", lambda: continuous_batching.run(
            n_rows=2_000 if args.quick else 4_000,
            n_requests=32 if args.quick else 64)),
        ("multi_tenant", lambda: multi_tenant_saturation.run(
            n_rows=2_000 if args.quick else 4_000,
            reqs_per_tenant=16 if args.quick else 32)),
        ("telemetry_overhead", lambda: telemetry_overhead.run(
            n_rows=5_000 if args.quick else 20_000,
            iters=20 if args.quick else 40)),
        ("streaming_ingest", lambda: streaming_ingest.run(
            n_rows=20_000 if args.quick else 100_000,
            append_rows=1_000 if args.quick else 2_000,
            cycles=3 if args.quick else 5)),
    ]
    failures = 0
    for name, job in jobs:
        try:
            job()
        except Exception:
            failures += 1
            print(f"{name},BENCH FAILED", file=sys.stderr)
            traceback.print_exc()
    if args.json is not None:
        write_json(args.json, args.quick, failures)
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
