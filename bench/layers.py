#!/usr/bin/env python3
"""Where a traced run's time goes, by the program's own spans and scopes.

    python3 bench/layers.py --workload flights_lr.batch --seed 7 --seconds 51

Runs one ``--trace 1`` run of the cell through ``bench/harness.py`` and
reduces the same profiler trace a second way, by what the program itself
names in it:

- host spans named ``repro.<name>`` (``jax.profiler.TraceAnnotation``,
  ``serve/telemetry.py``), kept per host thread;
- the ``repro.<layer>`` name scope of each device op (``jax.named_scope``,
  ``core/codegen.py``), read from the op's trace stats, or, where no stat
  carries it, from the optimized HLO that ``--hlo-dump`` has XLA write.

After the harness's ``label: value`` lines it prints, as the last line,
one JSON object: the harness's result under ``result``, and ``layers``:

- ``device_by_layer``: device seconds per scope in the window, each the
  union of that scope's op intervals (a ``while`` and its body count
  once), summed over the chips; ops with no scope under ``other``;
- ``program_s``: host seconds per span name in the window, the union
  across threads;
- ``idle_gaps``: the device's idle seconds named as ``bench/trace.py``
  names them, each name extended with the innermost program span open on
  each host thread (``query/morsel.launch``);
- per query: ``front_door_ms`` (``parse``, ``admit``, ``compile``),
  ``launch_ms`` (``morsel.slice``, ``morsel.launch``, ``assemble``,
  ``shard.prepare``, ``shard.split``), ``launches``
  (``ServiceStats.launches`` over the window), ``join_ms`` (device);
  ``model_step_mfu`` (the ``mfu`` count over ``model``-scoped busy time)
  and ``rows_per_s`` with the profiler on.

A program that emits no ``repro.*`` span or scope gives empty tables and
no per-query numbers, and the ``idle_gaps`` names of ``bench/trace.py``.
These are not metrics of ``BENCHMARK.json``: the harness does not call
this reduction.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tr  # noqa: E402

PREFIX = "repro."
SCOPE = re.compile(r"repro\.([a-z_]+)")
OTHER = "other"
FRONT_DOOR = ("parse", "admit", "compile")
LAUNCH = ("morsel.slice", "morsel.launch", "assemble", "shard.prepare",
          "shard.split")

Thread = Tuple[str, int]                       # host plane, line index
Op = Tuple[float, float, str, str]             # start, end, scope, name


@dataclasses.dataclass
class Layers:
    ops: Dict[int, List[Op]]                   # device id -> scoped ops
    spans: Dict[Thread, List[tr.Span]]         # program spans per thread
    bench: List[tr.Span]                       # the benchmark's own spans


def scope_of(texts: Sequence[str]) -> Optional[str]:
    """The innermost ``repro.<layer>`` scope named in any of ``texts``."""
    for text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1]
    return None


def instruction_key(text: str) -> Optional[str]:
    """``<name> <shape> <opcode>`` of an HLO instruction, from its text as
    a TPU trace names the op (``%fusion.59 = f32[704,32768]{...}
    fusion(...)``) or as the optimized HLO dump prints it: the two print
    operands differently, and this leaves them out."""
    m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)", text)
    if not m:
        return None
    name, rest = m.groups()
    depth, end = 0, None
    for i, ch in enumerate(rest):             # a tuple shape nests parens
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    if end is None:
        return None
    opcode = rest[end + 1:].split("(", 1)[0]
    return f"{name} {rest[:end]} {opcode}"


def hlo_scopes(dump_dir: str) -> Dict[str, str]:
    """Instruction key (``instruction_key``) -> scope, from the optimized
    HLO text that ``--xla_dump_to`` writes; a key that two programs give
    different scopes is left out."""
    out: Dict[str, str] = {}
    clash = set()
    for path in glob.glob(os.path.join(dump_dir,
                                       "*after_optimizations.txt")):
        with open(path) as fh:
            for line in fh:
                m = re.search(r'op_name="([^"]*)"', line)
                key = instruction_key(line) if m else None
                scope = scope_of([m.group(1)]) if m else None
                if key is None or scope is None:
                    continue
                if out.setdefault(key, scope) != scope:
                    clash.add(key)
    for key in clash:
        del out[key]
    return out


def read(log_dir: str, hlo: Optional[Dict[str, str]] = None) -> Layers:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[int, List[Op]] = {}
    spans: Dict[Thread, List[tr.Span]] = defaultdict(list)
    bench: List[tr.Span] = []
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            dev = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    texts = [e.name] + [v for v in stats.values()
                                        if isinstance(v, str)]
                    scope = scope_of(texts)
                    if scope is None and hlo:
                        scope = hlo.get(instruction_key(e.name) or "")
                    dev.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                scope or OTHER, e.name))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    span = (e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    if e.name.startswith(PREFIX):
                        spans[(plane.name, i)].append(
                            (span[0], span[1], e.name[len(PREFIX):]))
                    elif e.name.startswith("bench."):
                        bench.append(span)
    return Layers(ops=ops, spans=dict(spans), bench=bench)


def window(layers: Layers) -> tr.Interval:
    marks = [(s, e) for s, e, n in layers.bench if n == tr.WINDOW]
    if not marks:
        raise ValueError("the trace has no bench.window span")
    return marks[-1]


def device_by_layer(layers: Layers, devices: Sequence[int],
                    win: tr.Interval) -> Dict[str, float]:
    """Device seconds of each scope in the window: per chip the union of
    the scope's op intervals, summed over the chips."""
    out: Dict[str, float] = defaultdict(float)
    for d in devices:
        by: Dict[str, List[tr.Interval]] = defaultdict(list)
        for s, e, scope, _ in layers.ops.get(d, []):
            by[scope].append((s, e))
        for scope, ivs in by.items():
            out[scope] += sum(e - s for s, e in tr.union(ivs, *win))
    return dict(out)


def top_ops(layers: Layers, win: tr.Interval, top: int = 3
            ) -> Dict[str, List[Tuple[str, float]]]:
    """Each scope's ``top`` ops by device seconds in the window, summed
    over the chips, named by their HLO name (``while.13``)."""
    by: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for ops in layers.ops.values():
        for s, e, scope, name in ops:
            clipped = min(e, win[1]) - max(s, win[0])
            if clipped > 0:
                by[scope][name.lstrip("%").split(" ")[0]] += clipped
    return {scope: sorted(t.items(), key=lambda kv: -kv[1])[:top]
            for scope, t in by.items()}


def program_seconds(layers: Layers, win: tr.Interval) -> Dict[str, float]:
    """Host seconds of each span name in the window, the union of its
    spans across threads."""
    by: Dict[str, List[tr.Interval]] = defaultdict(list)
    for spans in layers.spans.values():
        for s, e, name in spans:
            by[name].append((s, e))
    return {name: sum(e - s for s, e in tr.union(ivs, *win))
            for name, ivs in by.items()}


def union_seconds(layers: Layers, names: Sequence[str],
                  win: tr.Interval) -> float:
    """Host seconds in which any of ``names`` was open, on any thread."""
    ivs = [(s, e) for spans in layers.spans.values()
           for s, e, n in spans if n in names]
    return sum(e - s for s, e in tr.union(ivs, *win))


def _segments(layers: Layers) -> List[Tuple[float, str]]:
    """(time, name from then on): the bench spans open (``+``-joined,
    ``window`` left out), then ``/`` and the innermost program span open
    on each host thread (``+``-joined), or ``none``."""
    edges = []
    for s, e, n in layers.bench:
        if n != tr.WINDOW and e > s:
            key = ("bench", n[len("bench."):])
            edges += [(s, 1, -(e - s), key), (e, 0, 0.0, key)]
    for thread, spans in layers.spans.items():
        for s, e, n in spans:
            if e > s:
                key = (thread, n, s)
                edges += [(s, 1, -(e - s), key), (e, 0, 0.0, key)]
    # at one instant closes go first, and the longer of two opens
    edges.sort(key=lambda edge: edge[:3])
    bench_open: Dict[str, int] = defaultdict(int)
    stacks: Dict[Thread, List[Tuple[str, float]]] = defaultdict(list)
    out: List[Tuple[float, str]] = []
    for t, opens, _, key in edges:
        if key[0] == "bench":
            bench_open[key[1]] += 1 if opens else -1
        elif opens:
            stacks[key[0]].append(key[1:])
        else:
            stack = stacks[key[0]]
            if key[1:] in stack:
                stack.remove(key[1:])
        names = sorted(n for n, c in bench_open.items() if c > 0)
        inner = sorted({stack[-1][0] for stack in stacks.values() if stack})
        name = "+".join(names) if names else "none"
        if inner:
            name += "/" + "+".join(inner)
        out.append((t, name))
    return out


def name_idle(layers: Layers, devices: Sequence[int], win: tr.Interval
              ) -> Dict[str, float]:
    """Idle seconds of the chips (their mean), by what the host was in."""
    segs = _segments(layers)
    out: Dict[str, float] = defaultdict(float)
    lo, hi = win
    for d in devices:
        busy = tr.union([(s, e) for s, e, _, _ in layers.ops.get(d, [])],
                        lo, hi)
        i, name = 0, "none"
        for g0, g1 in tr.gaps(busy, lo, hi):
            while i < len(segs) and segs[i][0] <= g0:
                name = segs[i][1]
                i += 1
            t = g0
            while i < len(segs) and segs[i][0] < g1:
                out[name] += (segs[i][0] - t) / len(devices)
                t, name = segs[i]
                i += 1
            out[name] += (g1 - t) / len(devices)
    return {k: v for k, v in out.items() if v > 0}


def reduce(layers: Layers, devices: Sequence[int], queries: int,
           launches: Optional[int] = None,
           model_flops: Optional[float] = None,
           peak_flops: Optional[float] = None) -> dict:
    """The numbers this tool prints, from a loaded trace and the window's
    counts: ``model_flops`` is the operations the window's queries scored
    (as ``bench/metrics/mfu.py`` counts them), ``peak_flops`` one chip's
    peak."""
    win = window(layers)
    by_layer = device_by_layer(layers, devices, win)
    out = {"window_s": win[1] - win[0],
           "device_by_layer": by_layer,
           "top_ops_by_layer": top_ops(layers, win),
           "program_s": program_seconds(layers, win),
           "idle_gaps": sorted(name_idle(layers, devices, win).items(),
                               key=lambda kv: -kv[1])}
    if not queries:
        return out
    if layers.spans:
        out["front_door_ms"] = 1e3 * union_seconds(
            layers, FRONT_DOOR, win) / queries
        out["launch_ms"] = 1e3 * union_seconds(layers, LAUNCH, win) / queries
    if launches is not None:
        out["launches"] = launches / queries
    if "join" in by_layer:
        out["join_ms"] = 1e3 * by_layer["join"] / queries
    if by_layer.get("model") and model_flops and peak_flops:
        out["model_step_mfu"] = 100.0 * model_flops / (
            by_layer["model"] * peak_flops)
    return out


# -- one traced run of a cell ------------------------------------------------

def traced_run(workload: str, seed: int, seconds: float,
               hlo_dump: Optional[str] = None, **run_kw) -> dict:
    """One ``--trace 1`` run through ``harness.run``; the trace it writes
    is reduced here too before the harness deletes it.  The harness is
    wrapped, not changed: its service (for ``launches``), its window's
    records and its candidate rows are read on the way."""
    from bench import check, harness, loads

    seen: dict = {}
    load, closed_loop, service, where_mask = (
        tr.load, loads.closed_loop, harness.service, check.where_mask)

    def keep_service(*a, **k):
        seen["svc"] = service(*a, **k)
        return seen["svc"]

    def count_window(*a, **k):
        stats = seen["svc"].stats
        before = getattr(stats, "launches", None)
        seen["records"] = closed_loop(*a, **k)
        if before is not None:
            seen["launches"] = stats.launches - before
        return seen["records"]

    def keep_candidates(*a, **k):
        mask = where_mask(*a, **k)
        seen["candidates"] = int(mask.sum())
        return mask

    def reduce_too(log_dir):
        seen["layers"] = read(log_dir, hlo_scopes(hlo_dump)
                              if hlo_dump else None)
        return load(log_dir)

    tr.load, loads.closed_loop = reduce_too, count_window
    harness.service, check.where_mask = keep_service, keep_candidates
    try:
        result = harness.run(workload, seed, seconds, True, T_START,
                             **run_kw)
    finally:
        tr.load, loads.closed_loop = load, closed_loop
        harness.service, check.where_mask = service, where_mask
    cell = harness.load_cell(workload)
    cfg = cell.config
    if run_kw.get("scale") is not None:
        cfg = dict(cfg, rows=max(64, int(cfg["rows"] * run_kw["scale"])))
    records = seen["records"]
    ok = sum(r.error is None for r in records)
    arrays = harness.fitted(cfg)
    flops = harness.model_kind(cfg).flops_per_row(arrays, cfg["model"])
    kind = run_kw.get("peaks_of") or result["device"]["kind"]
    layers = reduce(seen["layers"], range(cell.chips), ok,
                    launches=seen.get("launches"),
                    model_flops=flops * ok * seen["candidates"],
                    peak_flops=harness.peaks(kind)["flops_per_s"])
    window_s = records[-1].done - records[0].due
    layers["rows_per_s"] = ok * cfg["rows"] / window_s
    layers["queries"] = ok
    return {"result": result, "layers": layers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--hlo-dump", default=None,
                    help="a new directory: XLA writes the optimized HLO of "
                         "every program there (a private compile cache "
                         "makes each compile), for scopes no trace stat "
                         "carries")
    args = ap.parse_args(argv)
    from bench import harness

    if args.hlo_dump:
        os.makedirs(args.hlo_dump, exist_ok=True)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + (
            f" --xla_dump_to={args.hlo_dump} --xla_dump_hlo_as_text"
            " --xla_dump_hlo_pass_re=^$")).strip()
        harness.JAX_CACHE = Path(args.hlo_dump) / "jax_cache"
    try:
        out = traced_run(args.workload, args.seed, args.seconds,
                         hlo_dump=args.hlo_dump)
    except harness.NoChip as err:
        print(f"layers: {err}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
