"""CPU tests of ``bench/layers.py``: the reduction of a trace by the
program's own spans and scopes, on synthetic traces, and one traced run
of a cell through the harness at a size a test can hold.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_layers.py
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, layers, loads  # noqa: E402
from bench import trace as tr  # noqa: E402

T = ("/host:CPU", 0)                           # the main thread
W = ("/host:CPU", 1)                           # a worker thread


def traced(ops, spans=None, bench=None):
    return layers.Layers(
        ops=ops, spans=spans or {},
        bench=bench or [(0.0, 10.0, "bench.window"),
                        (0.0, 8.0, "bench.query")])


def test_without_program_spans_idle_is_named_as_bench_trace_names_it():
    """A trace with no ``repro.*`` span or scope: every idle second keeps
    the name ``bench/trace.py`` gives it, and the ops fall under other."""
    ops = {0: [(1.0, 3.0, "other", "fusion.1"), (2.0, 4.0, "other", "dot.2"),
               (6.0, 7.0, "other", "fusion.1")]}
    lay = traced(ops, bench=[(0.0, 10.0, "bench.window"),
                             (0.0, 5.0, "bench.query"),
                             (5.0, 8.0, "bench.fetch")])
    old = tr.summarize(tr.Trace(
        ops={0: [(s, e, n) for s, e, _, n in ops[0]]}, spans=lay.bench),
        [0])
    out = layers.reduce(lay, [0], queries=0)
    assert dict(out["idle_gaps"]) == pytest.approx(dict(old.idle_by_host))
    assert out["device_by_layer"] == {"other": 4.0}
    assert out["program_s"] == {}
    out = layers.reduce(lay, [0], queries=3)
    for key in ("front_door_ms", "launch_ms", "launches", "join_ms",
                "model_step_mfu"):
        assert key not in out, key


def test_device_time_per_scope_is_a_union():
    """A ``while`` and the ops of its body count once; scopes apart."""
    ops = {0: [(0.0, 5.0, "model", "%while.13 = (s32[]) while(...)"),
               (1.0, 2.0, "model", "%fusion.59 = f32[8] fusion(...)"),
               (3.0, 4.0, "model", "%fusion.59 = f32[8] fusion(...)"),
               (6.0, 7.0, "join", "%while.14 = (s32[]) while(...)"),
               (6.5, 7.5, "join", "%fusion.64 = s32[8] fusion(...)"),
               (9.0, 9.5, "other", "%copy.1 = s32[8] copy(...)")],
           1: [(0.0, 2.0, "model", "%while.13 = (s32[]) while(...)")]}
    out = layers.device_by_layer(traced(ops), [0, 1], (0.0, 10.0))
    assert out == pytest.approx({"model": 7.0, "join": 1.5, "other": 0.5})
    top = layers.top_ops(traced(ops), (0.0, 10.0), top=1)
    assert top == {"model": [("while.13", 7.0)],
                   "join": [("while.14", 1.0)], "other": [("copy.1", 0.5)]}


def test_idle_gaps_take_the_innermost_program_span():
    """Device busy 3-4 only.  Main thread: compile 0-2 around optimize
    0.5-1.5, then the morsel loop's launch 2-5; a worker runs shard.run
    4-6."""
    spans = {T: [(0.0, 2.0, "compile"), (0.5, 1.5, "optimize"),
                 (2.0, 5.0, "morsel.launch")],
             W: [(4.0, 6.0, "shard.run")]}
    lay = traced({0: [(3.0, 4.0, "model", "dot.1")]}, spans=spans)
    got = layers.name_idle(lay, [0], (0.0, 10.0))
    assert got == pytest.approx({
        "query/compile": 1.0, "query/optimize": 1.0,
        "query/morsel.launch": 1.0,                       # 2-3
        "query/morsel.launch+shard.run": 1.0,             # 4-5
        "query/shard.run": 1.0,                           # 5-6
        "query": 2.0,                                     # 6-8
        "none": 2.0})                                     # 8-10


def test_host_seconds_per_span_are_a_union_across_threads():
    spans = {T: [(0.0, 2.0, "shard.prepare"), (2.0, 3.0, "assemble")],
             W: [(1.0, 4.0, "shard.prepare"), (9.0, 12.0, "parse")]}
    lay = traced({}, spans=spans)
    assert layers.program_seconds(lay, (0.0, 10.0)) == pytest.approx(
        {"shard.prepare": 4.0, "assemble": 1.0, "parse": 1.0})
    out = layers.reduce(lay, [0], queries=2, launches=150)
    assert out["front_door_ms"] == pytest.approx(500.0)
    # shard.prepare 0-4 holds assemble 2-3: 4 s over 2 queries
    assert out["launch_ms"] == pytest.approx(2000.0)
    assert out["launches"] == 75.0


def test_per_query_device_numbers():
    # 2 queries; model busy 4 s of a 1e6 FLOP/s chip over 2e5 FLOP: 5%
    lay = traced({0: [(0.0, 4.0, "model", "dot.1"),
                      (5.0, 6.0, "join", "fusion.2")]})
    out = layers.reduce(lay, [0], queries=2, model_flops=2e5,
                        peak_flops=1e6)
    assert out["join_ms"] == pytest.approx(500.0)
    assert out["model_step_mfu"] == pytest.approx(5.0)
    lay = traced({0: [(5.0, 6.0, "join", "fusion.2")]})
    assert "model_step_mfu" not in layers.reduce(
        lay, [0], queries=2, model_flops=2e5, peak_flops=1e6)


def test_scope_from_stats_or_from_the_hlo(tmp_path):
    assert layers.scope_of(["fusion.3", "jit(traced)/repro.model/dot"]) \
        == "model"
    assert layers.scope_of(["jit(f)/repro.join/repro.model/x"]) == "model"
    assert layers.scope_of(["fusion.3", "jit(f)/add"]) is None
    # a TPU trace prints operands with their shapes, the dump without
    traced = ("%while.13 = (s32[]{:T(128)}, f32[8,1]{0,1:T(1,128)}) "
              "while((s32[]{:T(128)}, f32[8,1]{0,1:T(1,128)}) %tuple.3), "
              "condition=%c, body=%b")
    key = layers.instruction_key(traced)
    assert key == ("while.13 (s32[]{:T(128)}, f32[8,1]{0,1:T(1,128)}) "
                   "while")
    (tmp_path / "module_0007.jit_traced.tpu_after_optimizations.txt"
     ).write_text(
        'HloModule jit_traced\n'
        '  %while.13 = (s32[]{:T(128)}, f32[8,1]{0,1:T(1,128)}) '
        'while(%tuple.3), condition=%c, body=%b, metadata={op_name='
        '"jit(traced)/repro.model/while" stack_frame_id=3}\n'
        '  ROOT %fusion.64 = s32[8]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(traced)/repro.join/gather"}\n'
        '  %add.1 = f32[] add(%a, %b)\n'
        '  %copy.2 = f32[8]{0} copy(%x), metadata={op_name='
        '"jit(traced)/repro.featurize/copy"}\n')
    (tmp_path / "module_0008.jit_traced.tpu_after_optimizations.txt"
     ).write_text(
        '  %copy.2 = f32[8]{0} copy(%y), metadata={op_name='
        '"jit(traced)/repro.model/copy"}\n'
        '  %fusion.64 = s32[8]{0} fusion(%q), metadata={op_name='
        '"jit(traced)/repro.join/gather"}\n')
    got = layers.hlo_scopes(str(tmp_path))
    assert got[key] == "model"
    assert got["fusion.64 s32[8]{0} fusion"] == "join"
    assert "copy.2 f32[8]{0} copy" not in got     # two scopes: ambiguous
    assert not any(k.startswith("add.1") for k in got)


def test_traced_run_through_the_harness():
    """One traced run of a cell on the CPU: the program's spans and the
    window's launches come back, and the harness is left as it was."""
    before = (tr.load, loads.closed_loop, harness.service,
              check.where_mask)
    out = layers.traced_run("flights_lr.batch", 5, 1.0,
                            require_tpu=False, scale=0.002,
                            peaks_of="TPU v5 lite")
    assert (tr.load, loads.closed_loop, harness.service,
            check.where_mask) == before
    assert out["result"]["correct"]
    lay = out["layers"]
    assert lay["queries"] == out["result"]["attempted"]
    for name in ("parse", "admit", "compile", "execute", "device_wait"):
        assert lay["program_s"].get(name, 0) > 0, name
    # whole-table execution on the CPU: one program a query
    assert lay["launches"] == 1.0
    assert lay["front_door_ms"] > 0 and lay["rows_per_s"] > 0
    # no device plane on the CPU: the whole window is idle, and named
    assert {n.split("/")[0] for n, _ in lay["idle_gaps"]} <= {"query",
                                                              "none"}
    assert any(n.startswith("query/") for n, _ in lay["idle_gaps"])
