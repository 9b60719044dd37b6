"""Partition-parallel SPMD execution of fused prediction plans.

``core/partition.py`` gives tables row-range partitions with zone maps and
the ``partition_pruning`` rule marks each scan with its surviving
partitions; this module actually *runs* the fused plan data-parallel over
those partitions on a 1-D ``data`` mesh (``launch.mesh.make_data_mesh`` —
real accelerators in production, simulated host devices via
``xla_force_host_platform_device_count`` in the benchmark and dry-run).

Two pieces:

- :func:`plan_morsels` — the **partition-morsel scheduler**.  Surviving
  partitions pack (in partition order, so reassembly preserves row order)
  into *morsels* of at most one shared power-of-two row bucket, and
  morsels are assigned to devices longest-processing-time-first.  When the
  partition count exceeds the device count a device simply owns several
  morsels and executes them as sequential waves.  Every morsel pads to
  the *same* bucket, so however many partitions/devices/waves are in
  play, exactly one executable shape reaches XLA per (plan signature,
  bucket, mesh shape) — the compile-count discipline the serving layer's
  shape-bucketed executables already enforce for batching.

- :class:`ShardedExecutor` — SPMD execution: **one** jitted closure (the
  same program), dispatched per-device on that device's morsels from one
  worker thread per device.  ``jax.jit`` traces the closure once and
  reuses the trace across devices, so warm repeats compile nothing.  Per
  -device threads (rather than a single GSPMD computation over a
  ``NamedSharding``-placed global array) are a deliberate choice: the
  external/container runtimes lower to ``pure_callback``, and host
  callbacks inside an SPMD-partitioned computation deadlock on this JAX
  version — per-device dispatch gives the same single-program
  multiple-data semantics with callbacks that genuinely overlap (the
  out-of-process hop is the dominant cost the paper's Raven Ext
  measurements fight).

Pad rows carry ``valid=False`` and row-local plans never mix rows, so
reassembling the per-partition output slices in partition order is
bit-exact against single-device execution over the same partitions.

Beyond row-local scans (``core/rules/distributed_plan.py``):

- **aligned morsel pairs** — for a partition-wise join, every non-anchor
  join input is gathered from *its own* partitioned table at the morsel's
  partition indices (co-partitioning makes index ``i`` of both sides hold
  the same key range) and padded to that side's shared bucket
  (:func:`side_bucket_rows`), so the fused local join still compiles to
  exactly one executable shape per (signature, buckets, mesh);
- **combine stage** — for a two-phase aggregation the per-morsel outputs
  are mergeable partial states, not row slices: ``execute(...,
  combine=...)`` skips the per-partition split and folds the partials
  host-side in ascending partition order (deterministic however morsels
  were placed, so 1-device and 8-device runs of the same placement are
  bit-identical);
- **exchange stage** — for an equi-join whose sides are *not*
  co-partitioned, :meth:`ShardedExecutor.execute_exchange` runs the
  hash-repartition shuffle planned by ``serve/exchange.py``: both sides
  bucket by join-key hash, bucket ``b`` joins locally on device
  ``b % n_devices``, and the row-local outputs scatter back to the
  anchor's original row positions (bit-exact against whole-table by the
  contract documented in ``serve/exchange.py``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.codegen import pow2_bucket
from ..core.partition import Partition
from ..distributed.sharding import data_axes_of
from ..relational.table import Table
from .telemetry import profile_span

__all__ = ["Morsel", "ShardPlacement", "ShardedExecutor", "plan_morsels",
           "side_bucket_rows"]


@dataclasses.dataclass(frozen=True)
class Morsel:
    """A unit of device work: one or more whole partitions (ascending
    index; partitions are atomic — never split across morsels)."""

    partitions: Tuple[int, ...]
    rows: int


@dataclasses.dataclass(frozen=True)
class ShardPlacement:
    """Output of the morsel scheduler: who runs what at which shape."""

    bucket_rows: int                        # shared padded morsel shape
    assignments: Tuple[Tuple[Morsel, ...], ...]   # per device, in wave order
    total_rows: int

    @property
    def n_morsels(self) -> int:
        return sum(len(a) for a in self.assignments)

    @property
    def n_waves(self) -> int:
        return max((len(a) for a in self.assignments), default=0)

    @property
    def padded_rows(self) -> int:
        return self.n_morsels * self.bucket_rows


def plan_morsels(part_rows: Sequence[Tuple[int, int]], n_devices: int,
                 min_bucket_rows: int = 64,
                 morsel_rows: int = 1 << 16) -> ShardPlacement:
    """Pack surviving partitions into bucket-shaped morsels and balance
    them across ``n_devices``.

    ``part_rows`` is ``(partition index, row count)`` in ascending index
    order.  The bucket is the power-of-two cover of the ideal per-device
    share, clamped below by the largest single partition (partitions are
    atomic) and above by ``morsel_rows`` (the morsel granularity cap that
    turns a huge table on few devices into multiple waves instead of one
    giant executable)."""
    n_devices = max(1, int(n_devices))
    if not part_rows:
        return ShardPlacement(
            bucket_rows=max(1, int(min_bucket_rows)),
            assignments=tuple(() for _ in range(n_devices)), total_rows=0)
    total = sum(r for _, r in part_rows)
    largest = max(r for _, r in part_rows)
    target = -(-total // n_devices)                       # ceil
    cap = max(int(morsel_rows), largest)
    bucket = pow2_bucket(min(max(target, largest), cap),
                         min_rows=min_bucket_rows)

    morsels: List[Morsel] = []
    cur: List[int] = []
    cur_rows = 0
    for idx, rows in part_rows:
        if cur and cur_rows + rows > bucket:
            morsels.append(Morsel(tuple(cur), cur_rows))
            cur, cur_rows = [], 0
        cur.append(idx)
        cur_rows += rows
    if cur:
        morsels.append(Morsel(tuple(cur), cur_rows))

    # LPT: biggest morsel to the least-loaded device (ties by device id).
    loads = [0] * n_devices
    per_device: List[List[Morsel]] = [[] for _ in range(n_devices)]
    for m in sorted(morsels, key=lambda m: -m.rows):
        d = min(range(n_devices), key=lambda i: (loads[i], i))
        per_device[d].append(m)
        loads[d] += m.rows
    return ShardPlacement(bucket_rows=bucket,
                          assignments=tuple(tuple(a) for a in per_device),
                          total_rows=total)


def side_bucket_rows(placement: ShardPlacement, side_partitions:
                     Sequence[Partition], min_bucket_rows: int = 64) -> int:
    """Shared padded row bucket for one non-anchor join input: the pow-2
    cover of the largest per-morsel row total that side contributes when
    gathered at the placement's aligned partition indices.  One bucket per
    side keeps the executable shape count at one however morsel
    compositions vary across waves."""
    most = 1
    for assignment in placement.assignments:
        for m in assignment:
            most = max(most, sum(side_partitions[i].n_rows
                                 for i in m.partitions))
    return pow2_bucket(most, min_rows=min_bucket_rows)


def _pad_rows(arr: np.ndarray, pad: int) -> np.ndarray:
    if pad <= 0:
        return arr
    return np.pad(arr, [(0, pad)] + [(0, 0)] * (arr.ndim - 1))


class ShardedExecutor:
    """Runs a fused row-local plan over the surviving partitions of one
    scanned table, data-parallel across a ``data`` mesh."""

    def __init__(self, mesh=None, devices: int = 0):
        if mesh is None:
            from ..launch.mesh import make_data_mesh
            mesh = make_data_mesh(devices)
        self.mesh = mesh
        axes = data_axes_of(mesh) or tuple(mesh.axis_names)
        if tuple(mesh.axis_names) != axes:
            raise ValueError(
                f"sharded execution wants a pure data mesh, got axes "
                f"{mesh.axis_names}")
        self.devices: List[Any] = list(np.asarray(mesh.devices).reshape(-1))
        self.mesh_shape: Tuple[int, ...] = tuple(
            np.asarray(mesh.devices).shape)
        # morsels executed on each device (each worker bumps its own slot)
        self.morsels_per_device: List[int] = [0] * len(self.devices)

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def plan(self, partitions: Sequence[Partition],
             min_bucket_rows: int = 64,
             morsel_rows: int = 1 << 16) -> ShardPlacement:
        return plan_morsels([(p.index, p.n_rows) for p in partitions],
                            self.n_devices, min_bucket_rows=min_bucket_rows,
                            morsel_rows=morsel_rows)

    def execute(self, fn: Callable[[Dict[str, Table]], Any], source: Any,
                scan_name: str, partitions: Sequence[Partition],
                placement: ShardPlacement,
                unwrap: Optional[Callable[[Any], Any]] = None,
                sides: Optional[Dict[str, Tuple[Any, int]]] = None,
                combine: Optional[Callable[[List[Any]], Any]] = None,
                capture: bool = False, trace: Any = None) -> Any:
        """Execute ``fn`` over ``partitions`` of ``source`` per
        ``placement`` and reassemble the output in partition order.

        ``source`` is the base ``Table`` or — preferably — the
        ``PartitionedTable``, whose memoized :meth:`host_view` amortizes
        the device->host snapshot across serves (it would otherwise be
        paid per execution, proportional to *total* table size however
        many partitions were pruned).  ``fn`` must be the jitted fused
        plan taking ``{scan_name: Table, ...}``; ``unwrap`` post-processes
        each morsel's raw result.  ``capture=True`` instead treats each raw
        result as an ``(output, capture)`` pair — both row-local over the
        anchor — and reassembles *both* in partition order, returning the
        pair (so the serving layer's result cache keeps its capture instead
        of dropping it whenever execution went sharded).

        ``sides`` maps additional scan names (partition-wise join inputs)
        to ``(PartitionedTable, bucket_rows)``: each morsel gathers the
        *same partition indices* from every side — co-partitioning
        guarantees the aligned pair holds all possible matches — padded to
        that side's shared bucket.

        ``combine=None`` (row-local output): returns a ``Table`` or matrix
        whose rows are exactly the anchor's surviving partitions' rows, in
        their original order — bit-exact against a single-device run of
        the same plan over the same partitions.  With ``combine`` (two-
        phase aggregation) every morsel's output is a mergeable partial
        state; they are folded host-side in ascending partition order
        (placement-independent, so any device count is bit-identical) and
        the combined value is returned.

        ``trace`` (a :class:`~repro.serve.telemetry.Trace`, or ``None``)
        records one ``shard_wave`` span per morsel — worker threads
        genuinely overlap, so spans go through the out-of-band
        ``add_span`` seam rather than the phase stack.  On the profiler's
        timeline each morsel's ``repro.shard.prepare`` (caller thread),
        ``repro.shard.run`` (its device's worker thread) and
        ``repro.shard.split`` carry the request's trace id."""
        if capture and (combine is not None or unwrap is not None):
            raise ValueError("capture=True is row-local reassembly; it "
                             "composes with neither combine nor unwrap")
        part_map = {p.index: p for p in partitions}
        tid = getattr(trace, "trace_id", 0)
        if hasattr(source, "host_view"):           # PartitionedTable
            host_cols, host_valid = source.host_view()
            table = source.table
        else:
            table = source
            host_cols = {k: np.asarray(v) for k, v in table.columns.items()}
            host_valid = np.asarray(table.valid)
        bucket = placement.bucket_rows
        # (host cols, host valid, partitions, bucket, schema) per join side
        side_views = {}
        for name, (src, srows) in (sides or {}).items():
            s_cols, s_valid = src.host_view()
            side_views[name] = (s_cols, s_valid, src.partitions,
                                int(srows), src.table.schema)

        def gather_pad(cols: Dict[str, np.ndarray], valid: np.ndarray,
                       parts: Sequence[Partition], pad: int, schema,
                       device) -> Table:
            def gather(arr: np.ndarray) -> np.ndarray:
                pieces = [arr[p.start:p.stop] for p in parts]
                out = pieces[0] if len(pieces) == 1 \
                    else np.concatenate(pieces, axis=0)
                return _pad_rows(out, pad)

            dev_cols = {k: jax.device_put(gather(arr), device)
                        for k, arr in cols.items()}
            return Table(dev_cols, jax.device_put(gather(valid), device),
                         schema)

        def prepare_morsel(device, morsel: Morsel) -> Dict[str, Table]:
            """Gather + pad + upload one morsel's inputs (anchor plus any
            aligned join sides).  Runs on the caller thread, serially: the
            numpy slicing and device_put are GIL-bound, and doing them
            inside the device workers makes the workers contend with each
            other instead of overlapping their (GIL-free) execution
            waits."""
            with profile_span("shard.prepare", tid):
                parts = [part_map[i] for i in morsel.partitions]
                tables = {scan_name: gather_pad(
                    host_cols, host_valid, parts, bucket - morsel.rows,
                    table.schema, device)}
                for name, (s_cols, s_valid, s_parts, srows, s_schema) \
                        in side_views.items():
                    aligned = [s_parts[i] for i in morsel.partitions]
                    rows = sum(p.n_rows for p in aligned)
                    tables[name] = gather_pad(s_cols, s_valid, aligned,
                                              srows - rows, s_schema, device)
            return tables

        def split_rows(raw: Any, parts: Sequence[Partition]) -> List[Any]:
            """Split one morsel's row-local result back per partition,
            host-side (one transfer per morsel); trailing pad rows fall
            off the last slice."""
            pieces: List[Any] = []
            if isinstance(raw, Table):
                out_cols = {k: np.asarray(v) for k, v in raw.columns.items()}
                out_valid = np.asarray(raw.valid)
                off = 0
                for p in parts:
                    pieces.append(({k: v[off:off + p.n_rows]
                                    for k, v in out_cols.items()},
                                   out_valid[off:off + p.n_rows], raw.schema))
                    off += p.n_rows
            else:
                arr = np.asarray(raw)
                off = 0
                for p in parts:
                    pieces.append(arr[off:off + p.n_rows])
                    off += p.n_rows
            return pieces

        def run_morsel(morsel: Morsel, tables: Dict[str, Table]
                       ) -> List[Tuple[int, Any, Any]]:
            parts = [part_map[i] for i in morsel.partitions]
            raw = fn(tables)
            cap = None
            if capture:
                raw, cap = raw
            elif unwrap is not None:
                raw = unwrap(raw)
            raw = jax.block_until_ready(raw)
            if combine is not None:
                # partial-aggregate state: one mergeable value per morsel,
                # ordered by its first partition for the combine fold
                return [(parts[0].index, raw, None)]
            with profile_span("shard.split", tid):
                outs = split_rows(raw, parts)
                caps = (split_rows(jax.block_until_ready(cap), parts)
                        if capture else [None] * len(parts))
            return [(p.index, o, c) for p, o, c in zip(parts, outs, caps)]

        active = [d for d in range(self.n_devices)
                  if placement.assignments[d]]
        prepared = {d: [(m, prepare_morsel(self.devices[d], m))
                        for m in placement.assignments[d]]
                    for d in active}
        live = trace is not None and getattr(trace, "enabled", False)

        def run_device(d: int) -> List[Tuple[int, Any, Any]]:
            pieces: List[Tuple[int, Any, Any]] = []
            for morsel, tables in prepared[d]:
                t0 = trace.clock.monotonic() if live else 0.0
                with profile_span("shard.run", tid):
                    out = run_morsel(morsel, tables)
                self.morsels_per_device[d] += 1
                if live:
                    trace.add_span("shard_wave", t0,
                                   trace.clock.monotonic(), device=d,
                                   partitions=len(morsel.partitions),
                                   rows=morsel.rows)
                pieces.extend(out)
            return pieces
        if not active:
            # every partition pruned: run one all-padding morsel to learn
            # the output schema, then keep zero of its rows — or, for a
            # combine stage, to produce the identity partial (no valid
            # rows), which folds to the same aggregate the whole plan
            # yields over a fully-filtered table
            def zeros_table(cols, valid_rows, schema):
                z = {k: np.zeros((valid_rows,) + arr.shape[1:], arr.dtype)
                     for k, arr in cols.items()}
                return Table({k: jax.device_put(v, self.devices[0])
                              for k, v in z.items()},
                             jax.device_put(np.zeros(valid_rows, np.bool_),
                                            self.devices[0]), schema)

            tables = {scan_name: zeros_table(host_cols, bucket,
                                             table.schema)}
            for name, (s_cols, _v, _p, srows, s_schema) \
                    in side_views.items():
                tables[name] = zeros_table(s_cols, srows, s_schema)
            raw = fn(tables)
            cap = None
            if capture:
                raw, cap = raw
            elif unwrap is not None:
                raw = unwrap(raw)
            raw = jax.block_until_ready(raw)
            if combine is not None:
                return combine([raw])

            def empty(v: Any) -> Any:
                if isinstance(v, Table):
                    return Table({k: c[:0] for k, c in v.columns.items()},
                                 v.valid[:0], v.schema)
                return v[:0]
            if capture:
                return empty(raw), empty(jax.block_until_ready(cap))
            return empty(raw)

        results: Dict[int, List[Tuple[int, Any, Any]]] = {}
        errors: List[BaseException] = []

        def worker(d: int):
            try:
                results[d] = run_device(d)
            except BaseException as err:   # propagate to the caller
                errors.append(err)

        if len(active) == 1:
            results[active[0]] = run_device(active[0])
        else:
            threads = [threading.Thread(target=worker, args=(d,),
                                        name=f"shard-exec-{d}")
                       for d in active]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        pieces = sorted((pair for r in results.values() for pair in r),
                        key=lambda pair: pair[0])
        if combine is not None:
            return combine([p[1] for p in pieces])

        def reassemble(items: List[Any]) -> Any:
            if isinstance(items[0], tuple):        # Table morsels
                schema = items[0][2]
                names = items[0][0].keys()
                cols = {k: jnp.asarray(
                    np.concatenate([it[0][k] for it in items], axis=0))
                    for k in names}
                valid = jnp.asarray(np.concatenate([it[1] for it in items]))
                return Table(cols, valid, schema)
            return jnp.asarray(np.concatenate(items, axis=0))

        out = reassemble([p[1] for p in pieces])
        if capture:
            return out, reassemble([p[2] for p in pieces])
        return out

    def execute_exchange(self, fn: Callable[[Dict[str, Table]], Any],
                         anchor: Tuple[Dict[str, np.ndarray], np.ndarray, Any],
                         scan_name: str,
                         side: Tuple[Dict[str, np.ndarray], np.ndarray, Any],
                         side_name: str, placement,
                         unwrap: Optional[Callable[[Any], Any]] = None,
                         combine: Optional[Callable[[List[Any]], Any]] = None,
                         capture: bool = False, trace: Any = None) -> Any:
        """Execute ``fn`` via a hash-repartition shuffle exchange.

        ``anchor`` and ``side`` are host ``(columns, valid, schema)``
        triples already restricted to the surviving rows (in original
        order — the rows the placement's index arrays address);
        ``placement`` is the :class:`~repro.serve.exchange
        .ExchangePlacement` planned from their join-key columns.  Bucket
        ``b`` gathers both sides' bucket-``b`` rows, pads each to its
        side's shared pow-2 capacity, uploads to device
        ``b % n_devices``, and runs the same jitted ``fn`` — one
        executable shape for every bucket, so warm repeats compile
        nothing.

        Row-local output (``combine=None``): bucket outputs scatter back
        to the anchor rows' original positions, so the result is bitwise
        the whole-table output (valid rows and validity mask alike) for
        any bucket count or device count.  With ``combine`` each bucket
        yields a mergeable partial state, folded in ascending bucket
        order — deterministic however buckets were placed."""
        if capture and (combine is not None or unwrap is not None):
            raise ValueError("capture=True is row-local reassembly; it "
                             "composes with neither combine nor unwrap")
        from .exchange import take_pad
        a_cols, a_valid, a_schema = anchor
        s_cols, s_valid, s_schema = side

        def bucket_table(cols, valid, idx, cap, schema, device) -> Table:
            dev_cols = {k: jax.device_put(take_pad(arr, idx, cap), device)
                        for k, arr in cols.items()}
            return Table(dev_cols,
                         jax.device_put(take_pad(valid, idx, cap), device),
                         schema)

        active = list(placement.active_buckets)
        if not active:
            # no surviving anchor rows anywhere: run one all-padding
            # bucket to learn the output schema (identity partial for a
            # combine stage), exactly as ``execute`` does when every
            # partition was pruned
            def zeros_table(cols, rows, schema):
                z = {k: np.zeros((rows,) + arr.shape[1:], arr.dtype)
                     for k, arr in cols.items()}
                return Table({k: jax.device_put(v, self.devices[0])
                              for k, v in z.items()},
                             jax.device_put(np.zeros(rows, np.bool_),
                                            self.devices[0]), schema)

            tables = {scan_name: zeros_table(a_cols, placement.anchor_rows,
                                             a_schema),
                      side_name: zeros_table(s_cols, placement.side_rows,
                                             s_schema)}
            raw = fn(tables)
            cap = None
            if capture:
                raw, cap = raw
            elif unwrap is not None:
                raw = unwrap(raw)
            raw = jax.block_until_ready(raw)
            if combine is not None:
                return combine([raw])

            def empty(v: Any) -> Any:
                if isinstance(v, Table):
                    return Table({k: c[:0] for k, c in v.columns.items()},
                                 v.valid[:0], v.schema)
                return v[:0]
            if capture:
                return empty(raw), empty(jax.block_until_ready(cap))
            return empty(raw)

        # bucket b -> device b % n_devices; several buckets on one device
        # execute as sequential waves, mirroring the morsel scheduler
        per_device: Dict[int, List[int]] = {}
        for b in active:
            per_device.setdefault(b % self.n_devices, []).append(b)
        # gather + upload on the caller thread, serially (same GIL
        # rationale as ``prepare_morsel``)
        prepared = {
            d: [(b, {scan_name: bucket_table(
                        a_cols, a_valid, placement.anchor_index[b],
                        placement.anchor_rows, a_schema, self.devices[d]),
                     side_name: bucket_table(
                        s_cols, s_valid, placement.side_index[b],
                        placement.side_rows, s_schema, self.devices[d])})
                for b in buckets]
            for d, buckets in per_device.items()}

        def trim(raw: Any, rows: int) -> Any:
            """Host-side copy of one bucket's output, padding dropped."""
            if isinstance(raw, Table):
                return ({k: np.asarray(v)[:rows]
                         for k, v in raw.columns.items()},
                        np.asarray(raw.valid)[:rows], raw.schema)
            return np.asarray(raw)[:rows]

        live = trace is not None and getattr(trace, "enabled", False)
        tid = getattr(trace, "trace_id", 0)

        def run_device(d: int) -> List[Tuple[int, Any, Any]]:
            pieces: List[Tuple[int, Any, Any]] = []
            for b, tables in prepared[d]:
                t0 = trace.clock.monotonic() if live else 0.0
                with profile_span("exchange_bucket", tid):
                    raw = fn(tables)
                    cap = None
                    if capture:
                        raw, cap = raw
                    elif unwrap is not None:
                        raw = unwrap(raw)
                    raw = jax.block_until_ready(raw)
                if live:
                    trace.add_span(
                        "exchange_bucket", t0, trace.clock.monotonic(),
                        device=d, bucket=b,
                        rows=len(placement.anchor_index[b]))
                if combine is not None:
                    pieces.append((b, raw, None))
                    continue
                rows = len(placement.anchor_index[b])
                pieces.append((b, trim(raw, rows),
                               trim(jax.block_until_ready(cap), rows)
                               if capture else None))
            return pieces

        results: Dict[int, List[Tuple[int, Any, Any]]] = {}
        errors: List[BaseException] = []

        def worker(d: int):
            try:
                results[d] = run_device(d)
            except BaseException as err:   # propagate to the caller
                errors.append(err)

        devices = sorted(prepared)
        if len(devices) == 1:
            results[devices[0]] = run_device(devices[0])
        else:
            threads = [threading.Thread(target=worker, args=(d,),
                                        name=f"exchange-exec-{d}")
                       for d in devices]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        pieces = sorted((trip for r in results.values() for trip in r),
                        key=lambda trip: trip[0])
        if combine is not None:
            return combine([p[1] for p in pieces])

        # scatter bucket outputs back to original anchor row positions:
        # `order` is where each stacked row came from, `inv` sends it home
        t_scatter = trace.clock.monotonic() if live else 0.0
        order = np.concatenate(
            [placement.anchor_index[b] for b, _, _ in pieces])
        inv = np.empty(placement.total_rows, np.int64)
        inv[order] = np.arange(len(order))

        def reassemble(items: List[Any]) -> Any:
            if isinstance(items[0], tuple):        # Table buckets
                schema = items[0][2]
                names = items[0][0].keys()
                cols = {k: jnp.asarray(np.concatenate(
                    [it[0][k] for it in items], axis=0)[inv])
                    for k in names}
                valid = jnp.asarray(
                    np.concatenate([it[1] for it in items])[inv])
                return Table(cols, valid, schema)
            return jnp.asarray(np.concatenate(items, axis=0)[inv])

        with profile_span("exchange_scatter", tid):
            out = reassemble([p[1] for p in pieces])
            cap_out = reassemble([p[2] for p in pieces]) if capture \
                else None
        if live:
            trace.add_span("exchange_scatter", t_scatter,
                           trace.clock.monotonic(),
                           buckets=len(pieces), rows=len(order))
        if capture:
            return out, cap_out
        return out
