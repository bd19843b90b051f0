"""TPU join operators.

Reference: GpuShuffledHashJoinBase + GpuHashJoin.scala (build side coalesced
to a single batch, stream side batched — :165-362) and the per-version
GpuBroadcastHashJoinExec shims. The kernel is the sort-merge matcher in
ops/join.py; the execution contract matches the reference: build on the
RIGHT side, stream the LEFT. Output buckets are sized by ONE batched device
sync per stream WINDOW (phase1 for up to _PROBE_WINDOW batches dispatches
before a single pull of their match totals — a per-batch sync would stall
dispatch once per batch).
"""
from __future__ import annotations

import dataclasses as dc
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..columnar.device import DeviceBatch, DeviceColumn, bucket_capacity, dc_replace, empty_batch
from ..expr import Expression, bind
from ..expr.base import BoundReference, Ctx, Val
from ..ops.concat import concat_device
from ..ops.gather import compact, gather_columns, shrink_one
from ..ops.join import gather_pairs, join_bounds, join_output_schema, pad_string_column
from ..plan.physical import Exec, ExecContext, PartitionSet
from ..types import Schema, StringType, StructField
from .tpu import val_to_column
from .. import kernels as K
from ..obs import metrics as obs_metrics

_M_CALLS = obs_metrics.GLOBAL.counter("join.calls")
_M_ROWS_OUT = obs_metrics.GLOBAL.counter("join.rowsOut")


def _colocate_with(batch: DeviceBatch, anchor: DeviceBatch) -> DeviceBatch:
    """Move ``batch`` onto ``anchor``'s device when they differ (mesh mode
    can mix mesh-exchanged and host-exchanged join inputs); single-device
    mode is a metadata check only."""

    def dev(b):
        if not b.columns:
            return None
        x = b.columns[0].data
        devices = getattr(x, "devices", None)
        if devices is None:
            return None
        try:
            return next(iter(devices()))
        except Exception:
            return None

    da, db = dev(batch), dev(anchor)
    if da is None or db is None or da == db:
        return batch
    return jax.device_put(batch, db)


def _link_aqe_exchanges(left: Exec, right: Exec, join_type: str = "inner") -> None:
    """Positional partition pairing requires both join inputs to share one
    AQE coalesce assignment. Find the shuffle exchange feeding each side
    (descending through batch-coalesce wrappers); link the pair so each
    computes the grouping from combined sizes, or disable coalescing when
    only one side is exchange-fed (the other side's partitioning is fixed).
    The join type rides along so the skew-split pass knows which side may
    be split (the other side is replicated — only legal when replication
    cannot emit unmatched rows). Spark parity: AQE applies identical
    CoalescedPartitionSpecs to both shuffle reads of a join
    (ShufflePartitionsUtil) and OptimizeSkewedJoin splits a skewed side
    while replicating the other."""
    from .tpu import TpuCoalesceBatchesExec, TpuShuffleExchangeExec

    def find(node: Exec):
        while True:
            if isinstance(node, TpuShuffleExchangeExec):
                return node
            if isinstance(node, TpuCoalesceBatchesExec):
                node = node.children[0]
                continue
            return None

    lex, rex = find(left), find(right)
    if lex is not None and rex is not None:
        lex._aqe_peer, rex._aqe_peer = rex, lex
        lex._aqe_side, rex._aqe_side = "left", "right"
        lex._aqe_join_type = rex._aqe_join_type = join_type
    else:
        for ex in (lex, rex):
            if ex is not None:
                ex._aqe_disabled = True


# probe batches whose phase1 results may be held on device concurrently
# while their match totals ride one batched sync (memory bound per stream)
_PROBE_WINDOW = 8


def _stream_probe_join(node, get_build, probe_thunk, phase1, phase2, jt,
                       matched_acc=None, ctx=None):
    """One probe stream joined against one build batch — the shared loop
    under the shuffled, runtime-broadcast-switched, and broadcast joins.
    ``get_build(first_probe)`` supplies the build batch lazily (broadcast
    materializes it on the probe's device); ``matched_acc['m']`` (when
    given) accumulates build-row match bits for right/full null-extension.

    The PROBE side is splittable (each probe row matches against the whole
    build batch independently, and the match-bit accumulator ORs across
    halves like across batches), so with an ``ctx`` the phase1 launch rides
    the OOM retry/split state machine (resilience/retry.py)."""
    from itertools import islice

    from ..resilience import retry as R

    build = None
    it = iter(probe_thunk())
    while True:
        # WINDOWED phase1 dispatch: up to _PROBE_WINDOW probe batches
        # dispatch before ONE batched pull of their match totals — one
        # host sync per window instead of per batch (a sync stalls
        # dispatch until the device drains). The window bound keeps join
        # memory O(window), not O(probe side), and an early-exiting
        # consumer (LIMIT) stops after the current window.
        window = []
        for probe in islice(it, _PROBE_WINDOW):
            if build is None:
                build = get_build(probe)
            # mesh mode: the two sides can land on different devices when
            # only one side's exchange took the mesh path — one jit needs
            # one device
            probe = _colocate_with(probe, build)
            if ctx is not None:
                window.extend(
                    R.run_with_retry(
                        ctx.catalog,
                        lambda b: (b, phase1(build, b)),
                        probe,
                        ctx.retry_policy,
                        op=node._breaker_op,
                        breaker=ctx.breaker,
                    )
                )
            else:
                window.append((probe, phase1(build, probe)))
        if not window:
            return
        # graft: ok(host-sync: output capacities must be chosen on host
        # (bucketed jit signatures) — ONE batched pull for the whole probe
        # window instead of a sync per batch)
        totals = jax.device_get([c.sum() for (_p, (_b, _l, c)) in window])
        tok = ctx.cancel_token if ctx is not None else None
        for i, total_dev in enumerate(totals):
            if tok is not None:
                tok.check()
            probe, (build_order, lower, counts) = window[i]
            window[i] = None  # release as consumed
            # graft: ok(host-sync: already on host — item of the single
            # windowed device_get above)
            total = int(total_dev)
            _M_CALLS.add(1)
            _M_ROWS_OUT.add(total)
            out_cap = bucket_capacity(max(total, 1))
            out, probe_matched, bmatch = phase2(
                build,
                probe,
                build_order,
                lower,
                counts,
                jnp.zeros(out_cap, jnp.int8),
            )
            if matched_acc is not None:
                matched_acc["m"] = matched_acc["m"] | bmatch
            # possibly-empty batches are yielded WITHOUT a row_count() host
            # sync: an empty capacity-masked batch costs downstream kernels
            # microseconds, a sync stalls dispatch
            if jt in ("left", "full"):
                unmatched = (~probe_matched) & probe.row_mask()
                yield node._null_extend(probe, unmatched, "left")
            yield out


class TpuShuffledHashJoinExec(Exec):
    #: planner rule name the circuit breaker counts runtime failures under
    #: (plan/overrides.py consults breaker.check(rule.name))
    _breaker_op = "ShuffledHashJoinExec"

    def __init__(
        self,
        join_type: str,
        left_keys: List[Expression],
        right_keys: List[Expression],
        residual: Optional[Expression],
        left: Exec,
        right: Exec,
        drop_right_keys: Optional[List[str]] = None,
    ):
        super().__init__([left, right])
        self.join_type = join_type
        self.left_keys = [bind(k, left.output) for k in left_keys]
        self.right_keys = [bind(k, right.output) for k in right_keys]
        self.residual = residual
        self.drop_right_keys = drop_right_keys or []
        self._schema = self._compute_schema()

    def _compute_schema(self) -> Schema:
        left, right = self.children
        return join_output_schema(
            self.join_type, left.output.fields, right.output.fields, self.drop_right_keys
        )

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def _right_ordinals(self) -> List[int]:
        right = self.children[1]
        return [
            i
            for i, f in enumerate(right.output.fields)
            if f.name not in self.drop_right_keys
        ]

    # ── kernels ─────────────────────────────────────────────────────────
    def _phase1(self):
        """counts per probe row (+ build order/lower for phase 2)."""
        left_keys, right_keys = tuple(self.left_keys), tuple(self.right_keys)

        def make():
            return _make_phase1(left_keys, right_keys)

        return K.jit_kernel(("join_p1", left_keys, right_keys), make)
    def _phase2(self):
        """Gather matched pairs into a static-capacity output batch."""
        out_schema = self._schema
        left_exec, right_exec = self.children
        right_ords = tuple(self._right_ordinals())
        jt = self.join_type
        residual = self.residual
        if residual is not None:
            pair_schema = Schema(
                list(left_exec.output.fields) + list(right_exec.output.fields)
            )
            residual = bind(residual, pair_schema)

        key = ("join_p2", jt, residual, right_ords, out_schema)
        return K.counted_kernel(
            key, lambda: _make_phase2(out_schema, right_ords, jt, residual)
        )

    def _null_extend(self, batch: DeviceBatch, keep: jax.Array, side: str) -> DeviceBatch:
        """Rows of one side with the other side's columns as NULLs."""
        left_exec, right_exec = self.children
        right_fields = [
            f for f in right_exec.output.fields if f.name not in self.drop_right_keys
        ]
        return null_extend_batch(
            self._schema,
            batch,
            keep,
            side,
            left_exec.output.fields,
            right_fields,
            self._right_ordinals(),
        )

    # ── execution ───────────────────────────────────────────────────────
    def _try_broadcast_switch(self, ctx: ExecContext):
        """AQE runtime join-strategy switch (Spark's DynamicJoinSelection +
        local shuffle reader; GpuCustomShuffleReaderExec analogue): when
        the build side's MEASURED map-output size fits the broadcast
        threshold, join every probe partition against ONE concatenated
        build table and read the probe side's exchange LOCALLY — its
        all-to-all bucketing is skipped entirely. Returns
        ``(switched_partition_set | None, reusable_build_parts | None)`` —
        the second slot hands an already-executed build exchange back to
        the normal path so declining never materializes it twice."""
        from .. import config as cfg
        from .tpu import TpuShuffleExchangeExec

        if ctx.mesh is not None or not cfg.ADAPTIVE_ENABLED.get(ctx.conf):
            return None, None
        # broadcast-build-right is only sound when unmatched BUILD rows
        # never surface (they would duplicate per probe partition)
        if self.join_type not in ("inner", "left", "left_semi", "left_anti"):
            return None, None
        left, right = self.children
        if not isinstance(right, TpuShuffleExchangeExec):
            return None, None
        thresh = cfg.ADAPTIVE_BROADCAST_THRESHOLD.get(ctx.conf)
        if thresh < 0:
            thresh = cfg.AUTO_BROADCAST_THRESHOLD.get(ctx.conf)
        if thresh < 0:
            return None, None
        rparts = right.execute(ctx)
        size_fn = ctx.aqe_size_providers.get(id(right))
        if size_fn is None:  # exchange didn't take the AQE path
            return None, rparts
        total = sum(size_fn())
        # the measurement materialized the build side ON THIS thread; drop
        # the device-semaphore permit it acquired or the main thread holds
        # one task slot for the rest of the query
        ctx.semaphore.release_if_necessary()
        if total > thresh:
            # declined: hand the already-executed build partitions back so
            # the normal path doesn't materialize the exchange twice
            return None, rparts
        self.aqe_broadcast_switched = True
        # local shuffle read: bypass the probe exchange's bucketing (the
        # broadcast build holds every key, so co-partitioning is moot)
        probe_src = (
            left.children[0] if isinstance(left, TpuShuffleExchangeExec) else left
        )
        lparts = probe_src.execute(ctx)
        phase1 = self._phase1()
        phase2 = self._phase2()
        jt = self.join_type
        bstate: dict = {}
        block = threading.Lock()

        def build_once() -> DeviceBatch:
            with block:
                if "b" not in bstate:
                    batches = [db for p in rparts.parts for db in p()]
                    bstate["b"] = (
                        concat_device(batches)
                        if batches
                        else empty_batch(right.output)
                    )
                return bstate["b"]

        def make(lt):
            def it():
                yield from _stream_probe_join(
                    self, lambda _p: build_once(), lt, phase1, phase2, jt,
                    ctx=ctx,
                )

            return it

        return PartitionSet([make(lt) for lt in lparts.parts]), None

    def execute(self, ctx: ExecContext) -> PartitionSet:
        left, right = self.children
        # link BEFORE any side executes: the AQE coalesce/skew assignment
        # must see its peer even when the broadcast-switch probe below
        # executes the build exchange first (and then declines)
        _link_aqe_exchanges(left, right, self.join_type)
        switched, reuse_rparts = self._try_broadcast_switch(ctx)
        if switched is not None:
            return switched
        lparts = left.execute(ctx)
        rparts = reuse_rparts if reuse_rparts is not None else right.execute(ctx)
        assert lparts.num_partitions == rparts.num_partitions, (
            f"{lparts.num_partitions} vs {rparts.num_partitions}"
        )
        phase1 = self._phase1()
        phase2 = self._phase2()
        jt = self.join_type

        def make(lt, rt):
            def it():
                bbatches = list(rt())
                build = (
                    concat_device(bbatches)
                    if bbatches
                    else empty_batch(right.output)
                )
                acc = {"m": jnp.zeros(build.capacity, dtype=bool)}
                yield from _stream_probe_join(
                    self, lambda _p: build, lt, phase1, phase2, jt, acc,
                    ctx=ctx,
                )
                if jt in ("right", "full"):
                    unmatched = (~acc["m"]) & build.row_mask()
                    yield self._null_extend(build, unmatched, "right")

            return it

        return PartitionSet([make(lt, rt) for lt, rt in zip(lparts.parts, rparts.parts)])

    def node_string(self):
        return (
            f"TpuShuffledHashJoin {self.join_type} "
            f"[{', '.join(map(str, self.left_keys))}] [{', '.join(map(str, self.right_keys))}]"
        )


class TpuBroadcastExchangeExec(Exec):
    """Build side collected once to a single device batch shared by all join
    tasks (GpuBroadcastExchangeExecBase:238; in-process, the serialize/
    JVM-broadcast/deserialize round trip collapses to one cached batch)."""

    def __init__(self, child: Exec):
        super().__init__([child])
        self._cache = None
        import threading

        self._lock = threading.Lock()

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def broadcast_batch(self, ctx: ExecContext) -> DeviceBatch:
        with self._lock:
            if self._cache is None:
                # exchanges under a broadcast build run WHOLE in every
                # process: the build table must be complete per executor
                # (multiproc rank-splitting or shared-registry map statuses
                # here would broadcast a partial table)
                ctx.broadcast_depth += 1
                try:
                    parts = self.children[0].execute(ctx)
                    batches = [b for t in parts.parts for b in t()]
                finally:
                    ctx.broadcast_depth -= 1
                self._cache = (
                    concat_device(batches) if batches else empty_batch(self.output)
                )
            return self._cache

    def broadcast_batch_like(self, ctx: ExecContext, peer: DeviceBatch) -> DeviceBatch:
        """Mesh mode: the build batch replicated onto the peer's device (the
        in-process analogue of the broadcast re-materializing per executor);
        per-device copies are cached for the node's lifetime."""
        build = self.broadcast_batch(ctx)
        if ctx.mesh is None:
            return build
        import jax

        dev = next(iter(peer.columns[0].data.devices()))
        with self._lock:
            cache = self.__dict__.setdefault("_dev_cache", {})
            if dev not in cache:
                cache[dev] = jax.device_put(build, dev)
            return cache[dev]

    def execute(self, ctx: ExecContext) -> PartitionSet:
        def it():
            yield self.broadcast_batch(ctx)

        return PartitionSet([it])

    def node_string(self):
        return "TpuBroadcastExchange"


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Hash join with a broadcast build (right) side: stream partitions stay
    put, each joins the one broadcast batch (GpuBroadcastHashJoinExec shims;
    build-side selection per the reference's
    shims/spark301/.../GpuBroadcastHashJoinExec.scala:63-75).

    right/full outer need BUILD-side null-extension: unmatched build rows
    must surface exactly ONCE globally even though every stream partition
    probes the same broadcast batch. Each partition accumulates its build
    match bits (host-side — per-device broadcast copies share row order);
    the LAST partition to finish ORs them and emits the unmatched tail. A
    partition abandoned early (its consumer stopped — e.g. a satisfied
    limit) skips the tail via GeneratorExit, which is sound: every consumer
    had stopped wanting rows."""

    _breaker_op = "BroadcastHashJoinExec"

    def execute(self, ctx: ExecContext) -> PartitionSet:
        left, right = self.children
        assert isinstance(right, TpuBroadcastExchangeExec)
        assert self.join_type in (
            "inner", "left", "left_semi", "left_anti", "right", "full",
        )
        lparts = left.execute(ctx)
        phase1 = self._phase1()
        phase2 = self._phase2()
        jt = self.join_type

        if jt not in ("right", "full"):
            def make(lt):
                def it():
                    yield from _stream_probe_join(
                        self,
                        lambda probe: right.broadcast_batch_like(ctx, probe),
                        lt,
                        phase1,
                        phase2,
                        jt,
                        ctx=ctx,
                    )

                return it

            return PartitionSet([make(lt) for lt in lparts.parts])

        state = {"remaining": len(lparts.parts), "mask": None, "emitted": False}
        lock = threading.Lock()

        def make_outer(lt):
            def it():
                acc = {"m": None}
                seen_build = {}

                def get_build(probe):
                    b = right.broadcast_batch_like(ctx, probe)
                    seen_build["b"] = b
                    if acc["m"] is None:
                        acc["m"] = jnp.zeros(b.capacity, dtype=bool)
                    return b

                done = False
                abandoned = False
                try:
                    yield from _stream_probe_join(
                        self, get_build, lt, phase1, phase2, jt, acc,
                        ctx=ctx,
                    )
                    done = True
                except GeneratorExit:
                    # consumer stopped wanting rows (e.g. satisfied limit):
                    # this partition is FINISHED for tail purposes
                    abandoned = True
                    raise
                finally:
                    with lock:
                        if acc["m"] is not None:
                            # merging a partial mask (failed/abandoned
                            # attempt) is safe: recorded matches are real,
                            # and a retry re-merges the complete mask.
                            # DEVICE-resident accumulation (the PR-1
                            # row-base pattern): the OR dispatches async —
                            # the old per-partition np.asarray pull paid a
                            # blocking host sync per finished partition.
                            # Masks from partitions placed on OTHER chips
                            # commit to the accumulator's device first
                            # (one bool[capacity] transfer per partition).
                            prev = state["mask"]
                            state["mask"] = (
                                acc["m"]
                                if prev is None
                                else prev | _colocated(prev, acc["m"])
                            )
                        # decrement once per FINISHED partition, never for a
                        # failed attempt — task retry (_run_task) re-runs the
                        # thunk and a per-attempt decrement would emit the
                        # tail early (duplicates) or mark it emitted with an
                        # incomplete mask (lost rows)
                        last = False
                        if done or abandoned:
                            state["remaining"] -= 1
                            last = (
                                state["remaining"] == 0
                                and not state["emitted"]
                            )
                            if last:
                                state["emitted"] = True
                    if last and done:
                        build = seen_build.get("b") or right.broadcast_batch(ctx)
                        mask = state["mask"]
                        rm = build.row_mask()
                        if mask is None:
                            mask = jnp.zeros(build.capacity, dtype=bool)
                        # the accumulated mask may live on another chip
                        # than this (last) partition's build replica
                        unmatched = (~_colocated(rm, mask)) & rm
                        yield self._null_extend(build, unmatched, "right")

            return it

        return PartitionSet([make_outer(lt) for lt in lparts.parts])

    def node_string(self):
        return (
            f"TpuBroadcastHashJoin {self.join_type} "
            f"[{', '.join(map(str, self.left_keys))}]"
        )


def _colocated(anchor, arr):
    """Commit ``arr`` to ``anchor``'s device when the two device arrays
    landed on different chips (placed partitions commit their batches —
    and so the per-partition match masks — to their own devices); an op
    over two differently-committed arrays raises in jax. No-op (and no
    transfer) when the devices already agree or placement is unsharded."""
    try:
        a_dev = anchor.devices()
        if arr.devices() != a_dev:
            (dev,) = a_dev
            arr = jax.device_put(arr, dev)
    except Exception:
        pass
    return arr


def _chunk_device_batch(db: DeviceBatch, rows: int):
    """Slice a device batch into static sub-batches of <= rows (shared by
    the nested-loop and cartesian pair loops)."""
    if db.capacity > rows:
        # graft: ok(host-sync: one sync per stream batch, to chunk the live
        # rows and not the capacity — an ungrouped aggregate's one row sits
        # in 16k slots and a pair batch's in MAX_PAIR_CAP, so a chain of
        # cross joins (TPC-DS q28) otherwise multiplies the chunk counts of
        # its stages)
        db = shrink_one(db, db.row_count())
    if db.capacity <= rows:
        yield db
        return
    n = db.capacity
    # graft: ok(cancel-beat: slices one already-resident batch; the
    # consuming join loop beats per chunk)
    for lo in range(0, max(n, 1), rows):
        idx = jnp.arange(rows, dtype=jnp.int32) + lo
        live = idx < db.num_rows
        cols = gather_columns(db.columns, idx, live)
        yield DeviceBatch(
            db.schema,
            cols,
            jnp.clip(db.num_rows - lo, 0, rows).astype(jnp.int32),
        )


class TpuBroadcastNestedLoopJoinExec(Exec):
    """Cross / conditional (non-equi) join on device.

    Reference: GpuBroadcastNestedLoopJoinExec.scala (Table.crossJoin +
    condition filter) and GpuCartesianProductExec.scala (pairwise batch
    cross join). TPU design: the pair space [n x m] is enumerated as a
    static-capacity flat index batch (li = k // m, ri = k % m), both sides
    gathered, the condition evaluated on the pairs, and matches compacted —
    one fused kernel per (shapes) pair; the stream side is chunked so the
    pair capacity stays bounded."""

    MAX_PAIR_CAP = 1 << 20

    def __init__(
        self,
        join_type: str,
        condition: Optional[Expression],
        left: Exec,
        right: Exec,
    ):
        super().__init__([left, right])
        self.join_type = join_type
        self._schema = join_output_schema(
            join_type, left.output.fields, right.output.fields
        )
        self.condition = (
            bind(condition, Schema(list(left.output.fields) + list(right.output.fields)))
            if condition is not None
            else None
        )

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def _pair_kernel(self):
        out_schema = self._schema
        condition = self.condition
        jt = self.join_type
        key = ("join_pair", jt, condition, out_schema)
        return K.counted_kernel(key, lambda: _make_pair_kernel(out_schema, condition, jt))


    def _null_extend(self, batch: DeviceBatch, keep: jax.Array, side: str) -> DeviceBatch:
        left_exec, right_exec = self.children
        return null_extend_batch(
            self._schema, batch, keep, side,
            left_exec.output.fields, right_exec.output.fields,
        )

    @staticmethod
    def _stream_rows(build_capacity: int) -> int:
        """Power-of-two stream-side chunk rows for a build of this size."""
        lrows = max(
            1, TpuBroadcastNestedLoopJoinExec.MAX_PAIR_CAP // max(build_capacity, 1)
        )
        p = 1
        while p * 2 <= lrows:
            p *= 2
        return p

    def execute(self, ctx: ExecContext) -> PartitionSet:
        left, right = self.children
        lparts = left.execute(ctx)
        kernel = self._pair_kernel()
        jt = self.join_type
        chunk = _chunk_device_batch

        def make(lt):
            def it():
                rparts = right.execute(ctx)
                rbatches = [b for t in rparts.parts for b in t()]
                build = (
                    concat_device(rbatches) if rbatches else empty_batch(right.output)
                )
                m = build.capacity
                lrows = self._stream_rows(m)
                build_matched = jnp.zeros(m, dtype=bool)
                tok = ctx.cancel_token
                for stream in lt():
                    for lb in chunk(stream, lrows):
                        if tok is not None:
                            tok.check()
                        out, lmatch, rmatch = kernel(lb, build)
                        build_matched = build_matched | rmatch
                        if jt in ("left_semi", "left_anti"):
                            want = lmatch if jt == "left_semi" else (
                                ~lmatch & lb.row_mask()
                            )
                            yield compact(lb, want)
                            continue
                        if jt in ("left", "full"):
                            unmatched = (~lmatch) & lb.row_mask()
                            yield self._null_extend(lb, unmatched, "left")
                        if out is not None:
                            yield out
                if jt in ("right", "full"):
                    unmatched = (~build_matched) & build.row_mask()
                    yield self._null_extend(build, unmatched, "right")

            return it

        # stream side is coalesced to one partition by the planner
        return PartitionSet([make(lt) for lt in lparts.parts])

    def node_string(self):
        return f"TpuBroadcastNestedLoopJoin {self.join_type} {self.condition or ''}"


def null_extend_batch(
    out_schema: Schema,
    batch: DeviceBatch,
    keep: jax.Array,
    side: str,
    left_fields,
    right_fields,
    right_ordinals=None,
) -> DeviceBatch:
    """Rows of one join side with the other side's columns as NULLs — shared
    by the hash and nested-loop joins' outer-extension paths. Cached fused
    kernel (one compact + null-column splice per call, not eager ops)."""
    lf, rf = tuple(left_fields), tuple(right_fields)
    ro = None if right_ordinals is None else tuple(right_ordinals)
    def make():
        def _join_null_extend(b, k):
            return _null_extend_impl(out_schema, b, k, side, lf, rf, ro)

        return _join_null_extend

    kernel = K.jit_kernel(("null_extend", out_schema, side, lf, rf, ro), make)
    return kernel(batch, keep)


def _null_extend_impl(
    out_schema: Schema,
    batch: DeviceBatch,
    keep: jax.Array,
    side: str,
    left_fields,
    right_fields,
    right_ordinals=None,
) -> DeviceBatch:
    sub = compact(batch, keep)
    cap = sub.capacity
    if side == "left":  # left rows + null right
        cols = list(sub.columns) + [_null_column(f, cap) for f in right_fields]
    else:  # null left + right rows
        ords = (
            right_ordinals
            if right_ordinals is not None
            else range(len(batch.columns))
        )
        cols = [_null_column(f, cap) for f in left_fields] + [
            sub.columns[i] for i in ords
        ]
    return DeviceBatch(out_schema, cols, sub.num_rows)


def _null_column(f: StructField, cap: int) -> DeviceColumn:
    from ..columnar.device import MIN_STR_WIDTH

    if isinstance(f.data_type, StringType):
        return DeviceColumn(
            f.data_type,
            jnp.zeros((cap, MIN_STR_WIDTH), jnp.uint8),
            jnp.zeros(cap, bool),
            jnp.zeros(cap, jnp.int32),
        )
    return DeviceColumn(
        f.data_type,
        jnp.zeros(cap, f.data_type.np_dtype),
        jnp.zeros(cap, bool),
    )

# Each jitted function has a name of its own, so that a device trace reads
# jit__join_bounds, jit__join_pairs, jit__join_cross_pairs and
# jit__join_null_extend and not four times jit_fn.
def _make_phase1(left_keys: tuple, right_keys: tuple):
    def _join_bounds(build: DeviceBatch, probe: DeviceBatch):
        bctx = Ctx.for_device(build)
        pctx = Ctx.for_device(probe)
        bcols = [val_to_column(bctx, k.eval(bctx), k.data_type) for k in right_keys]
        pcols = [val_to_column(pctx, k.eval(pctx), k.data_type) for k in left_keys]
        # unify string widths across sides per key position
        for i, (b, p) in enumerate(zip(bcols, pcols)):
            if isinstance(b.dtype, StringType):
                w = max(b.data.shape[1], p.data.shape[1])
                bcols[i] = pad_string_column(b, w)
                pcols[i] = pad_string_column(p, w)
        build_order, lower, upper = join_bounds(
            bcols, build.row_mask(), pcols, probe.row_mask()
        )
        counts = upper - lower
        return build_order, lower, counts

    return _join_bounds


def _bound_ordinals(e, into: set) -> set:
    """The input ordinals an expression reads (``BoundReference`` is the one
    reader of ``Ctx.columns``)."""
    if isinstance(e, BoundReference):
        into.add(e.ordinal)
    for c in e.children():
        _bound_ordinals(c, into)
    return into


def _gather_read(cols, read, idx, idx_valid) -> list:
    """``cols`` through ``idx``, ``None`` where an ordinal is not in ``read``."""
    read = sorted(read)
    out: list = [None] * len(cols)
    for i, c in zip(read, gather_columns([cols[i] for i in read], idx, idx_valid)):
        out[i] = c
    return out


def _gather_sides(left, right, li, ri, pair_live, condition_ords, semi, right_out):
    """Both sides' columns at their pair indices. A stack is gathered whole,
    so only what is read is handed over: the condition's columns, and unless
    the join is a semi or anti join (which returns no pairs) the left side
    and the ``right_out`` ordinals of the right."""
    nl = len(left)
    lread = {o for o in condition_ords if o < nl}
    rread = {o - nl for o in condition_ords if o >= nl}
    if not semi:
        lread, rread = range(nl), rread | set(right_out)
    return (
        _gather_read(left, lread, li, pair_live),
        _gather_read(right, rread, ri, pair_live),
    )


def _make_phase2(out_schema: Schema, right_ords: tuple, jt: str, residual):
    residual_ords = set() if residual is None else _bound_ordinals(residual, set())

    def _join_pairs(
            build: DeviceBatch,
            probe: DeviceBatch,
            build_order,
            lower,
            counts,
            out_cap_arr,
        ):
            out_cap = out_cap_arr.shape[0]
            probe_idx, build_idx, pair_live, total = gather_pairs(
                build_order, lower, counts, probe.row_mask(), out_cap
            )
            semi = jt in ("left_semi", "left_anti")
            lcols, rcols_all = _gather_sides(
                probe.columns, build.columns, probe_idx, build_idx, pair_live,
                residual_ords, semi, right_ords,
            )
            live = pair_live
            if residual is not None:
                rctx = Ctx(
                    jnp,
                    out_cap,
                    True,
                    [
                        None if c is None else Val(c.data, c.validity, c.lengths)
                        for c in lcols + rcols_all
                    ],
                    total,
                )
                rv = residual.eval(rctx)
                keep = rctx.broadcast_bool(rv.data) & rv.full_valid(rctx) & pair_live
                live = keep
            # per-probe / per-build matched flags (for outer joins)
            npr = probe.capacity
            nb = build.capacity
            probe_matched = (
                jnp.zeros(npr, bool).at[jnp.where(live, probe_idx, npr)].set(True, mode="drop")
            )
            build_matched = (
                jnp.zeros(nb, bool).at[jnp.where(live, build_idx, nb)].set(True, mode="drop")
            )
            if semi:
                want = probe_matched if jt == "left_semi" else (
                    ~probe_matched & probe.row_mask()
                )
                return compact(probe, want), probe_matched, build_matched
            cols = lcols + [rcols_all[i] for i in right_ords]
            # num_rows = full capacity: live pairs are scattered across the
            # pair grid, so compact must see every slot (its keep mask is
            # intersected with row_mask)
            out = DeviceBatch(
                out_schema,
                [
                    dc_replace(c, validity=c.validity & live)
                    for c in cols
                ],
                jnp.asarray(out_cap, jnp.int32),
            )
            out = compact(out, live)
            return out, probe_matched, build_matched

    return _join_pairs


def _make_pair_kernel(out_schema: Schema, condition, jt: str):
    condition_ords = set() if condition is None else _bound_ordinals(condition, set())

    def _join_cross_pairs(lb: DeviceBatch, rb: DeviceBatch):
            n, m = lb.capacity, rb.capacity
            cap = n * m
            li = jnp.arange(cap, dtype=jnp.int32) // m
            ri = jnp.arange(cap, dtype=jnp.int32) % m
            pair_live = (li < lb.num_rows) & (ri < rb.num_rows)
            semi = jt in ("left_semi", "left_anti")
            lcols, rcols = _gather_sides(
                lb.columns, rb.columns, li, ri, pair_live,
                condition_ords, semi, range(len(rb.columns)),
            )
            live = pair_live
            if condition is not None:
                cctx = Ctx(
                    jnp,
                    cap,
                    True,
                    [
                        None if c is None else Val(c.data, c.validity, c.lengths)
                        for c in lcols + rcols
                    ],
                    live.sum().astype(jnp.int32),
                )
                cv = condition.eval(cctx)
                live = cctx.broadcast_bool(cv.data) & cv.full_valid(cctx) & pair_live
            # matched flags per side row (outer/semi/anti bookkeeping)
            left_matched = (
                jnp.zeros(n, bool).at[jnp.where(live, li, n)].set(True, mode="drop")
            )
            right_matched = (
                jnp.zeros(m, bool).at[jnp.where(live, ri, m)].set(True, mode="drop")
            )
            if semi:
                return None, left_matched, right_matched
            # num_rows = cap: live pairs are scattered over the [n x m] grid
            # and compact intersects its keep mask with row_mask
            out = DeviceBatch(
                out_schema,
                [
                    dc_replace(c, validity=c.validity & live)
                    for c in lcols + rcols
                ],
                jnp.asarray(cap, jnp.int32),
            )
            return compact(out, live), left_matched, right_matched

    return _join_cross_pairs


class TpuCartesianProductExec(TpuBroadcastNestedLoopJoinExec):
    """Pairwise-partition cross join — GpuCartesianProductExec.scala:349.

    Where the nested-loop join concatenates/broadcasts one side, this exec
    schedules n_left × n_right tasks, each crossing ONE (left, right)
    partition pair through the same fused pair kernel. Only cross/inner
    shapes plan here (outer variants need global matched-set bookkeeping and
    stay on the NLJ path — same split as the reference)."""

    def execute(self, ctx: ExecContext) -> PartitionSet:
        left, right = self.children
        lparts = left.execute(ctx)
        rparts = right.execute(ctx)
        kernel = self._pair_kernel()

        chunk = _chunk_device_batch

        def make(lt, rt):
            def it():
                rbatches = list(rt())
                build = (
                    concat_device(rbatches) if rbatches else empty_batch(right.output)
                )
                p = self._stream_rows(build.capacity)
                tok = ctx.cancel_token
                for stream in lt():
                    for lb in chunk(stream, p):
                        if tok is not None:
                            tok.check()
                        out, _lm, _rm = kernel(lb, build)
                        if out is not None:
                            yield out

            return it

        return PartitionSet(
            [make(lt, rt) for lt in lparts.parts for rt in rparts.parts]
        )

    def node_string(self):
        return f"TpuCartesianProduct {self.condition or ''}"
