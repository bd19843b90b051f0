"""TPU physical operators — the GpuExec family.

Reference analogues: basicPhysicalOperators.scala (GpuProjectExec,
GpuFilterExec), aggregate.scala (GpuHashAggregateExec), GpuSortExec.scala,
GpuShuffleExchangeExec + GpuPartitioning, GpuTransitionOverrides' transitions.

Each operator compiles ONE fused XLA program per (expression tree, schema,
capacity) via jax.jit over DeviceBatch pytrees; the device semaphore gates
first touch of the device per partition-task (GpuSemaphore protocol).

Kernels live in the module-level ``kernels`` cache keyed by bound expression
trees + schemas — NOT on exec instances — so re-running a query (which
rebuilds the exec tree) reuses every compiled program. See kernels.py.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from ..columnar.device import (
    DeviceBatch,
    DeviceColumn,
    bucket_capacity,
    dc_replace,
    device_to_host,
    empty_batch,
    host_to_device,
)
from ..columnar.host import concat_batches
from ..expr import Expression, bind, output_name
from ..expr.aggregates import AggregateFunction
from ..expr.base import BoundReference, Ctx, Val
from ..expr.misc import contains_task_dependent
from . import task
from ..ops.aggregate import group_aggregate
from ..ops.concat import concat_device
from ..ops.gather import bulk_shrink, compact, gather_batch, gather_columns
from ..ops.hash import murmur3_rows, partition_ids
from ..ops.sortkeys import packed_key, packed_sort
from ..plan.logical import SortOrder
from ..plan.physical import Exec, ExecContext, PartitionSet
from ..types import Schema, StringType, StructField
from .. import kernels as K
from ..obs import ledger as obs_ledger


_lscope = obs_ledger.scope_or_null


def val_to_column(ctx: Ctx, val: Val, dtype) -> DeviceColumn:
    """Materialize an expression result into a full DeviceColumn."""
    from ..types import ArrayType, MapType, StructType

    if isinstance(dtype, (ArrayType, MapType)):
        lengths = ctx.broadcast(val.lengths).astype(jnp.int32)
        return DeviceColumn(dtype, None, val.full_valid(ctx), lengths, val.children)
    if isinstance(dtype, StructType):
        return DeviceColumn(dtype, None, val.full_valid(ctx), None, val.children)
    if isinstance(dtype, StringType):
        data = val.data
        if data.ndim == 1:  # scalar string literal [w]
            data = jnp.broadcast_to(data[None, :], (ctx.n, data.shape[0]))
        lengths = jnp.broadcast_to(jnp.asarray(val.lengths), (ctx.n,))
        return DeviceColumn(dtype, data, val.full_valid(ctx), lengths)
    data = ctx.broadcast(val.data)
    if data.dtype != dtype.np_dtype:
        data = data.astype(dtype.np_dtype)
    return DeviceColumn(dtype, data, val.full_valid(ctx))


# ── transitions ─────────────────────────────────────────────────────────────


def _row_bytes(schema: Schema) -> int:
    """Rough per-row device footprint for batch-size targeting."""
    total = 0
    for f in schema:
        dt = f.data_type
        if isinstance(dt, StringType):
            total += 64  # padded bytes + lengths, typical bucket
        else:
            try:
                total += dt.np_dtype.itemsize
            except Exception:
                total += 16
        total += 1  # validity
    return max(total, 1)


def _expr_has_error_site(e) -> bool:
    """Fusion guard: expressions that raise through the kernel error
    channel (ANSI casts, split's maxTokens overflow) must keep their
    standalone kernel — a fused copy would silently swallow the error."""
    from ..expr.cast import Cast as _Cast
    from ..expr.strings_ext import StringSplit as _Split

    if isinstance(e, _Cast) and e.ansi:
        return True
    if isinstance(e, _Split):
        return True
    return any(_expr_has_error_site(c) for c in e.children())


def _upload_cache_budget(conf) -> int:
    """H2D upload-cache byte budget (spark.rapids.tpu.uploadCache.maxBytes):
    explicit when set; else a quarter of the device's reported byte limit;
    on the CPU backend, which reports none, 4 GiB."""
    from .. import config as cfg
    from ..mem import device_bytes_limit

    b = cfg.UPLOAD_CACHE_MAX_BYTES.get(conf)
    if b > 0:
        return b
    total = device_bytes_limit()
    return total // 4 if total else 4 << 30


def _placed_partitions(ctx: "ExecContext", pset: PartitionSet) -> PartitionSet:
    """Mesh mode: commit partition p's batches to device p%n so per-partition
    kernels run data-parallel across chips from the scan onward (single-
    device mode passes through untouched)."""
    if ctx.mesh is None:
        return pset
    from ..parallel.mesh import put_batch

    mc = ctx.mesh

    def make(p, t):
        def it():
            dev = mc.device_for(p)
            # graft: ok(cancel-beat: upstream partition iterator beats per
            # batch; put_batch is one async device placement)
            for db in t():
                yield put_batch(db, dev)

        return it

    return PartitionSet([make(p, t) for p, t in enumerate(pset.parts)])


class HostToDeviceExec(Exec):
    """Host Arrow batches → device batches (HostColumnarToGpu analogue).

    Incoming batches are re-chunked to ``spark.rapids.sql.batchSizeBytes``
    (the CoalesceGoal TargetSize contract — GpuExec.scala:173-188) so one
    oversized host batch cannot blow the device working set."""

    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        from .. import config as cfg

        schema = self.output
        max_rows = max(
            1, cfg.BATCH_SIZE_BYTES.get(ctx.conf) // _row_bytes(schema)
        )
        max_str = cfg.STRING_MAX_BYTES.get(ctx.conf)
        rows_m = self.metric("numInputRows", "ESSENTIAL")
        time_m = self.metric("hostToDeviceTime", "MODERATE")
        bytes_m = self.metric("hostToDeviceBytes", "MODERATE")
        timing = self.metrics_on(ctx, "MODERATE")

        led = getattr(ctx, "ledger", None)

        def fn(it):
            tok = ctx.cancel_token
            for rb in it:
                if tok is not None:
                    tok.check()  # sched/: stop uploads at batch boundaries
                if rb.num_rows == 0:
                    continue
                rows_m.add(rb.num_rows)
                bytes_m.add(rb.nbytes)
                for off in range(0, rb.num_rows, max_rows):
                    if tok is not None:
                        tok.check()  # beat per uploaded chunk, not just
                        # per host batch — one oversized source batch
                        # re-chunks into many uploads
                    chunk = (
                        rb
                        if rb.num_rows <= max_rows
                        else rb.slice(off, max_rows)
                    )
                    ctx.semaphore.acquire_if_necessary()
                    # scopes close BEFORE the yield: a ledger phase (and
                    # the transfer timer) must measure the upload, not the
                    # consumer's work while this generator is suspended
                    if timing:
                        with _lscope(led, "h2d"), time_m.timed():
                            db = host_to_device(chunk, max_str_bytes=max_str)
                    else:
                        with _lscope(led, "h2d"):
                            db = host_to_device(chunk, max_str_bytes=max_str)
                    yield db
                    if rb.num_rows <= max_rows:
                        break

        child = self.children[0]
        from .cpu import CpuScanExec

        if isinstance(child, CpuScanExec) and ctx.session is not None:
            # Session-level upload cache for in-memory relations: repeated
            # collects over the same (immutable) arrow table reuse the
            # device-resident batches instead of re-padding + re-uploading —
            # the device analogue of Spark's in-memory scan staying hot.
            # The cached entry holds a reference to the source table, so
            # id() stays valid for the session's lifetime.
            key = (
                "h2d",
                id(child.source),
                child.num_partitions,
                K.schema_key(schema),  # field names participate here
                max_rows,
                max_str,
            )
            import threading

            # concurrent queries race this LRU (get/insert vs evict-pop →
            # KeyError, double-insert): all cache BOOKKEEPING serializes
            # under one session lock; the uploads themselves stay outside it
            with ctx.session._h2d_lock:
                cache = ctx.session._h2d_cache
                entry = cache.get(key)
                if entry is None:
                    entry = {
                        # pin BOTH: the source anchors the cache key's id()
                        # across pruning passes, the pruned table backs the
                        # uploaded batches
                        "table": (child.source, child.table),
                        "parts": [None] * child.num_partitions,
                        "rows": [0] * child.num_partitions,
                        # per-partition in-flight build event (single-
                        # flight: concurrent cold queries must not each
                        # upload the partition — N transient HBM copies
                        # would defeat the scheduler's admission budget)
                        "building": [None] * child.num_partitions,
                        "lock": threading.Lock(),
                    }
                    # BYTES-bounded LRU: cached uploads are plain references
                    # (never registered with the spill catalog), so this bound
                    # is the ONLY thing standing between many-table sessions
                    # and pinned-HBM OOM. The old 4-ENTRY bound thrashed on
                    # TPC-H's 8-table star schema, re-uploading every table
                    # each run; a byte budget keeps whole star schemas
                    # resident while still evicting when the cached set
                    # actually grows large.
                    # Arrow nbytes underestimates the padded device footprint —
                    # ~2x covers pow2 row padding; string byte-planes can
                    # exceed it, which only makes eviction earlier (safe side).
                    new_bytes = 2 * child.table.nbytes
                    budget = _upload_cache_budget(ctx.conf)
                    held = sum(c.get("est_bytes", 0) for c in cache.values())
                    while cache and held + new_bytes > budget:
                        old = cache.pop(next(iter(cache)))  # LRU head
                        held -= old.get("est_bytes", 0)
                    entry["est_bytes"] = new_bytes
                    cache[key] = entry
                else:
                    cache[key] = cache.pop(key)  # refresh LRU order
            child_parts = child.execute(ctx)

            def make_cached(p, thunk):
                def it():
                    # single-flight per partition: one builder uploads, the
                    # rest wait on its event and replay; a failed builder
                    # clears its event so a waiter takes over (same
                    # contract as the session's df.cache() store)
                    tok = ctx.cancel_token
                    while True:
                        with entry["lock"]:
                            built = entry["parts"][p]
                            ev = entry["building"][p]
                            builder = built is None and ev is None
                            if builder:
                                ev = entry["building"][p] = threading.Event()
                        if built is not None:
                            # replay: keep the metric honest, no device sync
                            rows_m.add(entry["rows"][p])
                            for db in built:
                                if tok is not None:
                                    tok.check()
                                ctx.semaphore.acquire_if_necessary()
                                yield db
                            return
                        if builder:
                            n_before = rows_m.value
                            try:
                                out = list(fn(thunk()))
                                with entry["lock"]:
                                    entry["parts"][p] = out
                                    entry["rows"][p] = rows_m.value - n_before
                            finally:
                                with entry["lock"]:
                                    entry["building"][p] = None
                                ev.set()
                            for db in out:
                                if tok is not None:
                                    tok.check()
                                yield db
                            return
                        # another query is uploading this partition: wait
                        # for it (cancellable — this thread's own token
                        # still fires at its admission deadline/cancel)
                        while not ev.wait(0.05):
                            if tok is not None:
                                tok.check()

                return it

            return _placed_partitions(
                ctx,
                PartitionSet(
                    [make_cached(p, t) for p, t in enumerate(child_parts.parts)]
                ),
            )

        return _placed_partitions(ctx, child.execute(ctx).map_partitions(fn))


class DeviceToHostExec(Exec):
    """Device batches → host Arrow (GpuColumnarToRow/GpuBringBackToHost)."""

    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute(self, ctx: ExecContext) -> PartitionSet:
        rows_m = self.metric("numOutputRows", "ESSENTIAL")
        time_m = self.metric("deviceToHostTime", "MODERATE")
        bytes_m = self.metric("deviceToHostBytes", "MODERATE")
        timing = self.metrics_on(ctx, "MODERATE")
        led = getattr(ctx, "ledger", None)

        # speculate only below execs whose results are usually tiny
        # relative to capacity (a big scan/filter single batch would pay a
        # guaranteed-wasted prefix round trip)
        def _result_shrinking(node) -> bool:
            while isinstance(
                node, (TpuCoalescePartitionsExec, TpuCoalesceBatchesExec)
            ):
                node = node.children[0]
            return isinstance(
                node,
                (
                    TpuHashAggregateExec,
                    TpuTakeOrderedAndProjectExec,
                    TpuLimitExec,
                ),
            )

        speculate = _result_shrinking(self.children[0])

        def fn(it):
            from itertools import islice

            from ..ops.concat import concat_device
            from ..ops.gather import bulk_shrink

            tok = ctx.cancel_token
            while True:
                if tok is not None:
                    tok.check()  # beat per D2H window: the pull below is
                    # where a collect() spends its host time
                # shrink to the live bucket before packing: the pack kernel
                # flattens the whole capacity, so a 6-row aggregate output in
                # a 512k-capacity batch would otherwise ship ~30MB over PJRT.
                # Windowed so at most 8 batches are held on device at once.
                chunk = list(islice(it, 8))
                if not chunk:
                    return
                shrunk = None
                if speculate and len(chunk) == 1:
                    # single batch below a result-shrinking exec (aggregate
                    # / TopN / limit): try the ONE-round-trip speculative
                    # pull before paying the shrink sync + pull pair
                    from ..columnar.device import device_to_host_speculative
                    from ..ops.gather import shrink_one

                    # device-completion wait separated from the copy: the
                    # block costs nothing extra (the transfer would wait
                    # anyway) and splits the ledger's 'device_execute'
                    # from 'd2h' at the only point the host truly waits
                    if led is not None:
                        with led.scope("device_execute"):
                            # graft: ok(host-sync: ledger attribution split
                            # — the D2H pull below would block here anyway)
                            jax.block_until_ready(chunk[0])
                    if timing:
                        with _lscope(led, "d2h"), time_m.timed():
                            rb, n_true = device_to_host_speculative(chunk[0])
                    else:
                        with _lscope(led, "d2h"):
                            rb, n_true = device_to_host_speculative(chunk[0])
                    if rb is not None:
                        ctx.semaphore.release_if_necessary()
                        if rb.num_rows:
                            rows_m.add(rb.num_rows)
                            bytes_m.add(rb.nbytes)
                            yield rb
                        continue
                    if n_true is not None:
                        # the count came back with the failed speculation —
                        # shrink without a second sync (and skip bulk_shrink,
                        # whose row-count fetch would re-pay that sync)
                        shrunk = [shrink_one(chunk[0], n_true, tight=False)]
                if shrunk is None:
                    # lattice-quantized (tight=False): the pack kernel keeps
                    # one stable geometry per shape bucket instead of
                    # compiling per live-row count — still cuts sparse
                    # multi-k capacities down to the floor
                    shrunk = bulk_shrink(chunk, tight=False)
                # merge SMALL shrunk batches on device: every pull is a host
                # sync that stalls dispatch, so 8 tiny result batches go as
                # one packed transfer, not 8
                if (
                    len(shrunk) > 1
                    and sum(b.capacity for b in shrunk) <= (1 << 16)
                ):
                    shrunk = [concat_device(shrunk)]
                for db in shrunk:
                    if tok is not None:
                        tok.check()
                    from ..mem.spill import with_oom_retry

                    pull = lambda b: device_to_host(b, shrink=False)  # noqa: E731
                    if led is not None:
                        with led.scope("device_execute"):
                            # graft: ok(host-sync: ledger attribution split
                            # — the D2H pull below would block here anyway)
                            jax.block_until_ready(db)
                    if timing:
                        with _lscope(led, "d2h"), time_m.timed():
                            rb = with_oom_retry(ctx.catalog, pull, db)
                    else:
                        with _lscope(led, "d2h"):
                            rb = with_oom_retry(ctx.catalog, pull, db)
                    ctx.semaphore.release_if_necessary()
                    if rb.num_rows:
                        rows_m.add(rb.num_rows)
                        bytes_m.add(rb.nbytes)
                        yield rb

        # Dispatch-ahead pipelining (exec/pipeline.py): the D2H pull above
        # blocks a full host round trip per window; driving the upstream
        # chain from a producer thread keeps batches i+1..k dispatching on
        # device while this sink blocks on batch i. Conf and metrics
        # resolve HERE, on the single-threaded plan walk — partition
        # thunks race on a thread pool.
        from .pipeline import pipe_metrics, pipeline_conf, pipelined_partition

        pconf = pipeline_conf(ctx)
        metrics = pipe_metrics(self, ctx) if pconf is not None else None

        def run(it):
            return pipelined_partition(pconf, ctx, it, fn, metrics)

        return self.children[0].execute(ctx).map_partitions(run)


# ── compute execs ───────────────────────────────────────────────────────────


class TpuRangeExec(Exec):
    """Device-side sequence generation (GpuRangeExec,
    basicPhysicalOperators.scala) — ids are born on device, no H2D copy."""

    def __init__(self, cpu_range):
        super().__init__([])
        self._cpu = cpu_range
        self._schema = cpu_range.output

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def _fn(self, cap: int):
        schema = self._schema
        step = self._cpu.step

        def make():
            def gen(first, m):
                ids = first + step * jnp.arange(cap, dtype=jnp.int64)
                valid = jnp.arange(cap, dtype=jnp.int32) < m
                from ..types import LONG

                col = DeviceColumn(LONG, jnp.where(valid, ids, 0), valid)
                return DeviceBatch(schema, [col], m.astype(jnp.int32))

            return gen

        return K.jit_kernel(("range", step, cap, schema), make)

    def execute(self, ctx: ExecContext) -> PartitionSet:
        from .. import config as cfg

        batch_rows = cfg.BATCH_SIZE_ROWS.get(ctx.conf)
        start, step = self._cpu.start, self._cpu.step
        parts = []
        for lo, cnt in self._cpu.partition_bounds():
            def make(lo=lo, cnt=cnt):
                def it():
                    ctx.semaphore.acquire_if_necessary()
                    tok = ctx.cancel_token
                    done = 0
                    while done < cnt:
                        if tok is not None:
                            tok.check()
                        m = min(batch_rows, cnt - done)
                        cap = bucket_capacity(max(m, 1))
                        first = start + (lo + done) * step
                        yield self._fn(cap)(
                            jnp.asarray(first, dtype=jnp.int64),
                            jnp.asarray(m, dtype=jnp.int32),
                        )
                        done += m

                return it()

            parts.append(make)
        return PartitionSet(parts)

    def node_string(self):
        c = self._cpu
        return f"TpuRange ({c.start}, {c.end}, step={c.step}, splits={c.num_partitions})"


class _ErrorCheckingKernel:
    """Wraps a jitted kernel returning ``(out, err_flags)``: raises
    ``AnsiError`` host-side when a flag fires (one sync per batch, and only
    for kernels whose expression tree registered error sites — non-ANSI
    queries return a statically-empty flag vector and never sync)."""

    def __init__(self, fn, sites: list):
        self._fn = fn
        self._sites = sites

    def __call__(self, batch, tvals):
        out, errs = self._fn(batch, tvals)
        if errs.shape[0]:
            import numpy as np

            from ..expr.base import AnsiError

            # graft: ok(host-sync: ANSI error-site check — kernels with
            # registered error expressions must surface the raise at THIS
            # batch; non-ANSI trees return a statically-empty flag vector
            # and never reach this sync)
            flags = np.asarray(errs)
            if flags.any():
                raise AnsiError(self._sites[int(np.argmax(flags))])
        return out

    def _cache_size(self):
        cs = getattr(self._fn, "_cache_size", None)
        return cs() if callable(cs) else 0

    def warm(self, *args) -> bool:
        """Pre-compilation passthrough (plan/planner.py precompile_plan)."""
        return self._fn.warm(*args)


def _error_flags(ctx: Ctx, live, sites: list):
    """Collect ANSI error sites registered during tracing into a flag vector
    (and capture their messages — tracing runs this Python code, so the
    closure list is filled before the first batch result is consumed)."""
    import jax.numpy as jnp

    sites[:] = [m for m, _ in ctx.errors]
    if not ctx.errors:
        return jnp.zeros((0,), dtype=bool)
    return jnp.stack([(mask & live).any() for _, mask in ctx.errors])


def project_kernel(exprs: tuple, schema: Schema):
    """Fused projection kernel, cached by (bound exprs, output schema)."""

    def make():
        import jax

        sites: list = []

        def _project(batch: DeviceBatch, tvals):
            c = Ctx.for_device(batch, task=tvals)
            cols = [val_to_column(c, e.eval(c), e.data_type) for e in exprs]
            # keep padding rows inert
            live = batch.row_mask()
            cols = [
                dc_replace(col, validity=col.validity & live)
                for col in cols
            ]
            errs = _error_flags(c, live, sites)
            return DeviceBatch(schema, cols, batch.num_rows), errs

        return _ErrorCheckingKernel(K.GuardedJit(_project), sites)

    return K.kernel(("project", exprs, schema), make)


def filter_kernel(condition: Expression):
    def make():
        import jax

        sites: list = []

        def _filter(batch: DeviceBatch, tvals):
            c = Ctx.for_device(batch, task=tvals)
            v = condition.eval(c)
            keep = c.broadcast_bool(v.data) & v.full_valid(c)
            errs = _error_flags(c, batch.row_mask(), sites)
            return compact(batch, keep), errs

        return _ErrorCheckingKernel(K.GuardedJit(_filter), sites)

    return K.kernel(("filter", condition), make)


class TpuProjectExec(Exec):
    def __init__(
        self,
        exprs: List[Expression],
        child: Exec,
        schema: Optional[Schema] = None,
    ):
        super().__init__([child])
        self.exprs = [bind(e, child.output) for e in exprs]
        # converted plans pass the CPU exec's schema: their exprs are already
        # bound, so output_name() would yield colN placeholders — and the
        # kernel bakes the schema into the DeviceBatch it emits
        self._schema = schema or Schema(
            [
                StructField(output_name(e0), e.data_type, e.nullable)
                for e0, e in zip(exprs, self.exprs)
            ]
        )
        self._needs_task = any(contains_task_dependent(e) for e in self.exprs)
        self._fn = project_kernel(tuple(self.exprs), self._schema)

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        fn = self._fn
        needs_task = self._needs_task

        def run(it):
            # splittable-operator opt-in: OOM at a launch spills, retries,
            # then recursively halves the batch (resilience/retry.py)
            return task.run_device(
                fn, it, needs_task, catalog=ctx.catalog,
                policy=ctx.retry_policy, op="ProjectExec",
                breaker=ctx.breaker, token=ctx.cancel_token,
            )

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        return f"TpuProject [{', '.join(map(str, self.exprs))}]"


class TpuFilterExec(Exec):
    def __init__(self, condition: Expression, child: Exec):
        super().__init__([child])
        self.condition = bind(condition, child.output)

        self._needs_task = contains_task_dependent(self.condition)
        self._fn = filter_kernel(self.condition)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        fn = self._fn
        needs_task = self._needs_task

        def run(it):
            # splittable: a filter over concat(a, b) is concat(filter(a),
            # filter(b)) — halves yield independently under OOM pressure
            return task.run_device(
                fn, it, needs_task, catalog=ctx.catalog,
                policy=ctx.retry_policy, op="FilterExec",
                breaker=ctx.breaker, token=ctx.cancel_token,
            )

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        return f"TpuFilter {self.condition}"


class TpuUnionExec(Exec):
    def __init__(self, children: List[Exec]):
        super().__init__(children)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        parts = []
        for c in self.children:
            parts.extend(c.execute(ctx).parts)
        return PartitionSet(parts)


class TpuCoalescePartitionsExec(Exec):
    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        from .. import config as cfg

        child_parts = self.children[0].execute(ctx)
        n_workers = min(
            len(child_parts.parts), cfg.CONCURRENT_TPU_TASKS.get(ctx.conf)
        )

        def it():
            if n_workers <= 1 or len(child_parts.parts) == 1:
                # graft: ok(cancel-beat: delegates to the upstream
                # partition iterators, which beat per batch)
                for t in child_parts.parts:
                    yield from t()
                return
            # drive child partitions concurrently (each per-partition chain
            # of kernel dispatches has its own host syncs; overlapping them is the
            # executor-task-slot model this node would otherwise collapse).
            # At most n_workers partitions are buffered at once (memory
            # bound), and each worker returns its semaphore permit when its
            # partition completes.
            from concurrent.futures import ThreadPoolExecutor

            # straggler speculation (sched/speculation.py): this node IS
            # the engine's executor-task-slot surface — the coalesce of a
            # collect() drives every leaf partition — so the monitor
            # watches HERE. A partition past the runtime bar gets a
            # duplicate attempt of the same pure thunk; first commit wins,
            # the loser unwinds through an attempt-scoped child token.
            spec = None
            token = getattr(ctx, "cancel_token", None)
            if cfg.SPECULATION_ENABLED.get(ctx.conf) and token is not None:
                from ..sched.speculation import SpeculationMonitor

                scheduler = getattr(ctx.session, "_scheduler", None)
                spec = SpeculationMonitor.from_conf(
                    ctx.conf, ctx=ctx, token=token,
                    pool=getattr(scheduler, "pool", None),
                    n_partitions=len(child_parts.parts),
                )

            def run_one(i, t):
                from ..resilience import faults as _faults

                if spec is None:
                    try:
                        _faults.on_task_attempt(i, 0, token)
                        return list(t())
                    finally:
                        ctx.semaphore.release_if_necessary()

                def attempt(attempt_token):
                    try:
                        # chaos straggler point: the first attempt of the
                        # configured partition crawls; a duplicate runs free
                        _faults.on_task_attempt(i, 0, attempt_token)
                        return list(t())
                    finally:
                        # primary runs on this worker thread, a duplicate
                        # on the monitor's — each returns its own permit
                        ctx.semaphore.release_if_necessary()

                return spec.run_partition(i, attempt)

            parts = child_parts.parts
            try:
                with ThreadPoolExecutor(max_workers=n_workers) as pool:
                    pending = {
                        i: pool.submit(run_one, i, parts[i])
                        for i in range(min(n_workers, len(parts)))
                    }
                    nxt = len(pending)
                    # graft: ok(cancel-beat: the worker threads drive the
                    # upstream iterators (which beat per batch); a cancel
                    # raises inside run_one and surfaces through result())
                    for i in range(len(parts)):
                        batches = pending.pop(i).result()
                        if nxt < len(parts):
                            pending[nxt] = pool.submit(run_one, nxt, parts[nxt])
                            nxt += 1
                        yield from batches
            finally:
                if spec is not None:
                    spec.close()

        return PartitionSet([it])


# Largest [capacity, W] collect element plane the device path will build
# (~1GB of int64). Beyond it (one group holding most of a huge input) the
# padded layout is the wrong tool — the query fails with the kill-switch
# hint instead of OOMing the device.
_COLLECT_PLANE_LIMIT = 1 << 27


class TpuHashAggregateExec(Exec):
    """Sort-based group-by on device; one phase (partial|final|complete).

    The reference's hot loop (aggregate.scala:406-468) is: per-batch update
    aggregate → concat partials → merge aggregate. Here both update and merge
    are the same fused kernel with different reduce ops.
    """

    def __init__(
        self,
        mode: str,
        grouping: List[Expression],
        agg_fns: List[AggregateFunction],
        result_exprs: Optional[List[Expression]],
        result_names: Optional[List[str]],
        child: Exec,
    ):
        super().__init__([child])
        self.mode = mode
        self.grouping = [bind(g, child.output) for g in grouping]
        self.agg_fns = list(agg_fns)
        self.result_exprs = None if result_exprs is None else list(result_exprs)
        self.result_names = None if result_names is None else list(result_names)
        self._schema = self._compute_schema(child)

    def _compute_schema(self, child: Exec) -> Schema:
        fields = []
        for g in self.grouping:
            fields.append(StructField(output_name(g), g.data_type, g.nullable))
        if self.mode == "partial":
            for i, f in enumerate(self.agg_fns):
                for j, bt in enumerate(f.buffer_types):
                    fields.append(StructField(f"buf{i}_{j}", bt, True))
            return Schema(fields)
        assert self.result_exprs is not None
        return Schema(
            [
                StructField(name, e.data_type, e.nullable)
                for name, e in zip(self.result_names, self.result_exprs)
            ]
        )

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def _buffer_ordinal(self, f: AggregateFunction, j: int) -> int:
        return _buffer_ordinal(self.grouping, self.agg_fns, f, j)

    def _make_kernel(
        self, child_schema: Schema, pre_filter=None, has_nans=True,
        collect_width: int = 0,
    ):
        return aggregate_kernel(
            self.mode,
            tuple(self.grouping),
            tuple(self.agg_fns),
            None if self.result_exprs is None else tuple(self.result_exprs),
            self._schema,
            child_schema,
            pre_filter,
            has_nans,
            collect_width,
        )

    @property
    def _has_collect(self) -> bool:
        return any(
            op in ("collect_list", "collect_set")
            for f in self.agg_fns
            for op in f.update_ops
        )

    def _width_kernel(self, child_schema: Schema, pre_filter, has_nans):
        """Max-group-size pre-pass for the collect plane width (one host
        sync per partition — the join sizes its output buckets the same
        way)."""
        grouping = tuple(self.grouping)

        def make():
            def _width(batch: DeviceBatch):
                from ..ops.aggregate import group_max_size

                c = Ctx.for_device(batch)
                live = batch.row_mask()
                if pre_filter is not None:
                    fv = pre_filter.eval(c)
                    live = live & c.broadcast_bool(fv.data) & fv.full_valid(c)
                if not grouping:
                    return live.sum().astype(jnp.int32)
                key_cols = [
                    val_to_column(c, g.eval(c), g.data_type) for g in grouping
                ]
                key_cols = [
                    dc_replace(k, validity=k.validity & live) for k in key_cols
                ]
                work = DeviceBatch(
                    Schema(
                        [
                            StructField(f"k{i}", k.dtype, True)
                            for i, k in enumerate(key_cols)
                        ]
                    ),
                    key_cols,
                    batch.num_rows,
                )
                return group_max_size(
                    work,
                    list(range(len(key_cols))),
                    live_mask=live if pre_filter is not None else None,
                    has_nans=has_nans,
                )

            return _width

        key = ("agg_width", grouping, child_schema, pre_filter, has_nans)
        return K.counted_kernel(key, make)

    def _fused_child(self) -> tuple:
        """(effective child, fused pre_filter) — the filter-fusion decision,
        shared by execute() and the kernel pre-compilation pass so both see
        the SAME kernel. Fusing folds the filter predicate into the
        aggregate as a liveness mask: a filter's schema equals its child's,
        so bindings hold, and the compaction gather of every column is
        skipped entirely. Filters with error sites (ANSI casts, split
        overflow) stay standalone — fusion would bypass their kernel error
        channel."""
        child = self.children[0]
        if (
            self.mode in ("partial", "complete")
            and isinstance(child, TpuFilterExec)
            and not child._needs_task
            and not _expr_has_error_site(child.condition)
        ):
            return child.children[0], child.condition
        return child, None

    def execute(self, ctx: ExecContext) -> PartitionSet:
        child, pre_filter = self._fused_child()
        from .. import config as cfg
        from ..resilience import retry as R

        child_schema = child.output
        has_nans = cfg.HAS_NANS.get(ctx.conf)
        kernel = self._make_kernel(child_schema, pre_filter, has_nans)
        merge_jit = self._merge_jit(has_nans)
        catalog, policy, breaker = ctx.catalog, ctx.retry_policy, ctx.breaker

        def run(it):
            if self.mode == "partial":
                # per-batch update aggregate, then concat + merge — the
                # reference's hot loop (aggregate.scala:406-468). Multi-batch
                # partitions shrink outputs to the live-group bucket before
                # the merge concat; single-batch outputs are shrunk by the
                # consumer (exchange) in one cross-partition bulk sync.
                # The update kernel is splittable (partials from the two
                # halves merge downstream exactly like two input batches),
                # so OOM escalates through the split state machine.
                partials = []
                for db in it:
                    partials.extend(
                        R.run_with_retry(
                            catalog, kernel, db, policy,
                            op="HashAggregateExec", breaker=breaker,
                        )
                    )
                if not partials:
                    if self.grouping:
                        return
                    partials = [kernel(empty_batch(child_schema))]
                if len(partials) == 1:
                    yield partials[0]
                else:
                    partials = bulk_shrink(partials)
                    yield R.run_once(
                        catalog, merge_jit, concat_device(partials), policy,
                        op="HashAggregateExec", breaker=breaker,
                    )
                return
            # final/complete: single merge+evaluate over the whole partition
            # (NOT splittable: merging halves separately would emit two
            # partial groups per key — spill-retry only)
            batches = list(it)
            if not batches:
                if self.grouping:
                    return
                batches = [empty_batch(child_schema)]
            merged = batches[0] if len(batches) == 1 else concat_device(batches)
            if self._has_collect:
                # collect plane width from the max-group-size pre-pass
                # (bucketed so recompiles stay logarithmic in group size).
                # Shrink first: the [capacity, W] element plane scales with
                # BOTH factors, and a sparse merged batch inflates capacity.
                merged = bulk_shrink([merged])[0]
                w = int(self._width_kernel(child_schema, pre_filter, has_nans)(merged))
                width = bucket_capacity(max(w, 1))
                if merged.capacity * width > _COLLECT_PLANE_LIMIT:
                    raise RuntimeError(
                        "device collect_list/collect_set needs a "
                        f"[{merged.capacity}, {width}] element plane "
                        f"(> {_COLLECT_PLANE_LIMIT} elements) — a single "
                        "group holds too many rows for the padded device "
                        "layout; disable the device path with "
                        "spark.rapids.sql.expression.CollectList=false / "
                        "spark.rapids.sql.expression.CollectSet=false"
                    )
                ck = self._make_kernel(
                    child_schema,
                    pre_filter,
                    has_nans,
                    collect_width=width,
                )
                yield R.run_once(
                    catalog, ck, merged, policy,
                    op="HashAggregateExec", breaker=breaker,
                )
                return
            yield R.run_once(
                catalog, kernel, merged, policy,
                op="HashAggregateExec", breaker=breaker,
            )

        return child.execute(ctx).map_partitions(run)

    def _merge_jit(self, has_nans=True):
        return aggregate_merge_kernel(
            tuple(self.grouping), tuple(self.agg_fns), self._schema, has_nans
        )

    def node_string(self):
        return (
            f"TpuHashAggregate({self.mode}) keys={[str(g) for g in self.grouping]} "
            f"aggs={[str(a) for a in self.agg_fns]}"
        )




def _buffer_ordinal(grouping, agg_fns, f: AggregateFunction, j: int) -> int:
    """Ordinal of buffer ``j`` of ``f`` in the keys ++ buffers layout."""
    base = len(grouping)
    for g in agg_fns:
        if g is f:
            return base + j
        base += len(g.buffer_types)
    raise KeyError


def aggregate_kernel(
    mode: str,
    grouping: tuple,
    agg_fns: tuple,
    result_exprs,
    out_schema: Schema,
    child_schema: Schema,
    pre_filter: Optional[Expression] = None,
    has_nans: bool = True,
    collect_width: int = 0,
):
    """The fused group-aggregate program (update or merge+evaluate), cached
    by the full aggregation signature. ``pre_filter`` fuses a child filter's
    predicate in as a liveness mask — no compaction (a full gather of every
    column, slow on TPU) between the filter and the aggregate."""

    def make():
        def _aggregate(batch: DeviceBatch) -> DeviceBatch:
            c = Ctx.for_device(batch)
            live = batch.row_mask()
            if pre_filter is not None:
                fv = pre_filter.eval(c)
                live = live & c.broadcast_bool(fv.data) & fv.full_valid(c)
            # materialize grouping keys + agg inputs as columns
            key_cols = [
                val_to_column(c, g.eval(c), g.data_type) for g in grouping
            ]
            key_cols = [
                dc_replace(k, validity=k.validity & live)
                for k in key_cols
            ]
            in_cols: list[DeviceColumn] = []
            ops: list[str] = []
            for f in agg_fns:
                if mode in ("partial", "complete"):
                    exprs = [bind(e, child_schema) for e in f.update_exprs]
                    for e, op in zip(exprs, f.update_ops):
                        col = val_to_column(c, e.eval(c), e.data_type)
                        in_cols.append(
                            dc_replace(col, validity=col.validity & live)
                        )
                        ops.append(op)
                else:
                    for j, op in enumerate(f.merge_ops):
                        in_cols.append(batch.columns[_buffer_ordinal(grouping, agg_fns, f, j)])
                        ops.append(op)
            tmp_schema = Schema(
                [StructField(f"k{i}", k.dtype, True) for i, k in enumerate(key_cols)]
            )
            work = DeviceBatch(
                Schema(list(tmp_schema.fields)), key_cols, batch.num_rows
            )
            # group_aggregate works on a batch containing the key columns;
            # ungrouped reductions force one output group even when empty
            out_keys, out_aggs, num_groups = group_aggregate(
                work,
                list(range(len(key_cols))),
                in_cols,
                ops,
                min_groups=0 if grouping else 1,
                live_mask=live if pre_filter is not None else None,
                has_nans=has_nans,
                collect_width=collect_width,
            )
            if mode == "partial":
                cols = out_keys + out_aggs
                return DeviceBatch(out_schema, cols, num_groups)
            # final/complete: evaluate aggregates + result projection
            cap = batch.capacity
            gctx = Ctx(jnp, cap, True, [Val(k.data, k.validity, k.lengths) for k in out_keys], num_groups)
            agg_results: list[Val] = []
            i = 0
            for f in agg_fns:
                nbuf = len(f.buffer_types)
                bufs = [
                    Val(
                        out_aggs[i + j].data,
                        out_aggs[i + j].validity,
                        out_aggs[i + j].lengths,
                        out_aggs[i + j].children,
                    )
                    for j in range(nbuf)
                ]
                agg_results.append(f.evaluate(gctx, bufs))
                i += nbuf
            rctx = Ctx(
                jnp,
                cap,
                True,
                [Val(k.data, k.validity, k.lengths) for k in out_keys] + agg_results,
                num_groups,
            )
            glive = jnp.arange(cap, dtype=jnp.int32) < num_groups
            cols = []
            for e in result_exprs:
                col = val_to_column(rctx, e.eval(rctx), e.data_type)
                cols.append(dc_replace(col, validity=col.validity & glive))
            return DeviceBatch(out_schema, cols, num_groups)

        return _aggregate

    key = (
        "agg",
        mode,
        grouping,
        agg_fns,
        result_exprs,
        out_schema,
        child_schema,
        pre_filter,
        has_nans,
        collect_width,
    )
    return K.counted_kernel(key, make)


def aggregate_merge_kernel(
    grouping: tuple, agg_fns: tuple, out_schema: Schema, has_nans: bool = True
):
    """Merge-mode aggregation kernel over (concatenated) partial batches.
    The partial-output layout is keys ++ buffers, so key ordinals and
    _buffer_ordinal line up with the final layout."""

    def make():
        def _m(batch: DeviceBatch) -> DeviceBatch:
            in_cols = []
            ops = []
            for f in agg_fns:
                for j, op in enumerate(f.merge_ops):
                    in_cols.append(batch.columns[_buffer_ordinal(grouping, agg_fns, f, j)])
                    ops.append(op)
            out_keys, out_aggs, num_groups = group_aggregate(
                batch,
                list(range(len(grouping))),
                in_cols,
                ops,
                min_groups=0 if grouping else 1,
                has_nans=has_nans,
            )
            return DeviceBatch(out_schema, out_keys + out_aggs, num_groups)

        return _m

    return K.counted_kernel(
        ("agg_merge", grouping, agg_fns, out_schema, has_nans), make
    )


class TpuSortExec(Exec):
    """Per-partition sort. Two modes (GpuSortExec.scala:36-42,212-510):

    * single-batch: coalesce the partition into one batch and sort it;
    * out-of-core: when the partition exceeds the configured threshold, sort
      each incoming batch into a *run*, park runs in the spill catalog
      (device→host→disk as memory demands), then merge runs pairwise — at
      most two runs are device-resident at any moment.
    """

    def __init__(self, order: List[SortOrder], child: Exec):
        super().__init__([child])
        self.order = [
            SortOrder(bind(o.child, child.output), o.ascending, o.nulls_first)
            for o in order
        ]

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        from .. import config as cfg
        from ..mem.spill import SpillPriorities, with_oom_retry

        _sort = device_sort_fn(self.order)
        _merge = device_merge_fn(self.order)
        threshold = cfg.OUT_OF_CORE_SORT_THRESHOLD.get(ctx.conf)
        catalog = ctx.catalog

        def make_run(b):
            """Sort one input batch into a spillable run; drop the input ref."""
            from ..mem.spill import _batch_device

            catalog.ensure_headroom(2 * b.size_bytes(), _batch_device(b))
            return catalog.register(
                with_oom_retry(catalog, _sort, b), SpillPriorities.WORKING
            )

        def run(it):
            # Stream the input: buffer small partitions for the single-batch
            # fast path; past the threshold, convert each incoming batch into
            # a sorted spillable run immediately so the unsorted input never
            # accumulates on device.
            pending, pending_bytes, runs = [], 0, None
            for b in it:
                if runs is None:
                    pending.append(b)
                    pending_bytes += b.size_bytes()
                    if pending_bytes > threshold and len(pending) > 1:
                        runs = [make_run(p) for p in pending]
                        pending = []
                else:
                    runs.append(make_run(b))
            if runs is None:
                if not pending:
                    return
                merged = concat_device(pending)
                del pending
                yield with_oom_retry(catalog, _sort, merged)
                return
            # Staged binary merge of sorted runs — a TRUE merge kernel
            # (binary-search ranks, linear work per level, O(n log k) total)
            # instead of re-sorting each concatenation; operands get_batch()
            # pins so the retry-spill cannot evict what it is merging.
            while len(runs) > 1:
                nxt = []
                for i in range(0, len(runs) - 1, 2):
                    a, b = runs[i], runs[i + 1]

                    def merge_pair(a=a, b=b):
                        # pin the operands FIRST so the headroom pass (and
                        # any retry-spill) cannot evict what is being merged
                        ba, bb = a.get_batch(), b.get_batch()
                        from ..mem.spill import _batch_device

                        catalog.ensure_headroom(
                            2 * (a.size_bytes + b.size_bytes),
                            _batch_device(ba),
                        )
                        return _merge(ba, bb)

                    out = with_oom_retry(catalog, merge_pair)
                    a.close(), b.close()
                    nxt.append(catalog.register(out, SpillPriorities.WORKING))
                if len(runs) % 2:
                    nxt.append(runs[-1])
                runs = nxt
            with runs[0] as final:
                yield final.get_batch()

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        return f"TpuSort [{', '.join(map(str, self.order))}]"


def _order_key(order: List[SortOrder]) -> tuple:
    return tuple((o.child, o.ascending, o.resolved_nulls_first()) for o in order)


def device_sort_fn(order: List[SortOrder]):
    """Jitted whole-batch sort kernel shared by TpuSortExec and TopN."""
    order = list(order)

    def make():
        def _sort(batch: DeviceBatch) -> DeviceBatch:
            c = Ctx.for_device(batch)
            live = batch.row_mask()
            cols = []
            for o in order:
                col = val_to_column(c, o.child.eval(c), o.child.data_type)
                cols.append(dc_replace(col, validity=col.validity & live))
            key = packed_key(
                cols,
                live,
                [o.ascending for o in order],
                [o.resolved_nulls_first() for o in order],
            )
            return gather_batch(batch, packed_sort(key), batch.num_rows)

        return _sort

    return K.counted_kernel(("sort", _order_key(order)), make)


def device_merge_fn(order: List[SortOrder]):
    """Two-run merge: concat the sorted runs (live segments land at [0, na)
    and [na, na+nb)), then ONE jitted kernel rebuilds radix words and
    gathers through ``merge_permutation``'s binary-search ranks — O(n log n)
    GATHERS per level instead of re-running the sort, whose TPU lowering is
    a sorting network with per-pass cost far above a gather sweep (see
    ops/sortkeys.py's compile-time notes). The reference's true
    out-of-core merge (GpuSortExec.scala:212-510). Caveat measured on the
    XLA-CPU backend: its lax.sort is a fast comparison sort, so there the
    re-sort wins — the merge is sized for TPU economics. The concat runs as
    its own cached kernel (kernels must not nest compiles)."""
    order = list(order)

    def make():
        def _merge(merged: DeviceBatch, na, nb) -> DeviceBatch:
            from ..ops.sortkeys import column_radix_words, merge_permutation

            c = Ctx.for_device(merged)
            words = []
            for o in order:
                col = val_to_column(c, o.child.eval(c), o.child.data_type)
                col = dc_replace(col, validity=col.validity & merged.row_mask())
                words.extend(
                    column_radix_words(col, o.ascending, o.resolved_nulls_first())
                )
            perm = merge_permutation(words, na, nb)
            return gather_batch(merged, perm, na + nb)

        return _merge

    kernel = K.jit_kernel(("merge_runs", _order_key(order)), make)

    def merge(ba: DeviceBatch, bb: DeviceBatch) -> DeviceBatch:
        import jax.numpy as jnp

        na, nb = ba.num_rows, bb.num_rows
        merged = concat_device([ba, bb])
        return kernel(
            merged,
            jnp.asarray(na, jnp.int32),
            jnp.asarray(nb, jnp.int32),
        )

    return merge


def _slice_head_impl(batch: DeviceBatch, take) -> DeviceBatch:
    """First min(num_rows, take) rows — shared by limit and TopN (module-
    level jit: one program per batch signature, cached for the process)."""
    take = jnp.minimum(batch.num_rows, take)
    live = jnp.arange(batch.capacity, dtype=jnp.int32) < take
    cols = [
        dc_replace(c, validity=c.validity & live)
        for c in batch.columns
    ]
    return DeviceBatch(batch.schema, cols, take.astype(jnp.int32))


slice_head = K.GuardedJit(_slice_head_impl)


def _radix_select_kth(w: "jax.Array", k: int) -> "jax.Array":
    """Exact k-th smallest of a uint64 vector, MSB→LSB radix select: fix
    one bit per step by counting how many values share the built prefix
    with the current bit 0. O(64·n) fully-vectorized elementwise work —
    no sorting network, no top_k."""
    def body(i, state):
        prefix, kk = state
        shift = jnp.uint64(63) - i.astype(jnp.uint64)
        bit = jnp.uint64(1) << shift
        # bits at/above the current position
        hi_mask = ~(bit - jnp.uint64(1))
        cnt0 = ((w & hi_mask) == prefix).sum(dtype=jnp.int64)
        take1 = kk > cnt0
        prefix = jnp.where(take1, prefix | bit, prefix)
        kk = jnp.where(take1, kk - cnt0, kk)
        return prefix, kk

    prefix, _ = jax.lax.fori_loop(
        0, 64, body, (jnp.uint64(0), jnp.asarray(k, jnp.int64))
    )
    return prefix


class TpuTakeOrderedAndProjectExec(Exec):
    """TopN on device: per-partition sort + head(n), then merged final
    sort + head(n) (reference: GpuTakeOrderedAndProjectExec, limit.scala)."""

    def __init__(self, n: int, order: List[SortOrder], child: Exec):
        super().__init__([child])
        self.n = n
        self.order = [
            SortOrder(bind(o.child, child.output), o.ascending, o.nulls_first)
            for o in order
        ]
        self.prefilter_hits = 0  # observability: candidate fast path taken

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    # below this capacity the full sort is cheap enough that the candidate
    # pass's extra host sync would dominate
    TOPK_MIN_CAPACITY = 1 << 15

    def _candidate_fn(self):
        """(mask, count) of rows whose FIRST radix word ties or beats the
        n-th best — a superset of the true top-n (ties at the boundary are
        kept; later sort keys only reorder within first-word ties). Lets
        TopN avoid the full multi-word sort of a huge padded batch: top_k
        is O(cap·log n), then only the candidates get sorted."""
        order = self.order
        k = self.n

        def make():
            def cand(batch: DeviceBatch):
                c = Ctx.for_device(batch)
                live = batch.row_mask()
                o = order[0]
                col = val_to_column(c, o.child.eval(c), o.child.data_type)
                col = dc_replace(col, validity=col.validity & live)
                from ..ops.sortkeys import column_radix_words

                # value_only: for unpacked layouts (64-bit/string/double)
                # word [0] would be the standalone VALIDITY word — a {0,1}
                # threshold that degenerates the prefilter (sortkeys.py's
                # docstring forbids slicing word 0). Nulls get explicit
                # boundary keys per the null ordering instead.
                w0 = column_radix_words(
                    col,
                    o.ascending,
                    o.resolved_nulls_first(),
                    value_only=True,
                )[0]
                dead = jnp.uint64(0xFFFFFFFFFFFFFFFF)
                null_key = (
                    jnp.uint64(0) if o.resolved_nulls_first() else dead
                )
                w0 = jnp.where(col.validity, w0, null_key)
                w0 = jnp.where(live, w0, dead)
                kk = min(k, int(w0.shape[0]))
                # k-th smallest via radix-select: 64 masked count-reductions
                # (lax.top_k at this size lowers to a pathological full
                # sort on TPU — measured minutes at 2M rows)
                kth = _radix_select_kth(w0, kk)
                mask = live & (w0 <= kth)
                return mask, mask.sum(dtype=jnp.int32)

            return K.GuardedJit(cand)

        return K.kernel(("topn_cand", _order_key(self.order), self.n), make)

    def execute(self, ctx: ExecContext) -> PartitionSet:
        n = jnp.asarray(self.n, jnp.int32)
        sort_fn = device_sort_fn(self.order)
        cand_fn = self._candidate_fn()
        limit = self.n

        def topn(batches):
            if not batches:
                return None
            merged = batches[0] if len(batches) == 1 else concat_device(batches)
            cand_cap = bucket_capacity(max(4 * limit, 4096))
            if (
                merged.capacity >= self.TOPK_MIN_CAPACITY
                # the gathered candidate batch must be meaningfully smaller
                # than the input or the pass does strictly more work
                and cand_cap <= merged.capacity // 4
            ):
                mask, cnt = cand_fn(merged)
                cnt = int(cnt)  # one host sync buys skipping the big sort
                if cnt <= cand_cap:
                    self.prefilter_hits += 1
                    # fixed-size nonzero + gather: O(cap) scan, NO sorting
                    # network over the huge padded batch (compact's argsort
                    # would be exactly the cost this path exists to skip)
                    def make_gather(cc=cand_cap):
                        def g(b: DeviceBatch, m: jax.Array):
                            idx = jnp.nonzero(
                                m, size=cc, fill_value=b.capacity - 1
                            )[0].astype(jnp.int32)
                            taken = m.sum(dtype=jnp.int32)
                            out = gather_batch(b, idx, taken)
                            live = (
                                jnp.arange(cc, dtype=jnp.int32) < taken
                            )
                            cols = [
                                dc_replace(c2, validity=c2.validity & live)
                                for c2 in out.columns
                            ]
                            return DeviceBatch(out.schema, cols, taken)

                        return K.GuardedJit(g)

                    gather_fn = K.kernel(
                        (
                            "topn_gather",
                            merged.schema,
                            merged.capacity,
                            cand_cap,
                        ),
                        make_gather,
                    )
                    return slice_head(sort_fn(gather_fn(merged, mask)), n)
            return slice_head(sort_fn(merged), n)

        child_parts = self.children[0].execute(ctx)

        def it():
            partials = []
            for t in child_parts.parts:
                out = topn(list(t()))
                if out is not None:
                    partials.append(out)
            final = topn(partials)
            if final is not None:
                yield final

        return PartitionSet([it])

    def node_string(self):
        return f"TpuTakeOrderedAndProject n={self.n} [{', '.join(map(str, self.order))}]"


class TpuExpandExec(Exec):
    """Projection-list fan-out per batch (GpuExpandExec analogue): each
    projection compiles into the same fused kernel; output batches share the
    input's row count."""

    def __init__(self, projections: List[List[Expression]], names: List[str], child: Exec):
        super().__init__([child])
        self.projections = [
            [bind(e, child.output) for e in proj] for proj in projections
        ]
        from ..types import NullType

        fields = []
        for i, name in enumerate(names):
            es = [proj[i] for proj in self.projections]
            dt = next(
                (e.data_type for e in es if not isinstance(e.data_type, NullType)),
                es[0].data_type,
            )
            fields.append(StructField(name, dt, any(e.nullable for e in es)))
        self._schema = Schema(fields)
        schema = self._schema
        projections = tuple(tuple(p) for p in self.projections)

        def make():
            def _expand(batch: DeviceBatch) -> list[DeviceBatch]:
                c = Ctx.for_device(batch)
                live = batch.row_mask()
                out = []
                for proj in projections:
                    cols = []
                    for e, f in zip(proj, schema):
                        col = val_to_column(c, e.eval(c), f.data_type)
                        cols.append(
                            dc_replace(col, dtype=f.data_type, validity=col.validity & live)
                        )
                    out.append(DeviceBatch(schema, cols, batch.num_rows))
                return out

            return _expand

        self._fn = K.jit_kernel(("expand", projections, schema), make)

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        fn = self._fn

        def run(it):
            tok = ctx.cancel_token
            for db in it:
                if tok is not None:
                    tok.check()
                yield from fn(db)

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        return f"TpuExpand x{len(self.projections)}"


class TpuGenerateExec(Exec):
    """explode/posexplode on device (GpuGenerateExec.scala analogue).

    TPU-first: instead of cudf's Table.explode, output slot j maps to
    (row r_j, element p_j) via a vectorized ``searchsorted`` over the
    cumulative element counts — log-depth, no scatters, static output
    capacity bucketed from one host sync of the total element count."""

    def __init__(self, cpu_gen, child: Exec):
        super().__init__([child])
        self.generator = cpu_gen.generator  # bound against same schema
        self.out_names = cpu_gen.out_names
        self._schema = cpu_gen.output

    @property
    def output(self) -> Schema:
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def _lengths_kernel(self):
        g = self.generator

        def make():
            def fn(batch: DeviceBatch):
                c = Ctx.for_device(batch)
                v = g.child.eval(c)
                live = batch.row_mask() & c.broadcast_bool(v.valid)
                lengths = jnp.where(live, c.broadcast(v.lengths), 0).astype(jnp.int32)
                return lengths, lengths.sum()

            return fn

        return K.jit_kernel(("gen_lengths", g), make)

    def _explode_kernel(self, out_cap: int):
        from ..types import MapType

        g = self.generator
        out_schema = self._schema
        is_map = isinstance(g.child.data_type, MapType)
        position = g.position

        def make():
            def fn(batch: DeviceBatch, lengths, total):
                c = Ctx.for_device(batch)
                v = g.child.eval(c)
                coff = jnp.cumsum(lengths)
                j = jnp.arange(out_cap, dtype=jnp.int32)
                r = jnp.searchsorted(coff, j, side="right").astype(jnp.int32)
                live = j < total
                r = jnp.clip(r, 0, batch.capacity - 1)
                prev = jnp.where(r > 0, coff[jnp.clip(r - 1, 0, None)], 0)
                p = (j - prev).astype(jnp.int32)
                out_cols = gather_columns(batch.columns, r, live)
                if position:
                    from ..types import INT

                    out_cols.append(
                        DeviceColumn(INT, jnp.where(live, p, 0), live)
                    )
                planes = v.children
                gctx = Ctx(jnp, out_cap, True, [], total)
                if is_map:
                    for plane, dt in (
                        (planes[0], g.child.data_type.key_type),
                        (planes[1], g.child.data_type.value_type),
                    ):
                        ev = _plane_element(plane, r, p, live)
                        out_cols.append(val_to_column(gctx, ev, dt))
                else:
                    ev = _plane_element(planes[0], r, p, live)
                    out_cols.append(
                        val_to_column(gctx, ev, g.child.data_type.element_type)
                    )
                return DeviceBatch(out_schema, out_cols, total.astype(jnp.int32))

            return fn

        return K.jit_kernel(("gen_explode", g, out_schema, out_cap), make)

    def execute(self, ctx: ExecContext) -> PartitionSet:
        lk = self._lengths_kernel()

        def run(it):
            tok = ctx.cancel_token
            for db in it:
                if tok is not None:
                    tok.check()
                lengths, total_dev = lk(db)
                # graft: ok(host-sync: the explode output CAPACITY must be
                # chosen on host (bucketed jit signature) — one scalar
                # pull per batch is inherent to row-expanding generators)
                total = int(total_dev)
                if total == 0:
                    continue
                out_cap = bucket_capacity(total)
                yield self._explode_kernel(out_cap)(
                    db, lengths, jnp.asarray(total, jnp.int32)
                )

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        return f"TpuGenerate {self.generator}"


def _plane_element(plane: DeviceColumn, r, p, live):
    """Element (r_j, p_j) of a padded element plane as a Val."""
    W = plane.data.shape[1]
    safe = jnp.clip(p, 0, W - 1)
    data = plane.data[r, safe]
    valid = plane.validity[r, safe] & live
    lengths = None
    if plane.lengths is not None:
        lengths = jnp.where(live, plane.lengths[r, safe], 0)
    if data.ndim == 2:
        data = jnp.where(live[:, None], data, 0)
    else:
        data = jnp.where(live, data, jnp.zeros_like(data))
    return Val(data, valid, lengths)


# which join side may be SPLIT under skew (the other side is replicated;
# replication must not be able to emit unmatched rows of its own side)
_SPLITTABLE_SIDES = {
    "inner": ("left", "right"),
    "left": ("left",),
    "left_semi": ("left",),
    "left_anti": ("left",),
    "right": ("right",),
    "full": (),
}


def _aqe_join_plan(sa, sb, n, advisory, sides, skew_thresh, skew_factor):
    """One shared AQE plan for both shuffle reads of a join: per output
    slot, a list of (source partition, split index, split count) for each
    side. Coalescing groups adjacent small partitions; a skewed partition
    (one side > max(threshold, factor x median), other side small) is
    split across the slots coalescing freed while the other side's
    partition replicates into each. Deterministic in (sa, sb) so both
    exchanges compute identical plans."""
    combined = [x + y for x, y in zip(sa, sb)]
    skewed: dict = {}
    if sides and skew_thresh > 0:
        med_a = sorted(sa)[n // 2]
        med_b = sorted(sb)[n // 2]
        for p in range(n):
            if (
                "left" in sides
                and sa[p] > max(skew_thresh, skew_factor * med_a)
                and sb[p] <= skew_thresh
            ):
                skewed[p] = "left"
            elif (
                "right" in sides
                and sb[p] > max(skew_thresh, skew_factor * med_b)
                and sa[p] <= skew_thresh
            ):
                skewed[p] = "right"
    groups: list = []
    cur: list = []
    by = 0
    for p in range(n):
        if p in skewed:
            if cur:
                groups.append(("g", cur))
                cur, by = [], 0
            groups.append(("s", [p]))
            continue
        if cur and by + combined[p] > advisory:
            groups.append(("g", cur))
            cur, by = [], 0
        cur.append(p)
        by += combined[p]
    if cur:
        groups.append(("g", cur))
    free = n - len(groups)
    out_a: list = [[] for _ in range(n)]
    out_b: list = [[] for _ in range(n)]
    slot = 0
    for kind, g in groups:
        if kind == "s" and free > 0:
            p = g[0]
            side = skewed[p]
            big = sa[p] if side == "left" else sb[p]
            want = max(2, int(big // max(advisory, 1)))
            k = min(free + 1, want, n)
            free -= k - 1
            for j in range(k):
                if side == "left":
                    out_a[slot].append((p, j, k))
                    out_b[slot].append((p, 0, 1))
                else:
                    out_a[slot].append((p, 0, 1))
                    out_b[slot].append((p, j, k))
                slot += 1
        else:
            for p in g:
                out_a[slot].append((p, 0, 1))
                out_b[slot].append((p, 0, 1))
            slot += 1
    return out_a, out_b


def _row_range_slice(db: DeviceBatch, j: int, k: int) -> Optional[DeviceBatch]:
    """Rows of capacity-range slice j of k, compacted (skew split unit)."""
    fn = K.jit_kernel(
        ("aqe_split", db.schema, db.capacity, j, k),
        lambda: _make_row_range_slice(j, k),
    )
    return fn(db)


def _make_row_range_slice(j: int, k: int):
    def run(db: DeviceBatch) -> DeviceBatch:
        # slice the LIVE prefix [0, num_rows), not the padded capacity —
        # rows are prefix-compacted, so capacity-based slices would leave
        # every live row in slice 0
        n = db.num_rows.astype(jnp.int32)
        lo = (n * j) // k
        hi = (n * (j + 1)) // k
        idx = jnp.arange(db.capacity, dtype=jnp.int32)
        keep = (idx >= lo) & (idx < hi) & db.row_mask()
        return compact(db, keep)

    return run


class TpuShuffleExchangeExec(Exec):
    """Partitioned exchange with on-device bucketing and device-side slicing
    (GpuShuffleExchangeExec + the four GpuPartitioning impls;
    sliceInternalOnGpu analogue). Hash = murmur3 pmod; range = radix-word
    compare against host-sampled bounds; round-robin; single. In-process:
    device batches move between partitions without leaving HBM; the
    multi-process serializer path lives in shuffle/."""

    def __init__(self, partitioning, child: Exec):
        super().__init__([child])
        from .cpu import _bind_partitioning

        self.partitioning = _bind_partitioning(partitioning, child.output)
        # AQE coalescing coordination: a co-partitioned consumer (shuffled
        # join) links its two feeding exchanges so both compute ONE shared
        # assignment from combined sizes; if only one side is an exchange,
        # coalescing is disabled to keep positional pairing intact.
        self._aqe_peer: "TpuShuffleExchangeExec | None" = None
        self._aqe_disabled = False

    @property
    def num_partitions(self) -> int:
        return self.partitioning.num_partitions

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def _scatter_fns(self, nparts, pre_filter=None):
        """Build the jitted kernels for this exchange's partitioning; XLA's
        own compile cache dedupes retraces across execute() calls.
        ``pre_filter`` fuses a child filter's predicate in as a liveness
        mask — dead rows fall out during bucketing, skipping the filter's
        own compaction sort + full-width gather."""
        from ..ops.gather import partition_slices
        from ..plan.partitioning import (
            HashPartitioning,
            RangePartitioning,
            RoundRobinPartitioning,
            words_partition_ids,
        )

        part = self.partitioning

        def live_of(batch: DeviceBatch, c: Ctx):
            if pre_filter is None:
                return None
            fv = pre_filter.eval(c)
            return c.broadcast_bool(fv.data) & fv.full_valid(c)

        if isinstance(part, HashPartitioning) and part.keys:
            keys = tuple(part.keys)

            def make_hash():
                def hash_slice(batch: DeviceBatch) -> list[DeviceBatch]:
                    c = Ctx.for_device(batch)
                    cols = []
                    for k in keys:
                        col = val_to_column(c, k.eval(c), k.data_type)
                        cols.append((k.data_type, col.data, col.validity, col.lengths))
                    h = murmur3_rows(jnp, cols, batch.capacity)
                    pids = partition_ids(jnp, h, nparts)
                    return partition_slices(
                        batch, pids, nparts, live_of(batch, c)
                    )

                return hash_slice

            return (
                "hash",
                K.counted_kernel(
                    ("exchange_hash", keys, nparts, pre_filter), make_hash
                ),
            )

        if isinstance(part, RoundRobinPartitioning):

            def make_rr():
                def rr_slice(batch: DeviceBatch, start) -> list[DeviceBatch]:
                    pids = (start + jnp.arange(batch.capacity, dtype=jnp.int32)) % nparts
                    c = Ctx.for_device(batch)
                    return partition_slices(
                        batch, pids, nparts, live_of(batch, c)
                    )

                return rr_slice

            return (
                "roundrobin",
                K.counted_kernel(("exchange_rr", nparts, pre_filter), make_rr),
            )

        if isinstance(part, RangePartitioning):
            order = part.order

            def make_words():
                def batch_word_groups(batch: DeviceBatch):
                    """Per-order-column radix word lists (aligned later)."""
                    from ..ops.sortkeys import column_radix_words

                    c = Ctx.for_device(batch)
                    return [
                        column_radix_words(
                            val_to_column(c, o.child.eval(c), o.child.data_type),
                            o.ascending,
                            o.resolved_nulls_first(),
                        )
                        for o in order
                    ]

                return batch_word_groups

            words_jit = K.jit_kernel(
                ("exchange_range_words", _order_key(order)), make_words
            )

            def make_range():
                def range_slice(batch: DeviceBatch, words, bounds) -> list[DeviceBatch]:
                    pids = words_partition_ids(jnp, words, bounds)
                    c = Ctx.for_device(batch)
                    return partition_slices(
                        batch, pids, nparts, live_of(batch, c)
                    )

                return range_slice

            return (
                "range",
                (
                    words_jit,
                    K.counted_kernel(
                        ("exchange_range_slice", nparts, pre_filter),
                        make_range,
                    ),
                ),
            )

        return ("single", None)

    # ── mesh (SPMD) path ────────────────────────────────────────────────
    def _pid_fns(self, nparts):
        """Per-row partition-id kernels (no per-partition compact): the mesh
        exchange scatters by pid inside one fused all_to_all program, so
        hash/range/round-robin all lower to the same ICI data plane."""
        from ..plan.partitioning import (
            HashPartitioning,
            RangePartitioning,
            RoundRobinPartitioning,
            words_partition_ids,
        )

        part = self.partitioning
        if isinstance(part, HashPartitioning) and part.keys:
            keys = tuple(part.keys)

            def make_hash():
                def pids(batch: DeviceBatch):
                    c = Ctx.for_device(batch)
                    cols = []
                    for k in keys:
                        col = val_to_column(c, k.eval(c), k.data_type)
                        cols.append(
                            (k.data_type, col.data, col.validity, col.lengths)
                        )
                    h = murmur3_rows(jnp, cols, batch.capacity)
                    return partition_ids(jnp, h, nparts).astype(jnp.int32)

                return pids

            return ("hash", K.jit_kernel(("mesh_pid_hash", keys, nparts), make_hash))
        if isinstance(part, RoundRobinPartitioning):

            def make_rr():
                def pids(batch: DeviceBatch, start):
                    return (
                        (start + jnp.arange(batch.capacity, dtype=jnp.int32))
                        % nparts
                    ).astype(jnp.int32)

                return pids

            return ("roundrobin", K.jit_kernel(("mesh_pid_rr", nparts), make_rr))
        if isinstance(part, RangePartitioning):
            order = part.order

            def make_words():
                def batch_word_groups(batch: DeviceBatch):
                    from ..ops.sortkeys import column_radix_words

                    c = Ctx.for_device(batch)
                    return [
                        column_radix_words(
                            val_to_column(c, o.child.eval(c), o.child.data_type),
                            o.ascending,
                            o.resolved_nulls_first(),
                        )
                        for o in order
                    ]

                return batch_word_groups

            words_jit = K.jit_kernel(
                ("mesh_range_words", _order_key(order)), make_words
            )

            def make_range():
                def pids(words, bounds):
                    return words_partition_ids(jnp, words, bounds).astype(jnp.int32)

                return pids

            return ("range", (words_jit, K.jit_kernel(("mesh_pid_range",), make_range)))
        return ("single", None)

    def _execute_mesh(self, ctx: ExecContext, mc) -> PartitionSet:
        """SPMD exchange: chip i contributes child partitions j ≡ i (mod n)
        concatenated to one batch; one fused all_to_all re-partitions every
        chip's rows over ICI; output partition i stays committed on chip i
        so downstream per-partition kernels run on their own devices.
        (GpuShuffleExchangeExec over the UCX data plane, engine-wired —
        RapidsShuffleInternalManagerBase.scala:200-396.)"""
        import threading

        from ..parallel.mesh import mesh_exchange, put_batch
        from ..plan.partitioning import SAMPLE_PER_BATCH, compute_range_bounds

        nparts = self.num_partitions
        kind, fn = self._pid_fns(nparts)
        schema = self.output
        child_parts = self.children[0].execute(ctx)
        state: dict = {"out": None}
        lock = threading.Lock()

        def materialize():
            with lock:
                if state["out"] is not None:
                    return state["out"]
                n = mc.n
                per_chip_lists: list = [[] for _ in range(n)]
                for j, t in enumerate(child_parts.parts):
                    per_chip_lists[j % n].extend(t())
                per_chip = [
                    concat_device(l) if l else empty_batch(schema)
                    for l in per_chip_lists
                ]
                # commit each chip's input to its device so the global
                # stacked view assembles zero-copy
                per_chip = [
                    put_batch(b, mc.device_for(i)) for i, b in enumerate(per_chip)
                ]
                if kind == "hash":
                    pids = [fn(b) for b in per_chip]
                elif kind == "roundrobin":
                    pids = [
                        fn(b, jnp.asarray(i, jnp.int32))
                        for i, b in enumerate(per_chip)
                    ]
                elif kind == "range":
                    words_jit, pid_jit = fn
                    import numpy as np

                    all_words = self._mesh_range_words(
                        ctx, words_jit, per_chip
                    )
                    dev_samples, dev_valid = [], []
                    for db, words in zip(per_chip, all_words):
                        s_idx = (
                            jnp.arange(SAMPLE_PER_BATCH, dtype=jnp.int32)
                            * jnp.maximum(db.num_rows, 1)
                        ) // SAMPLE_PER_BATCH
                        dev_samples.append(jnp.stack([w[s_idx] for w in words]))
                        dev_valid.append(
                            jnp.broadcast_to(db.num_rows > 0, (SAMPLE_PER_BATCH,))
                        )
                    # graft: ok(host-sync: range bounds need the samples on
                    # host — ONE batched transfer for every chip's samples,
                    # once per exchange materialization)
                    host_samples, host_valid = jax.device_get(
                        (dev_samples, dev_valid)
                    )
                    sample_words = [
                        np.concatenate(
                            [s[i][v] for s, v in zip(host_samples, host_valid)]
                        )
                        for i in range(len(all_words[0]))
                    ]
                    if sample_words[0].size:
                        bounds = compute_range_bounds(sample_words, nparts)
                        jb = [jnp.asarray(b) for b in bounds]
                        pids = [
                            pid_jit(w, jb) for w in all_words
                        ]
                    else:
                        pids = [
                            jnp.zeros(b.capacity, jnp.int32) for b in per_chip
                        ]
                else:
                    raise AssertionError(kind)
                out = mesh_exchange(mc, schema, per_chip, pids)
                state["out"] = out
                return out

        def make(p):
            def it():
                db = materialize()[p]
                yield db

            return it

        return PartitionSet([make(p) for p in range(nparts)])

    def _mesh_range_words(self, ctx, words_jit, per_chip):
        from ..plan.partitioning import align_word_groups

        group_lists = [words_jit(b) for b in per_chip]
        aligned, _targets = align_word_groups(
            group_lists, self.partitioning.order, jnp
        )
        return aligned

    def execute(self, ctx: ExecContext) -> PartitionSet:
        # exchange reuse (plan/reuse.py): a node shared by several consumers
        # materializes once per query — the ReuseExchange analogue
        if getattr(self, "_reuse_shared", False):
            cached = ctx.reuse_cache.get(id(self))
            if cached is None:
                cached = self._execute_impl(ctx)
                ctx.reuse_cache[id(self)] = cached
            return cached
        return self._execute_impl(ctx)

    def _execute_impl(self, ctx: ExecContext) -> PartitionSet:
        from ..mem.spill import with_oom_retry
        from ..plan.partitioning import SAMPLE_PER_BATCH, compute_range_bounds

        import threading

        nparts = self.num_partitions
        mc = ctx.mesh
        if mc is not None and nparts == mc.n:
            from ..parallel.mesh import mesh_supported_schema
            from ..plan.partitioning import SinglePartitioning

            if (
                mesh_supported_schema(self.output)
                and not isinstance(self.partitioning, SinglePartitioning)
                and self._pid_fns(nparts)[0] != "single"
            ):
                return self._execute_mesh(ctx, mc)
        exchange_child = self.children[0]
        pre_filter = None
        if (
            isinstance(exchange_child, TpuFilterExec)
            and not exchange_child._needs_task
            and not _expr_has_error_site(exchange_child.condition)
            # round-robin balances by ROW POSITION: fusing a filter would
            # assign pids over unfiltered positions and can degenerate to
            # total skew — hash/range pids are value-based and unaffected
            and self._scatter_fns(nparts)[0] in ("hash", "range")
        ):
            # fuse the filter into the bucketing kernel: its rows fall out
            # during the partition sort, skipping the filter's own
            # compaction sort + full-width gather (same fusion the
            # aggregate does with its pre_filter)
            pre_filter = exchange_child.condition
            exchange_child = exchange_child.children[0]
        kind, fn = self._scatter_fns(nparts, pre_filter)
        catalog = ctx.catalog
        child_parts = exchange_child.execute(ctx)
        from .. import config as cfg

        # Multi-process query (spark.rapids.shuffle.multiproc.*): this
        # executor maps only the child partitions its rank owns; peers map
        # the rest and serve them over the TCP transport (DCN path). The
        # topology comes from the CONTEXT, frozen at session init — the
        # multiproc keys are startup_only, and re-reading the conf here
        # would let a live set_conf disagree with the running transport
        # (the conf-key lint's scope rule).
        mp_size = ctx.mp_size
        mp_rank = ctx.mp_rank
        in_broadcast = getattr(ctx, "broadcast_depth", 0) > 0
        multiproc = (
            bool(ctx.mp_driver)
            and mp_size > 1
            and cfg.SHUFFLE_MANAGER_ENABLED.get(ctx.conf)
            and not in_broadcast
        )
        if multiproc:
            child_parts = PartitionSet(
                [
                    t if i % mp_size == mp_rank else (lambda: iter(()))
                    for i, t in enumerate(child_parts.parts)
                ]
            )
        state = {"buckets": None}
        mat_lock = threading.Lock()

        def materialize():
            with mat_lock:
                return _materialize_locked()

        def _materialize_locked():
            if state["buckets"] is not None:
                return state["buckets"]
            buckets = [[] for _ in range(nparts)]
            if kind == "range":
                from ..plan.partitioning import (
                    align_word_groups,
                    merge_sampled_word_groups,
                    pad_flat_words,
                )

                words_jit, range_slice = fn
                order = self.partitioning.order
                batches, group_lists = [], []
                for t in child_parts.parts:
                    for db in t():
                        batches.append(db)
                        group_lists.append(with_oom_retry(catalog, words_jit, db))
                # string columns may encode to different word counts per
                # batch (bucketed widths) — align before sampling/bucketing
                all_words, local_targets = align_word_groups(
                    group_lists, order, jnp
                )
                del group_lists
                # Sample on device, then fetch everything in ONE transfer —
                # per-batch np.asarray syncs are lethal over slow PJRT links.
                dev_samples, dev_valid = [], []
                for db, words in zip(batches, all_words):
                    s_idx = (
                        jnp.arange(SAMPLE_PER_BATCH, dtype=jnp.int32)
                        * jnp.maximum(db.num_rows, 1)
                    ) // SAMPLE_PER_BATCH
                    dev_samples.append(jnp.stack([w[s_idx] for w in words]))
                    # duplicates (n < SAMPLE_PER_BATCH) just weight the
                    # sample; only an empty batch must be excluded outright
                    dev_valid.append(
                        jnp.broadcast_to(db.num_rows > 0, (SAMPLE_PER_BATCH,))
                    )
                sample_words = None
                if batches:
                    # graft: ok(host-sync: ONE batched pull for all range
                    # samples, once per exchange — the per-batch np.asarray
                    # alternative is what the comment above rules out)
                    host_samples, host_valid = jax.device_get((dev_samples, dev_valid))
                    sample_words = [
                        np.concatenate(
                            [s[i][v] for s, v in zip(host_samples, host_valid)]
                        )
                        for i in range(len(all_words[0]))
                    ]
                bounds = None
                if multiproc:
                    # Every rank sees only its own child partitions, so
                    # per-rank bounds would send the same key range to
                    # different reduce partitions on different ranks —
                    # globally wrong ORDER BY results. Gather all ranks'
                    # samples through the driver service and replay one
                    # deterministic merge so every rank buckets with
                    # identical bounds (the bounds-on-the-Spark-driver
                    # analogue, GpuRangePartitioner.createRangeBounds).
                    payload = {
                        "targets": local_targets,
                        # graft: ok(host-sync: host numpy after the single
                        # batched device_get above — JSON payload for the
                        # driver's bounds sync, no device traffic)
                        "words": [w.tolist() for w in (sample_words or [])],
                    }
                    contribs = ctx.shuffle_manager.registry.range_bounds_sync(
                        key=f"{base_sid}:range",
                        rank=mp_rank,
                        size=mp_size,
                        payload=payload,
                    )
                    merged, gtargets = merge_sampled_word_groups(contribs, order)
                    if merged is not None:
                        bounds = compute_range_bounds(merged, nparts)
                        if gtargets != local_targets and batches:
                            # peers saw wider string keys: re-pad this
                            # rank's words to the agreed global widths so
                            # rows and bounds compare word-for-word
                            all_words = [
                                pad_flat_words(
                                    w, local_targets, gtargets, order, jnp
                                )
                                for w in all_words
                            ]
                elif sample_words is not None and sample_words[0].size:
                    bounds = compute_range_bounds(sample_words, nparts)
                jb = None if bounds is None else [jnp.asarray(b) for b in bounds]
                for db, words in zip(batches, all_words):
                    if jb is None:
                        buckets[0].append(db)
                        continue
                    for p, s in enumerate(
                        with_oom_retry(catalog, range_slice, db, words, jb)
                    ):
                        buckets[p].append(s)
            elif kind == "hash":
                # Drain every partition first (dispatches all upstream work
                # asynchronously), then ONE bulk shrink sync, then slice —
                # partitions overlap on device instead of serializing. The
                # cost is holding the drained inputs concurrently; slices
                # consume the list destructively so inputs free as we go.
                drained = bulk_shrink(
                    [db for t in child_parts.parts for db in t()]
                )
                while drained:
                    db = drained.pop(0)
                    for p, s in enumerate(with_oom_retry(catalog, fn, db)):
                        buckets[p].append(s)
                    del db
            elif kind == "single":
                # coalesce to one partition; shrink sparse batches (e.g.
                # ungrouped partial aggregates: 1 live row in a huge cap)
                drained = [db for t in child_parts.parts for db in t()]
                buckets[0].extend(bulk_shrink(drained))
            else:  # roundrobin
                for pi, t in enumerate(child_parts.parts):
                    # device-resident running offset: no host sync per batch
                    offset = jnp.asarray(pi % nparts, jnp.int32)
                    for db in t():
                        for p, s in enumerate(
                            with_oom_retry(catalog, fn, db, offset % nparts)
                        ):
                            buckets[p].append(s)
                        offset = offset + db.num_rows
            state["buckets"] = buckets
            return buckets

        from .. import config as cfg

        if cfg.SHUFFLE_MANAGER_ENABLED.get(ctx.conf) and not in_broadcast:
            # Accelerated path: park partition buckets in the spillable
            # shuffle catalog and read them back through the caching
            # reader (RapidsShuffleManager writer/reader protocol). A
            # broadcast-build subtree stays on the in-process path: its
            # results are per-executor by definition (no shared catalog /
            # registry traffic).
            #
            # The shuffle id is minted HERE, during the single-threaded
            # plan-execution walk — identical plans mint identical ids in
            # every rank. Minting lazily inside ensure_written would let
            # the partition THREAD POOL order decide which exchange gets
            # which id (nondeterministic across ranks → peers would pair
            # the wrong exchanges). Retries re-run the map stage under a
            # deterministic per-generation offset within the query's id
            # namespace.
            base_sid = ctx.next_shuffle_id()
            mgr_state = {"shuffle_id": None, "generation": 0, "attempt": 0}
            mgr_lock = threading.Lock()

            def ensure_written():
                with mgr_lock:
                    if mgr_state["shuffle_id"] is not None:
                        return mgr_state["shuffle_id"]
                    manager = ctx.shuffle_manager
                    sid = base_sid + mgr_state["generation"] * 10_000
                    writer = manager.get_writer(
                        sid, map_id=mp_rank if multiproc else 0,
                        num_partitions=nparts,
                        attempt=mgr_state["attempt"],
                    )
                    try:
                        for p, bucket in enumerate(materialize()):
                            for db in bucket:
                                # graft: ok(host-sync: shuffle-manager write
                                # filter — serializing an empty bucket batch
                                # costs a frame per peer; one scalar pull per
                                # bucket batch on the manager path only)
                                if db.row_count():
                                    writer.write(p, db)
                        writer.commit()
                    except BaseException:
                        # atomic per-(map, attempt) commit: a mid-write
                        # failure drops THIS attempt's partial blocks and
                        # advances the attempt id, so the task retry's
                        # re-write can never duplicate batches a consumer
                        # would read twice
                        writer.abort()
                        mgr_state["attempt"] += 1
                        raise
                    state["buckets"] = None  # catalog owns the batches now
                    mgr_state["shuffle_id"] = sid
                    return sid

            consumed: set = set()

            def make_managed(p):
                def it():
                    with mgr_lock:
                        if mgr_state.get("released"):
                            # task retry AFTER the map output was freed: the
                            # thunk must stay re-runnable (lineage recovery),
                            # so re-run the map stage under the next
                            # generation's shuffle id — materialize()
                            # re-executes the child pipeline since its
                            # buckets were handed to the (now unregistered)
                            # catalog. Without this, the retry would read an
                            # unknown shuffle id and silently commit ZERO
                            # rows for this partition.
                            mgr_state["shuffle_id"] = None
                            mgr_state["generation"] += 1
                            mgr_state["released"] = False
                            # full reset: stale entries would re-trip the
                            # release after ONE retried read, forcing every
                            # other retried partition to re-run the whole
                            # map stage again; the fresh generation frees
                            # only when it fully drains (query end is the
                            # backstop for partially-retried generations)
                            consumed.clear()
                    sid = ensure_written()
                    if multiproc and p % mp_size != mp_rank:
                        # a peer owns this reduce partition; this executor
                        # only had to contribute its map output (above)
                        return
                    from ..resilience import faults as _faults
                    from ..shuffle.client import ShuffleFetchError
                    from ..shuffle.manager import MapOutputLostError

                    if _faults.lose_map_output():
                        # chaos: the committed map output vanishes wholesale
                        # (peer death) — the recovery path below must rebuild
                        # it from lineage, not silently read zero rows
                        ctx.shuffle_manager.unregister_shuffle(sid)

                    def _lost(cause):
                        # Map-output recomputation: mark this generation
                        # released so the NEXT attempt of any reduce task
                        # re-runs the map stage under a fresh shuffle id,
                        # then raise the recoverable error the session's
                        # task-retry loop re-executes on. Guarded by the
                        # sid match: concurrent losers of one generation
                        # bump it exactly once.
                        if not cfg.RECOVERY_RECOMPUTE_ENABLED.get(ctx.conf):
                            raise cause
                        if mgr_state["generation"] >= (
                            cfg.RECOVERY_MAX_MAP_RECOMPUTES.get(ctx.conf)
                        ):
                            raise cause
                        with mgr_lock:
                            if (
                                mgr_state["shuffle_id"] == sid
                                and not mgr_state.get("released")
                            ):
                                mgr_state["released"] = True
                                from ..obs.metrics import GLOBAL as _obs

                                _obs.counter(
                                    "shuffle.recomputedPartitions"
                                ).add(1)
                        raise MapOutputLostError(
                            f"shuffle {sid} partition {p}: map output lost "
                            f"({cause}); recomputing from lineage under "
                            "generation "
                            f"{mgr_state['generation'] + 1}"
                        ) from cause

                    if not multiproc and not (
                        ctx.shuffle_manager.registry.outputs_for(sid)
                    ):
                        # single-process reads pass expected_maps=0, so an
                        # emptied registry would otherwise yield NOTHING —
                        # ensure_written always commits a MapStatus (even
                        # all-empty sizes), so absence means loss
                        _lost(MapOutputLostError(
                            f"shuffle {sid}: no map outputs registered"
                        ))
                    try:
                        yield from ctx.shuffle_manager.get_reader().read_partitions(
                            sid, p, p + 1,
                            expected_maps=mp_size if multiproc else 0,
                        )
                    except (ShuffleFetchError, TimeoutError) as e:
                        # blacklisted peer / exhausted fetch budget: the
                        # peer's output is unreachable — rebuild it
                        _lost(e)
                    # free catalog-held map output once every partition has
                    # been drained (ShuffleBufferCatalog unregisterShuffle)
                    with mgr_lock:
                        consumed.add(p)
                        done = (
                            len(consumed) == nparts
                            and not mgr_state.get("released")
                            and mgr_state["shuffle_id"] == sid
                            # a reused exchange is drained once per consumer;
                            # early release would force a map-stage re-run.
                            # Multi-process: peers fetch on their own clock —
                            # map output lives until the executor exits.
                            and not getattr(self, "_reuse_shared", False)
                            and not multiproc
                        )
                        if done:
                            mgr_state["released"] = True
                    if done:
                        ctx.shuffle_manager.unregister_shuffle(sid)

                return it

            return PartitionSet([make_managed(p) for p in range(nparts)])

        if cfg.ADAPTIVE_ENABLED.get(ctx.conf) and not self._aqe_disabled:
            # AQE partition coalescing + skew-join splitting
            # (GpuCustomShuffleReaderExec / CoalescedPartitionSpec +
            # OptimizeSkewedJoin analogues): measured output sizes group
            # adjacent small partitions into one reduce task, and — when
            # this exchange feeds a shuffled join — an oversized partition
            # is split across the freed slots while the peer's partition is
            # replicated. The partition COUNT stays static (PartitionSets
            # are fixed-arity); both join sides compute the SAME plan from
            # the combined measurements, so positional pairing holds.
            advisory = cfg.ADVISORY_PARTITION_SIZE.get(ctx.conf)
            skew_on = cfg.SKEW_JOIN_ENABLED.get(ctx.conf)
            skew_thresh = cfg.SKEW_JOIN_THRESHOLD.get(ctx.conf)
            skew_factor = cfg.SKEW_JOIN_FACTOR.get(ctx.conf)
            aqe_state = {"assign": None}

            def my_sizes():
                # LIVE-row bytes, not capacity bytes: bucket batches share
                # the input's (padded) capacity, which would make every
                # bucket look equally big and hide both small partitions
                # and skew. One pipelined device_get for all counts,
                # memoized — both sides of a linked join read each
                # exchange's sizes (each host sync stalls dispatch).
                if aqe_state.get("sizes") is None:
                    buckets = materialize()
                    # graft: ok(host-sync: AQE needs measured sizes on host
                    # to plan coalescing — ONE pipelined device_get for all
                    # bucket counts, memoized per exchange)
                    counts = jax.device_get(
                        [[db.num_rows for db in b] for b in buckets]
                    )
                    rb = _row_bytes(self.output)
                    aqe_state["sizes"] = [int(sum(c)) * rb for c in counts]
                return aqe_state["sizes"]

            ctx.aqe_size_providers[id(self)] = my_sizes

            def assignment():
                if aqe_state["assign"] is not None:
                    return aqe_state["assign"]
                sizes = my_sizes()
                peer = self._aqe_peer
                if peer is None:
                    assign, _ = _aqe_join_plan(
                        sizes, [0] * nparts, nparts, advisory, (), 0, 0
                    )
                else:
                    peer_fn = ctx.aqe_size_providers.get(id(peer))
                    if peer_fn is None:
                        # peer never took the AQE path: identity grouping
                        # preserves positional pairing
                        assign = [[(p, 0, 1)] for p in range(nparts)]
                        aqe_state["assign"] = assign
                        self.aqe_groups = nparts
                        return assign
                    sides = (
                        _SPLITTABLE_SIDES.get(
                            getattr(self, "_aqe_join_type", "inner"), ()
                        )
                        if skew_on
                        else ()
                    )
                    mine, theirs = sizes, peer_fn()
                    if getattr(self, "_aqe_side", "left") == "left":
                        a, b = _aqe_join_plan(
                            mine, theirs, nparts, advisory, sides,
                            skew_thresh, skew_factor,
                        )
                        assign = a
                    else:
                        a, b = _aqe_join_plan(
                            theirs, mine, nparts, advisory, sides,
                            skew_thresh, skew_factor,
                        )
                        assign = b
                self.aqe_groups = sum(1 for a in assign if a)
                self.aqe_splits = sum(
                    1 for slot in assign for (_, j, k) in slot if k > 1 and j == 0
                )
                aqe_state["assign"] = assign
                return assign

            def make_aqe(p):
                def it():
                    buckets = materialize()
                    tok = ctx.cancel_token
                    for src, j, k in assignment()[p]:
                        if tok is not None:
                            tok.check()
                        if k == 1:
                            yield from buckets[src]
                        else:
                            for db in buckets[src]:
                                if tok is not None:
                                    tok.check()
                                part = _row_range_slice(db, j, k)
                                if part is not None:
                                    yield part

                return it

            return PartitionSet([make_aqe(p) for p in range(nparts)])

        def make(p):
            def it():
                tok = ctx.cancel_token
                for db in materialize()[p]:
                    if tok is not None:
                        tok.check()
                    yield db

            return it

        return PartitionSet([make(p) for p in range(nparts)])

    def node_string(self):
        return f"TpuShuffleExchange {self.partitioning} p={self.num_partitions}"


class TpuLimitExec(Exec):
    def __init__(self, n: int, child: Exec):
        super().__init__([child])
        self.n = n

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        limit = self.n
        child_parts = self.children[0].execute(ctx)
        # LIMIT syncs a row count per batch (it must know when to stop);
        # prefetching the upstream stream hides the dispatch gap behind
        # those syncs, and the bounded window caps how far past the limit
        # the producer can run before the early-exit close() stops it.
        from .pipeline import pipe_metrics, pipeline_conf, pipelined_partition

        pconf = pipeline_conf(ctx)
        metrics = pipe_metrics(self, ctx) if pconf is not None else None

        def it():
            remaining = limit
            tok = ctx.cancel_token

            def consume(src):
                nonlocal remaining
                for db in src:
                    if tok is not None:
                        tok.check()
                    if remaining <= 0:
                        return
                    out = slice_head(db, jnp.asarray(remaining, jnp.int32))
                    # graft: ok(host-sync: LIMIT must learn the row count
                    # to know when to stop — the documented per-batch sync
                    # the pipelined prefetch window exists to hide)
                    n = out.row_count()
                    remaining -= n
                    if n:
                        yield out

            for t in child_parts.parts:
                yield from pipelined_partition(pconf, ctx, t(), consume, metrics)
                if remaining <= 0:
                    return

        return PartitionSet([it])


# ── batch coalescing (GpuCoalesceBatches.scala:92-455) ─────────────────────


class CoalesceGoal:
    """Batching contract lattice (CoalesceGoal: RequireSingleBatch >
    TargetSize) — how much input batching an operator needs."""

    __slots__ = ("target_bytes",)
    SINGLE = None  # sentinel set below

    def __init__(self, target_bytes: int):
        self.target_bytes = target_bytes

    def __repr__(self):
        if self.target_bytes < 0:
            return "RequireSingleBatch"
        return f"TargetSize({self.target_bytes})"

    def __eq__(self, o):
        return isinstance(o, CoalesceGoal) and o.target_bytes == self.target_bytes

    def __hash__(self):
        return hash(("goal", self.target_bytes))


CoalesceGoal.SINGLE = CoalesceGoal(-1)


class TpuCoalesceBatchesExec(Exec):
    """Concatenate undersized device batches up to the goal before handing
    them to the parent (GpuCoalesceBatches' Table.concatenate accumulation
    loop :133-455). Many-small-file scans otherwise push one tiny batch per
    file through every downstream kernel — each a device round trip."""

    def __init__(self, child: Exec, goal: CoalesceGoal):
        super().__init__([child])
        self.goal = goal

    @property
    def output(self) -> Schema:
        return self.children[0].output

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        goal = self.goal
        batches_m = self.metric("numOutputBatches", "ESSENTIAL")

        def fn(it):
            tok = ctx.cancel_token
            acc: list = []
            acc_bytes = 0

            def flush():
                nonlocal acc, acc_bytes
                if not acc:
                    return None
                out = acc[0] if len(acc) == 1 else concat_device(acc)
                acc, acc_bytes = [], 0
                batches_m.add(1)
                return out

            for db in it:
                if tok is not None:
                    tok.check()
                sz = db.size_bytes()
                if (
                    goal.target_bytes >= 0
                    and acc
                    and acc_bytes + sz > goal.target_bytes
                ):
                    out = flush()
                    if out is not None:
                        yield out
                acc.append(db)
                acc_bytes += sz
            out = flush()
            if out is not None:
                yield out

        return self.children[0].execute(ctx).map_partitions(fn)

    def node_string(self):
        return f"TpuCoalesceBatches {self.goal!r}"
