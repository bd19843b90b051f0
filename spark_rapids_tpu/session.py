"""TpuSession + DataFrame — the SparkSession-shaped entry point.

The reference is a plugin into a running SparkSession (Plugin.scala injects
ColumnarOverrideRules); standalone, this module owns the whole query
lifecycle: DataFrame → logical plan → CPU physical plan → TpuOverrides
rewrite → execution. ``conf["spark.rapids.sql.enabled"]=False`` gives the
pure-CPU run — which is exactly how the differential test harness produces
its oracle (the reference's with_cpu_session/with_gpu_session idiom,
integration_tests asserts.py:313-377).
"""
from __future__ import annotations

from contextlib import contextmanager as _contextmanager
from typing import Any, Iterable, List, Optional, Sequence, Union

import pyarrow as pa

from . import config as cfg
from .config import TpuConf
from .expr import Alias, Expression, UnresolvedAttribute, output_name
from .functions import Column, _e, col
from .plan import logical as L
from .plan.overrides import TpuOverrides
from .plan.physical import Exec, ExecContext
from .plan.planner import plan_physical
from .types import Schema
from .columnar.host import concat_batches
from .obs import metrics as _obs_metrics

_M_SUBQUERY_SEMI_JOINS = _obs_metrics.GLOBAL.counter("subquery.semiJoins")
_M_SUBQUERY_HOST_VALUES = _obs_metrics.GLOBAL.counter("subquery.hostValues")
_M_EXCHANGES_REUSED = _obs_metrics.GLOBAL.counter("exchange.reused")

# threading.stack_size is process-global: EVERY set→spawn→restore window in
# the engine (partition workers here, pipeline producers) shares this one
# lock — two independent locks could interleave and spawn a thread after
# the other window's restore (utils/threads.py)
from .utils.threads import BIG_STACK_BYTES, STACK_SIZE_LOCK as _STACK_SIZE_LOCK


def _token_checked(thunk, token, ledger=None):
    """Wrap a partition thunk so the query's cancel token is checked once
    per result batch — with CPU-only plans (no device loop to check) this
    IS the batch-boundary cancellation guarantee."""
    if token is None and ledger is None:
        return thunk

    def it():
        # install the token as this worker thread's watchdog current so
        # blocking regions beneath the pull (kernel compile, shuffle
        # fetch) can label their stall phase; every check() is a beat —
        # and the query's phase ledger rides the same install so those
        # regions attribute their time (obs/ledger.py current-ledger)
        from .obs import ledger as _ledger
        from .resilience import watchdog as _wd

        if token is not None:
            _wd.set_current(token)
        _ledger.set_current(ledger)
        try:
            for rb in thunk():
                if token is not None:
                    token.check()
                yield rb
        finally:
            _wd.set_current(None)
            _ledger.set_current(None)

    return it


class TpuSession:
    def __init__(self, conf: Optional[dict] = None):
        from . import kernels as K

        K.enable_persistent_cache()  # reuse XLA binaries across processes
        self.conf = TpuConf(conf or {})
        # version shim (ShimLoader analogue): semantics knobs route through
        # it; shim-driven defaults fill keys the user left unset
        from .shims import get_shim

        self.shim = get_shim(cfg.SPARK_VERSION.get(self.conf))
        if self.conf.get_raw(cfg.ANSI_ENABLED.key) is None and self.shim.ansi_default():
            self.conf = self.conf.set(cfg.ANSI_ENABLED.key, True)
        if (
            self.conf.get_raw(cfg.ADAPTIVE_ENABLED.key) is None
            and self.shim.adaptive_default()
        ):
            self.conf = self.conf.set(cfg.ADAPTIVE_ENABLED.key, True)
        if cfg.CPU_ONLY.get(self.conf):
            import jax

            jax.config.update("jax_platforms", "cpu")
        # native host data plane gate (spark.rapids.native.enabled)
        from . import native as _native

        _native.set_enabled(cfg.NATIVE_ENABLED.get(self.conf))
        from .ops import pallas_strings as _ps

        _ps.set_enabled(cfg.PALLAS_ENABLED.get(self.conf))
        self._mesh_ctx = None
        # startup_only: mesh mode is committed at construction (partition
        # arity, exchange lowering); per-query surfaces read this frozen
        # flag, never the conf (conf-key lint, scope rule)
        self._mesh_on = cfg.MESH_ENABLED.get(self.conf)
        if self._mesh_on:
            # mesh mode: one exchange partition per chip, so the planner's
            # shuffle arity matches the mesh unless the user pinned it
            if self.conf.get_raw(cfg.SHUFFLE_PARTITIONS.key) is None:
                self.conf = self.conf.set(
                    cfg.SHUFFLE_PARTITIONS.key, self.mesh_context().n
                )
        elif (
            cfg.SQL_ENABLED.get(self.conf)
            and self.conf.get_raw(cfg.SHUFFLE_PARTITIONS.key) is None
        ):
            # single-device default: ONE task (the reference's
            # concurrentGpuTasks model). Without mesh mode every partition
            # runs serialized on the default device — each extra partition
            # is another kernel pipeline + host sync (not measured on the
            # chip). Mesh mode above
            # sets one partition per chip instead.
            self.conf = self.conf.set(cfg.SHUFFLE_PARTITIONS.key, 1)
        self.read = DataFrameReader(self)
        self._temp_views: dict = {}  # lower-case name -> DataFrame
        self._last_plan: Optional[Exec] = None
        self._last_overrides: Optional[TpuOverrides] = None
        self._last_fused_stages = 0
        self._task_retries = 0
        self._query_seq = 0
        import threading as _threading

        self._retry_lock = _threading.Lock()
        # multi-tenant scheduler (sched/): admission control + cancellation
        # registry for concurrent collect()/toPandas() callers. Scheduler
        # CONFS are re-read at every admission (nothing frozen here).
        from .sched import QueryScheduler

        self._scheduler = QueryScheduler()
        # concurrency guards for the session-lifetime caches: the df.cache()
        # store (single-flight per cache key) and the device-upload LRU
        self._cache_lock = _threading.Lock()
        self._h2d_lock = _threading.Lock()
        # the caches those locks guard (previously lazy __dict__ entries;
        # eager init lets the guarded-by pass anchor its annotations)
        self._cache_store: dict = {}  # graft: guarded_by(_cache_lock)
        self._h2d_cache: dict = {}  # graft: guarded_by(_h2d_lock)
        # common-work sharing (cache/keys|results|subplan): per-table
        # monotonic write counters behind result/prepared invalidation
        # (every write path routes through cache/keys.bump_table_version,
        # which also bumps the global _catalog_version the prepared-plan
        # cache keys on), the bounded semantic result cache, and the
        # in-flight shared-subtree registry. See docs/result-cache.md.
        self._catalog_lock = _threading.Lock()
        self._catalog_version = 0  # graft: guarded_by(_catalog_lock)
        self._table_versions: dict = {}  # graft: guarded_by(_catalog_lock)
        self._view_sources: dict = {}  # graft: guarded_by(_catalog_lock)
        self._view_source_ids: dict = {}  # graft: guarded_by(_catalog_lock)
        from .cache.results import ResultCache
        from .cache.subplan import SubplanRegistry

        self._result_cache = ResultCache(self.conf)
        self._subplan_registry = SubplanRegistry()
        # live analytics runtime (live/): built lazily by the `live`
        # property, gated on spark.rapids.tpu.live.enabled
        self._live_runtime = None
        # resilience: session-lifetime CPU-fallback circuit breaker (runtime
        # kernel failures flip ops to CPU at the next planning pass) and the
        # deterministic fault-injection scenario (None unless
        # spark.rapids.tpu.faults.enabled — chaos testing only)
        from .resilience import CircuitBreaker

        self._breaker = CircuitBreaker.from_conf(self.conf)
        # survivability wiring: the watchdog feeds op-attributed stalls to
        # this session's breaker, and the first-touch compile budget is
        # process-global like the kernel cache it guards
        self._scheduler.breaker = self._breaker
        K.set_compile_deadline(cfg.COMPILE_DEADLINE_S.get(self.conf))
        # shape-bucket lattice: process-global like the kernel cache whose
        # entry count it bounds (columnar/device.py bucket_capacity reads it)
        K.set_shape_bucket_floor(
            cfg.SHAPE_BUCKETS_MIN_ROWS.get(self.conf)
            if cfg.SHAPE_BUCKETS_ENABLED.get(self.conf)
            else 1
        )
        # restart survivability: the process-global on-disk XLA executable
        # store (cache/xla_store.py) — GuardedJit consults it before
        # compiling, so a restarted server starts hot in seconds
        from .cache import xla_store as _xc

        _xc.configure(self.conf)
        # obs wiring: the dynamic-series cardinality cap is process-global
        # (the registry it guards is), and the live scrape endpoint starts
        # here for bare sessions (TpuServer.start also ensures it)
        from .obs import metrics as _obs_metrics
        from .obs.scrape import ensure_scrape

        _obs_metrics.set_slug_cap(cfg.METRICS_MAX_DYNAMIC_SLUGS.get(self.conf))
        ensure_scrape(self)
        self._fault_injector = self._build_fault_injector()
        mp_driver = cfg.MULTIPROC_DRIVER.get(self.conf)
        mp_rank = cfg.MULTIPROC_RANK.get(self.conf)
        mp_size = cfg.MULTIPROC_SIZE.get(self.conf)
        if mp_driver:
            # fail fast on inconsistent multi-process settings — a missing
            # piece silently double-counts (every rank runs the full query)
            if not cfg.SHUFFLE_MANAGER_ENABLED.get(self.conf):
                raise ValueError(
                    "spark.rapids.shuffle.multiproc.driver requires "
                    "spark.rapids.shuffle.manager.enabled=true"
                )
            if mp_size < 2 or not (0 <= mp_rank < mp_size):
                raise ValueError(
                    f"multiproc rank/size invalid: rank={mp_rank} "
                    f"size={mp_size}"
                )
        # The multiproc keys are startup_only: the transport, executor id,
        # and driver registration commit to this topology NOW, so every
        # per-query surface (ExecContext, the exchange's rank split) reads
        # the frozen tuple instead of re-reading the conf — a live
        # set_conf can no longer make the plan disagree with the running
        # transport (conf-key lint, scope rule). The thread-local override
        # lets subquery resolution run single-process WITHOUT mutating the
        # shared conf (the old saved/restored-conf dance raced concurrent
        # queries on other threads into multiproc-off planning).
        self._mp_topology = (
            (mp_driver, mp_rank, mp_size) if mp_driver else ("", 0, 1)
        )
        self._mp_off_tls = _threading.local()

    def _build_fault_injector(self):
        """One injector for the session's lifetime, so every-Nth fault
        counters accumulate across queries (None unless faults enabled)."""
        from .resilience import faults as _faults

        config = _faults.config_from_conf(self.conf)
        return None if config is None else _faults.FaultInjector(config)

    def sql(self, text: str, params=None) -> "DataFrame":
        """Run a SELECT statement over registered temp views (sql/ package —
        the standalone analogue of riding Spark's parser; reference QA
        battery: integration_tests/src/main/python/qa_nightly_sql.py).
        ``params`` binds the statement's ``?`` placeholders positionally —
        AST-level substitution (sql/parser.py::bind_parameters), so values
        are always literals, never spliced text."""
        from .sql import Compiler, bind_parameters, parse

        q = parse(text)
        if params is not None:
            q = bind_parameters(q, params)
        return Compiler(self).compile(q)

    def create_or_replace_temp_view(self, name: str, df: "DataFrame"):
        from .cache import keys as _ckeys

        self._temp_views[name.lower()] = df
        key = _ckeys.table_key_for_view(name)
        # map the view's backing tables so result-cache read sets resolve
        # physical scans (keyed by source identity) back to this view
        _ckeys.register_view_sources(
            self, key, _ckeys.view_backing_tables(df._plan)
        )
        # bumps this view's write counter AND the global catalog version
        # (the serve prepared-plan cache keys on the global), and evicts
        # dependent result-cache entries
        _ckeys.bump_table_version(self, key)

    def drop_temp_view(self, name: str) -> bool:
        """Unregister a temp view. A write path like any other: the
        view's version bumps so cached results and prepared plans built
        against it can never serve after the drop."""
        from .cache import keys as _ckeys

        df = self._temp_views.pop(name.lower(), None)
        if df is None:
            return False
        key = _ckeys.table_key_for_view(name)
        _ckeys.register_view_sources(self, key, ())
        _ckeys.bump_table_version(self, key)
        return True

    def table(self, name: str) -> "DataFrame":
        try:
            return self._temp_views[name.lower()]
        except KeyError:
            raise ValueError(f"unknown table {name!r}") from None

    def _next_query_seq(self) -> int:
        with self._retry_lock:
            self._query_seq += 1
            return self._query_seq

    # ── live analytics (live/) ──────────────────────────────────────────
    @property
    def live(self):
        """The session's :class:`live.LiveRuntime` — streaming append
        ingestion, incremental view maintenance, and subscription fan-out
        (ISSUE 20). Gated on ``spark.rapids.tpu.live.enabled`` (default
        off); built lazily on first touch."""
        if not cfg.LIVE_ENABLED.get(self.conf):
            raise RuntimeError(
                "live analytics is disabled: set "
                "spark.rapids.tpu.live.enabled=true before using "
                "session.live"
            )
        rt = self._live_runtime
        if rt is None:
            from .live import LiveRuntime

            # construct OUTSIDE the session lock: the runtime's __init__
            # acquires its own tier-17 live locks (listener registration),
            # which must never nest under a tier-78 session lock. A racing
            # loser is discarded before it spawns any thread or state.
            candidate = LiveRuntime(self)
            with self._retry_lock:
                if self._live_runtime is None:
                    self._live_runtime = candidate
                rt = self._live_runtime
        return rt

    # ── multi-tenant scheduling (sched/) ────────────────────────────────
    @property
    def scheduler(self):
        """The session's QueryScheduler (admission pool + active-query
        registry) — read-only introspection for services and tests."""
        return self._scheduler

    def active_queries(self) -> dict:
        """query_id → {pool, permits, granted, running, queue_wait_s} of
        every query currently queued or executing in this session — the
        live queue view the serve STATUS command and ops tooling render."""
        return self._scheduler.active_queries()

    def cancel(self, query_id: str, reason: str = "cancelled by user") -> bool:
        """Cancel one in-flight query (the ``cancelJobGroup`` analogue for
        a single query): it stops at its next batch boundary, releases its
        admission permits, and raises QueryCancelledError to its caller.
        True when a matching active query existed."""
        return self._scheduler.cancel(query_id, reason)

    def cancel_all(self, reason: str = "cancel_all") -> int:
        """Cancel every queued and running query; returns how many were
        flagged. The session stays fully usable afterwards."""
        return self._scheduler.cancel_all(reason)

    def multiproc_topology(self) -> tuple:
        """``(driver, rank, size)`` as frozen at session construction —
        the only sanctioned read of the startup_only multiproc keys on
        the query path. Returns the single-process tuple while the
        calling thread is inside a subquery-resolution scope (see
        ``_resolve_subqueries``: subqueries must run WHOLE on every
        rank, and the thread-local override gets that without mutating
        the shared conf under concurrent queries)."""
        if getattr(self._mp_off_tls, "depth", 0) > 0:
            return ("", 0, 1)
        return self._mp_topology

    @_contextmanager
    def _single_process_scope(self):
        """Thread-local multiproc-off scope for subquery resolution. A
        DEPTH counter, not a flag: a subquery nested inside another
        subquery must not re-enable multiproc for the still-executing
        outer one when the inner scope exits."""
        tls = self._mp_off_tls
        tls.depth = getattr(tls, "depth", 0) + 1
        try:
            yield
        finally:
            tls.depth -= 1

    def mesh_context(self):
        """Lazily build the session's MeshContext (mesh mode only)."""
        if self._mesh_ctx is None:
            import jax

            from .parallel.mesh import MeshContext

            n = cfg.MESH_SIZE.get(self.conf) or len(jax.devices())
            self._mesh_ctx = MeshContext(min(n, len(jax.devices())))
        return self._mesh_ctx

    # ── builders ────────────────────────────────────────────────────────
    def create_dataframe(
        self,
        data: Union[pa.Table, pa.RecordBatch, dict, list],
        schema: Optional[Schema] = None,
        num_partitions: int = 1,
    ) -> "DataFrame":
        if isinstance(data, pa.RecordBatch):
            table = pa.Table.from_batches([data])
        elif isinstance(data, pa.Table):
            table = data
        elif isinstance(data, dict):
            table = pa.table(data)
        else:
            raise TypeError(f"cannot create dataframe from {type(data)}")
        if schema is None:
            schema = Schema.from_arrow(table.schema)
        else:
            table = table.cast(schema.to_arrow())
        return DataFrame(self, L.LocalRelation(table, schema, num_partitions))

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None, step: int = 1, num_partitions: int = 1):
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Range(start, end, step, num_partitions))

    def set_conf(self, key: str, value: Any):
        self.conf = self.conf.set(key, value)
        if key.startswith("spark.rapids.tpu.faults."):
            self._fault_injector = self._build_fault_injector()
        if key == cfg.COMPILE_DEADLINE_S.key:
            from . import kernels as K

            K.set_compile_deadline(cfg.COMPILE_DEADLINE_S.get(self.conf))
        if key.startswith("spark.rapids.tpu.compileCache."):
            from .cache import xla_store as _xc

            _xc.configure(self.conf)
        if key.startswith("spark.rapids.tpu.shapeBuckets."):
            from . import kernels as K

            K.set_shape_bucket_floor(
                cfg.SHAPE_BUCKETS_MIN_ROWS.get(self.conf)
                if cfg.SHAPE_BUCKETS_ENABLED.get(self.conf)
                else 1
            )

    # ── execution ───────────────────────────────────────────────────────
    def _resolve_subqueries(self, lp: L.LogicalPlan) -> L.LogicalPlan:
        """Execute every subquery plan through the full engine and inline
        the results (Spark executes subqueries before the main query;
        reference GpuScalarSubquery.scala / GpuInSet.scala):

            ScalarSubquery(plan) → Literal(value)
            InSubquery(c, plan)  → InSet(c, distinct values)

        An ``InSubquery`` that is a conjunct of a filter never gets here:
        ``plan/subquery.py`` has made it a left-semi join (the shapes that
        do get here are listed in ``expr/subquery.py``).
        """
        from .expr.base import Literal
        from .expr.subquery import InSet, InSubquery, ScalarSubquery

        def run_whole(plan):
            """Subqueries resolve to literals every executor needs — under a
            multi-process query each process computes the WHOLE subquery
            locally (rank-splitting it would inline a partial aggregate).
            The single-process override is THREAD-LOCAL (ExecContext reads
            multiproc_topology() at construction): the old save/restore of
            the shared conf let a concurrent query on another thread plan
            itself multiproc-off mid-subquery."""
            from .obs import ledger as obs_ledger

            # the subquery is a job of its own with a ledger of its own;
            # the main query waits for it here and says so
            with obs_ledger.phase("subquery"):
                if self._mp_topology[0]:
                    with self._single_process_scope():
                        return self._execute(plan)
                return self._execute(plan)

        def fix(e):
            if isinstance(e, ScalarSubquery):
                tbl = run_whole(e.plan)
                if tbl.num_columns != 1:
                    raise ValueError(
                        "scalar subquery must return one column, got "
                        f"{tbl.num_columns}"
                    )
                if tbl.num_rows > 1:
                    raise ValueError(
                        "scalar subquery returned more than one row"
                    )
                val = tbl.column(0)[0].as_py() if tbl.num_rows else None
                from .types import DateType, TimestampType

                if val is not None and isinstance(
                    e.data_type, (DateType, TimestampType)
                ):
                    # date/timestamp literals store their physical ints
                    # (Literal.eval special-cases only None/string/decimal)
                    val = InSet._encode_values([val], e.data_type)[0]
                return Literal(val, e.data_type)
            if isinstance(e, InSubquery):
                tbl = run_whole(e.plan)
                if tbl.num_columns != 1:
                    raise ValueError(
                        "IN-subquery must return one column, got "
                        f"{tbl.num_columns}"
                    )
                vals = tbl.column(0).to_pylist()
                _M_SUBQUERY_HOST_VALUES.add(len(vals))
                seen: set = set()
                out = []
                has_null = False
                for x in vals:
                    if x is None:
                        has_null = True
                        continue
                    try:
                        new = x not in seen
                        if new:
                            seen.add(x)
                    except TypeError:
                        new = True
                    if new:
                        out.append(x)
                if has_null:
                    out.append(None)
                return InSet(e.c, tuple(out))
            return e

        return L.transform_expressions(lp, fix)

    def _translate_udfs(self, lp: L.LogicalPlan) -> L.LogicalPlan:
        """udf-compiler pass: rewrite translatable python UDFs into plain
        expression trees so they fuse on device (reference
        udf-compiler/CatalystExpressionBuilder.scala; subset documented in
        expr/udf_compiler.py). Untranslatable UDFs keep their CPU
        fallback."""
        from .expr.udf import PythonUdf
        from .expr.udf_compiler import try_translate

        def fix(e):
            if isinstance(e, PythonUdf):
                t = try_translate(e.fn, list(e.args), e.return_type)
                if t is not None:
                    return t
            return e

        return L.transform_expressions(lp, fix)

    def _resolve_cached(self, lp: L.LogicalPlan) -> L.LogicalPlan:
        """Materialize InMemoryRelation nodes: first touch executes the
        subtree and stores the result as PARQUET BYTES in memory (the
        ParquetCachedBatchSerializer analogue — compressed columnar cache,
        reference shims/spark311/ParquetCachedBatchSerializer.scala);
        later touches decode from the store."""
        import dataclasses as _dc

        if not isinstance(lp, L.LogicalPlan):
            return lp
        if isinstance(lp, L.InMemoryRelation):
            entry = self._cache_entry(lp)
            return L.LocalRelation(
                entry["table"], lp.schema, lp.num_partitions
            )
        kw = {}
        changed = False
        for f in _dc.fields(lp):
            v = getattr(lp, f.name)
            if isinstance(v, L.LogicalPlan):
                nv = self._resolve_cached(v)
            elif isinstance(v, list) and v and isinstance(v[0], L.LogicalPlan):
                nv = [self._resolve_cached(c) for c in v]
            else:
                nv = v
            kw[f.name] = nv
            if nv is not v:
                changed = True
        return _dc.replace(lp, **kw) if changed else lp

    def _cache_entry(self, lp: "L.InMemoryRelation") -> dict:
        """Materialize (or await) one InMemoryRelation's cache entry with
        SINGLE-FLIGHT semantics: the first toucher of a cold key executes
        the subtree; concurrent touchers of the same key block on its done
        event instead of re-executing the subtree or racing the dict (two
        threads double-executing an expensive cached aggregate is precisely
        what cache() exists to prevent). A failed materialization clears
        the key and raises only to the OWNER; waiters retry ownership
        themselves — the owner's failure may be its own cancellation or
        deadline, which must not poison an innocent tenant's query. The
        retry loop terminates: each pass either waits for a different
        owner or becomes the owner, and an owner always returns or
        raises."""
        import io
        import threading

        import pyarrow.parquet as papq

        while True:
            with self._cache_lock:
                store = self._cache_store
                entry = store.get(lp.cache_key)
                owner = entry is None
                if owner:
                    entry = {
                        "bytes": None,
                        "table": None,
                        "error": None,
                        "done": threading.Event(),
                        "lock": threading.Lock(),
                    }
                    store[lp.cache_key] = entry
            if owner:
                try:
                    table = self._execute(lp.child)
                    buf = io.BytesIO()
                    papq.write_table(table, buf, compression="zstd")
                    entry["bytes"] = buf.getvalue()
                except BaseException as e:
                    entry["error"] = e
                    with self._cache_lock:
                        if store.get(lp.cache_key) is entry:
                            del store[lp.cache_key]
                    raise
                finally:
                    entry["done"].set()
                break
            # this wait predates the waiter's own admission (no CancelToken
            # yet), so session.cancel_all() reaches it through the
            # scheduler's cancel epoch instead — shutdown must not leave a
            # thread parked on another query's materialization
            from .sched import QueryCancelledError

            epoch = self._scheduler.cancel_epoch
            while not entry["done"].wait(0.05):
                if self._scheduler.cancel_epoch != epoch:
                    raise QueryCancelledError(
                        "cancel_all while waiting on cache "
                        f"({lp.cache_key}) materialization"
                    )
            if entry["error"] is None:
                break  # materialized: decode below
        with entry["lock"]:
            if entry["table"] is None:
                entry["table"] = papq.read_table(io.BytesIO(entry["bytes"]))
                # the decoded table serves all later reads (and anchors the
                # device-upload cache); the compressed bytes are done
                entry["bytes"] = None
        return entry

    def uncache(self, key: int) -> None:
        with self._cache_lock:
            entry = self._cache_store.pop(key, None)
        if entry and entry.get("table") is not None:
            # also evict the device uploads anchored on the decoded table —
            # unpersist() must actually free HBM. Same lock as the H2D
            # LRU's insert/evict path: a concurrent query's upload must not
            # race this iteration.
            tid = id(entry["table"])
            with self._h2d_lock:
                h2d = self._h2d_cache
                for k in [k for k in h2d if len(k) > 1 and k[1] == tid]:
                    h2d.pop(k, None)

    def _execute(self, lp: L.LogicalPlan) -> pa.Table:
        from .resilience import faults as _faults

        # chaos harness scope: injection points fire only while THIS
        # session's queries execute (no-op when faults are not enabled)
        with _faults.scoped(self._fault_injector):
            final_plan, ctx = self._prepare_plan(lp)
            # semantic result cache (cache/results.py): an identical
            # completed query short-circuits HERE — before tracing,
            # ledgers, and scheduler admission; a hit must cost no
            # scheduler state at all
            rkey, rkeys = None, ()
            if cfg.RESULT_CACHE_ENABLED.get(self.conf):
                from .cache import results as _rcache

                rkey, rkeys = _rcache.key_for(self, final_plan)
                if rkey is not None:
                    hit = self._result_cache.get(rkey)
                    if hit is not None:
                        return _assemble_result(hit, final_plan.output)
            from .obs import ledger as obs_ledger
            from .obs import trace as obs_trace
            from .profiling import query_trace

            seq = ctx.query_seq
            # concurrent subplan dedup (cache/subplan.py): wrap shareable
            # subtrees for single-flight execution. Admission and
            # calibration keep keying off final_plan; only execution
            # runs the wrapped exec_plan.
            exec_plan, lease = self._subplan_registry.prepare(
                self, final_plan, self.conf, f"q{seq}"
            )
            led = getattr(ctx, "ledger", None)
            tracer = self._maybe_tracer(seq)
            if tracer is not None:
                # tracer pinned into the wrappers: a straggling producer
                # thread keeps recording into ITS query's buffer, never
                # into a later query's active tracer
                obs_trace.instrument_plan(exec_plan, tracer)
            if led is not None:
                led.wall_start()
            try:
                with obs_ledger.ledger_scope(led), obs_trace.query_scope(
                    tracer, f"query-{seq}", {"seq": seq}
                ):
                    # multi-tenant admission (sched/): estimate the HBM
                    # footprint, take a weighted permit share (queueing
                    # under the fair-share policy — the wait shows as a
                    # 'queued' span), install the cancel token, run. The
                    # context manager releases permits on every exit path.
                    with self._scheduler.admit(
                        f"q{seq}", final_plan, self.conf, tracer
                    ) as admission:
                        ctx.cancel_token = admission.token
                        if led is not None:
                            led.add("queue_wait", admission.queue_wait_ns)
                        with query_trace(cfg.PROFILE_PATH.get(self.conf)):
                            result = self._run_plan(exec_plan, ctx)
                        if rkey is not None:
                            # admission re-fingerprints: a write that
                            # raced this execution rejects the store
                            self._result_cache.admit(
                                self, rkey, rkeys, result.to_batches()
                            )
                        return result
            finally:
                if lease is not None:
                    lease.release()
                if led is not None:
                    led.wall_stop()
                    self._last_ledger = led
                self._harvest_calibration(final_plan)
                if tracer is not None:
                    self._export_trace(tracer, exec_plan, seq, ledger=led)
                self._leak_check(ctx)

    def _harvest_calibration(self, final_plan) -> None:
        """Feed the measured per-op cost table at query exit
        (spark.rapids.tpu.cbo.calibration.enabled): opTime ÷ rows per node
        into the EWMA, persisted so later sessions plan on measured costs
        (obs/calibration.py). Never fails a query."""
        if not cfg.CBO_CALIBRATION_ENABLED.get(self.conf):
            return
        from .obs import calibration as obs_cal

        try:
            cal = obs_cal.get(cfg.CBO_CALIBRATION_FILE.get(self.conf))
            if cal.observe_plan(final_plan):
                cal.save()
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "cost-calibration harvest failed", exc_info=True
            )

    def _maybe_tracer(self, seq: int):
        """The span tracer for this query when tracing is on AND this query
        is sampled, else None. Sampling is deterministic in the session's
        query sequence (Dapper-style cheap sampled spans;
        spark.rapids.tpu.trace.sample)."""
        trace_dir = cfg.TRACE_DIR.get(self.conf)
        if not (cfg.TRACE_ENABLED.get(self.conf) or trace_dir):
            return None
        sample = cfg.TRACE_SAMPLE.get(self.conf)
        # Weyl-sequence hash of the seq → [0, 1): deterministic, well
        # spread even for consecutive seqs
        u = ((seq * 2654435761) & 0xFFFFFFFF) / 2**32
        if u >= sample:
            return None
        from .obs.trace import Tracer

        return Tracer(capacity=cfg.TRACE_BUFFER_SPANS.get(self.conf))

    def _export_trace(self, tracer, plan, seq: int, ledger=None) -> None:
        """Per-query artifacts (spark.rapids.tpu.trace.dir): the Chrome-
        trace/Perfetto span dump plus the metrics JSON. Export failures
        never fail the query."""
        self._last_tracer = tracer
        trace_dir = cfg.TRACE_DIR.get(self.conf)
        if not trace_dir:
            return
        import os

        from .obs import export as obs_export

        try:
            tracer.export_chrome(
                os.path.join(trace_dir, f"query-{seq}.trace.json")
            )
            obs_export.write_query_artifact(
                os.path.join(trace_dir, f"query-{seq}.metrics.json"),
                plan=plan,
                session=self,
                tracer=tracer,
                ledger=ledger,
            )
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "trace export to %s failed", trace_dir, exc_info=True
            )

    def _leak_check(self, ctx) -> None:
        if ctx.catalog.debug:
            leaks = ctx.catalog.leak_report()
            if leaks:
                import logging

                logging.getLogger(__name__).warning(
                    "spillable-buffer LEAKS at query end (%d, %d bytes): %s",
                    len(leaks),
                    sum(l["size"] for l in leaks),
                    leaks[:10],
                )

    def _prepare_plan(self, lp: L.LogicalPlan):
        """Analysis + physical planning + overrides: everything _execute
        does before running the plan. Split out so ``DataFrame.to_jax`` can
        execute the same plan WITHOUT the final device→host transition.

        Creates the query's host-overhead ledger (obs/ledger.py, attached
        as ``ctx.ledger``) and bills this whole pass to its ``parse_plan``
        phase — nested compile-warm scopes subtract themselves out."""
        from .obs import ledger as obs_ledger

        led = (
            obs_ledger.PhaseLedger()
            if cfg.LEDGER_ENABLED.get(self.conf)
            else None
        )
        if led is not None:
            led.wall_start()
        try:
            with obs_ledger.ledger_scope(led), obs_ledger.phase("parse_plan"):
                final_plan, ctx = self._prepare_plan_inner(lp)
        finally:
            if led is not None:
                led.wall_stop()
        ctx.ledger = led
        return final_plan, ctx

    def _prepare_plan_inner(self, lp: L.LogicalPlan):
        from .plan.pruning import prune_columns
        from .plan.subquery import rewrite_in_subqueries

        lp, semi_joins = rewrite_in_subqueries(lp)
        if semi_joins:
            _M_SUBQUERY_SEMI_JOINS.add(semi_joins)
        lp = self._resolve_cached(lp)
        lp = self._resolve_subqueries(lp)
        if cfg.UDF_COMPILER_ENABLED.get(self.conf):
            lp = self._translate_udfs(lp)
        mt = cfg.SPLIT_MAX_TOKENS.get(self.conf)
        import dataclasses as _dc

        from .expr.strings_ext import StringSplit as _SS

        lp = L.transform_expressions(
            lp,
            lambda e: _dc.replace(e, max_tokens=mt)
            if isinstance(e, _SS) and e.max_tokens != mt
            else e,
        )
        if cfg.ANSI_ENABLED.get(self.conf):
            # Spark resolves ansiEnabled into Cast at analysis time; same
            # here — the rewrite happens before planning so both the CPU
            # oracle and the device plan see ANSI casts
            import dataclasses as _dc

            from .expr.cast import Cast

            lp = L.transform_expressions(
                lp,
                lambda e: _dc.replace(e, ansi=True)
                if isinstance(e, Cast) and not e.ansi
                else e,
            )
        lp = prune_columns(lp)
        cpu_plan = plan_physical(lp, self.conf)
        overrides = TpuOverrides(self.conf, breaker=self._breaker)
        final_plan = overrides.apply(cpu_plan)
        # whole-stage fusion BEFORE exchange reuse: fusing rewrites operator
        # chains consistently across the plan, so identical exchange
        # subtrees still canonicalize identically — while fusing after
        # reuse would rewrite through physically-shared nodes
        from .plan.fusion import fuse_stages

        final_plan, self._last_fused_stages = fuse_stages(
            final_plan, self.conf, breaker=self._breaker
        )
        if cfg.EXCHANGE_REUSE_ENABLED.get(self.conf):
            from .plan.reuse import reuse_exchanges

            final_plan, self._last_reused_exchanges = reuse_exchanges(final_plan)
            _M_EXCHANGES_REUSED.add(self._last_reused_exchanges)
        else:
            self._last_reused_exchanges = 0
        self._last_plan = final_plan
        self._last_overrides = overrides
        self._assert_test_mode(overrides, final_plan)
        ctx = ExecContext(self.conf, self)
        if cfg.PROFILE_OPTIME.get(self.conf) or cfg.CBO_CALIBRATION_ENABLED.get(
            self.conf
        ):
            # calibration needs per-op opTime attribution (block-until-ready
            # per batch — a measurement mode) to harvest measured ns/row
            from .profiling import instrument_plan

            instrument_plan(final_plan)
        self._last_precompile = {}
        from . import kernels as K

        if cfg.PRECOMPILE_ENABLED.get(self.conf) and (
            self.conf.get_raw(cfg.PRECOMPILE_ENABLED.key) is not None
            or K.precompile_worthwhile()
        ):
            # kernel pre-compilation pass (plan/planner.py): warm the
            # shape-predictable kernels before execution so XLA compiles
            # overlap across plan nodes instead of serializing at first
            # touch of each operator; best-effort by design
            from .plan.planner import precompile_plan

            try:
                self._last_precompile = precompile_plan(final_plan, self.conf)
            except Exception:
                pass
        return final_plan, ctx

    def _run_task(self, thunk, attempts: int, on_retry=None,
                  partition_id: int = 0, token=None, ledger=None,
                  tracer=None) -> List[pa.RecordBatch]:
        """One partition task with Spark's retry model (spark.task.maxFailures;
        SURVEY §5 failure detection): the lineage IS the recovery mechanism —
        a partition thunk is a pure closure over its upstream pipeline, so a
        failed attempt simply re-runs it. Results commit only on success (a
        partial stream from a failed attempt is discarded). Deterministic
        semantic errors surface immediately: retrying an ANSI overflow or an
        assertion can only fail again — and so can a cancelled or
        deadline-expired query (sched/ errors never retry).

        Each attempt runs under a lineage attempt scope
        (resilience/lineage.py): the attempt id becomes this worker
        thread's ``TaskInfo.attempt`` for every plan layer, shuffle writers
        commit atomically per (map, attempt), and re-executions are
        accounted on ``task.reattempts`` with their wall time attributed
        to the ledger's ``recovery`` phase."""
        from .expr.base import AnsiError
        from .resilience import CompileDeadlineError
        from .resilience import faults as _faults
        from .resilience import lineage as _lineage
        from .sched import SchedulerError

        desc = _lineage.TaskDescriptor(partition_id, query_id=getattr(
            token, "query_id", ""
        ))
        last: Optional[Exception] = None
        for attempt in range(max(1, attempts)):
            desc.attempt = attempt
            try:
                with _lineage.attempt_scope(attempt):
                    # chaos straggler point: the configured partition's
                    # FIRST attempt crawls (token-beating sleep) — what the
                    # speculation monitor must overtake
                    _faults.on_task_attempt(partition_id, attempt, token)
                    if attempt == 0:
                        return list(thunk())
                    with _lineage.recovery_scope(ledger):
                        return list(thunk())
            except (AssertionError, AnsiError, SchedulerError,
                    CompileDeadlineError):
                # a blown compile budget is never task-retried: the retry
                # would re-enter the same compile and burn the budget
                # again; the breaker is already forced open, so the
                # caller's NEXT run plans the op on CPU
                raise
            except Exception as e:  # noqa: BLE001 - Spark retries any task failure
                last = e
                if on_retry is not None:
                    on_retry()  # per-query accounting (_run_plan)
                else:
                    with self._retry_lock:
                        self._task_retries += 1
                if attempt + 1 < attempts:
                    import logging

                    _lineage.record_reattempt(desc, e, ledger=ledger,
                                              tracer=tracer)
                    logging.getLogger(__name__).warning(
                        "task failed (partition %d, attempt %d/%d), "
                        "retrying from lineage: %s",
                        partition_id,
                        attempt + 1,
                        attempts,
                        e,
                    )
        assert last is not None
        raise last

    def run_plan_stream(self, final_plan, ctx, on_retry=None):
        """Generator over a prepared plan's result record batches,
        partition by partition — the serving front-end's streaming
        currency (serve/server.py), and the serial collect() path.

        Retry semantics match collect(): a partition's task commits only
        when it SUCCEEDED (``_run_task`` discards the partial stream of a
        failed attempt before any of it is yielded), so the stream never
        duplicates rows; cancellation/deadline raise between batches via
        the context's cancel token. Empty batches are filtered — the wire
        never carries zero-row frames mid-stream (the END frame closes a
        result, not a sentinel batch)."""
        parts = final_plan.execute(ctx)
        attempts = cfg.TASK_MAX_FAILURES.get(self.conf)
        token = getattr(ctx, "cancel_token", None)
        ledger = getattr(ctx, "ledger", None)
        yield from self._stream_parts(parts, attempts, token, on_retry, ledger)

    def _stream_parts(self, parts, attempts, token, on_retry, ledger=None):
        for i, thunk in enumerate(parts.parts):
            for rb in self._run_task(
                _token_checked(thunk, token, ledger), attempts, on_retry,
                partition_id=i, token=token, ledger=ledger,
            ):
                if rb.num_rows:
                    yield rb

    def _run_plan(self, final_plan, ctx) -> pa.Table:
        parts = final_plan.execute(ctx)
        batches: List[pa.RecordBatch] = []
        attempts = cfg.TASK_MAX_FAILURES.get(self.conf)
        token = getattr(ctx, "cancel_token", None)
        ledger = getattr(ctx, "ledger", None)
        # per-QUERY retry count (concurrent queries must not clobber each
        # other mid-flight); the session attribute becomes the last
        # finished query's total, assigned once in the finally below
        query_retries = [0]

        def on_retry():
            with self._retry_lock:
                query_retries[0] += 1

        # concurrentGpuTasks is re-read HERE, per query — a long-lived
        # service retunes it live with set_conf (docs/configs.md scope)
        n_threads = min(len(parts.parts), cfg.CONCURRENT_TPU_TASKS.get(self.conf))
        if n_threads > 1:
            # Run partition tasks concurrently (the reference's executor task
            # slots + GpuSemaphore model): device dispatch and D2H waits of
            # different partitions overlap instead of serializing per
            # partition; jax releases the GIL while blocking on transfers.
            import threading
            from concurrent.futures import ThreadPoolExecutor

            # XLA compilation can run inside these workers (first touch of a
            # kernel); LLVM passes recurse deeply on large fused programs and
            # overflow the default worker stack — give executors a big one.
            # stack_size() is PROCESS-global: the set→spawn→restore window
            # serializes under a lock so a concurrently-admitted query
            # cannot restore the small stack while this one's workers are
            # still being spawned (workers all exist once every submit
            # returns — ThreadPoolExecutor spawns up to max_workers threads
            # on submission, and len(parts) >= n_threads here).
            # straggler speculation (sched/speculation.py): when enabled
            # and this query runs under a cancel token, partitions route
            # through the monitor — it launches duplicate attempts for
            # stragglers, first commit wins, the loser is cancelled with
            # reason 'speculation' through an attempt-scoped child token
            spec = None
            if cfg.SPECULATION_ENABLED.get(self.conf) and token is not None:
                from .sched.speculation import SpeculationMonitor

                spec = SpeculationMonitor.from_conf(
                    self.conf, ctx=ctx, token=token,
                    pool=getattr(self._scheduler, "pool", None),
                    n_partitions=len(parts.parts),
                )

            def _submit_task(i, t):
                if spec is None:
                    return lambda: self._run_task(
                        _token_checked(t, token, ledger), attempts, on_retry,
                        partition_id=i, token=token, ledger=ledger,
                    )

                def run_attempt(attempt_token):
                    return self._run_task(
                        _token_checked(t, attempt_token, ledger), attempts,
                        on_retry, partition_id=i, token=attempt_token,
                        ledger=ledger,
                    )

                return lambda: spec.run_partition(i, run_attempt)

            with _STACK_SIZE_LOCK:
                prev_stack = threading.stack_size(BIG_STACK_BYTES)
                try:
                    pool = ThreadPoolExecutor(max_workers=n_threads)
                    futures = [
                        pool.submit(_submit_task(i, t))
                        for i, t in enumerate(parts.parts)
                    ]
                finally:
                    threading.stack_size(prev_stack)
            try:
                results = [f.result() for f in futures]
            finally:
                pool.shutdown(wait=True)
                if spec is not None:
                    spec.close()
                self._task_retries = query_retries[0]
            batches = [rb for rbs in results for rb in rbs if rb.num_rows]
        else:
            try:
                batches.extend(
                    self._stream_parts(parts, attempts, token, on_retry, ledger)
                )
            finally:
                self._task_retries = query_retries[0]
        from .obs import ledger as obs_ledger

        schema = final_plan.output
        with obs_ledger.scope_or_null(ledger, "serialize"):
            if not batches:
                return pa.table(
                    {
                        f.name: pa.array([], type=f.data_type.to_arrow())
                        for f in schema
                    }
                )
            return pa.Table.from_batches(batches)

    def _assert_test_mode(self, overrides: TpuOverrides, plan: Exec):
        """TEST_CONF: fail when expected-on-device execs fell back
        (reference: GpuTransitionOverrides validation under TEST_CONF)."""
        if not cfg.TEST_CONF.get(self.conf):
            return
        allowed = (cfg.TEST_ALLOWED_NONTPU.get(self.conf) or "").split(",")
        allowed = {a.strip() for a in allowed if a.strip()}
        # WriteFiles encodes on the host side of D2H by design (no device
        # Parquet codec on TPU — io/writer.py docstring)
        allowed |= {
            "CpuScan",
            "CpuFileScan",
            "DeviceToHost",
            "HostToDevice",
            "WriteFiles",
        }
        bad = []
        for e in overrides.explain:
            if e.on_device:
                continue
            name = e.node.split(" ")[0].split("[")[0]
            if not any(name.startswith(a) for a in allowed):
                bad.append((e.node, e.reasons))
        if bad:
            msg = "; ".join(f"{n}: {r}" for n, r in bad)
            raise AssertionError(f"execs unexpectedly not on device: {msg}")


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self._session = session
        self._options: dict = {}

    def option(self, k: str, v) -> "DataFrameReader":
        self._options[k] = v
        return self

    def _rewrite(self, paths) -> tuple:
        """spark.rapids.alluxio.pathsToReplace: 'src->dst' prefix rewrites
        applied before file listing (RapidsConf.scala:929 — route cloud
        reads through a cache mount)."""
        raw = cfg.ALLUXIO_PATHS_TO_REPLACE.get(self._session.conf)
        if not raw:
            return tuple(paths)
        rules = []
        for part in raw.split(","):
            if "->" in part:
                src, dst = part.split("->", 1)
                rules.append((src.strip(), dst.strip()))
        out = []
        for p in paths:
            for src, dst in rules:
                if p.startswith(src):
                    p = dst + p[len(src) :]
                    break
            out.append(p)
        return tuple(out)

    def _bucket_options(self, paths) -> dict:
        """Attach the _bucket_spec.json sidecar (one consistent spec across
        all roots) so the scan can bucket-prune (io/bucketing.py)."""
        import os

        from .io.bucketing import read_spec

        opts = dict(self._options)
        specs = [read_spec(p) for p in paths if os.path.isdir(p)]
        specs = [s for s in specs if s is not None]
        if specs and all(s == specs[0] for s in specs) and len(specs) == len(
            [p for p in paths if os.path.isdir(p)]
        ):
            opts["__bucket_spec"] = specs[0]
        return self._root_options(paths, opts)

    @staticmethod
    def _root_options(roots, opts: dict) -> dict:
        """Record the scan ROOTS (not just the expanded files) on the scan
        node: cache/keys.py needs them so an append that creates a NEW
        partition subdirectory under a scanned root — a directory that did
        not exist at registration time — still invalidates entries keyed
        by that root."""
        import os

        opts["__roots"] = tuple(os.path.realpath(r) for r in roots)
        return opts

    def parquet(self, *paths: str) -> "DataFrame":
        from .io.files import infer_schema, expand_paths

        roots = self._rewrite(paths)
        files = expand_paths(roots, "parquet")
        schema = infer_schema(files, "parquet", self._options)
        return DataFrame(
            self._session,
            L.FileScan(files, "parquet", schema, self._bucket_options(roots)),
        )

    def orc(self, *paths: str) -> "DataFrame":
        from .io.files import infer_schema, expand_paths

        roots = self._rewrite(paths)
        files = expand_paths(roots, "orc")
        schema = infer_schema(files, "orc", self._options)
        return DataFrame(
            self._session,
            L.FileScan(files, "orc", schema, self._bucket_options(roots)),
        )

    def csv(self, *paths: str, **kwargs) -> "DataFrame":
        from .io.files import infer_schema, expand_paths

        opts = dict(self._options)
        opts.update(kwargs)
        # shim-routed default (SparkShims seam): what string reads as NULL
        opts.setdefault("nullValue", self._session.shim.csv_null_value())
        roots = self._rewrite(paths)
        files = expand_paths(roots, "csv")
        schema = infer_schema(files, "csv", opts)
        return DataFrame(
            self._session,
            L.FileScan(files, "csv", schema, self._root_options(roots, opts)),
        )


def _to_exprs(cols: Sequence[Union[str, Column, Expression]]) -> List[Expression]:
    out = []
    for c in cols:
        if isinstance(c, str):
            out.append(UnresolvedAttribute(c))
        elif isinstance(c, Column):
            out.append(c.expr)
        else:
            out.append(c)
    return out


def _extract_windows(
    exprs: List[Expression], plan: L.LogicalPlan
) -> tuple[List[Expression], L.LogicalPlan]:
    """Pull WindowExpressions out of a projection into Window nodes below it
    (Spark's ExtractWindowExpressions): expressions sharing a
    (partition_by, order_by) spec land in one Window node; the projection
    references the appended columns."""
    from .expr.base import bind as _bind
    from .expr.base import map_child_exprs
    from .expr.windows import WindowExpression, WindowOrder, WindowSpec, contains_window

    if not any(contains_window(e) for e in exprs):
        return exprs, plan

    groups: dict = {}  # (partition_by, order_by) -> list[(name, wexpr)]
    counter = [0]
    child_schema = plan.schema

    def pull(e: Expression) -> Expression:
        if isinstance(e, WindowExpression):
            # resolve against the child schema now: the Window node's own
            # schema needs the function's type before planning
            spec = WindowSpec(
                tuple(_bind(p, child_schema) for p in e.spec.partition_by),
                tuple(
                    WindowOrder(_bind(o.child, child_schema), o.ascending, o.nulls_first)
                    for o in e.spec.order_by
                ),
                e.spec.frame,
            )
            e = WindowExpression(_bind(e.function, child_schema), spec)
            key = (spec.partition_by, spec.order_by)
            name = f"__w{counter[0]}"
            counter[0] += 1
            groups.setdefault(key, []).append((name, e))
            return UnresolvedAttribute(name)
        if not e.children():
            return e
        return map_child_exprs(e, pull)

    new_exprs = [pull(e) for e in exprs]
    for cols in groups.values():
        plan = L.Window(cols, plan)
    return new_exprs, plan


def _extract_generators(
    exprs: List[Expression], plan: L.LogicalPlan
) -> tuple[List[Expression], L.LogicalPlan]:
    """Pull a top-level explode/posexplode out of a projection into a
    Generate node below it (Spark's ExtractGenerator); the projection then
    references the generator's output columns by name."""
    from .expr.complex import Explode, contains_generator
    from .types import MapType

    if not any(contains_generator(e) for e in exprs):
        return exprs, plan
    new_exprs: List[Expression] = []
    generator = None
    internal: List[str] = []  # collision-proof Generate output names
    for e in exprs:
        alias = e.name if isinstance(e, Alias) else None
        target = e.child if isinstance(e, Alias) else e
        if isinstance(target, Explode):
            if generator is not None:
                raise ValueError("only one generator per select is supported")
            generator = target
            from .expr import bind as _bind

            ct = _bind(target.child, plan.schema).data_type
            public: List[str] = []
            if target.position:
                public.append("pos")
            if isinstance(ct, MapType):
                if alias is not None:
                    raise ValueError(
                        "explode of a map produces two columns (key, value); "
                        "select them by name instead of aliasing the explode"
                    )
                public.extend(["key", "value"])
            else:
                public.append(alias or "col")
            internal = [f"__gen{i}" for i in range(len(public))]
            new_exprs.extend(
                Alias(UnresolvedAttribute(g), p)
                for g, p in zip(internal, public)
            )
        elif contains_generator(e):
            raise ValueError("explode() must be a top-level select expression")
        else:
            new_exprs.append(e)
    return new_exprs, L.Generate(generator, internal, plan)


def _assemble_result(batches, schema) -> pa.Table:
    """Rebuild a collect() table from cached batches — the exact
    construction ``_run_plan`` uses, so cached and cold results are
    bit-identical (including the empty-result arrow schema)."""
    if not batches:
        return pa.table(
            {f.name: pa.array([], type=f.data_type.to_arrow()) for f in schema}
        )
    return pa.Table.from_batches(batches)


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self._session = session
        self._plan = plan

    @property
    def schema(self) -> Schema:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return self.schema.names

    # ── transformations ─────────────────────────────────────────────────
    def select(self, *cols) -> "DataFrame":
        exprs, plan = _extract_windows(_to_exprs(cols), self._plan)
        exprs, plan = _extract_generators(exprs, plan)
        return DataFrame(self._session, L.Project(exprs, plan))

    def cache(self) -> "DataFrame":
        """Materialize this DataFrame's result on first use and serve later
        uses from a parquet-compressed in-memory store (the
        ParquetCachedBatchSerializer analogue)."""
        import itertools

        counter = self._session.__dict__.setdefault(
            "_cache_ids", itertools.count(1)
        )
        key = next(counter)

        def parts_of(p) -> int:
            own = getattr(p, "num_partitions", 0)
            kids = [parts_of(c) for c in p.children()]
            return max([own] + kids + [1])

        return DataFrame(
            self._session,
            L.InMemoryRelation(self._plan, key, parts_of(self._plan)),
        )

    persist = cache

    def unpersist(self) -> "DataFrame":
        if isinstance(self._plan, L.InMemoryRelation):
            self._session.uncache(self._plan.cache_key)
            return DataFrame(self._session, self._plan.child)
        return self

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """``fn(iterator of pd.DataFrame) -> iterator of pd.DataFrame`` per
        partition (pyspark mapInPandas; reference GpuMapInPandasExec).
        ``schema`` declares the result columns."""
        schema = _to_schema(schema)
        return DataFrame(self._session, L.MapInPandas(fn, schema, self._plan))

    mapInPandas = map_in_pandas

    def with_column(self, name: str, c: Column) -> "DataFrame":
        exprs: List[Expression] = []
        replaced = False
        for f in self.schema:
            if f.name == name:
                exprs.append(Alias(c.expr, name))
                replaced = True
            else:
                exprs.append(UnresolvedAttribute(f.name))
        if not replaced:
            exprs.append(Alias(c.expr, name))
        exprs, plan = _extract_windows(exprs, self._plan)
        return DataFrame(self._session, L.Project(exprs, plan))

    withColumn = with_column

    def filter(self, condition: Union[Column, Expression]) -> "DataFrame":
        e = condition.expr if isinstance(condition, Column) else condition
        return DataFrame(self._session, L.Filter(e, self._plan))

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        return GroupedData(self, _to_exprs(cols))

    groupBy = group_by

    def rollup(self, *cols) -> "GroupedData":
        """ROLLUP grouping sets: (all), (all-1), …, () — reference analogue:
        GpuExpandExec under the aggregate."""
        exprs = _to_exprs(cols)
        sets = [list(range(k)) for k in range(len(exprs), -1, -1)]
        return GroupedData(self, exprs, grouping_sets=sets)

    def cube(self, *cols) -> "GroupedData":
        """CUBE grouping sets: every subset of the grouping columns."""
        exprs = _to_exprs(cols)
        n = len(exprs)
        sets = [
            [i for i in range(n) if mask & (1 << i)] for mask in range(2**n - 1, -1, -1)
        ]
        return GroupedData(self, exprs, grouping_sets=sets)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def sort(self, *cols, ascending: Union[bool, List[bool]] = True) -> "DataFrame":
        orders = self._sort_orders(cols, ascending)
        return DataFrame(self._session, L.Sort(orders, True, self._plan))

    orderBy = sort
    order_by = sort

    def sort_within_partitions(self, *cols, ascending=True) -> "DataFrame":
        orders = self._sort_orders(cols, ascending)
        return DataFrame(self._session, L.Sort(orders, False, self._plan))

    def _sort_orders(self, cols, ascending) -> List[L.SortOrder]:
        exprs = _to_exprs(cols)
        if isinstance(ascending, bool):
            ascending = [ascending] * len(exprs)
        # Column.desc()/asc() markers override the ascending kwarg
        ascending = [
            False if (isinstance(c, Column) and getattr(c, "_sort_desc", False)) else a
            for c, a in zip(cols, ascending)
        ]
        return [
            L.SortOrder(e, a, getattr(c, "_sort_nulls_first", None))
            for c, e, a in zip(cols, exprs, ascending)
        ]

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self._session, L.Limit(n, self._plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self._session, L.Union([self._plan, other._plan]))

    unionAll = union

    def repartition(self, n: int, *cols) -> "DataFrame":
        exprs = _to_exprs(cols) if cols else None
        return DataFrame(self._session, L.Repartition(n, exprs, self._plan))

    def join(
        self,
        other: "DataFrame",
        on: Union[str, List, None] = None,
        how: str = "inner",
    ) -> "DataFrame":
        how = {
            "inner": "inner",
            "left": "left",
            "left_outer": "left",
            "leftouter": "left",
            "right": "right",
            "right_outer": "right",
            "rightouter": "right",
            "outer": "full",
            "full": "full",
            "full_outer": "full",
            "cross": "cross",
            "semi": "left_semi",
            "left_semi": "left_semi",
            "leftsemi": "left_semi",
            "anti": "left_anti",
            "left_anti": "left_anti",
            "leftanti": "left_anti",
        }[how]
        lk: List[Expression] = []
        rk: List[Expression] = []
        using = False
        residual = None
        if on is None:
            pass
        elif isinstance(on, str):
            lk, rk, using = [UnresolvedAttribute(on)], [UnresolvedAttribute(on)], True
        elif isinstance(on, list) and on and isinstance(on[0], str):
            lk = [UnresolvedAttribute(n) for n in on]
            rk = [UnresolvedAttribute(n) for n in on]
            using = True
        elif isinstance(on, list) and on and isinstance(on[0], tuple):
            lk = [UnresolvedAttribute(l) for l, _ in on]
            rk = [UnresolvedAttribute(r) for _, r in on]
        elif isinstance(on, Column):
            # split a boolean condition into equi keys + residual predicate
            from .exec.cpu_join import extract_equi_join_keys

            lk, rk, residual = extract_equi_join_keys(
                on.expr, self.schema, other.schema
            )
        else:
            raise TypeError(
                "join on= must be a name, list of names, list of (l, r) pairs, "
                "or a Column condition"
            )
        return DataFrame(
            self._session,
            L.Join(self._plan, other._plan, how, lk, rk, residual, using),
        )

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(
            self._session,
            L.Join(self._plan, other._plan, "cross", [], [], None, False),
        )

    def distinct(self) -> "DataFrame":
        """Spark plans Distinct as Aggregate(all columns) — same here, so it
        rides the two-phase device group-by."""
        cols = [UnresolvedAttribute(n) for n in self.schema.names]
        return DataFrame(self._session, L.Aggregate(cols, list(cols), self._plan))

    def drop(self, *cols: str) -> "DataFrame":
        """Project out the named columns (pyspark: unknown names ignored)."""
        gone = set(cols)
        keep = [n for n in self.schema.names if n not in gone]
        return self.select(*keep)

    def with_column_renamed(self, existing: str, new: str) -> "DataFrame":
        """Rename one column; no-op when absent (pyspark semantics)."""
        if existing not in self.schema.names:
            return self
        exprs = [
            Alias(UnresolvedAttribute(n), new) if n == existing else col(n)
            for n in self.schema.names
        ]
        return self.select(*exprs)

    withColumnRenamed = with_column_renamed

    def fillna(self, value, subset: Optional[List[str]] = None) -> "DataFrame":
        """Replace nulls with ``value`` in type-compatible columns
        (pyspark DataFrameNaFunctions.fill: numeric values fill numeric
        columns, strings fill strings, bools fill bools)."""
        from .expr.base import Literal
        from .expr.conditional import Coalesce
        from .types import (
            BooleanType,
            FractionalType,
            IntegralType,
            NumericType,
            StringType,
        )

        if isinstance(value, dict):
            # pyspark's per-column form: {'a': 0, 'b': 'x'}; subset is
            # documented as IGNORED for dict values
            per_col = dict(value)
            subset = None
        elif isinstance(value, (bool, int, float, str)):
            per_col = None
        else:
            raise TypeError(
                f"fillna value must be bool/int/float/str/dict, got {type(value)}"
            )

        def compatible(v, dt) -> bool:
            return (
                (isinstance(v, bool) and isinstance(dt, BooleanType))
                or (
                    isinstance(v, (int, float))
                    and not isinstance(v, bool)
                    and isinstance(dt, NumericType)
                )
                or (isinstance(v, str) and isinstance(dt, StringType))
            )

        names = set(subset) if subset is not None else None
        exprs: List[Expression] = []
        for f in self.schema:
            dt = f.data_type
            if per_col is not None:
                v = per_col.get(f.name)
                applies = v is not None and compatible(v, dt)
            else:
                v = value
                applies = (names is None or f.name in names) and compatible(v, dt)
            if applies:
                if isinstance(dt, FractionalType):
                    v = float(v)
                elif isinstance(dt, IntegralType) and not isinstance(v, bool):
                    v = int(v)
                exprs.append(
                    Alias(
                        Coalesce(
                            (UnresolvedAttribute(f.name), Literal(v, dt))
                        ),
                        f.name,
                    )
                )
            else:
                exprs.append(UnresolvedAttribute(f.name))
        return self.select(*exprs)

    def dropna(
        self,
        how: str = "any",
        thresh: Optional[int] = None,
        subset: Optional[List[str]] = None,
    ) -> "DataFrame":
        """Drop rows with nulls (pyspark DataFrameNaFunctions.drop):
        ``how='any'`` drops rows with any null among the subset,
        ``'all'`` only all-null rows; ``thresh`` keeps rows with at least
        that many non-nulls."""
        from .expr.base import Literal
        from .expr.conditional import If
        from .types import INT

        if how not in ("any", "all"):
            raise ValueError(f"how must be 'any' or 'all', got {how!r}")
        names = subset if subset is not None else list(self.schema.names)
        if not names:
            return self
        non_null_count: Optional[Expression] = None
        for n in names:
            one = If(
                _e(col(n).is_not_null()), Literal(1, INT), Literal(0, INT)
            )
            non_null_count = (
                one
                if non_null_count is None
                else _e(Column(non_null_count) + Column(one))
            )
        if thresh is None:
            thresh = len(names) if how == "any" else 1
        return self.filter(Column(non_null_count) >= thresh)

    def sample(self, *args, **kwargs) -> "DataFrame":
        """Bernoulli sample. Accepts pyspark's signatures:
        ``sample(fraction, seed=0)`` or
        ``sample(withReplacement, fraction, seed)`` (replacement must be
        falsy — with-replacement sampling is not implemented)."""
        from .functions import rand as rand_fn

        a = list(args)
        with_replacement = kwargs.pop("withReplacement", None)
        if a and isinstance(a[0], bool):
            with_replacement = a.pop(0)
        if with_replacement:
            raise NotImplementedError(
                "sample(withReplacement=True) is not supported"
            )
        fraction = kwargs.get("fraction", a[0] if a else None)
        if fraction is None:
            raise TypeError("sample() requires a fraction")
        seed = kwargs.get("seed", a[1] if len(a) > 1 else 0)
        return self.filter(rand_fn(int(seed)) < float(fraction))

    def head(self, n: Optional[int] = None):
        """pyspark: head() → first row or None; head(n) → list of rows
        (including head(1) → one-element list)."""
        if n is None:
            rows = self.limit(1).collect()
            return rows[0] if rows else None
        return self.limit(n).collect()

    def first(self):
        """pyspark: first() == head() — a single row, or None when empty."""
        return self.head()

    def take(self, n: int) -> List[tuple]:
        return self.limit(n).collect()

    def show(self, n: int = 20, truncate: bool = True) -> None:
        """Print the first ``n`` rows in pyspark's grid format."""
        rows = self.limit(n).collect()
        names = list(self.schema.names)
        def fmt(v):
            s = "null" if v is None else str(v)
            return s[:17] + "..." if truncate and len(s) > 20 else s
        table = [[fmt(v) for v in r] for r in rows]
        widths = [
            max(len(names[i]), *(len(r[i]) for r in table)) if table else len(names[i])
            for i in range(len(names))
        ]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        print(sep)
        print("|" + "|".join(f" {names[i]:<{widths[i]}} " for i in range(len(names))) + "|")
        print(sep)
        for r in table:
            print("|" + "|".join(f" {r[i]:<{widths[i]}} " for i in range(len(names))) + "|")
        print(sep)

    def _set_op(self, other: "DataFrame", keep_matched: bool) -> "DataFrame":
        """Null-safe INTERSECT/EXCEPT: tag each side, union, group by all
        columns (GROUP BY treats nulls as equal — exactly Spark's set-op
        null semantics, which a hash join's null-skipping keys would NOT
        give), then filter on side presence."""
        from .functions import lit, max as max_fn

        names = list(self.schema.names)
        left = self.with_column("__side_l", lit(1)).with_column("__side_r", lit(0))
        right = other.with_column("__side_l", lit(0)).with_column("__side_r", lit(1))
        grouped = (
            left.union(right)
            .group_by(*names)
            .agg(
                max_fn(col("__side_l")).alias("__hl"),
                max_fn(col("__side_r")).alias("__hr"),
            )
        )
        cond = (col("__hl") == 1) & (
            (col("__hr") == 1) if keep_matched else (col("__hr") == 0)
        )
        return grouped.filter(cond).select(*names)

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in both frames (Spark INTERSECT,
        null-safe: a (null, 1) row on both sides IS returned)."""
        return self._set_op(other, keep_matched=True)

    def subtract(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of this frame absent from the other (Spark
        EXCEPT, null-safe)."""
        return self._set_op(other, keep_matched=False)

    def drop_duplicates(self, subset: Optional[List[str]] = None) -> "DataFrame":
        if subset is None:
            return self.distinct()
        from .functions import first as first_fn

        keys = [UnresolvedAttribute(n) for n in subset]
        keep = set(subset)
        # output preserves the original column order (pyspark semantics)
        aggs: List[Expression] = []
        for f in self.schema:
            if f.name in keep:
                aggs.append(UnresolvedAttribute(f.name))
            else:
                aggs.append(Alias(first_fn(col(f.name)).expr, f.name))
        return DataFrame(self._session, L.Aggregate(keys, aggs, self._plan))

    dropDuplicates = drop_duplicates

    def create_or_replace_temp_view(self, name: str) -> None:
        self._session.create_or_replace_temp_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def to_jax(self):
        """Zero-copy device export: run the query and hand out the LIVE
        device-resident result as one :class:`DeviceBatch` — a jax pytree
        (per-column ``data``/``validity``/``lengths`` arrays) consumable by
        a jitted function with NO host round trip. The TPU-natural analogue
        of the reference's ML export path (ColumnarRdd.scala,
        InternalColumnarRddConverter.scala:1-579, docs/ml-integration.md),
        where cuDF tables are handed to XGBoost without leaving the GPU.

        The batch is padded to capacity: rows ``[0, num_rows)`` are live
        (``num_rows`` is a device scalar — ``row_count()`` syncs it);
        padding rows have ``validity == False``. Use ``batch.by_name(c)``
        for column access.
        """
        from .exec.tpu import DeviceToHostExec
        from .ops.concat import concat_device
        from .ops.gather import bulk_shrink

        final_plan, ctx = self._session._prepare_plan(self._plan)
        plan = final_plan
        if isinstance(plan, DeviceToHostExec):
            plan = plan.children[0]
        else:
            raise ValueError(
                "to_jax(): plan does not end on the device (fell back to "
                "CPU?) — use to_arrow() instead"
            )
        try:
            # device export rides the same admission control as collect():
            # its result stays resident in HBM, exactly what the permit
            # pool is budgeting
            with self._session._scheduler.admit(
                f"q{ctx.query_seq}", final_plan, self._session.conf
            ) as admission:
                ctx.cancel_token = admission.token
                parts = plan.execute(ctx)
                # same retry model as collect(): partition thunks re-run
                # from lineage on transient failures (spark.task.maxFailures)
                # — with the same per-QUERY retry accounting (a concurrent
                # collect's counter must not be clobbered mid-flight)
                attempts = cfg.TASK_MAX_FAILURES.get(self._session.conf)
                query_retries = [0]

                def on_retry():
                    with self._session._retry_lock:
                        query_retries[0] += 1

                try:
                    batches = [
                        db
                        for i, t in enumerate(parts.parts)
                        for db in self._session._run_task(
                            t, attempts, on_retry, partition_id=i,
                            token=admission.token,
                        )
                    ]
                finally:
                    self._session._task_retries = query_retries[0]
            batches = [b for b in bulk_shrink(batches) if b.capacity]
            if not batches:
                from .columnar.device import empty_batch

                return empty_batch(plan.output)
            if len(batches) == 1:
                return batches[0]
            return concat_device(batches)
        finally:
            self._session._leak_check(ctx)

    # ── actions ─────────────────────────────────────────────────────────
    def to_arrow(self) -> pa.Table:
        return self._session._execute(self._plan)

    def collect(self) -> List[tuple]:
        t = self.to_arrow()
        from . import native

        rows = native.rows_decode(t)  # C row assembly (srt_rows.cc)
        if rows is not None:
            return rows
        cols = [c.to_pylist() for c in t.columns]
        return [tuple(c[i] for c in cols) for i in range(t.num_rows)]

    def count(self) -> int:
        from .functions import count as count_fn

        t = self.agg(count_fn("*").alias("count")).to_arrow()
        return t.column(0)[0].as_py()

    def explain(self, mode: str = "plans") -> str:
        if mode == "metrics":
            # reference-style: per-op metrics inline on the physical plan
            # (the Spark-UI node annotations). Metrics live on the EXECUTED
            # plan instance, so this renders the session's last run —
            # collect() first (matching the UI, which is also post-run).
            from .obs.export import render_ledger, render_plan_metrics

            plan = self._session._last_plan
            if plan is None:
                s = "<no query executed yet — collect() first>"
            else:
                # every collected metric (ESSENTIAL always; MODERATE/DEBUG
                # when the level conf collected them), headed by the host-
                # overhead ledger: where the last query's wall clock went
                s = render_plan_metrics(plan)
                led = render_ledger(
                    getattr(self._session, "_last_ledger", None)
                )
                if led:
                    s = led + "\n" + s
            print(s)
            return s
        cpu_plan = plan_physical(self._plan, self._session.conf)
        overrides = TpuOverrides(self._session.conf)
        final_plan = overrides.apply(cpu_plan)
        s = final_plan.tree_string()
        print(s)
        return s

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    toPandas = to_pandas

    @property
    def write(self):
        from .io.writer import DataFrameWriter

        return DataFrameWriter(self)


def _to_schema(schema) -> Schema:
    """Accept a Schema, a pyspark-style DDL string (``"a long, b double"``),
    or a list of (name, DataType) pairs / StructFields."""
    from .types import StructField

    if isinstance(schema, Schema):
        return schema
    if isinstance(schema, str):
        from .types import parse_ddl_schema

        return parse_ddl_schema(schema)
    fields = []
    for f in schema:
        if isinstance(f, StructField):
            fields.append(f)
        else:
            name, dt = f
            fields.append(StructField(name, dt, True))
    return Schema(fields)


GROUPING_ID = "__grouping_id"


class GroupedData:
    def __init__(
        self,
        df: DataFrame,
        grouping: List[Expression],
        grouping_sets: Optional[List[List[int]]] = None,
        pivot: Optional[tuple] = None,
    ):
        self._df = df
        self._grouping = grouping
        self._grouping_sets = grouping_sets
        self._pivot = pivot

    def pivot(self, pivot_col: str, values: Optional[list] = None) -> "GroupedData":
        """Pivot on ``pivot_col`` — Catalyst's RewritePivot shape: each
        (value, aggregate) pair becomes ``agg(if(p <=> value, x, null))``
        (reference analogue: GpuPivotFirst); ``count`` yields null for
        absent (group, value) combinations like Spark's DataFrame pivot.
        When ``values`` is omitted they are collected eagerly from the data
        (sorted, like Spark's auto-detection)."""
        if self._grouping_sets is not None:
            raise ValueError("pivot is only supported after a groupBy")
        if values is None:
            key = UnresolvedAttribute(pivot_col)
            vals_df = DataFrame(
                self._df._session, L.Aggregate([key], [key], self._df._plan)
            )
            collected = [v for (v,) in vals_df.collect()]
            non_null = sorted(v for v in collected if v is not None)
            values = non_null + ([None] if None in collected else [])
        return GroupedData(self._df, self._grouping, pivot=(pivot_col, values))

    def _expand_pivot(self, agg_exprs: List[Expression]) -> List[Expression]:
        import dataclasses as _dc

        from .expr.aggregates import AggregateFunction
        from .expr.base import Literal, map_child_exprs, to_expr
        from .expr.conditional import If
        from .expr.predicates import EqualNullSafe
        from .types import NULL

        pcol, values = self._pivot

        def wrap(e: Expression, v) -> Expression:
            if isinstance(e, AggregateFunction):
                from .expr.aggregates import Count
                from .expr.predicates import GreaterThan

                cond = EqualNullSafe(UnresolvedAttribute(pcol), to_expr(v))
                guarded = If(cond, e.child, Literal(None, NULL))
                agg = _dc.replace(e, child=guarded)
                if isinstance(e, Count):
                    # Spark's DataFrame pivot (PivotFirst / GpuPivotFirst)
                    # yields NULL, not 0, when no input row matched the
                    # pivot value; gate the count on a matched-row count
                    matched = _dc.replace(
                        e, child=If(cond, to_expr(1), Literal(None, NULL))
                    )
                    return If(
                        GreaterThan(matched, to_expr(0)), agg, Literal(None, NULL)
                    )
                return agg
            if not e.children():
                return e
            return map_child_exprs(e, lambda c: wrap(c, v))

        out: List[Expression] = []
        multiple = len(agg_exprs) > 1
        for v in values:
            for a in agg_exprs:
                base = str(v) if v is not None else "null"
                name = f"{base}_{output_name(a)}" if multiple else base
                target = a.child if isinstance(a, Alias) else a
                out.append(Alias(wrap(target, v), name))
        return out

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """``fn(pd.DataFrame) -> pd.DataFrame`` once per key group (pyspark
        applyInPandas; reference GpuFlatMapGroupsInPandasExec). Grouping
        must be plain columns; ``schema`` declares the result columns."""
        if self._grouping_sets is not None or self._pivot is not None:
            raise ValueError("apply_in_pandas requires a plain groupBy")
        names = []
        for g in self._grouping:
            if not isinstance(g, UnresolvedAttribute):
                raise ValueError(
                    "apply_in_pandas grouping must be plain columns"
                )
            names.append(g.name)
        schema = _to_schema(schema)
        return DataFrame(
            self._df._session,
            L.FlatMapGroupsInPandas(names, fn, schema, self._df._plan),
        )

    applyInPandas = apply_in_pandas

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Pair this grouped frame with another for
        ``cogroup(...).apply_in_pandas(fn, schema)`` (pyspark cogroup;
        reference GpuFlatMapCoGroupsInPandasExec)."""
        if not isinstance(other, GroupedData):
            raise TypeError("cogroup expects another groupBy()")
        return CoGroupedData(self, other)

    def _plain_key_names(self, what: str) -> List[str]:
        if self._grouping_sets is not None or self._pivot is not None:
            raise ValueError(f"{what} requires a plain groupBy")
        names = []
        for g in self._grouping:
            if not isinstance(g, UnresolvedAttribute):
                raise ValueError(f"{what} grouping must be plain columns")
            names.append(g.name)
        return names

    def _agg_in_pandas(self, agg_exprs: List[Expression]) -> DataFrame:
        """GROUPED_AGG pandas UDF route: pre-project key + argument columns,
        then AggregateInPandas evaluates one scalar per (group, udf)."""
        from .expr.udf import GroupedAggUdf
        from .types import StructField

        keys = self._plain_key_names("grouped-agg pandas UDFs")
        proj: List[Expression] = [UnresolvedAttribute(n) for n in keys]
        udfs = []
        out_fields = []
        child_schema = self._df.schema
        for n in keys:
            out_fields.append(StructField(n, child_schema[n].data_type, True))
        for i, a in enumerate(agg_exprs):
            target = a.child if isinstance(a, Alias) else a
            if not isinstance(target, GroupedAggUdf):
                raise ValueError(
                    "grouped-agg pandas UDFs cannot be mixed with other "
                    f"aggregates in one agg() (got {a})"
                )
            arg_names = []
            for j, arg in enumerate(target.args):
                nm = f"__pagg_arg{i}_{j}"
                proj.append(Alias(arg, nm))
                arg_names.append(nm)
            out_name = output_name(a)
            udfs.append((out_name, target.fn, target.return_type, arg_names))
            out_fields.append(StructField(out_name, target.return_type, True))
        projected = L.Project(proj, self._df._plan)
        return DataFrame(
            self._df._session,
            L.AggregateInPandas(keys, udfs, Schema(out_fields), projected),
        )

    def agg(self, *aggs) -> DataFrame:
        agg_exprs = []
        for a in aggs:
            e = a.expr if isinstance(a, Column) else a
            agg_exprs.append(e)
        from .expr.udf import GroupedAggUdf

        def _has_grouped_agg(e) -> bool:
            stack = [e]
            while stack:
                x = stack.pop()
                if isinstance(x, GroupedAggUdf):
                    return True
                stack.extend(x.children())
            return False

        if any(_has_grouped_agg(a) for a in agg_exprs):
            return self._agg_in_pandas(agg_exprs)
        if self._pivot is not None:
            agg_exprs = self._expand_pivot(agg_exprs)
        if self._grouping_sets is not None:
            return self._agg_grouping_sets(agg_exprs)
        # Spark: group-by output = grouping columns ++ aggregates
        all_out = list(self._grouping) + agg_exprs
        return DataFrame(
            self._df._session,
            L.Aggregate(self._grouping, all_out, self._df._plan),
        )

    def _agg_grouping_sets(self, agg_exprs: List[Expression]) -> DataFrame:
        """rollup/cube: Expand fans each row out once per grouping set with
        non-member keys nulled and a grouping-id tiebreaker column, then a
        plain aggregate groups on [keys…, grouping_id] (Spark's
        ResolveGroupingAnalytics → Expand plan; reference GpuExpandExec)."""
        from .expr import Literal
        from .types import INT

        child_schema = self._df.schema
        n_keys = len(self._grouping)
        names = list(child_schema.names)
        key_names = [f"__key{i}" for i in range(n_keys)]
        out_names = names + key_names + [GROUPING_ID]
        projections: List[List[Expression]] = []
        for s in self._grouping_sets:
            proj: List[Expression] = [UnresolvedAttribute(nm) for nm in names]
            for i, g in enumerate(self._grouping):
                if i in s:
                    proj.append(Alias(g, key_names[i]))
                else:
                    from .expr import bind as _bind

                    dt = _bind(g, child_schema).data_type
                    proj.append(Alias(Literal(None, dt), key_names[i]))
            gid = sum((1 << (n_keys - 1 - i)) for i in range(n_keys) if i not in s)
            proj.append(Alias(Literal(gid, INT), GROUPING_ID))
            projections.append(proj)
        expand = L.Expand(projections, out_names, self._df._plan)
        grouping = [UnresolvedAttribute(nm) for nm in key_names] + [
            UnresolvedAttribute(GROUPING_ID)
        ]
        # output: original grouping names, then aggregates (gid internal)
        out_keys = [
            Alias(UnresolvedAttribute(kn), output_name(g))
            for kn, g in zip(key_names, self._grouping)
        ]
        # aggregate inputs read the ORIGINAL columns (passed through Expand
        # unchanged), exactly like Spark's grouping-analytics plan
        return DataFrame(
            self._df._session,
            L.Aggregate(grouping, out_keys + agg_exprs, expand),
        )

    def count(self) -> DataFrame:
        from .functions import count as count_fn

        return self.agg(count_fn("*").alias("count"))

    def sum(self, *names: str) -> DataFrame:
        from .functions import sum as sum_fn

        return self.agg(*[sum_fn(col(n)).alias(f"sum({n})") for n in names])

    def avg(self, *names: str) -> DataFrame:
        from .functions import avg as avg_fn

        return self.agg(*[avg_fn(col(n)).alias(f"avg({n})") for n in names])

    def min(self, *names: str) -> DataFrame:
        from .functions import min as min_fn

        return self.agg(*[min_fn(col(n)).alias(f"min({n})") for n in names])

    def max(self, *names: str) -> DataFrame:
        from .functions import max as max_fn

        return self.agg(*[max_fn(col(n)).alias(f"max({n})") for n in names])


class CoGroupedData:
    """Two co-grouped frames awaiting ``apply_in_pandas`` (pyspark
    ``GroupedData.cogroup``; reference GpuFlatMapCoGroupsInPandasExec)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """``fn(left_pd, right_pd) -> pd.DataFrame`` once per key group
        present on either side; an absent side arrives as an empty frame
        with that side's columns."""
        lk = self._left._plain_key_names("cogroup apply_in_pandas")
        rk = self._right._plain_key_names("cogroup apply_in_pandas")
        if len(lk) != len(rk):
            raise ValueError(
                f"cogroup key counts differ: {lk} vs {rk}"
            )
        schema = _to_schema(schema)
        return DataFrame(
            self._left._df._session,
            L.FlatMapCoGroupsInPandas(
                lk, rk, fn, schema, self._left._df._plan, self._right._df._plan
            ),
        )

    applyInPandas = apply_in_pandas
