"""Physical plan base — the ``SparkPlan``/``GpuExec`` seam.

Reference: GpuExec.scala (the GpuExec trait: supportsColumnar, GpuMetric
system, CoalesceGoal batching contracts :166-277). Here every node is an
``Exec`` producing a ``PartitionSet`` — a list of lazily-computable partition
iterators of batches. CPU execs stream ``pyarrow.RecordBatch``; TPU execs
stream ``DeviceBatch``; explicit transition execs convert (the
GpuRowToColumnarExec / GpuColumnarToRowExec / HostColumnarToGpu analogues are
HostToDeviceExec / DeviceToHostExec — rows never exist as a format here, the
engine is columnar end to end).
"""
from __future__ import annotations

import threading
from typing import Callable, Iterator, List, Optional, Sequence

from ..config import TpuConf
from ..obs.metrics import METRIC_LEVELS, Metric, MetricKind, MetricRegistry
from ..types import Schema

__all__ = [
    "METRIC_LEVELS",
    "Metric",
    "MetricKind",
    "MetricRegistry",
    "Exec",
    "ExecContext",
    "PartitionSet",
]


class ExecContext:
    """Per-query execution context: conf, semaphore, memory, metrics."""

    def __init__(self, conf: TpuConf, session=None):
        self.conf = conf
        self.session = session
        from ..mem.semaphore import DeviceSemaphore
        from ..mem.spill import BufferCatalog
        from .. import config as cfg

        self.semaphore = DeviceSemaphore(cfg.CONCURRENT_TPU_TASKS.get(conf))
        self.catalog = BufferCatalog.from_conf(conf)
        # resilience: the OOM retry/split policy splittable operators use,
        # and the session's CPU-fallback circuit breaker (failures recorded
        # here are consulted by the NEXT planning pass)
        from ..resilience.retry import RetryPolicy

        self.retry_policy = RetryPolicy.from_conf(conf)
        self.breaker = getattr(session, "_breaker", None)
        # Multiproc topology: startup_only keys, so the per-query surfaces
        # (the exchange's rank split, the shuffle manager) read THESE
        # fields, frozen here from the session's init-time tuple — never
        # the conf (conf-key lint, scope rule). A session-less context
        # (unit rigs) freezes its own view once, at construction.
        if session is not None:
            self.mp_driver, self.mp_rank, self.mp_size = (
                session.multiproc_topology()
            )
        else:
            # graft: ok(conf-key: session-less context freezes the value at
            # construction — read once, never re-read per query)
            self.mp_driver = cfg.MULTIPROC_DRIVER.get(conf)
            # graft: ok(conf-key: session-less construction-time freeze)
            self.mp_rank = cfg.MULTIPROC_RANK.get(conf)
            # graft: ok(conf-key: session-less construction-time freeze)
            self.mp_size = cfg.MULTIPROC_SIZE.get(conf)
        # spark.rapids.tpu.metrics.level wins when set; else the reference's
        # spark.rapids.sql.metrics.level key (obs/metrics.py taxonomy)
        level = (
            cfg.METRICS_LEVEL_TPU.get(conf)
            or cfg.METRICS_LEVEL.get(conf)
            or "MODERATE"
        )
        self.metrics_level = METRIC_LEVELS.get(level.upper(), 1)
        limit = cfg.DEVICE_POOL_LIMIT.get(conf)
        if limit > 0:
            self.catalog.device_limit = limit
        else:
            # size the spillable budget from device memory × allocFraction
            # (GpuDeviceManager.initializeRmm's pool sizing); the CPU
            # backend reports no limit: unlimited, spill-on-demand
            from ..mem import device_bytes_limit

            total = device_bytes_limit()
            if total:
                self.catalog.device_limit = int(
                    total * cfg.POOL_SIZE_FRACTION.get(conf)
                )
        import itertools

        import threading

        self._shuffle_manager = None
        self._shuffle_mgr_lock = threading.Lock()
        # Shuffle ids are namespaced by a per-session query sequence: the
        # multi-process driver registry outlives one query, and all ranks
        # must mint IDENTICAL ids for the same exchange (both run the same
        # driver program, so the (query_seq, per-query counter) pair is
        # deterministic across processes).
        seq = session._next_query_seq() if session is not None else 0
        self.query_seq = seq
        self._shuffle_ids = itertools.count(seq * 1_000_000 + 1)
        # multi-tenant scheduler (sched/): the per-query cancellation token,
        # installed by the session at admission; operators check it at batch
        # boundaries. None = unscheduled execution (no checks). Worker
        # threads may install a thread-local override (an attempt-scoped
        # LinkedCancelToken) via ``token_override`` so ONE partition attempt
        # can be cancelled — speculation losing the race — without touching
        # the query token every other partition checks.
        self._cancel_token = None
        self._token_tls = threading.local()
        # depth counter: >0 while building a broadcast batch — exchanges
        # below a broadcast must run WHOLE in every process (no rank split,
        # no shared-registry map statuses). Thread-LOCAL: broadcast builds
        # fire lazily from partition thunks on pool threads, and the nested
        # execute() always runs synchronously on the building thread; a
        # shared counter would let two concurrent builds race the += and a
        # sibling exchange observe depth 0 mid-build (rank-splitting a
        # broadcast build subtree → partial build table).
        self._broadcast_tls = threading.local()
        # AQE: per-exchange measured-size providers, so the two exchanges
        # feeding a co-partitioned join can compute ONE shared coalesce
        # assignment (Spark applies identical CoalescedPartitionSpecs to
        # both shuffle reads of a join).
        self.aqe_size_providers: dict = {}
        # Exchange reuse (plan/reuse.py): shared exchange nodes memoize
        # their PartitionSet here so every consumer reads one materialization
        self.reuse_cache: dict = {}
        # Mesh execution: session-held MeshContext (stable across queries so
        # exchange programs stay compile-cached); None = single-device mode.
        self.mesh = None
        if session is not None and getattr(session, "_mesh_on", False):
            # session-init frozen flag, not the conf: mesh mode committed
            # the partition arity and exchange lowering at construction
            self.mesh = session.mesh_context()

    @property
    def cancel_token(self):
        """The token operators should check: the thread-local attempt
        override when one is installed (speculative/re-executed attempts),
        else the query-level token set at admission. Operators capture this
        lazily inside their partition closures, so the override reaches
        every node of the running partition without plan surgery."""
        tok = getattr(self._token_tls, "token", None)
        return tok if tok is not None else self._cancel_token

    @cancel_token.setter
    def cancel_token(self, token) -> None:
        self._cancel_token = token

    def token_override(self, token):
        """Context manager installing ``token`` as this worker thread's
        cancel token for the duration of one partition attempt."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            prev = getattr(self._token_tls, "token", None)
            self._token_tls.token = token
            try:
                yield token
            finally:
                self._token_tls.token = prev

        return _scope()

    @property
    def broadcast_depth(self) -> int:
        return getattr(self._broadcast_tls, "depth", 0)

    @broadcast_depth.setter
    def broadcast_depth(self, value: int) -> None:
        self._broadcast_tls.depth = value

    @property
    def shuffle_manager(self):
        """Lazily built accelerated shuffle manager (GpuShuffleEnv.init
        analogue) — one in-process 'executor' per session context.
        Lock-guarded: partition tasks run on a thread pool and sibling
        exchanges may first-touch this concurrently."""
        with self._shuffle_mgr_lock:
            return self._shuffle_manager_locked()

    def _shuffle_manager_locked(self):
        if self._shuffle_manager is None:
            from .. import config as cfg
            from ..shuffle.heartbeat import ShuffleHeartbeatManager
            from ..shuffle.local import InProcessRegistry, InProcessTransport
            from ..shuffle.manager import MapOutputRegistry, ShuffleEnv, TpuShuffleManager

            driver = self.mp_driver  # frozen topology, never the live conf
            if driver:
                # one executor of a multi-process query: TCP data plane +
                # driver-service control plane (shuffle/driver_service.py).
                # The manager lives on the SESSION, not the query context —
                # a real executor keeps ONE shuffle server for its lifetime;
                # per-query servers would re-register the executor id with a
                # new port peers never re-learn, and map output must stay
                # servable across queries (the release path is query-local).
                cached = getattr(self.session, "_mp_shuffle_manager", None)
                if cached is not None:
                    self._shuffle_manager = cached
                    return self._shuffle_manager
                from ..shuffle import driver_service as ds
                from ..shuffle.tcp import TcpTransport

                host, _, port = driver.rpartition(":")
                heartbeats, registry = ds.connect((host, int(port)))
                rank = self.mp_rank
                executor_id = f"executor-{rank}"
                transport = TcpTransport(
                    executor_id,
                    handshake_timeout_s=cfg.SHUFFLE_HANDSHAKE_TIMEOUT_S.get(
                        self.conf
                    ),
                )
                from ..mem.spill import BufferCatalog

                # executor-lifetime store, NOT a query's catalog: shuffle
                # output outlives the query that wrote it (peers fetch on
                # their own clock), and pinning the first query's catalog
                # would account later queries' shuffle bytes against a
                # dead context (Spark's shuffle files are executor-scoped
                # the same way)
                shuffle_store = BufferCatalog.from_conf(self.conf)
                env = ShuffleEnv(
                    executor_id,
                    transport,
                    shuffle_store,
                    heartbeats,
                    codec=cfg.SHUFFLE_COMPRESSION_CODEC.get(self.conf),
                    max_inflight_bytes=cfg.SHUFFLE_MAX_RECEIVE_INFLIGHT.get(self.conf),
                    fetch_timeout_s=cfg.SHUFFLE_FETCH_TIMEOUT_S.get(self.conf),
                    bounce_buffer_size=cfg.SHUFFLE_BOUNCE_BUFFER_SIZE.get(self.conf),
                    bounce_buffer_count=cfg.SHUFFLE_BOUNCE_BUFFER_COUNT.get(self.conf),
                    address=tuple(transport.address),
                    fetch_max_retries=cfg.RETRY_FETCH_MAX_RETRIES.get(self.conf),
                    fetch_backoff_ms=cfg.RETRY_FETCH_BACKOFF_MS.get(self.conf),
                    fetch_max_backoff_ms=cfg.RETRY_FETCH_MAX_BACKOFF_MS.get(
                        self.conf
                    ),
                    blacklist_after=cfg.RETRY_FETCH_BLACKLIST_AFTER.get(self.conf),
                    heartbeat_max_age_s=cfg.HEARTBEAT_MAX_AGE_S.get(self.conf),
                )
                self._shuffle_manager = TpuShuffleManager(env, registry)
                if self.session is not None:
                    self.session._mp_shuffle_manager = self._shuffle_manager
                return self._shuffle_manager
            reg = InProcessRegistry()
            env = ShuffleEnv(
                "driver-executor",
                InProcessTransport("driver-executor", reg),
                self.catalog,
                ShuffleHeartbeatManager(),
                codec=cfg.SHUFFLE_COMPRESSION_CODEC.get(self.conf),
                max_inflight_bytes=cfg.SHUFFLE_MAX_RECEIVE_INFLIGHT.get(self.conf),
                fetch_timeout_s=cfg.SHUFFLE_FETCH_TIMEOUT_S.get(self.conf),
                bounce_buffer_size=cfg.SHUFFLE_BOUNCE_BUFFER_SIZE.get(self.conf),
                bounce_buffer_count=cfg.SHUFFLE_BOUNCE_BUFFER_COUNT.get(self.conf),
                fetch_max_retries=cfg.RETRY_FETCH_MAX_RETRIES.get(self.conf),
                fetch_backoff_ms=cfg.RETRY_FETCH_BACKOFF_MS.get(self.conf),
                fetch_max_backoff_ms=cfg.RETRY_FETCH_MAX_BACKOFF_MS.get(self.conf),
                blacklist_after=cfg.RETRY_FETCH_BLACKLIST_AFTER.get(self.conf),
                heartbeat_max_age_s=cfg.HEARTBEAT_MAX_AGE_S.get(self.conf),
            )
            self._shuffle_manager = TpuShuffleManager(env, MapOutputRegistry())
        return self._shuffle_manager

    def next_shuffle_id(self) -> int:
        return next(self._shuffle_ids)


def _scoped_part(index: int, thunk):
    """Wrap a partition thunk so a TaskInfo (TaskContext analogue) is the
    active thread-local whenever this partition's frames execute. Nested
    PartitionSets re-assert their own TaskInfo before each pull, so each
    operator's loop body sees the TaskInfo of the stage directly beneath it
    (stable across batches — what row counters need)."""

    def run():
        from ..exec import task as _task

        # attempt id comes from the worker thread's retry/speculation scope
        # (session._run_task): every plan-node layer of a re-executed
        # partition observes the same attempt number
        info = _task.TaskInfo(index, attempt=_task.current_attempt())

        def gen():
            _task.set_current(info)
            _task.reset_input_file()
            it = thunk()
            while True:
                try:
                    x = next(it)
                except StopIteration:
                    return
                # Re-assert AFTER the pull: deeper stages set their own info
                # while producing x; the consumer's loop body must run under
                # THIS stage's info (the stage directly beneath the consumer),
                # not the deepest one — otherwise stacked task-dependent
                # operators would share and double-advance one row counter.
                _task.set_current(info)
                yield x

        return gen()

    return run


class PartitionSet:
    """Lazily computable partitions (the RDD[ColumnarBatch] analogue).

    Each partition thunk is wrapped with a task scope carrying the partition
    index (Spark's TaskContext.partitionId analogue) — see exec/task.py.
    """

    def __init__(self, parts: List[Callable[[], Iterator]]):
        self.parts = [_scoped_part(i, t) for i, t in enumerate(parts)]

    @property
    def num_partitions(self) -> int:
        return len(self.parts)

    def map_partitions(self, fn) -> "PartitionSet":
        def wrap(thunk):
            return lambda: fn(thunk())

        return PartitionSet([wrap(t) for t in self.parts])

    def materialize(self) -> List[list]:
        return [list(t()) for t in self.parts]


class Exec:
    """Physical operator base."""

    def __init__(self, children: Sequence["Exec"]):
        self._children = list(children)
        self.metrics: MetricRegistry = MetricRegistry()

    # ── tree ────────────────────────────────────────────────────────────
    @property
    def children(self) -> List["Exec"]:
        return self._children

    def with_new_children(self, children: List["Exec"]) -> "Exec":
        import copy

        new = copy.copy(self)
        new._children = list(children)
        new.metrics = MetricRegistry()
        return new

    # ── contract ────────────────────────────────────────────────────────
    @property
    def output(self) -> Schema:
        raise NotImplementedError

    @property
    def is_device(self) -> bool:
        """True if this exec produces DeviceBatch (the supportsColumnar bit)."""
        return False

    def execute(self, ctx: ExecContext) -> PartitionSet:
        raise NotImplementedError

    # ── metrics ─────────────────────────────────────────────────────────
    def metric(
        self, name: str, level: str = "ESSENTIAL", kind: Optional[str] = None
    ) -> Metric:
        """Get-or-create this node's metric (locked — partition tasks and
        pipeline producers may race first touch). ``kind`` (MetricKind)
        drives exporter rendering; inferred from the name when omitted."""
        return self.metrics.get_or_create(name, level, kind)

    def metrics_on(self, ctx: "ExecContext", level: str) -> bool:
        """Is a metric of ``level`` collected under this query's
        ``spark.rapids.sql.metrics.level``?"""
        return METRIC_LEVELS[level] <= ctx.metrics_level

    def collect_metrics(self) -> dict:
        """node → {metric: value} for the whole subtree (Spark-UI stand-in)."""
        out = {}
        if self.metrics:
            out[self.node_string()] = {
                m.name: m.value for m in self.metrics.values()
            }
        for c in self.children:
            for k, v in c.collect_metrics().items():
                out.setdefault(k, {}).update(v)
        return out

    # ── pretty print ────────────────────────────────────────────────────
    def node_string(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        lines = [" " * indent + ("* " if self.is_device else "  ") + self.node_string()]
        for c in self.children:
            lines.append(c.tree_string(indent + 2))
        return "\n".join(lines)

    def __str__(self):
        return self.tree_string()
