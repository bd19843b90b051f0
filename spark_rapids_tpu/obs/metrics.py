"""Typed metric registry — the ``GpuMetric`` analogue, generalized.

Reference: GpuExec.scala:40-157 — one metric class with ESSENTIAL /
MODERATE / DEBUG levels gated by ``spark.rapids.sql.metrics.level``, plus
the Spark ``SQLMetrics`` accumulator taxonomy (sum / timing / size /
average). Here a :class:`Metric` is one thread-safe value with a *kind*
that tells exporters how to render it:

- ``COUNTER``   — monotonic sum (rows, bytes, retries, cache hits);
- ``NANOS``     — accumulated ``perf_counter_ns`` durations (rendered ms);
- ``GAUGE``     — last-set value (dispatch window, pool size);
- ``WATERMARK`` — high-watermark via ``set_max`` (peak HBM bytes, max
  in-flight depth — the reference's ``peakDevMemory``).

A :class:`MetricRegistry` is a dict of metrics with a *locked*
get-or-create (``Exec.metric``'s old check-then-insert raced under the
pipeline's producer threads). Two scopes exist:

- per-operator-instance: ``Exec.metrics`` (plan/physical.py) — rebuilt per
  query with the plan;
- process-wide: :data:`GLOBAL` — kernel compile/warm counts, spill bytes by
  tier, shuffle bytes, semaphore waits, resilience counters. Module-level
  code (kernels.py, mem/, shuffle/, resilience/) publishes here; sessions
  read it through :mod:`spark_rapids_tpu.obs.export` views.

This module is dependency-free (stdlib threading only) so every layer of
the engine can import it without cycles.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, Optional

METRIC_LEVELS = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}


class MetricKind:
    COUNTER = "counter"
    NANOS = "nanos"
    GAUGE = "gauge"
    WATERMARK = "watermark"
    HISTOGRAM = "histogram"


_SLUG_RE = __import__("re").compile(r"[^a-z0-9]+")


def metric_slug(name: str, fallback: str = "unspecified") -> str:
    """Free-form text → a bounded metric-name segment, the ONE rule for
    dynamically-named series (``scheduler.cancelled.reason.<slug>``,
    ``serve.tenant.<slug>.queries``) so their naming never diverges."""
    s = _SLUG_RE.sub("_", (name or fallback).lower()).strip("_")
    return (s or fallback)[:48]


# ── dynamic-series cardinality guard ────────────────────────────────────────
# metric_slug bounds each segment's LENGTH but not how many DISTINCT slugs a
# prefix accumulates: cancel reasons carry free-ish text and tenant names
# arrive from the wire, so an adversarial (or merely buggy) caller could mint
# unbounded Prometheus series. Every dynamically-named series therefore goes
# through dynamic_name(), which admits at most the configured number of
# distinct slugs per prefix (spark.rapids.tpu.metrics.maxDynamicSlugs) and
# folds the overflow into one shared 'other' bucket, counted in
# metrics.slugOverflow so the truncation is itself observable.

_SLUG_CAP = [64]
_SLUG_SEEN: Dict[str, set] = {}
_SLUG_LOCK = threading.Lock()

#: prefixes known to mint series dynamically — the metrics-lint allowlist
#: (a GLOBAL.counter(f"...") call whose literal prefix is listed here is a
#: catalogued dynamic family, not catalog drift)
DYNAMIC_PREFIXES = (
    "scheduler.cancelled.reason.",
    "scheduler.shed.reason.",
    "scheduler.pool.",
    "serve.tenant.",
    "watchdog.stalls.site.",
)


def set_slug_cap(n: int) -> None:
    """Install the per-prefix distinct-slug budget (session init reads
    spark.rapids.tpu.metrics.maxDynamicSlugs)."""
    _SLUG_CAP[0] = max(1, int(n))


def dynamic_name(prefix: str, raw: str, suffix: str = "",
                 fallback: str = "unspecified") -> str:
    """``prefix + metric_slug(raw) + suffix`` with the per-prefix
    cardinality cap applied: the cap+1-th distinct slug (and every one
    after it) becomes ``other``, and metrics.slugOverflow counts each
    folded observation."""
    s = metric_slug(raw, fallback)
    with _SLUG_LOCK:
        seen = _SLUG_SEEN.setdefault(prefix, set())
        if s not in seen:
            if len(seen) >= _SLUG_CAP[0]:
                GLOBAL.counter("metrics.slugOverflow").add(1)
                s = "other"
            else:
                seen.add(s)
    return f"{prefix}{s}{suffix}"


def infer_kind(name: str) -> str:
    """Kind from naming convention when a call site doesn't say: ``*Time`` /
    ``*Ns`` are timers, ``peak*`` / ``*HighWatermark`` are watermarks."""
    if name.endswith("Time") or name.endswith("Ns") or name.endswith("TimeNs"):
        return MetricKind.NANOS
    low = name.lower()
    if low.startswith("peak") or low.endswith("highwatermark"):
        return MetricKind.WATERMARK
    return MetricKind.COUNTER


class Metric:
    """One thread-safe metric value (the GpuMetric analogue)."""

    __slots__ = ("name", "value", "level", "kind", "_lock")

    def __init__(
        self,
        name: str,
        level: str = "ESSENTIAL",
        kind: Optional[str] = None,
    ):
        self.name = name
        self.value = 0  # graft: guarded_by(_lock)
        self.level = level
        self.kind = kind or infer_kind(name)
        self._lock = threading.Lock()

    def add(self, v: int):
        with self._lock:
            self.value += v

    def set(self, v: int):
        """Gauge semantics: last write wins."""
        with self._lock:
            self.value = v

    def set_max(self, v: int):
        """High-water-mark semantics (e.g. pipeline dispatch depth)."""
        with self._lock:
            if v > self.value:
                self.value = v

    class _Timer:
        __slots__ = ("m", "t0")

        def __init__(self, m):
            self.m = m

        def __enter__(self):
            self.t0 = time.perf_counter_ns()
            return self

        def __exit__(self, *a):
            self.m.add(time.perf_counter_ns() - self.t0)

    def timed(self) -> "_Timer":
        return Metric._Timer(self)

    def __repr__(self):
        # graft: ok(guarded-by: debug repr — a torn read of a CPython int
        # is impossible and a stale one is fine here)
        return f"Metric({self.name}={self.value}, {self.kind}/{self.level})"


class Histogram(Metric):
    """Fixed log₂-bucket histogram — real latency distributions for every
    series that used to keep bounded raw-sample lists (serve wait/run,
    scheduler queue wait, kernel compile, shuffle fetch).

    Bucket ``i`` holds observations ``v`` with ``2^(i-1) < v <= 2^i``
    (``v <= 0`` lands in bucket 0), so 64 buckets cover the whole int64
    range with no per-series configuration and ~7% worst-case relative
    quantile error — the GWP-style always-on tradeoff: cheap enough to
    leave running, accurate enough to rank.

    ``value`` is the observation COUNT (so generic exporters render
    something sane); ``add``/``timed()`` observe, so a Histogram drops in
    anywhere a NANOS timer was fed durations. ``state()`` snapshots
    ``(counts, sum, count)`` for delta-based percentile math (a run's
    phases)."""

    N_BUCKETS = 64

    __slots__ = ("counts", "sum")

    def __init__(self, name: str, level: str = "ESSENTIAL"):
        super().__init__(name, level, MetricKind.HISTOGRAM)
        self.counts = [0] * self.N_BUCKETS
        self.sum = 0

    def observe(self, v) -> None:
        v = int(v)
        i = v.bit_length() if v > 0 else 0
        if i >= self.N_BUCKETS:
            i = self.N_BUCKETS - 1
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.value += 1

    # timers feed durations through add() — same call shape as Metric
    def add(self, v) -> None:
        self.observe(v)

    def state(self) -> tuple:
        """Point-in-time ``(counts tuple, sum, count)`` — consistent under
        the metric lock, subtractable for windowed percentiles."""
        with self._lock:
            return (tuple(self.counts), self.sum, self.value)

    def quantile(self, q: float, state: Optional[tuple] = None) -> float:
        """Estimated q-quantile (0 <= q <= 1) by linear interpolation
        inside the selected bucket; 0.0 when empty."""
        counts, _s, total = state if state is not None else self.state()
        return quantile_from_counts(counts, total, q)


def histogram_delta(after: tuple, before: tuple) -> tuple:
    """``after - before`` of two Histogram.state() snapshots — the windowed
    view a measured phase uses (percentiles of only this run's observations)."""
    ca, sa, na = after
    cb, sb, nb = before
    return (
        tuple(a - b for a, b in zip(ca, cb)),
        sa - sb,
        na - nb,
    )


def quantile_from_counts(counts, total: int, q: float) -> float:
    """Interpolated quantile over log₂ bucket counts (bucket i spans
    (2^(i-1), 2^i]); 0.0 for an empty distribution."""
    if total <= 0:
        return 0.0
    rank = max(0.0, min(1.0, q)) * total
    seen = 0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if seen + c >= rank:
            lo = 0.0 if i == 0 else float(1 << (i - 1))
            hi = 1.0 if i == 0 else float(1 << i)
            frac = (rank - seen) / c
            return lo + (hi - lo) * frac
        seen += c
    return float(1 << (len(counts) - 1))


class _NullMetric:
    """Shared no-op sink for metrics gated off by the level conf: call
    sites keep one unconditional code path with zero per-batch allocation
    or bookkeeping (the <2% instrumentation-cost contract)."""

    __slots__ = ()
    name = "__null__"
    value = 0
    level = "DEBUG"
    kind = MetricKind.COUNTER

    def add(self, v: int):
        pass

    def set(self, v: int):
        pass

    def set_max(self, v: int):
        pass

    class _NoopTimer:
        __slots__ = ()

        def __enter__(self):
            return self

        def __exit__(self, *a):
            pass

    _TIMER = _NoopTimer()

    def timed(self):
        return _NullMetric._TIMER


NULL_METRIC = _NullMetric()


class MetricRegistry(dict):
    """name → :class:`Metric` with a locked get-or-create.

    Subclasses ``dict`` so existing consumers (``node.metrics.values()``,
    ``.get(name)``, iteration) keep working unchanged.
    """

    def __init__(self, scope: str = ""):
        super().__init__()
        self.scope = scope
        self._lock = threading.Lock()

    def get_or_create(
        self, name: str, level: str = "ESSENTIAL", kind: Optional[str] = None
    ) -> Metric:
        m = self.get(name)
        if m is None:
            with self._lock:
                m = self.get(name)
                if m is None:
                    if kind == MetricKind.HISTOGRAM:
                        m = Histogram(name, level)
                    else:
                        m = Metric(name, level, kind)
                    self[name] = m
        return m

    # kind shorthands (the typed-registry surface)
    def counter(self, name: str, level: str = "ESSENTIAL") -> Metric:
        return self.get_or_create(name, level, MetricKind.COUNTER)

    def timer(self, name: str, level: str = "ESSENTIAL") -> Metric:
        return self.get_or_create(name, level, MetricKind.NANOS)

    def gauge(self, name: str, level: str = "ESSENTIAL") -> Metric:
        return self.get_or_create(name, level, MetricKind.GAUGE)

    def watermark(self, name: str, level: str = "ESSENTIAL") -> Metric:
        return self.get_or_create(name, level, MetricKind.WATERMARK)

    def histogram(self, name: str, level: str = "ESSENTIAL") -> "Histogram":
        return self.get_or_create(name, level, MetricKind.HISTOGRAM)

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time name → value (stable iteration copy)."""
        with self._lock:
            return {name: m.value for name, m in self.items()}

    def view(self, prefix: str, strip: bool = True) -> Dict[str, int]:
        """Snapshot of the metrics under ``prefix`` (``resilience.``,
        ``spill.`` …), optionally with the prefix stripped — the registry
        view the old bespoke report functions became."""
        with self._lock:
            return {
                (name[len(prefix):] if strip else name): m.value
                for name, m in self.items()
                if name.startswith(prefix)
            }

    def reset(self, prefix: str = "") -> None:
        """Zero the metrics under ``prefix`` ('' = all). Values are zeroed
        in place — published references stay live. Each metric's own lock
        is taken so a racing ``add`` cannot resurrect the pre-reset total
        (the unlocked write could land inside add's read-modify-write)."""
        with self._lock:
            for name, m in self.items():
                if name.startswith(prefix):
                    with m._lock:
                        m.value = 0
                        if isinstance(m, Histogram):
                            m.counts = [0] * Histogram.N_BUCKETS
                            m.sum = 0


#: Process-wide registry (kernel compiles, spill tiers, shuffle bytes,
#: semaphore waits, resilience counters). Sessions read it via export views.
GLOBAL = MetricRegistry(scope="process")


# ── well-known process metrics (the metric catalog) ─────────────────────────
# Registered eagerly so exporters always emit the full series set (a
# Prometheus scrape sees `spark_rapids_tpu_spill_bytes_device_to_host 0`
# on a healthy run instead of a missing series), and so docs/observability.md
# can list the catalog. Per-operator metrics (numInputRows, opTime, pipe*)
# live on Exec instances and are documented there.

CATALOG: Iterable[tuple] = (
    # kernels.py — compile vs execute attribution, cache behavior
    ("kernel.builds", MetricKind.COUNTER, "distinct kernels built (cache misses)"),
    ("kernel.cacheHits", MetricKind.COUNTER, "kernel-cache hits (kernels.kernel)"),
    ("kernel.warms", MetricKind.COUNTER, "pre-compilations performed (GuardedJit.warm)"),
    ("kernel.warmTimeNs", MetricKind.NANOS, "time spent in pre-compilation lower+compile"),
    ("kernel.firstCalls", MetricKind.COUNTER, "first executions per signature (trace+compile)"),
    ("kernel.compileTimeNs", MetricKind.NANOS, "time spent in first-call trace+compile"),
    ("kernel.compileDeadlines", MetricKind.COUNTER,
     "first-touch compiles abandoned at spark.rapids.tpu.compile."
     "deadlineSeconds (the op force-opens its circuit breaker)"),
    # exec/tpu_window.py — counted per kernel launch from values the host
    # already holds (no device sync)
    ("window.calls", MetricKind.COUNTER,
     "window kernel launches (one per merged partition batch)"),
    ("window.rowsCapacity", MetricKind.COUNTER,
     "summed row capacity of the merged batches the window kernel was given"),
    # session.py / plan/subquery.py — how each IN (subquery) predicate ran
    ("subquery.semiJoins", MetricKind.COUNTER,
     "IN (subquery) predicates planned as left-semi joins "
     "(plan/subquery.py), per prepared plan"),
    ("subquery.hostValues", MetricKind.COUNTER,
     "values of IN-subquery results brought to the host and inlined as an "
     "InSet literal (TpuSession._resolve_subqueries: NOT IN, IN under "
     "OR/NOT/CASE or in a SELECT list)"),
    ("exchange.reused", MetricKind.COUNTER,
     "exchanges replaced by a reference to an identical one of the same plan "
     "(plan/reuse.py under spark.sql.exchange.reuse), per prepared plan"),
    # exec/tpu_join.py — counted where the host already holds the numbers:
    # the match totals it pulled to size each output batch
    ("join.calls", MetricKind.COUNTER,
     "pair-gather launches of the equi-join (one per probe batch)"),
    ("join.rowsOut", MetricKind.COUNTER,
     "key-matched pairs the equi-joins sized their output batches for, "
     "before any residual condition; a semi or anti join counts the pairs "
     "it examined"),
    # kernels.py counted_kernel — per launch of an aggregate, sort, window or
    # join phase-1 kernel, static per kernel and input signature (no device
    # sync)
    ("sort.keyPasses", MetricKind.COUNTER,
     "sort passes run over packed key words (ops/sortkeys.py packed_sort: "
     "one stable single-key uint32 pass a word)"),
    ("sort.keyPassesUnpacked", MetricKind.COUNTER,
     "passes the same sorts would have run at two a uint64 radix word (for "
     "a join's phase 1, its three former sorts); over sort.keyPasses it is "
     "how far the packing engages"),
    # the same hook, and the join-pair, exchange-slice and shrink kernels too
    ("gather.planes", MetricKind.COUNTER,
     "planes handed to ops/gather.py gather_planes (data, validity, lengths "
     "and child planes that share one index)"),
    ("gather.launches", MetricKind.COUNTER,
     "gathers gather_planes issued for them, one a stack; gather.planes "
     "over it is how far the stacking engages"),
    ("decimal.widePlanes", MetricKind.COUNTER,
     "32-bit planes of decimals above 18 digits (four a 128-bit value: its "
     "two words' halves) among what the same kernels hand to gather_planes"),
    # cache/xla_store.py — the persistent XLA executable store
    ("cache.xla.hit", MetricKind.COUNTER,
     "compiled executables deserialized from the on-disk store instead "
     "of compiled (the warm-restart fast path)"),
    ("cache.xla.miss", MetricKind.COUNTER,
     "store consults that found no usable entry (absent, version-fenced, "
     "corrupt, or undeserializable) — a fresh compile follows"),
    ("cache.xla.stores", MetricKind.COUNTER,
     "executables published to the store (atomic temp+fsync+rename)"),
    ("cache.xla.storeNs", MetricKind.NANOS,
     "time serializing + publishing executables to the store"),
    ("cache.xla.loadNs", MetricKind.NANOS,
     "time deserializing executables from the store"),
    ("cache.xla.evicted", MetricKind.COUNTER,
     "entries removed by LRU eviction at compileCache.maxBytes"),
    ("cache.xla.corrupt", MetricKind.COUNTER,
     "entries quarantined for structural damage or CRC mismatch "
     "(moved to <dir>/quarantine for triage; the kernel rebuilds fresh)"),
    ("cache.xla.deserializeFailures", MetricKind.COUNTER,
     "CRC-valid entries that failed to deserialize or blew up on their "
     "proving run (quarantined; repeated failures trip the load breaker "
     "and disable the store for the process)"),
    ("cache.xla.lockTimeouts", MetricKind.COUNTER,
     "single-flight compile locks held past compileCache.lockTimeout "
     "(the caller compiled without the cross-process dedup)"),
    # mem/spill.py — spill bytes by tier transition + HBM watermark
    ("spill.bytesDeviceToHost", MetricKind.COUNTER, "bytes spilled HBM → host RAM"),
    ("spill.bytesHostToDisk", MetricKind.COUNTER, "bytes spilled host RAM → disk"),
    ("spill.bytesDiskToHost", MetricKind.COUNTER, "bytes re-materialized disk → host RAM"),
    ("spill.count", MetricKind.COUNTER, "tier-transition spill operations"),
    # columnar/device.py — shape-bucket padding overhead (the lattice's
    # cost side; the ledger's `pad` phase is the per-query view)
    ("batch.padTimeNs", MetricKind.NANOS,
     "host time padding batches out to the pow-2 shape-bucket lattice "
     "capacity before H2D upload (spark.rapids.tpu.shapeBuckets.*)"),
    ("batch.padStringPlanes", MetricKind.COUNTER,
     "string planes padded for H2D upload, one per string column (or "
     "list-of-string element plane) of each uploaded batch"),
    ("batch.padStringPlanesFixedLen", MetricKind.COUNTER,
     "of batch.padStringPlanes, those whose values all had one length and "
     "no nulls (char(n) columns): filled by one strided copy of the value "
     "buffer; the rest are right-padded by pyarrow a chunk of rows at a time"),
    ("mem.deviceBytesHighWatermark", MetricKind.WATERMARK,
     "peak registered spillable bytes on device, sampled at batch boundaries"),
    # mem/semaphore.py — admission control
    ("semaphore.acquires", MetricKind.COUNTER, "device-semaphore acquisitions"),
    ("semaphore.waitNs", MetricKind.NANOS, "time blocked acquiring the device semaphore"),
    # shuffle/* — data-plane volume + codec efficiency
    ("shuffle.bytesWritten", MetricKind.COUNTER, "map-output bytes parked in the shuffle catalog"),
    ("shuffle.bytesFetched", MetricKind.COUNTER, "payload bytes received from peer executors"),
    ("shuffle.bytesCompressedOut", MetricKind.COUNTER, "serialized shuffle payload bytes after compression"),
    ("shuffle.bytesUncompressed", MetricKind.COUNTER, "serialized shuffle payload bytes before compression"),
    ("shuffle.corruptFrames", MetricKind.COUNTER,
     "TCP DATA frames dropped on checksum mismatch (recovered by the "
     "fetch retry's missing-block re-request)"),
    ("shuffle.evictedStale", MetricKind.COUNTER,
     "executors evicted by age-based registry sweeps (heartbeat "
     "evict_stale — including the watchdog's periodic sweep)"),
    ("shuffle.recomputedPartitions", MetricKind.COUNTER,
     "map outputs rebuilt from lineage after a lost/blacklisted peer or "
     "an empty registry (spark.rapids.tpu.recovery.recomputeMapOutputs)"),
    # sched/* — multi-tenant admission control (per-pool admitted counters
    # under scheduler.pool.<name>.admitted and per-cause cancellations
    # under scheduler.cancelled.reason.<slug> register dynamically on
    # first use)
    ("scheduler.admitted", MetricKind.COUNTER, "queries granted device permits"),
    ("scheduler.rejected", MetricKind.COUNTER, "admissions rejected (QueryQueueFull)"),
    ("scheduler.cancelled", MetricKind.COUNTER,
     "queries cancelled (queued or running) — the aggregate over every "
     "scheduler.cancelled.reason.* series, deadline expiries INCLUDED "
     "(a timeout is a cancellation with reason 'deadline')"),
    ("scheduler.timeouts", MetricKind.COUNTER,
     "queries past their deadline (QueryTimeoutError); each is also "
     "counted in scheduler.cancelled under reason.deadline"),
    ("scheduler.queueWaitNs", MetricKind.NANOS, "time queries spent waiting for admission"),
    ("scheduler.queueDepth", MetricKind.GAUGE, "queries currently waiting for admission"),
    ("scheduler.permitsInUse", MetricKind.GAUGE, "admission permits currently held"),
    ("scheduler.effectivePermits", MetricKind.GAUGE,
     "live permit limit (configured permits, halved under OOM pressure)"),
    ("scheduler.shed", MetricKind.COUNTER,
     "admissions shed by deadline-aware load shedding (per-cause series "
     "under scheduler.shed.reason.*; each also counts in rejected)"),
    # resilience/watchdog.py — hung-query detection (per-site series under
    # watchdog.stalls.site.* register dynamically on first use)
    ("watchdog.stalls", MetricKind.COUNTER,
     "queries cancelled by the progress watchdog (no beat for "
     "stallTimeout); classified per stall site (compile/launch/fetch/"
     "client) under watchdog.stalls.site.*"),
    # serve/* — the network front-end (per-tenant query counters under
    # serve.tenant.<name>.queries register dynamically on first use)
    ("serve.connections", MetricKind.COUNTER, "client connections accepted (HELLO ok)"),
    ("serve.connectionsRejected", MetricKind.COUNTER,
     "connections refused (bad token / connection limit)"),
    ("serve.connectionsActive", MetricKind.GAUGE, "currently open client connections"),
    ("serve.queries", MetricKind.COUNTER, "queries executed over the wire"),
    ("serve.queryErrors", MetricKind.COUNTER, "served queries that ended in an ERROR frame"),
    ("serve.preparedStatements", MetricKind.COUNTER, "PREPARE commands handled"),
    ("serve.preparedHits", MetricKind.COUNTER,
     "prepared-plan cache hits (parse/plan/compile skipped)"),
    ("serve.preparedMisses", MetricKind.COUNTER,
     "prepared-plan cache misses (full parse+plan performed)"),
    ("serve.streamedBatches", MetricKind.COUNTER, "result BATCH frames sent to clients"),
    ("serve.streamedBytes", MetricKind.COUNTER, "result payload bytes sent to clients"),
    ("serve.cancels", MetricKind.COUNTER,
     "server-side cancellations (CANCEL frames + client disconnects)"),
    ("serve.queryWaitNs", MetricKind.NANOS, "served queries' admission queue wait"),
    ("serve.queryRunNs", MetricKind.NANOS, "served queries' execution+stream time"),
    ("serve.overloaded", MetricKind.COUNTER,
     "typed OVERLOADED rejections answered over the wire (queue full, "
     "deadline-unmeetable shed, tenant in-flight cap) — each carries a "
     "retry-after hint"),
    ("serve.corruptFrames", MetricKind.COUNTER,
     "protocol frames failing their CRC (FrameCorruptError; the "
     "connection closes cleanly)"),
    ("serve.draining", MetricKind.GAUGE,
     "1 while the server is draining (drain()/SIGTERM)"),
    ("serve.drainCancelled", MetricKind.COUNTER,
     "in-flight queries cancelled at drainTimeout with reason "
     "'shutdown'"),
    ("serve.failovers", MetricKind.COUNTER,
     "client-side redials to a peer server after mid-stream transport "
     "death (query replayed under its dedup key)"),
    ("serve.dedupReplays", MetricKind.COUNTER,
     "EXECUTE/BIND commands recognised as failover replays by their "
     "dedup key (spark.rapids.tpu.serve.failover.dedupWindow)"),
    # latency distributions (HISTOGRAM kind, log2 buckets; Prometheus
    # renders _bucket/_sum/_count) — the series that used to be bounded
    # raw-sample lists or bare nanos totals
    ("serve.queryWaitHist", MetricKind.HISTOGRAM,
     "served queries' admission queue wait (ns distribution)"),
    ("serve.queryRunHist", MetricKind.HISTOGRAM,
     "served queries' execution+stream time (ns distribution)"),
    ("serve.queryTotalHist", MetricKind.HISTOGRAM,
     "served queries' wait+run total (ns distribution — the SLO series)"),
    ("scheduler.queueWaitHist", MetricKind.HISTOGRAM,
     "admission queue wait per query (ns distribution)"),
    ("kernel.compileHist", MetricKind.HISTOGRAM,
     "first-touch trace+compile time per kernel (ns distribution)"),
    ("shuffle.fetchHist", MetricKind.HISTOGRAM,
     "shuffle fetch wall time per fetch_blocks call (ns distribution)"),
    ("pipeline.dispatchHist", MetricKind.HISTOGRAM,
     "per-batch upstream production time on pipeline producers "
     "(ns distribution)"),
    # obs/ self-observation — the attribution layer watches itself
    ("trace.droppedSpans", MetricKind.COUNTER,
     "spans overwritten by ring-buffer wrap across all tracers (a "
     "truncated Perfetto export is detectable, not silent)"),
    ("metrics.slugOverflow", MetricKind.COUNTER,
     "dynamic-series observations folded into an 'other' bucket because "
     "their prefix hit spark.rapids.tpu.metrics.maxDynamicSlugs"),
    # resilience/* — the old retry.report() counters (registry view now)
    ("resilience.oom_retries", MetricKind.COUNTER, "spill-and-retry launches after device OOM"),
    ("resilience.splits", MetricKind.COUNTER, "OOM batch halvings"),
    ("resilience.fetch_retries", MetricKind.COUNTER, "shuffle fetch retry waves"),
    ("resilience.peers_evicted", MetricKind.COUNTER, "stale + blacklisted executors evicted"),
    ("resilience.circuit_breaker_trips", MetricKind.COUNTER, "ops flipped to CPU by the breaker"),
    ("resilience.transport_reconnects", MetricKind.COUNTER, "TCP transport reconnects"),
    ("resilience.spill_write_errors", MetricKind.COUNTER, "disk-spill write failures (degraded to HOST)"),
    ("resilience.faults_injected", MetricKind.COUNTER, "chaos-harness injections fired"),
    # resilience/lineage.py + sched/speculation.py — partition-granular
    # recovery (task re-execution, straggler speculation, stage fallback)
    ("task.reattempts", MetricKind.COUNTER,
     "partition tasks re-executed under a fresh attempt id after a "
     "recoverable fault (spark.task.maxFailures bounds the loop)"),
    ("speculation.launched", MetricKind.COUNTER,
     "speculative duplicate attempts launched for straggling partitions"),
    ("speculation.won", MetricKind.COUNTER,
     "speculative attempts that committed first (original cancelled)"),
    ("fusion.breakerFallbacks", MetricKind.COUNTER,
     "fused stages rebuilt as their unfused per-op chain because the "
     "circuit breaker opened on the stage signature"),
    # cache/results.py — the semantic result cache (dashboard re-execution)
    ("cache.result.hits", MetricKind.COUNTER,
     "queries served from the result cache without scheduler admission"),
    ("cache.result.misses", MetricKind.COUNTER,
     "result-cache lookups that fell through to execution"),
    ("cache.result.stores", MetricKind.COUNTER,
     "completed results admitted into the cache"),
    ("cache.result.evictions", MetricKind.COUNTER,
     "entries dropped for entry-count or disk-budget overflow (LRU)"),
    ("cache.result.invalidations", MetricKind.COUNTER,
     "entries dropped because a read table's version moved (writes), "
     "plus admissions rejected for racing a write mid-execution"),
    ("cache.result.spills", MetricKind.COUNTER,
     "memory-tier entries demoted to Arrow IPC files on disk"),
    ("cache.result.spillDrops", MetricKind.COUNTER,
     "demotions abandoned (spill-write failure or disk tier full) — the "
     "entry is dropped, the query unaffected"),
    ("cache.result.bytes", MetricKind.GAUGE,
     "memory-resident cached result bytes (reserved against the host "
     "spill budget)"),
    ("cache.result.diskBytes", MetricKind.GAUGE,
     "disk-tier cached result bytes"),
    ("cache.result.entries", MetricKind.GAUGE,
     "live result-cache entries across both tiers"),
    ("cache.result.hitRatio", MetricKind.GAUGE,
     "hits per mille of lookups since session start (0-1000)"),
    # cache/subplan.py — concurrent common-subtree single-flight
    ("subplan.dedupOwners", MetricKind.COUNTER,
     "shared subtrees computed once on behalf of concurrent queries"),
    ("subplan.dedupHits", MetricKind.COUNTER,
     "queries that consumed another in-flight query's subtree batches"),
    ("subplan.dedupFallbacks", MetricKind.COUNTER,
     "sharing attempts that degraded to independent execution (unshaped "
     "entry, owner abort, or re-entry)"),
    ("subplan.dedupAborts", MetricKind.COUNTER,
     "owners that exited without completing their shared entry (error, "
     "cancellation, partial consumption) — waiters woken to recompute"),
    ("subplan.entries", MetricKind.GAUGE,
     "in-flight shared-subtree entries (concurrent-only, pin-bounded)"),
    ("subplan.bytes", MetricKind.GAUGE,
     "bytes materialized in completed shared-subtree entries"),
    # live/ — streaming ingestion + incremental view maintenance +
    # SUBSCRIBE delta streaming
    ("live.appends", MetricKind.COUNTER,
     "append batches landed into registered live tables"),
    ("live.delta.rows", MetricKind.COUNTER,
     "rows appended through the live ingestion path"),
    ("live.delta.bytes", MetricKind.COUNTER,
     "bytes appended through the live ingestion path"),
    ("live.refreshes", MetricKind.COUNTER,
     "live-query refreshes computed (incremental + full fallback)"),
    ("live.refresh.incremental", MetricKind.COUNTER,
     "refreshes served by delta-only incremental maintenance"),
    ("live.refresh.fallbackFull", MetricKind.COUNTER,
     "refreshes that fell back to full re-execution (unsupported plan "
     "shape, delta-log gap, or unordered append) — each carries an "
     "explain reason in the query's live status"),
    ("live.refresh.latencyHist", MetricKind.HISTOGRAM,
     "version-advance to refreshed-result latency per refresh (ns "
     "distribution — the dashboard-freshness SLO series)"),
    ("live.subscriptions.active", MetricKind.GAUGE,
     "wire subscriptions currently registered across all connections"),
    ("live.updates.sent", MetricKind.COUNTER,
     "epoch-stamped UPDATE frames delivered to subscribers"),
    ("live.updates.collapsed", MetricKind.COUNTER,
     "pending epochs collapsed into a snapshot for a slow subscriber"),
    ("live.state.bytes", MetricKind.GAUGE,
     "host-resident maintained-state bytes (reserved against the spill "
     "catalog's host budget)"),
    ("live.state.demotions", MetricKind.COUNTER,
     "maintained-state tables demoted to disk through the fault-"
     "injected spill IO points"),
)

for _name, _kind, _doc in CATALOG:
    GLOBAL.get_or_create(_name, "ESSENTIAL", _kind)


def shuffle_compression_ratio() -> float:
    """Uncompressed / compressed across all serialized shuffle payloads
    (1.0 = incompressible or codec 'none'; 0.0 = nothing shuffled yet)."""
    u = GLOBAL.counter("shuffle.bytesUncompressed").value
    c = GLOBAL.counter("shuffle.bytesCompressedOut").value
    if not u or not c:
        return 0.0
    return u / c
