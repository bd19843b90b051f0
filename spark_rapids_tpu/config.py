"""Typed configuration registry — the ``RapidsConf`` analogue.

Mirrors the reference's config system (sql-plugin RapidsConf.scala: ``ConfEntry``
builder DSL ~:60-120, ~120 ``spark.rapids.*`` keys, and the markdown doc
generator at :1052-1149). Key names keep the ``spark.rapids.`` namespace so a
spark-rapids user finds the same switches; device-specific keys live under
``spark.rapids.tpu.*``.

Every operator/expression replacement rule additionally gets an auto-derived
kill switch (``spark.rapids.sql.exec.*`` / ``spark.rapids.sql.expression.*``),
registered by the planner — the reference's ``DataFromReplacementRule.confKey``
pattern (RapidsMeta.scala:35-43).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Generic, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: dict[str, "ConfEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


class ConfEntry(Generic[T]):
    def __init__(
        self,
        key: str,
        default: T,
        doc: str,
        conv: Callable[[str], T],
        internal: bool = False,
        startup_only: bool = False,
    ):
        self.key = key
        self.default = default
        self.doc = doc
        self.conv = conv
        self.internal = internal
        self.startup_only = startup_only

    def get(self, conf: "TpuConf") -> T:
        return conf.get(self.key, self.default, self.conv)


class _EntryBuilder:
    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._internal = False
        self._startup = False

    def doc(self, text: str) -> "_EntryBuilder":
        self._doc = text
        return self

    def internal(self) -> "_EntryBuilder":
        self._internal = True
        return self

    def startup_only(self) -> "_EntryBuilder":
        self._startup = True
        return self

    def _register(self, default, conv) -> ConfEntry:
        entry = ConfEntry(
            self._key, default, self._doc, conv, self._internal, self._startup
        )
        with _REGISTRY_LOCK:
            if self._key in _REGISTRY:
                raise ValueError(f"duplicate conf key {self._key}")
            _REGISTRY[self._key] = entry
        return entry

    def boolean_conf(self, default: bool) -> ConfEntry[bool]:
        return self._register(default, lambda s: s.strip().lower() in ("true", "1"))

    def int_conf(self, default: int) -> ConfEntry[int]:
        return self._register(default, int)

    def bytes_conf(self, default: int) -> ConfEntry[int]:
        return self._register(default, _parse_bytes)

    def double_conf(self, default: float) -> ConfEntry[float]:
        return self._register(default, float)

    def string_conf(self, default: Optional[str]) -> ConfEntry[Optional[str]]:
        return self._register(default, lambda s: s)


def conf(key: str) -> _EntryBuilder:
    return _EntryBuilder(key)


def _parse_bytes(s: str) -> int:
    s = s.strip().lower()
    mult = 1
    for suffix, m in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("t", 1 << 40)):
        if s.endswith(suffix + "b"):
            s, mult = s[:-2], m
            break
        if s.endswith(suffix):
            s, mult = s[:-1], m
            break
    return int(float(s) * mult)


# ── Core keys (subset growing toward the reference's ~120) ──────────────────

SQL_ENABLED = conf("spark.rapids.sql.enabled").doc(
    "Enable (true) or disable (false) TPU acceleration of SQL operators."
).boolean_conf(True)

PALLAS_ENABLED = conf("spark.rapids.sql.pallas.enabled").doc(
    "Use hand-written Pallas TPU kernels for hot string ops (substring "
    "search over the padded byte planes) instead of the pure-XLA lowering. "
    "Results are bit-identical; this only changes the kernel strategy."
).startup_only().boolean_conf(True)

TASK_MAX_FAILURES = conf("spark.task.maxFailures").doc(
    "Task-retry budget (Spark's key): a failed partition task re-runs from "
    "its lineage up to this many total attempts before the query fails. "
    "Deterministic semantic errors (ANSI arithmetic/cast errors, "
    "assertions) are never retried."
).int_conf(4)

NATIVE_ENABLED = conf("spark.rapids.native.enabled").doc(
    "Use the native (C++) host data plane — Spark-exact murmur3 hashing, "
    "the best-fit staging-arena sub-allocator, and contiguous spill frames "
    "(built from native/srt_host.cc; auto-compiled with g++ on first use). "
    "Pure-python/numpy fallbacks run when disabled or when no toolchain is "
    "available."
).startup_only().boolean_conf(True)

EXPLAIN = conf("spark.rapids.sql.explain").doc(
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE, NOT_ON_GPU (only log un-replaced nodes), ALL."
).string_conf("NONE")

INCOMPATIBLE_OPS = conf("spark.rapids.sql.incompatibleOps.enabled").doc(
    "Enable operators that produce results that differ from Spark in corner "
    "cases (e.g. float aggregation ordering)."
).boolean_conf(False)

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").doc(
    "Target size of a columnar batch the operators work on "
    "(reference: RapidsConf.scala:402)."
).bytes_conf(1 << 30)

BATCH_SIZE_ROWS = conf("spark.rapids.sql.batchSizeRows").doc(
    "Target row count of a device batch; capacities are bucketed to powers of "
    "two above this to bound XLA recompilation."
).int_conf(1 << 20)

MAX_READER_BATCH_SIZE_ROWS = conf("spark.rapids.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per batch produced by file readers "
    "(reference: RapidsConf.scala READER_BATCH_SIZE_ROWS)."
).int_conf(1 << 20)

MAX_READER_BATCH_SIZE_BYTES = conf("spark.rapids.sql.reader.batchSizeBytes").doc(
    "Soft cap on bytes per batch produced by file readers."
).bytes_conf(1 << 30)

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks").doc(
    "Number of concurrent tasks that may hold the device at once — admission "
    "control via the device semaphore (reference: GpuSemaphore.scala), and "
    "the size of the session's partition-task thread pool. Re-read at every "
    "query, so a long-lived service can retune it live; query-level "
    "admission across tenants is the scheduler's permit pool "
    "(spark.rapids.tpu.scheduler.*)."
).int_conf(4)

HAS_NANS = conf("spark.rapids.sql.hasNans").doc(
    "Assume floating point values may contain NaNs (gates some operators, "
    "matching the reference)."
).boolean_conf(True)

VARIABLE_FLOAT_AGG = conf("spark.rapids.sql.variableFloatAgg.enabled").doc(
    "Allow float/double aggregations whose result can vary with evaluation "
    "order (sum/avg over float)."
).boolean_conf(True)

CAST_FLOAT_TO_STRING = conf("spark.rapids.sql.castFloatToString.enabled").doc(
    "Enable float→string casts, which may differ from Spark in formatting."
).boolean_conf(False)

CAST_STRING_TO_FLOAT = conf("spark.rapids.sql.castStringToFloat.enabled").doc(
    "Enable string→float casts, which may differ from Spark in corner cases."
).boolean_conf(False)

CAST_STRING_TO_TIMESTAMP = conf(
    "spark.rapids.sql.castStringToTimestamp.enabled"
).doc(
    "Enable string→timestamp casts on device; the device grammar is the "
    "UTC-only subset of Spark's (no zone offsets), matching the reference's "
    "gated support (GpuCast.scala castStringToTimestamp)."
).boolean_conf(False)

EXCHANGE_REUSE_ENABLED = conf("spark.sql.exchange.reuse").doc(
    "Deduplicate identical exchange subtrees so repeated subplans "
    "(self-joins of an aggregate, CTE fan-out) materialize once "
    "(Spark's ReuseExchange; reference GpuExec.doCanonicalize — "
    "GpuExec.scala:251-276)."
).boolean_conf(True)

PYTHON_PREFETCH_BATCHES = conf("spark.rapids.sql.python.prefetchBatches").doc(
    "Bounded producer/consumer queue depth between the engine's batch "
    "pipeline and streaming python UDF execs (mapInPandas): upstream "
    "production overlaps python compute on a producer thread (the "
    "reference's BatchQueue, GpuArrowEvalPythonExec.scala:188). 0 disables."
).int_conf(2)

GET_JSON_OBJECT_DEVICE = conf("spark.rapids.sql.getJsonObject.enabled").doc(
    "Run get_json_object on device via the span-extraction kernel. Like the "
    "reference's cudf get_json_object (GpuOverrides.scala:2519) it returns "
    "nested results as written (no re-serialization) and does not unescape "
    "string values — exact on compact escape-free JSON; off by default "
    "because CPU Spark normalizes through Jackson (docs/compatibility.md)."
).boolean_conf(False)

ADAPTIVE_ENABLED = conf("spark.sql.adaptive.enabled").doc(
    "Adaptive query execution (Spark's key, honored here): exchanges "
    "coalesce small output partitions at runtime from measured sizes "
    "(the GpuCustomShuffleReaderExec analogue)."
).boolean_conf(False)

ADVISORY_PARTITION_SIZE = conf(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes"
).doc(
    "Target post-shuffle partition size for adaptive coalescing."
).bytes_conf(64 << 20)

SKEW_JOIN_ENABLED = conf("spark.sql.adaptive.skewJoin.enabled").doc(
    "Runtime skew-join handling (Spark's key, honored here): an oversized "
    "join-side partition is split across the slots freed by coalescing "
    "while the other side's partition is replicated "
    "(OptimizeSkewedJoin analogue)."
).boolean_conf(True)

SKEW_JOIN_THRESHOLD = conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes"
).doc(
    "A partition larger than this (and skewedPartitionFactor x the median) "
    "is considered skewed."
).bytes_conf(256 << 20)

SKEW_JOIN_FACTOR = conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor"
).doc(
    "Skew multiplier over the median partition size."
).int_conf(5)

SPARK_VERSION = conf("spark.rapids.tpu.sparkVersion").doc(
    "Spark version whose semantics to emulate; selects the shim provider "
    "(reference: ShimLoader + per-version shims/ modules). Shim-dependent "
    "defaults (ANSI, adaptive execution) apply when their keys are unset."
).startup_only().string_conf("3.1")

CBO_ENABLED = conf("spark.rapids.sql.optimizer.enabled").doc(
    "Cost-based un-conversion: device islands whose estimated compute is "
    "too small to pay for their H2D/D2H transitions revert to the CPU "
    "engine (reference: CostBasedOptimizer.scala, default off there too)."
).boolean_conf(False)

ANSI_ENABLED = conf("spark.sql.ansi.enabled").doc(
    "Spark's ANSI mode (honored here): casts raise on overflow or malformed "
    "input instead of returning NULL, and integral narrowing range-checks "
    "instead of wrapping."
).boolean_conf(False)

STRING_MAX_BYTES = conf("spark.rapids.tpu.string.maxBytes").doc(
    "Maximum per-value string width the fixed-width device representation "
    "pads to before the column falls back to the CPU."
).int_conf(256)

POOL_SIZE_FRACTION = conf("spark.rapids.memory.gpu.allocFraction").doc(
    "Fraction of device memory the HBM pool may use "
    "(reference: RapidsConf.scala RMM_ALLOC_FRACTION)."
).double_conf(0.9)

MEMORY_DEBUG = conf("spark.rapids.memory.tpu.debug").doc(
    "Debug-allocator mode (the reference's spark.rapids.memory.gpu.debug + "
    "ai.rapids.refcount.debug): the spill catalog records the registration "
    "site of every spillable buffer, logs tier transitions, and reports any "
    "buffer still registered at query end as a LEAK with its origin."
).boolean_conf(False)

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").doc(
    "Amount of host memory to use for spilled device buffers before "
    "overflowing to disk."
).bytes_conf(1 << 31)

SPILL_DIR = conf("spark.rapids.memory.spillDir").doc(
    "Directory for the disk spill tier."
).string_conf(None)

SHUFFLE_PARTITIONS = conf("spark.sql.shuffle.partitions").doc(
    "Default number of partitions for exchanges (Spark's key, honored here)."
).int_conf(8)

MESH_ENABLED = conf("spark.rapids.sql.mesh.enabled").doc(
    "Execute planner-built queries SPMD over a jax.sharding.Mesh: shuffle "
    "exchanges lower to one fused all_to_all over ICI (the accelerated-"
    "shuffle data plane wired into query execution, the UCX analogue — "
    "RapidsShuffleInternalManagerBase.scala) and each partition's kernels "
    "run on its own chip. Requires shuffle partitions == mesh size (the "
    "session aligns the default automatically)."
).startup_only().boolean_conf(False)

MESH_SIZE = conf("spark.rapids.sql.mesh.size").doc(
    "Number of devices in the execution mesh; 0 uses every visible device."
).startup_only().int_conf(0)

SPLIT_MAX_TOKENS = conf("spark.rapids.sql.split.maxTokens").doc(
    "Static token-plane width for device split(): a row splitting into "
    "more tokens fails loudly (never truncates) — raise this or disable "
    "spark.rapids.sql.expression.StringSplit for such data."
).int_conf(16)

UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled").doc(
    "Translate simple python UDFs (arithmetic/comparison/conditional/math/"
    "string-method subset) into expression trees that fuse on device — the "
    "udf-compiler analogue. Off by default like the reference: a translated "
    "UDF null-propagates where the raw python function would raise on None."
).boolean_conf(False)

PROFILE_PATH = conf("spark.rapids.sql.profile.path").doc(
    "When set, each collect() is wrapped in a jax.profiler trace dumped to "
    "this directory (TensorBoard XPlane capture with per-operator "
    "TraceAnnotation ranges) — the Nsight+NVTX analogue "
    "(NvtxWithMetrics.scala)."
).string_conf("")

PROFILE_OPTIME = conf("spark.rapids.sql.profile.opTime.enabled").doc(
    "Per-operator device-time attribution: every exec's output batches are "
    "block_until_ready'd under a timer feeding its opTime metric. "
    "Serializes the pipeline (CUDA_LAUNCH_BLOCKING-style) — debug only."
).boolean_conf(False)

TEST_CONF = conf("spark.rapids.sql.test.enabled").doc(
    "Test mode: fail if any operator that was expected on device fell back "
    "(reference: RapidsConf TEST_CONF)."
).internal().boolean_conf(False)

TEST_ALLOWED_NONTPU = conf("spark.rapids.sql.test.allowedNonGpu").doc(
    "Comma-separated exec names allowed to stay on CPU in test mode."
).internal().string_conf(None)

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").doc(
    "ESSENTIAL, MODERATE or DEBUG — how many metrics operators publish "
    "(reference: RapidsConf.scala:456)."
).string_conf("MODERATE")

METRICS_LEVEL_TPU = conf("spark.rapids.tpu.metrics.level").doc(
    "TPU-engine override of spark.rapids.sql.metrics.level for the obs/ "
    "subsystem: ESSENTIAL (counters only — no per-batch timer reads), "
    "MODERATE (plus transfer/pipeline timings) or DEBUG (plus opTime "
    "device-time attribution). Unset inherits the sql key."
).string_conf(None)

TRACE_ENABLED = conf("spark.rapids.tpu.trace.enabled").doc(
    "Hierarchical query tracing (obs/trace.py): each sampled query records "
    "query → operator → batch spans — including work executed on pipeline "
    "producer threads via span-context propagation — into a ring buffer "
    "exportable as Chrome-trace/Perfetto JSON. Implied by "
    "spark.rapids.tpu.trace.dir; see docs/observability.md."
).boolean_conf(False)

TRACE_SAMPLE = conf("spark.rapids.tpu.trace.sample").doc(
    "Fraction of queries traced when tracing is enabled (Dapper-style "
    "sampling): 1.0 traces every query, 0.01 one in a hundred. The "
    "per-query decision is deterministic in the session's query sequence "
    "number, so a rerun traces the same queries."
).double_conf(1.0)

TRACE_DIR = conf("spark.rapids.tpu.trace.dir").doc(
    "When set, every traced query writes query-<n>.trace.json (Chrome-"
    "trace/Perfetto: load at ui.perfetto.dev) and query-<n>.metrics.json "
    "(the per-query metrics artifact) into this directory. Setting it "
    "implies spark.rapids.tpu.trace.enabled."
).string_conf(None)

TRACE_BUFFER_SPANS = conf("spark.rapids.tpu.trace.bufferSpans").doc(
    "Span ring-buffer capacity per traced query; the oldest spans are "
    "overwritten beyond it (exporters report the drop count, and the "
    "process-wide trace.droppedSpans counter records every overwrite)."
).int_conf(65536)

TRACE_PROPAGATE = conf("spark.rapids.tpu.trace.propagate").doc(
    "Cross-process span-context propagation: serve protocol frames and "
    "multiproc shuffle requests carry a compact (trace id, parent span id, "
    "sampled) context so client spans, server query trees, and remote "
    "shuffle-worker fetch spans merge into one Perfetto trace "
    "(obs/trace.py SpanContext; the Dapper propagation model)."
).boolean_conf(True)

METRICS_HTTP_PORT = conf("spark.rapids.tpu.metrics.httpPort").doc(
    "Live scrape endpoint (obs/scrape.py): a stdlib HTTP listener serving "
    "/metrics (Prometheus text exposition of the process registry, "
    "histograms included) and /healthz (liveness + serve readiness). "
    "0 disables (default), a positive port binds there, -1 binds an "
    "ephemeral port. Started by TpuServer.start() and by bare sessions at "
    "construction."
).int_conf(0)

METRICS_MAX_DYNAMIC_SLUGS = conf("spark.rapids.tpu.metrics.maxDynamicSlugs").doc(
    "Cardinality cap for dynamically-named metric series (cancel-reason, "
    "tenant, stall-site, pool families): at most this many distinct slugs "
    "per prefix; overflow folds into one 'other' bucket and counts in "
    "metrics.slugOverflow. Guards the Prometheus export against unbounded "
    "series from wire-supplied names."
).int_conf(64)

LEDGER_ENABLED = conf("spark.rapids.tpu.ledger.enabled").doc(
    "Host-overhead ledger (obs/ledger.py): decompose each query's wall "
    "clock into exhaustive non-overlapping phases (parse/plan, compile, "
    "h2d, dispatch, device wait, d2h, serialize, queue wait, glue "
    "residual), exported via df.explain('metrics') and the per-query "
    "JSON artifact."
).boolean_conf(True)

CBO_CALIBRATION_ENABLED = conf("spark.rapids.tpu.cbo.calibration.enabled").doc(
    "Harvest measured per-op device/host ns-per-row into the persisted "
    "calibration table at every query exit (obs/calibration.py). Implies "
    "per-batch opTime attribution (profiling.instrument_plan) while on — "
    "a measurement mode, not a hot-path default."
).boolean_conf(False)

CBO_CALIBRATION_FILE = conf("spark.rapids.tpu.cbo.calibrationFile").doc(
    "Path of the persisted JSON calibration table (EWMA per-op-signature "
    "measured costs), shared across sessions and processes. Default: "
    "~/.cache/spark_rapids_tpu/cbo_calibration.json."
).string_conf(None)

CBO_MEASURED_WEIGHTS = conf("spark.rapids.tpu.cbo.measuredWeights").doc(
    "Drive the cost-based optimizer's island un-conversion from the "
    "MEASURED calibration table instead of the hardcoded per-op weights "
    "(plan/overrides.py). With this off — or the calibration file absent "
    "or empty — planning is bit-identical to the hardcoded table; the "
    "chosen weight source and numbers appear in the explain output."
).boolean_conf(False)

CPU_ONLY = conf("spark.rapids.tpu.cpuOnly").doc(
    "Force the JAX CPU backend (testing; the virtual-device mesh path)."
).internal().boolean_conf(False)

CLOUD_SCHEMES = conf("spark.rapids.cloudSchemes").doc(
    "Comma-separated URI schemes treated as cloud storage: the AUTO reader "
    "type picks MULTITHREADED for them (background prefetch hides object-"
    "store latency) and COALESCING otherwise (reference: "
    "RapidsConf.scala:651)."
).string_conf("dbfs,s3,s3a,s3n,wasbs,gs,abfs,abfss")

ALLUXIO_PATHS_TO_REPLACE = conf("spark.rapids.alluxio.pathsToReplace").doc(
    "Comma-separated 'src->dst' prefix rewrites applied to read paths "
    "before file listing — route cloud reads through an Alluxio-style "
    "cache mount (reference: RapidsConf.scala:929)."
).string_conf(None)

PARQUET_READER_TYPE = conf("spark.rapids.sql.format.parquet.reader.type").doc(
    "File reader strategy: AUTO (COALESCING for local paths, MULTITHREADED "
    "when any path scheme is in spark.rapids.cloudSchemes — the reference's "
    "default), PERFILE (one task per file), COALESCING (small files "
    "stitched into shared partitions), or MULTITHREADED (cloud-style "
    "thread-pool reads). The per-read option 'readerType' overrides this "
    "per DataFrame (reference: RapidsConf.scala:624-671)."
).string_conf("AUTO")

ORC_READER_TYPE = conf("spark.rapids.sql.format.orc.reader.type").doc(
    "ORC file reader strategy; same values as the parquet key."
).string_conf("AUTO")

MULTITHREADED_READ_NUM_THREADS = conf(
    "spark.rapids.sql.multiThreadedRead.numThreads"
).doc(
    "Thread pool size for the multithreaded (cloud) file reader "
    "(reference: RapidsConf.scala:624-671)."
).int_conf(20)

DECIMAL_ENABLED = conf("spark.rapids.sql.decimalType.enabled").doc(
    "Enable decimal (64-bit) processing on device."
).boolean_conf(True)

DEVICE_POOL_LIMIT = conf("spark.rapids.tpu.memory.deviceLimitBytes").doc(
    "Spillable-buffer budget on device; 0 means unlimited. When registered "
    "spillable bytes would exceed this, the catalog proactively spills "
    "(reference: RMM pool size via spark.rapids.memory.gpu.allocFraction)."
).bytes_conf(0)

ADAPTIVE_BROADCAST_THRESHOLD = conf(
    "spark.sql.adaptive.autoBroadcastJoinThreshold"
).doc(
    "AQE runtime join-strategy switch: a shuffled hash join whose MEASURED "
    "build side is at most this many bytes re-plans as a broadcast join at "
    "execution time (the probe side's exchange is read locally, skipping "
    "its all-to-all). -1 falls back to spark.sql.autoBroadcastJoinThreshold."
).bytes_conf(-1)

AUTO_BROADCAST_THRESHOLD = conf("spark.sql.autoBroadcastJoinThreshold").doc(
    "Maximum estimated build-side size for which a join is planned as a "
    "broadcast hash join (Spark's key, honored here; -1 disables)."
).bytes_conf(10 << 20)

PIPELINE_ENABLED = conf("spark.rapids.tpu.pipeline.enabled").doc(
    "Dispatch-ahead partition pipelining: blocking plan sinks (the D2H "
    "pull at collect(), LIMIT's per-batch row-count sync) consume their "
    "upstream batch stream through a bounded prefetch window driven by a "
    "producer thread, so device work for batches i+1..k dispatches while "
    "the sink blocks on batch i, in place of one host round trip per "
    "batch with the device idle (gain not measured on the chip). Kill "
    "switch for the pipelined path; see docs/pipelined-execution.md."
).boolean_conf(True)

PIPELINE_MAX_BATCHES = conf("spark.rapids.tpu.pipeline.maxBatches").doc(
    "Maximum batches in flight per pipelined partition stream (the "
    "dispatch-ahead window depth). Bounds device-buffer growth together "
    "with spark.rapids.tpu.pipeline.maxInflightBytes."
).int_conf(4)

PIPELINE_MAX_INFLIGHT_BYTES = conf(
    "spark.rapids.tpu.pipeline.maxInflightBytes"
).doc(
    "Byte bound on the batches buffered ahead by a pipelined partition "
    "stream; the producer also requests spill-catalog headroom before "
    "each prefetch. 0 (default) sizes automatically: a quarter of the "
    "spillable device budget when known, else 1 GiB."
).bytes_conf(0)

PRECOMPILE_ENABLED = conf("spark.rapids.tpu.precompile.enabled").doc(
    "Kernel pre-compilation pass: after planning, walk the exec tree, "
    "derive the batch geometry of shape-predictable scan-side chains, and "
    "compile their kernels ahead of execution on a small compile pool "
    "(concurrent on TPU, serialized on XLA:CPU), warm-starting the "
    "persistent XLA cache — compile latency overlaps across plan nodes "
    "instead of serializing at first touch of each operator."
).boolean_conf(True)

PRECOMPILE_PARALLELISM = conf("spark.rapids.tpu.precompile.parallelism").doc(
    "Compile-pool width for the kernel pre-compilation pass; 0 picks "
    "automatically (1 on the CPU backend, up to 4 elsewhere)."
).int_conf(0)

FUSION_ENABLED = conf("spark.rapids.tpu.fusion.enabled").doc(
    "Whole-stage fusion (plan/fusion.py): maximal chains of adjacent "
    "device project/filter operators collapse into a single StageExec "
    "whose body is ONE jitted XLA program — one kernel launch (and one "
    "downstream D2H sync) per stage instead of one per operator. "
    "Bit-identical to per-op execution by construction; chains break at "
    "task-dependent expressions (row_base semantics) and at kernels with "
    "ANSI error sites (their per-op error channel must keep its batch "
    "attribution). Kill switch for the fused path."
).boolean_conf(True)

FUSION_MAX_OPS = conf("spark.rapids.tpu.fusion.maxOps").doc(
    "Maximum operators fused into one StageExec program; longer chains "
    "split into consecutive stages. Bounds single-program XLA trace and "
    "compile time."
).int_conf(16)

SHAPE_BUCKETS_ENABLED = conf("spark.rapids.tpu.shapeBuckets.enabled").doc(
    "Pow-2 shape-bucket lattice (kernels.shape_bucket_floor): batch "
    "capacities round up to at least shapeBuckets.minRows, so one cached "
    "XLA executable serves every batch geometry inside the bucket — "
    "first-touch compiles amortize across batch sizes and the persistent "
    "xla_store entry count collapses for warm restarts. Padding rows are "
    "masked inert (the existing capacity > num_rows invariant); results "
    "are bit-identical. Off restores exact pow-2-of-row-count capacities."
).boolean_conf(True)

SHAPE_BUCKETS_MIN_ROWS = conf("spark.rapids.tpu.shapeBuckets.minRows").doc(
    "Floor of the shape-bucket lattice: the smallest batch capacity the "
    "engine compiles for (rounded up to a power of two). Larger floors "
    "mean fewer distinct compiled shapes at the cost of more masked "
    "padding per small batch."
).int_conf(1024)

ROUTING_ENABLED = conf("spark.rapids.tpu.routing.enabled").doc(
    "Calibrated engine routing (plan/overrides.py): with a measured cost "
    "table present (obs/calibration.py), predict each device island's "
    "device time (ns/row x estimated rows + per-launch and transfer "
    "overheads) against its CPU-engine time and route sub-threshold "
    "islands — the tiny-input, full-dispatch-tax shape — back to the CPU "
    "engine, with the prediction and its numbers in the explain reason. "
    "Off (default), or with no calibration data, planning is unchanged."
).boolean_conf(False)

ROUTING_LAUNCH_OVERHEAD_NS = conf("spark.rapids.tpu.routing.launchOverheadNs").doc(
    "Fixed per-kernel-launch host overhead the routing predictor charges "
    "each device operator (dispatch + enqueue tax measured by the "
    "attribution ledger's dispatch phase)."
).int_conf(1_500_000)

ROUTING_TRANSFER_OVERHEAD_NS = conf("spark.rapids.tpu.routing.transferOverheadNs").doc(
    "Fixed per-island transfer overhead the routing predictor charges a "
    "device island (H2D upload + D2H result round trip on the PJRT link)."
).int_conf(4_000_000)

UPLOAD_CACHE_MAX_BYTES = conf("spark.rapids.tpu.uploadCache.maxBytes").doc(
    "Byte budget for the session's device-upload (H2D) cache of in-memory "
    "relations — the LRU bound standing between many-table sessions and "
    "pinned-HBM OOM. 0 (default) sizes automatically from device memory "
    "stats (a quarter of the device's byte limit) with a 4 GiB fallback "
    "when no stats are available."
).bytes_conf(0)

OUT_OF_CORE_SORT_THRESHOLD = conf("spark.rapids.tpu.sort.outOfCoreThresholdBytes").doc(
    "Partition size above which TpuSortExec switches from single-batch sort "
    "to spillable sorted-run merge (reference: GpuSortExec.scala:212 "
    "out-of-core mode gated by targetSize)."
).bytes_conf(1 << 30)


SHUFFLE_COMPRESSION_CODEC = conf("spark.rapids.shuffle.compression.codec").doc(
    "Codec for shuffle buffers on the inter-host (DCN) path: none, copy, "
    "lz4, zstd (reference: TableCompressionCodec + nvcomp LZ4)."
).string_conf("lz4")

SHUFFLE_MAX_RECEIVE_INFLIGHT = conf(
    "spark.rapids.shuffle.transport.maxReceiveInflightBytes"
).doc(
    "Bytes a reduce task may have requested but not yet received "
    "(reference: RapidsConf.scala:850)."
).bytes_conf(1 << 30)

SHUFFLE_BOUNCE_BUFFER_SIZE = conf("spark.rapids.shuffle.bounceBufferSize").doc(
    "Size of each host staging (bounce) buffer used to window large shuffle "
    "payloads into frames (reference: BounceBufferManager)."
).bytes_conf(4 << 20)

SHUFFLE_BOUNCE_BUFFER_COUNT = conf("spark.rapids.shuffle.bounceBufferCount").doc(
    "Number of bounce buffers in the staging pool."
).int_conf(8)

SHUFFLE_FETCH_TIMEOUT_S = conf("spark.rapids.shuffle.fetchTimeoutSeconds").doc(
    "Seconds a reduce task waits for shuffle data before raising a fetch "
    "failure (reference: shuffleFetchTimeoutSeconds)."
).int_conf(120)

SHUFFLE_MANAGER_ENABLED = conf("spark.rapids.shuffle.manager.enabled").doc(
    "Route exchanges through the accelerated shuffle manager (device-"
    "resident spillable map output + transport fetches) instead of the "
    "in-process default path (reference: RapidsShuffleManager)."
).boolean_conf(False)

MULTIPROC_DRIVER = conf("spark.rapids.shuffle.multiproc.driver").doc(
    "host:port of the cross-process driver service (heartbeat registry + "
    "map-output tracker — shuffle/driver_service.py). When set, this "
    "session is ONE executor of a multi-process query: exchanges run only "
    "the map/reduce partitions this rank owns and fetch peer map output "
    "over the TCP transport (the DCN path; reference: "
    "RapidsShuffleHeartbeatManager + UCX executor-to-executor traffic)."
).startup_only().string_conf("")

MULTIPROC_RANK = conf("spark.rapids.shuffle.multiproc.rank").doc(
    "This executor's rank in the multi-process query (0-based)."
).startup_only().int_conf(0)

MULTIPROC_SIZE = conf("spark.rapids.shuffle.multiproc.size").doc(
    "Total executors cooperating on the multi-process query."
).startup_only().int_conf(1)

SHUFFLE_HANDSHAKE_TIMEOUT_S = conf("spark.rapids.tpu.shuffle.handshakeTimeout").doc(
    "Seconds the TCP transport waits for a dialing peer's HELLO frame "
    "before dropping the connection (the WorkerAddress-exchange deadline)."
).double_conf(10.0)

HEARTBEAT_MAX_AGE_S = conf("spark.rapids.tpu.shuffle.heartbeatMaxAgeSeconds").doc(
    "An executor whose last heartbeat is older than this is considered "
    "dead and evicted from the peer registry (ShuffleHeartbeatManager."
    "evict_stale); 0 disables age-based eviction."
).double_conf(0.0)


# ── resilience: OOM split-and-retry, fetch retry, circuit breaker ──────────

RETRY_OOM_MAX_RETRIES = conf("spark.rapids.tpu.retry.oom.maxRetries").doc(
    "Spill-and-retry attempts per kernel launch on a device OOM "
    "(RESOURCE_EXHAUSTED) before the retry state machine starts splitting "
    "the input batch (reference: DeviceMemoryEventHandler.scala:42-69 "
    "spill-retry loop)."
).int_conf(2)

RETRY_OOM_SPLIT_ENABLED = conf("spark.rapids.tpu.retry.oom.splitEnabled").doc(
    "After the spill-retry budget is exhausted, recursively halve the "
    "input batch of splittable operators (project/filter, partial "
    "aggregate update, join probe) and retry each half — the "
    "split-and-retry escalation for work that genuinely does not fit."
).boolean_conf(True)

RETRY_OOM_MIN_SPLIT_ROWS = conf("spark.rapids.tpu.retry.oom.minSplitRows").doc(
    "Floor on the batch capacity the OOM retry state machine will split "
    "down to; a batch at or below this capacity that still OOMs fails "
    "the task."
).int_conf(1024)

RETRY_FETCH_MAX_RETRIES = conf("spark.rapids.tpu.retry.fetch.maxRetries").doc(
    "Per-peer shuffle fetch retries (metadata request or transfer wave) "
    "before the fetch surfaces as a ShuffleFetchError; each retry "
    "re-requests only the blocks not yet received."
).int_conf(3)

RETRY_FETCH_BACKOFF_MS = conf("spark.rapids.tpu.retry.fetch.backoffMs").doc(
    "Base backoff between shuffle fetch retries; attempt k sleeps "
    "backoffMs * 2^(k-1) with deterministic seeded jitter, capped by "
    "spark.rapids.tpu.retry.fetch.maxBackoffMs."
).double_conf(50.0)

RETRY_FETCH_MAX_BACKOFF_MS = conf("spark.rapids.tpu.retry.fetch.maxBackoffMs").doc(
    "Upper bound on the exponential shuffle-fetch backoff."
).double_conf(2000.0)

RETRY_FETCH_BLACKLIST_AFTER = conf("spark.rapids.tpu.retry.fetch.blacklistAfter").doc(
    "Consecutive exhausted fetch-retry budgets against one peer before "
    "that peer is blacklisted (evicted from the executor's peer table; "
    "later fetches to it fail fast). 0 disables blacklisting."
).int_conf(3)

CIRCUIT_BREAKER_ENABLED = conf("spark.rapids.tpu.retry.circuitBreaker.enabled").doc(
    "When a device kernel for an op signature fails repeatedly with "
    "non-OOM XLA errors, mark that op CPU-fallback for the session and "
    "log the reason in the explain output (the per-node fallback contract "
    "extended to runtime failures)."
).boolean_conf(True)

CIRCUIT_BREAKER_THRESHOLD = conf("spark.rapids.tpu.retry.circuitBreaker.threshold").doc(
    "Device-kernel failures for one op signature that trip its circuit "
    "breaker."
).int_conf(3)


# ── multi-tenant query scheduler (sched/) ──────────────────────────────────

SCHEDULER_ENABLED = conf("spark.rapids.tpu.scheduler.enabled").doc(
    "Gate every query action (collect/toPandas/to_jax) through the "
    "session's multi-tenant scheduler: HBM-aware admission control over a "
    "weighted permit pool, fair-share pools, bounded queueing with typed "
    "QueryQueueFull backpressure. Disabling skips permit gating; "
    "cancellation and deadlines keep working. See docs/scheduler.md."
).boolean_conf(True)

SCHEDULER_PERMITS = conf("spark.rapids.tpu.scheduler.permits").doc(
    "Device capacity units of the admission pool. Each query takes "
    "ceil(estimatedPeakBytes / bytesPerPermit) permits (clamped to the "
    "pool size), so several small queries or one scan-heavy join hold the "
    "device at a time — the query-granular generalization of "
    "spark.rapids.sql.concurrentGpuTasks. Re-read per query."
).int_conf(8)

SCHEDULER_MAX_QUEUED = conf("spark.rapids.tpu.scheduler.maxQueued").doc(
    "Maximum queries waiting for admission across all pools; an admission "
    "past this bound is rejected with the typed QueryQueueFull error — the "
    "backpressure signal a service in front of the engine sheds load on. "
    "Re-read per query."
).int_conf(32)

SCHEDULER_POOL = conf("spark.rapids.tpu.scheduler.pool").doc(
    "Fair-share pool this session's queries are admitted under (Spark FAIR "
    "scheduler pools analogue). Set per-session or flip between queries "
    "with set_conf — the value is read at each query's admission."
).string_conf("default")

SCHEDULER_POOLS = conf("spark.rapids.tpu.scheduler.pools").doc(
    "Pool weight spec 'name:weight,name:weight' (e.g. 'etl:1,interactive:"
    "3'). Under saturation a pool is admitted permit-capacity proportional "
    "to its weight (stride scheduling); FIFO within each pool. Unlisted "
    "pools get weight 1. Re-read per query."
).string_conf(None)

SCHEDULER_QUERY_TIMEOUT_S = conf("spark.rapids.tpu.scheduler.queryTimeout").doc(
    "Per-query deadline in seconds, measured from admission request "
    "(queue wait included). Expiry raises the typed QueryTimeoutError at "
    "the next batch boundary — queued or mid-execution. 0 disables."
).double_conf(0.0)

SCHEDULER_BYTES_PER_PERMIT = conf("spark.rapids.tpu.scheduler.bytesPerPermit").doc(
    "Estimated-footprint bytes one admission permit stands for; a query "
    "needs ceil(estimate / this) permits. Tune so permits × bytesPerPermit "
    "≈ the HBM budget you want admission to protect."
).bytes_conf(256 << 20)

SCHEDULER_DEFAULT_QUERY_BYTES = conf(
    "spark.rapids.tpu.scheduler.defaultQueryBytes"
).doc(
    "Footprint assumed for a query whose plan yields no measurable "
    "estimate (no scans with stats — sched/estimate.py returns 0)."
).bytes_conf(256 << 20)


# ── service survivability: watchdog, shedding, compile deadlines ───────────

WATCHDOG_ENABLED = conf("spark.rapids.tpu.watchdog.enabled").doc(
    "Master switch for the progress watchdog thread (resilience/watchdog."
    "py): scans running queries for missing progress beats and runs the "
    "periodic stale-peer sweep. The thread only exists while stallTimeout "
    "or evictStalePeriod is non-zero."
).boolean_conf(True)

WATCHDOG_STALL_TIMEOUT_S = conf("spark.rapids.tpu.watchdog.stallTimeout").doc(
    "Seconds a RUNNING query may go without a progress beat (batch "
    "boundary, H2D upload, pipeline pull, shuffle fetch, compile "
    "start/end) before the watchdog cancels it with reason "
    "'stall:<site>', feeds the circuit breaker, and releases its permits "
    "through the normal admission exit. Must exceed the longest legit "
    "beat gap — in particular first-touch XLA compiles (set "
    "spark.rapids.tpu.compile.deadlineSeconds below this so a hung "
    "compile is cut first). 0 disables stall detection."
).double_conf(0.0)

WATCHDOG_BEAT_INTERVAL_S = conf("spark.rapids.tpu.watchdog.beatInterval").doc(
    "Watchdog scan period in seconds; a stalled query is cancelled within "
    "stallTimeout + one beat interval. 0 picks stallTimeout/4 clamped to "
    "[0.05, 5]."
).double_conf(0.0)

WATCHDOG_EVICT_STALE_PERIOD_S = conf(
    "spark.rapids.tpu.watchdog.evictStalePeriod"
).doc(
    "Seconds between the watchdog's periodic shuffle-registry "
    "evict_stale sweeps (±20% jitter so many sessions never sweep in "
    "lockstep); dead peers older than spark.rapids.tpu.shuffle."
    "heartbeatMaxAgeSeconds (or 3x this period when that is unset) are "
    "evicted without waiting for an explicit heartbeat. 0 disables the "
    "periodic sweep (eviction then happens only on heartbeat calls)."
).double_conf(0.0)

SCHEDULER_SHED_EXPIRED = conf("spark.rapids.tpu.scheduler.shedExpired").doc(
    "Deadline-aware load shedding: reject a query at admission when its "
    "estimated queue wait plus estimated run time (calibrated from "
    "completed-query timings) already exceeds its deadline — the typed "
    "QueryOverloadedError carries a retry-after hint instead of wasting "
    "device time on a query that cannot finish. Queued queries whose "
    "deadlines expire while waiting are shed by the deadline check "
    "either way."
).boolean_conf(True)

COMPILE_DEADLINE_S = conf("spark.rapids.tpu.compile.deadlineSeconds").doc(
    "Budget in seconds for one first-touch XLA kernel compile "
    "(kernels.GuardedJit). On timeout the compile is abandoned to a "
    "daemon thread and the typed CompileDeadlineError force-opens the "
    "op's circuit breaker — the NEXT planning pass runs that op on CPU "
    "instead of blocking the tenant behind a 6-90s compile wall. "
    "Process-global (the kernel cache is process-global); the last "
    "session to set it wins. 0 disables."
).double_conf(0.0)


# ── persistent XLA executable cache (cache/xla_store.py) ───────────────────

COMPILE_CACHE_ENABLED = conf("spark.rapids.tpu.compileCache.enabled").doc(
    "Crash-safe on-disk XLA executable store (cache/xla_store.py): "
    "kernels.GuardedJit serializes compiled executables keyed by kernel "
    "structural identity + batch geometry + jax/jaxlib/XLA version + "
    "backend fingerprint, and consults the store before compiling — a "
    "restarted server deserializes yesterday's binaries in milliseconds "
    "instead of re-paying 6-90s first-touch compiles per query shape. "
    "Corrupt, truncated, or version-skewed entries degrade to a fresh "
    "compile (quarantine + cache.xla.corrupt), never to a failure. "
    "Process-global; reconfigured on set_conf."
).boolean_conf(True)

COMPILE_CACHE_DIR = conf("spark.rapids.tpu.compileCache.dir").doc(
    "Directory for the executable store. Empty (default) auto-selects "
    "$JAX_COMPILATION_CACHE_DIR/xc-<backend> when that variable is set, "
    "else .cache/xc-<backend> inside the checkout. Point every server "
    "of a fleet at ONE shared directory: a per-entry file lock makes the "
    "fleet compile each shape once (docs/operations.md restart runbook)."
).string_conf(None)

COMPILE_CACHE_MAX_BYTES = conf("spark.rapids.tpu.compileCache.maxBytes").doc(
    "Disk budget for the executable store; oldest-use entries (mtime LRU "
    "— loads touch their entry) are evicted past it. 0 = unbounded."
).bytes_conf(2 << 30)

COMPILE_CACHE_LOCK_TIMEOUT_S = conf(
    "spark.rapids.tpu.compileCache.lockTimeout"
).doc(
    "Seconds to wait on another process's per-entry compile lock before "
    "giving up the single-flight dedup and compiling anyway "
    "(cache.xla.lockTimeouts). The flock dies with its holder, so a "
    "CRASHED peer never blocks past its own death; this bounds a WEDGED "
    "one. Size it above your slowest expected compile."
).double_conf(120.0)


# ── network serving front-end (serve/) ─────────────────────────────────────

SERVE_HOST = conf("spark.rapids.tpu.serve.host").doc(
    "Interface the Arrow-IPC SQL endpoint binds (serve/server.py). The "
    "default stays loopback-only; bind 0.0.0.0 explicitly to expose the "
    "service."
).string_conf("127.0.0.1")

SERVE_PORT = conf("spark.rapids.tpu.serve.port").doc(
    "TCP port for the serving endpoint; 0 picks an ephemeral port "
    "(reported by TpuServer.start(); what the tests and the benchmark "
    "use)."
).int_conf(8045)

SERVE_TENANTS = conf("spark.rapids.tpu.serve.tenants").doc(
    "Auth spec 'token:tenant:pool,…' mapping each HELLO auth token to a "
    "tenant name and the fair-share scheduler pool its queries are "
    "admitted under (spark.rapids.tpu.scheduler.pools weights apply). "
    "Empty = open access: every client is tenant 'anonymous' in pool "
    "'default'. When set, a HELLO with an unknown token is rejected."
).string_conf(None)

SERVE_MAX_CONNECTIONS = conf("spark.rapids.tpu.serve.maxConnections").doc(
    "Concurrent client connections the server accepts; further connects "
    "are refused at HELLO with a typed error (admission-queue backpressure "
    "for queries is the scheduler's maxQueued, this bounds sockets/threads)."
).int_conf(64)

SERVE_STREAM_BATCH_ROWS = conf("spark.rapids.tpu.serve.streamBatchRows").doc(
    "Maximum rows per streamed result BATCH frame: engine result batches "
    "are re-chunked to this bound so clients see incremental frames (and "
    "mid-stream CANCEL has boundaries to act on) even when a partition "
    "produced one huge batch."
).int_conf(65536)

SERVE_MAX_CONNECTIONS_PER_TENANT = conf(
    "spark.rapids.tpu.serve.maxConnectionsPerTenant"
).doc(
    "Concurrent connections one tenant may hold; further connects from "
    "that tenant are refused at HELLO with a typed error so one tenant "
    "cannot wedge the accept loop for everyone (the global bound is "
    "spark.rapids.tpu.serve.maxConnections). 0 = unlimited."
).int_conf(0)

SERVE_MAX_INFLIGHT_PER_TENANT = conf(
    "spark.rapids.tpu.serve.maxInflightPerTenant"
).doc(
    "Concurrent in-flight (fetching) queries one tenant may run; a FETCH "
    "past the bound answers a typed OVERLOADED error with a retry-after "
    "hint while the connection stays alive. 0 = unlimited."
).int_conf(0)

SERVE_DRAIN_TIMEOUT_S = conf("spark.rapids.tpu.serve.drainTimeout").doc(
    "Seconds server.drain() (and the SIGTERM handler) waits for in-flight "
    "streams to finish before cancelling them with reason 'shutdown'. "
    "Every stream still ends with a typed END or ERROR frame; new "
    "commands during the drain answer a typed ServerDraining error."
).double_conf(30.0)

SERVE_SEND_TIMEOUT_S = conf("spark.rapids.tpu.serve.sendTimeout").doc(
    "Socket send timeout per result frame: a client that stops draining "
    "its socket (slow-loris reads) is treated as disconnected after this "
    "many seconds — its query cancels and the worker thread frees — "
    "instead of pinning a permit on a zero-window send forever. 0 "
    "disables."
).double_conf(60.0)

SERVE_HELLO_TIMEOUT_S = conf("spark.rapids.tpu.serve.helloTimeout").doc(
    "Seconds a fresh connection gets to complete its HELLO before being "
    "dropped (slow-loris connects hold a handler thread, never the "
    "accept loop)."
).double_conf(10.0)

SERVE_WARMUP_STATEMENTS = conf("spark.rapids.tpu.serve.warmupStatements").doc(
    "Semicolon-separated SQL statements the server plans+precompiles in "
    "the background after start(); STATUS reports ready=false until the "
    "warm pool is primed, so a rolling restart can wait for readiness "
    "before shifting traffic. Empty = ready immediately."
).string_conf(None)

SERVE_READY_TIMEOUT_S = conf("spark.rapids.tpu.serve.readyTimeout").doc(
    "Readiness budget the server ADVERTISES to clients (HELLO_OK and "
    "STATUS carry it): Connection.wait_ready() with no explicit timeout "
    "polls this long before giving up. Size it above the server's worst "
    "cold warmup (one q8-class XLA compile is ~90s); warm restarts "
    "against a populated compile cache finish in seconds regardless. "
    "STATUS reports per-warmup-statement progress so a caller can "
    "distinguish 'still compiling' from 'hung'."
).double_conf(600.0)

SERVE_PREPARED_CACHE_ENTRIES = conf(
    "spark.rapids.tpu.serve.preparedCacheEntries"
).doc(
    "Bound of the prepared-plan cache (serve/prepared.py): compiled "
    "physical plans keyed by canonicalized statement + bound parameters + "
    "batch geometry, LRU-evicted past this many entries. A hit skips "
    "parse/plan/compile entirely — the repeated-dashboard fast path."
).int_conf(128)


# ── deterministic fault injection (resilience/faults.py) ───────────────────

FAULTS_ENABLED = conf("spark.rapids.tpu.faults.enabled").doc(
    "Master switch for the deterministic fault-injection harness; all "
    "spark.rapids.tpu.faults.* points are inert unless enabled. Drives "
    "the chaos test suite — never enable in production."
).boolean_conf(False)

FAULTS_SEED = conf("spark.rapids.tpu.faults.seed").doc(
    "Seed for the injection jitter RNG, so a chaos run replays "
    "identically."
).int_conf(0)

FAULTS_DEVICE_OOM_EVERY_N = conf("spark.rapids.tpu.faults.deviceOomEveryN").doc(
    "Raise a synthetic RESOURCE_EXHAUSTED on every Nth compiled-kernel "
    "launch under an OOM-recovery scope (kernels.GuardedJit inside "
    "with_oom_retry / the retry state machine) — each injection "
    "deterministically exercises the spill/split recovery; 0 disables."
).int_conf(0)

FAULTS_OOM_ABOVE_BYTES = conf("spark.rapids.tpu.faults.oomAboveBytes").doc(
    "Raise a synthetic RESOURCE_EXHAUSTED whenever a splittable operator "
    "launches a batch larger than this many bytes — the deterministic "
    "driver for demonstrating recursive split-and-retry; 0 disables."
).bytes_conf(0)

FAULTS_KERNEL_ERROR_EVERY_N = conf("spark.rapids.tpu.faults.kernelErrorEveryN").doc(
    "Raise a synthetic non-OOM XLA error on every Nth splittable-operator "
    "launch (drives the circuit breaker); 0 disables."
).int_conf(0)

FAULTS_COMPILE_FAIL_EVERY_N = conf("spark.rapids.tpu.faults.compileFailEveryN").doc(
    "Fail every Nth first-touch kernel compile with a transient error "
    "(exercises the compile retry path); 0 disables."
).int_conf(0)

FAULTS_SPILL_WRITE_ERROR_EVERY_N = conf(
    "spark.rapids.tpu.faults.spill.writeErrorEveryN"
).doc(
    "Fail every Nth disk-tier spill write with an IO error (the buffer "
    "stays at the host tier); 0 disables."
).int_conf(0)

FAULTS_SPILL_READ_ERROR_EVERY_N = conf(
    "spark.rapids.tpu.faults.spill.readErrorEveryN"
).doc(
    "Fail every Nth disk-tier re-materialization read with an IO error "
    "(surfaces as a catalog SpillError naming the buffer); 0 disables."
).int_conf(0)

FAULTS_TCP_DROP_EVERY_N = conf("spark.rapids.tpu.faults.transport.dropEveryN").doc(
    "Silently drop every Nth outgoing shuffle DATA frame on the TCP "
    "transport (the fetch times out and retries); 0 disables."
).int_conf(0)

FAULTS_TCP_DELAY_EVERY_N = conf("spark.rapids.tpu.faults.transport.delayEveryN").doc(
    "Delay every Nth outgoing shuffle DATA frame by "
    "spark.rapids.tpu.faults.transport.delayMs; 0 disables."
).int_conf(0)

FAULTS_TCP_DELAY_MS = conf("spark.rapids.tpu.faults.transport.delayMs").doc(
    "Injected per-frame delay for the transport delay point."
).double_conf(50.0)

FAULTS_TCP_CORRUPT_EVERY_N = conf(
    "spark.rapids.tpu.faults.transport.corruptEveryN"
).doc(
    "Flip one payload byte in every Nth outgoing shuffle DATA frame "
    "AFTER its checksum is stamped (the receiver's CRC check drops the "
    "frame and the fetch retry recovers); 0 disables."
).int_conf(0)

FAULTS_KERNEL_STALL_EVERY_N = conf(
    "spark.rapids.tpu.faults.kernelStallEveryN"
).doc(
    "Stall every Nth compiled-kernel launch for kernelStallMs before "
    "running it (a wedged-device simulation — no error is raised; the "
    "progress watchdog is what must notice); 0 disables."
).int_conf(0)

FAULTS_KERNEL_STALL_MS = conf("spark.rapids.tpu.faults.kernelStallMs").doc(
    "Injected stall duration for the kernel-stall point."
).double_conf(500.0)

FAULTS_COMPILE_DELAY_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileDelayEveryN"
).doc(
    "Delay every Nth first-touch kernel compile by compileDelayMs "
    "(inside the compile-deadline scope, so "
    "spark.rapids.tpu.compile.deadlineSeconds can cut it); 0 disables."
).int_conf(0)

FAULTS_COMPILE_DELAY_MS = conf("spark.rapids.tpu.faults.compileDelayMs").doc(
    "Injected delay for the compile-delay point."
).double_conf(500.0)

FAULTS_CACHE_TRUNCATE_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileCache.truncateEveryN"
).doc(
    "Truncate every Nth compile-cache entry to half its size right after "
    "it is published (a torn write that survived the rename) — the load "
    "path must quarantine it and rebuild; 0 disables."
).int_conf(0)

FAULTS_CACHE_CORRUPT_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileCache.corruptEveryN"
).doc(
    "Flip one payload byte in every Nth published compile-cache entry "
    "AFTER its CRC is stamped — the payload CRC on load must catch it "
    "(quarantine + cache.xla.corrupt, fresh compile); 0 disables."
).int_conf(0)

FAULTS_CACHE_STALE_VERSION_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileCache.staleVersionEveryN"
).doc(
    "Write every Nth compile-cache entry with a perturbed format "
    "version in its header — the version fence must turn it into a "
    "SILENT miss (no load attempt, no quarantine); 0 disables."
).int_conf(0)

FAULTS_CACHE_CRASH_BEFORE_RENAME_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileCache.crashBeforeRenameEveryN"
).doc(
    "Abandon every Nth compile-cache publish between its temp-file fsync "
    "and the rename (a crash at the worst moment of the atomic-write "
    "protocol) — the orphan must never serve a load and a later boot "
    "sweeps it; 0 disables."
).int_conf(0)

FAULTS_CACHE_LOCK_HOLDER_EVERY_N = conf(
    "spark.rapids.tpu.faults.compileCache.lockHolderEveryN"
).doc(
    "On every Nth compile-cache single-flight acquisition, a simulated "
    "wedged peer grabs the entry's flock first and holds it for "
    "lockHolderHoldMs — past compileCache.lockTimeout the caller must "
    "compile without the dedup instead of hanging; 0 disables."
).int_conf(0)

FAULTS_CACHE_LOCK_HOLDER_HOLD_MS = conf(
    "spark.rapids.tpu.faults.compileCache.lockHolderHoldMs"
).doc(
    "How long the simulated wedged lock holder keeps the entry flock."
).double_conf(500.0)

FAULTS_MAP_OUTPUT_LOSS_EVERY_N = conf(
    "spark.rapids.tpu.faults.shuffle.mapOutputLossEveryN"
).doc(
    "On every Nth managed shuffle-read, drop the shuffle's registered map "
    "outputs AND its catalog-held blocks before the read — the lost-"
    "executor simulation. The lineage recovery layer must rebuild the map "
    "stage from its partition thunks instead of failing the query "
    "(spark.rapids.tpu.recovery.recomputeMapOutputs); 0 disables."
).int_conf(0)

FAULTS_STALL_PARTITION = conf("spark.rapids.tpu.faults.stallPartition").doc(
    "Stall the FIRST attempt of this partition id for stallPartitionSeconds "
    "at task start — the deterministic straggler the speculation layer must "
    "overtake (re-attempts and speculative duplicates never stall, so the "
    "duplicate wins and the stalled loser is cancelled); -1 disables."
).int_conf(-1)

FAULTS_STALL_PARTITION_S = conf(
    "spark.rapids.tpu.faults.stallPartitionSeconds"
).doc(
    "Injected stall duration for the straggler point. The sleep beats the "
    "attempt's cancel token, so a cancelled loser exits within ~20ms."
).double_conf(2.0)


# ── lineage-based partition recovery (resilience/lineage.py) ───────────────

RECOVERY_RECOMPUTE_ENABLED = conf(
    "spark.rapids.tpu.recovery.recomputeMapOutputs"
).doc(
    "Rebuild lost shuffle map outputs from lineage instead of failing the "
    "query: when a managed shuffle read hits an exhausted fetch budget, a "
    "blacklisted peer, or finds its committed map outputs gone (lost "
    "executor), the exchange marks the shuffle released and the partition "
    "task's re-attempt re-runs the map stage under the next generation's "
    "shuffle id. Counted in shuffle.recomputedPartitions."
).boolean_conf(True)

RECOVERY_MAX_MAP_RECOMPUTES = conf(
    "spark.rapids.tpu.recovery.maxMapRecomputes"
).doc(
    "How many map-stage regenerations one exchange may perform per query "
    "before a shuffle-read failure is allowed to propagate (a persistently "
    "failing peer must not recompute forever; spark.task.maxFailures "
    "bounds the per-partition attempts on top)."
).int_conf(3)


# ── straggler speculation (sched/speculation.py) ───────────────────────────

SPECULATION_ENABLED = conf("spark.rapids.tpu.speculation.enabled").doc(
    "Launch a speculative duplicate attempt for partitions that run far "
    "past the measured baseline (spark.speculation analogue). The monitor "
    "watches per-partition runtimes once speculation.quantile of the "
    "query's partitions completed; first commit wins, the loser is "
    "cancelled through its attempt token, and the duplicate's device "
    "share is accounted as one extra scheduler permit (skipped when none "
    "is free). Applies to multi-partition parallel collect()s."
).boolean_conf(False)

SPECULATION_QUANTILE = conf("spark.rapids.tpu.speculation.quantile").doc(
    "Fraction of the query's partitions that must have completed before "
    "stragglers are considered (the baseline sample; "
    "spark.speculation.quantile)."
).double_conf(0.75)

SPECULATION_MULTIPLIER = conf("spark.rapids.tpu.speculation.multiplier").doc(
    "A running partition is speculatable once its elapsed time exceeds "
    "this multiple of the completed partitions' median runtime "
    "(spark.speculation.multiplier)."
).double_conf(1.5)

SPECULATION_MIN_RUNTIME_S = conf(
    "spark.rapids.tpu.speculation.minRuntime"
).doc(
    "Floor (seconds) under the speculation threshold: partitions faster "
    "than this are never speculated regardless of the multiplier — "
    "duplicating sub-100ms tasks only burns permits."
).double_conf(0.25)

SPECULATION_INTERVAL_S = conf("spark.rapids.tpu.speculation.interval").doc(
    "How often (seconds) the speculation monitor scans running partitions "
    "against the baseline (spark.speculation.interval)."
).double_conf(0.05)


# ── serve-fleet failover (serve/client.py dedup bookkeeping) ───────────────

SERVE_FAILOVER_DEDUP_WINDOW = conf(
    "spark.rapids.tpu.serve.failover.dedupWindow"
).doc(
    "How many client-generated dedup keys the server remembers (LRU). A "
    "failover replay arriving with a key this server has already executed "
    "counts serve.dedupReplays and is annotated in the query log — the "
    "at-most-once bookkeeping behind mid-stream client failover."
).int_conf(1024)


# ── common-work sharing (cache/results.py, cache/subplan.py) ───────────────

RESULT_CACHE_ENABLED = conf("spark.rapids.tpu.resultCache.enabled").doc(
    "Serve repeated queries from the bounded semantic result cache: a "
    "completed query's Arrow batches are stored under (plan canonical "
    "key, bound params, conf fingerprint, per-table data version) and an "
    "identical later query streams them back WITHOUT touching scheduler "
    "admission. Invalidation is table-granular — any write path (temp-"
    "view replacement, DataFrameWriter append/overwrite, view drop) "
    "bumps the written table's version and evicts its dependents. Off by "
    "default (kill switch): results are bit-identical by construction, "
    "but a cache hit skips execution-side effects some harnesses assert "
    "on (kernel first-call counters, retry metrics)."
).boolean_conf(False)

RESULT_CACHE_MAX_BYTES = conf("spark.rapids.tpu.resultCache.maxBytes").doc(
    "In-memory budget of the result cache; the same figure again bounds "
    "its disk tier (LRU entries demote to Arrow IPC files in the spill "
    "directory before being dropped). Memory-resident bytes are reserved "
    "against the host spill budget (mem/spill.py), so cached results "
    "compete with spilled device buffers instead of hiding from the "
    "memory ledger."
).bytes_conf(256 * 1024 * 1024)

RESULT_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.resultCache.maxEntries"
).doc(
    "Entry-count bound of the result cache across both tiers (LRU). "
    "Bounds key-map growth for fleets cycling many distinct small "
    "queries under the byte budget."
).int_conf(256)

SUBPLAN_DEDUP_ENABLED = conf("spark.rapids.tpu.subplanDedup.enabled").doc(
    "Single-flight execution of common subtrees across CONCURRENT "
    "in-flight queries: at admission each plan is scanned for subtrees "
    "sharing a canonical key with another in-flight query's, and the "
    "subtree is computed once — the first executor owns it, the rest "
    "consume its materialized batches. Owner failure or cancellation "
    "wakes waiters into independent execution (never cascades). Off by "
    "default (kill switch); entries are concurrent-only and never "
    "outlive the queries pinning them."
).boolean_conf(False)

SUBPLAN_DEDUP_MIN_COST_NS = conf(
    "spark.rapids.tpu.subplanDedup.minCostNs"
).doc(
    "Estimated device cost (nanoseconds, from the calibration table via "
    "sched/estimate.py::estimate_plan_cost_ns) below which a subtree is "
    "not worth sharing — waiter coordination overhead beats recompute "
    "for point lookups."
).int_conf(1_000_000)


# ── live analytics (live/ingest.py, live/maintain.py, serve SUBSCRIBE) ─────

LIVE_ENABLED = conf("spark.rapids.tpu.live.enabled").doc(
    "Master kill switch for the live-analytics subsystem: streaming "
    "append ingestion with a per-table delta log, incremental view "
    "maintenance (pass-through / aggregate / top-N classes, full "
    "re-execution fallback with an explain reason otherwise), and the "
    "serve-side SUBSCRIBE/UPDATE delta-streaming protocol. Off by "
    "default: SUBSCRIBE frames are rejected and session.live raises "
    "until it is set."
).boolean_conf(False)

LIVE_POOL = conf("spark.rapids.tpu.live.pool").doc(
    "Scheduler pool refresh re-executions are admitted under (created at "
    "weight 1 if absent from spark.rapids.tpu.scheduler.pools). A "
    "dedicated pool keeps a dashboard fleet's refresh storm from "
    "starving ad-hoc interactive queries — size it explicitly in the "
    "pools spec when refreshes dominate."
).string_conf("live")

LIVE_DELTA_LOG_MAX_ENTRIES = conf(
    "spark.rapids.tpu.live.deltaLog.maxEntries"
).doc(
    "Per-table bound on retained delta-log entries. A consumer whose "
    "last-seen version has been truncated past detects the gap and "
    "falls back to a full re-execution for that refresh (correct, just "
    "not incremental), so small bounds trade memory for fallbacks."
).int_conf(256)

LIVE_STATE_MAX_BYTES = conf("spark.rapids.tpu.live.state.maxBytes").doc(
    "Host-memory budget for maintained query state (aggregate partials, "
    "top-N candidate sets, accumulated pass-through output), reserved "
    "against the spill catalog's host budget. On reserve failure state "
    "demotes to Arrow IPC files in the spill directory through the "
    "fault-injected spill IO points and is promoted back on next use."
).bytes_conf(128 * 1024 * 1024)

LIVE_SUBSCRIBER_MAX_PENDING = conf(
    "spark.rapids.tpu.live.subscriber.maxPending"
).doc(
    "Per-subscription bound on queued-but-unsent UPDATE epochs for a "
    "slow consumer. On overflow the pending deltas collapse into one "
    "full snapshot at the latest version — the subscriber sees every "
    "version's effect, not every version."
).int_conf(8)


class TpuConf:
    """An immutable-ish view over a key→string dict, with typed access.

    Mirrors ``RapidsConf``'s construction from the Spark conf; here it is
    constructed from a plain dict plus ``SPARK_RAPIDS_*``-style environment
    overrides.
    """

    def __init__(self, settings: Optional[dict[str, Any]] = None):
        self._settings: dict[str, str] = {}
        for k, v in (settings or {}).items():
            self._settings[k] = str(v) if not isinstance(v, bool) else str(v).lower()

    def get(self, key: str, default: T, conv: Callable[[str], T]) -> T:
        raw = self._settings.get(key)
        if raw is None:
            raw = os.environ.get("SRT_CONF_" + key.replace(".", "_").upper())
        if raw is None:
            return default
        return conv(raw)

    def get_raw(self, key: str) -> Optional[str]:
        return self._settings.get(key)

    def set(self, key: str, value: Any) -> "TpuConf":
        new = dict(self._settings)
        new[key] = str(value) if not isinstance(value, bool) else str(value).lower()
        return TpuConf(new)

    def is_enabled(self, entry: ConfEntry[bool]) -> bool:
        return entry.get(self)

    # Rule kill switches (auto-derived keys): default True unless set.
    def rule_enabled(self, conf_key: str, default: bool = True) -> bool:
        raw = self._settings.get(conf_key)
        if raw is None:
            return default
        return raw.strip().lower() in ("true", "1")

    def items(self):
        return self._settings.items()


def registry() -> dict[str, ConfEntry]:
    return dict(_REGISTRY)


def startup_only_keys() -> set:
    """Keys frozen when the session is constructed (topology, backend,
    shims). THE single source of truth for conf scope: docs_gen renders
    configs.md's Scope column from it, and graft-lint's conf-key pass
    flags any re-read of one of these outside the session-init surface
    (docs/static-analysis.md)."""
    return {k for k, e in _REGISTRY.items() if e.startup_only}


def generate_docs() -> str:
    """Markdown doc table — the analogue of RapidsConf.scala's doc generator
    (:1052-1149), so configuration docs cannot drift from the code."""
    lines = [
        "# Configuration",
        "",
        "Name | Description | Default",
        "-----|-------------|--------",
    ]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        lines.append(f"{e.key} | {e.doc} | {e.default}")
    return "\n".join(lines) + "\n"
