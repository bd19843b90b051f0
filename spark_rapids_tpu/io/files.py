"""File scans — the L5 I/O layer.

Reference: GpuParquetScan.scala (1830 LoC: PERFILE/COALESCING/MULTITHREADED
reader strategies), GpuOrcScan.scala, GpuBatchScanExec.scala (CSV). On TPU
there is no device-side Parquet decode (cudf's Table.readParquet has no XLA
analogue), so the architecture keeps the reference's *host-side* half — file
listing, footer/schema handling, multi-file coalescing, background prefetch
threads — and feeds decoded Arrow batches to the H2D transition. pyarrow is
the decode engine (the host-buffer role of ParquetCopyBlocksRunner).

Reader strategies (spark.rapids.sql.format.parquet.reader.type analogue):
* PERFILE: one partition per file, streamed batch reads
* COALESCING: small files grouped into shared partitions by size until
  the reader byte target (MultiFileParquetPartitionReader,
  GpuParquetScan.scala:939 — there the stitch is row-group chunks into one
  host buffer; here it is files into one partition stream)
* MULTITHREADED: a background thread pool prefetches file batches (the cloud
  reader, GpuParquetScan.scala:1358)

Also here:
* Hive-style partition discovery + per-file constant-column splicing
  (ColumnarPartitionReaderWithPartitionValues analogue).
* Parquet row-group pruning from footer min/max statistics for pushed-down
  predicates (GpuParquetFileFilterHandler, GpuParquetScan.scala:253), plus
  whole-file pruning on partition values. The scan exec counts skipped row
  groups in ``pruned_row_groups`` so tests can prove pruning happened.
"""
from __future__ import annotations

import glob as _glob
import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List, Optional, Tuple

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.orc as paorc
import pyarrow.parquet as papq

from .. import config as cfg
from ..config import TpuConf
from ..exec import task
from ..plan.physical import Exec, ExecContext, PartitionSet
from ..types import DOUBLE, LONG, STRING, Schema, StructField


_EXT = {"parquet": ".parquet", "orc": ".orc", "csv": ".csv"}


def expand_paths(paths, fmt: str) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.startswith(("_", ".")):
                        continue
                    out.append(os.path.join(root, f))
        elif any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        else:
            out.append(p)
    if not out:
        raise FileNotFoundError(f"no {fmt} files found in {paths}")
    return out


# ── Hive-style partition discovery ─────────────────────────────────────────

# Spark's PartitioningUtils.charToEscape set (escapePathName/unescapePathName)
_ESCAPE_CHARS = set('"#%\'*/:=?\\\x7f{[]^') | {chr(c) for c in range(0x20)}


def escape_path_name(s: str) -> str:
    return "".join(
        f"%{ord(c):02X}" if c in _ESCAPE_CHARS else c for c in s
    )


def unescape_path_name(s: str) -> str:
    out = []
    i = 0
    while i < len(s):
        if s[i] == "%" and i + 3 <= len(s):
            try:
                out.append(chr(int(s[i + 1 : i + 3], 16)))
                i += 3
                continue
            except ValueError:
                pass
        out.append(s[i])
        i += 1
    return "".join(out)


def _partition_segments(path: str) -> List[Tuple[str, str]]:
    segs = []
    for part in path.split(os.sep)[:-1]:  # exclude the file name
        if "=" in part and not part.startswith("."):
            k, _, v = part.partition("=")
            if k:
                segs.append((unescape_path_name(k), unescape_path_name(v)))
    return segs


def discover_partitions(files: List[str]):
    """Infer Hive-layout partition columns from ``key=value`` directory
    segments. Returns (partition Schema, per-file value dicts); empty schema
    when the files carry no partition segments (Spark's
    PartitioningAwareFileIndex inference, narrowed to long/double/string)."""
    per_file = [dict(_partition_segments(f)) for f in files]
    keys: List[str] = []
    for d in per_file:
        for k in d:
            if k not in keys:
                keys.append(k)
    if not keys or any(set(d) != set(keys) for d in per_file):
        return Schema([]), [dict() for _ in files]

    def infer(vals):
        def is_long(s):
            try:
                int(s)
                return True
            except ValueError:
                return False

        def is_double(s):
            try:
                float(s)
                return True
            except ValueError:
                return False

        vals = [v for v in vals if v != _HIVE_NULL]
        if vals and all(is_long(v) for v in vals):
            return LONG
        if vals and all(is_double(v) for v in vals):
            return DOUBLE
        return STRING

    fields = []
    for k in keys:
        vals = [d[k] for d in per_file]
        nullable = any(d[k] == _HIVE_NULL for d in per_file)
        fields.append(StructField(k, infer(vals), nullable))
    return Schema(fields), per_file


_HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"


def _typed_partition_value(raw: str, dt):
    if raw == _HIVE_NULL:
        return None
    if dt == LONG:
        return int(raw)
    if dt == DOUBLE:
        return float(raw)
    return raw


def splice_partition_values(
    rb: pa.RecordBatch, part_schema: Schema, values: dict
) -> pa.RecordBatch:
    """Append constant partition-value columns to a data batch
    (ColumnarPartitionReaderWithPartitionValues.scala analogue)."""
    if not len(part_schema.fields):
        return rb
    arrays = list(rb.columns)
    names = list(rb.schema.names)
    for f in part_schema:
        v = _typed_partition_value(values[f.name], f.data_type)
        arrays.append(
            pa.array([v] * rb.num_rows, type=f.data_type.to_arrow())
        )
        names.append(f.name)
    return pa.RecordBatch.from_arrays(arrays, names=names)


def infer_schema(files: List[str], fmt: str, options: dict) -> Schema:
    if fmt == "parquet":
        base = Schema.from_arrow(papq.read_schema(files[0]))
    elif fmt == "orc":
        base = Schema.from_arrow(paorc.ORCFile(files[0]).schema)
    elif fmt == "csv":
        table = _read_csv(files[0], options)
        base = Schema.from_arrow(table.schema)
    else:
        raise ValueError(fmt)
    part_schema, _ = discover_partitions(files)
    extra = [f for f in part_schema if f.name not in base.names]
    return Schema(list(base.fields) + extra)


def _read_csv(path: str, options: dict) -> pa.Table:
    header = str(options.get("header", "false")).lower() in ("true", "1")
    sep = options.get("sep", options.get("delimiter", ","))
    read_opts = pacsv.ReadOptions(autogenerate_column_names=not header)
    parse_opts = pacsv.ParseOptions(delimiter=sep)
    # Spark's CSV defaults: nullValue is the empty string (and ONLY it —
    # "NaN" must parse as a float NaN, not null), empty strings read as
    # null; the default routes through the version shim, users override
    # with the nullValue option
    null_opts = dict(
        null_values=[options.get("nullValue", "")], strings_can_be_null=True
    )
    conv = pacsv.ConvertOptions(**null_opts)
    if "schema" in options:
        schema: Schema = options["schema"]
        conv = pacsv.ConvertOptions(
            **null_opts,
            column_types=dict(
                zip(schema.names, (f.data_type.to_arrow() for f in schema))
            ),
        )
        if not header:
            read_opts = pacsv.ReadOptions(column_names=schema.names)
    return pacsv.read_csv(path, read_options=read_opts, parse_options=parse_opts, convert_options=conv)


# ── predicate pushdown: row-group pruning ──────────────────────────────────


def _stat_allows(op: str, value, mn, mx) -> bool:
    """Could any row in [mn, mx] satisfy ``col <op> value``? Conservative:
    True when stats are missing, and for NaN operands (the engine orders
    NaN greatest / NaN == NaN, which min/max stats cannot witness)."""
    if mn is None or mx is None:
        return True
    if isinstance(value, float) and math.isnan(value):
        return True
    try:
        if op == ">":
            return mx > value
        if op == ">=":
            return mx >= value
        if op == "<":
            return mn < value
        if op == "<=":
            return mn <= value
        if op == "=":
            return mn <= value <= mx
    except TypeError:
        return True
    return True


def row_group_survives(md, rg_index: int, predicates) -> bool:
    """Evaluate pushed-down conjuncts against one row group's footer stats
    (GpuParquetFileFilterHandler analogue over pyarrow metadata)."""
    rg = md.row_group(rg_index)
    cols = {rg.column(i).path_in_schema: rg.column(i) for i in range(rg.num_columns)}
    for name, op, value in predicates:
        c = cols.get(name)
        if c is None or c.statistics is None or not c.statistics.has_min_max:
            continue
        if c.physical_type in ("FLOAT", "DOUBLE"):
            # float min/max stats are NaN-blind (a NaN row can hide in any
            # group) and the engine treats NaN as the greatest value — never
            # prune float columns on stats
            continue
        st = c.statistics
        if not _stat_allows(op, value, st.min, st.max):
            return False
    return True


def partition_value_survives(values: dict, part_schema: Schema, predicates) -> bool:
    """Whole-file pruning on Hive partition values."""
    types = {f.name: f.data_type for f in part_schema}
    for name, op, value in predicates:
        if name not in values:
            continue
        v = _typed_partition_value(values[name], types[name])
        if not _stat_allows(op, value, v, v):
            return False
    return True


def _iter_file(
    path: str,
    fmt: str,
    schema: Schema,
    options: dict,
    batch_rows: int,
    part_schema: Optional[Schema] = None,
    part_values: Optional[dict] = None,
    predicates=(),
    pruned_counter=None,
) -> Iterator[pa.RecordBatch]:
    target = schema.to_arrow()
    part_schema = part_schema or Schema([])
    part_names = set(part_schema.names)

    def out(rb):
        return _conform(
            splice_partition_values(rb, part_schema, part_values or {}), target
        )

    if fmt == "parquet":
        pf = papq.ParquetFile(path)
        want = [
            n
            for n in schema.names
            if n in pf.schema_arrow.names and n not in part_names
        ]
        md = pf.metadata
        groups = list(range(md.num_row_groups))
        if predicates:
            survivors = [g for g in groups if row_group_survives(md, g, predicates)]
            if pruned_counter is not None and len(survivors) < len(groups):
                pruned_counter(len(groups) - len(survivors))
            groups = survivors
        # pruned schema ⇒ pruned decode (pushed-down column projection)
        for rb in pf.iter_batches(
            batch_size=batch_rows, columns=want, row_groups=groups
        ):
            yield out(rb)
        pf.close()
    elif fmt == "orc":
        f = paorc.ORCFile(path)
        want = [
            n for n in schema.names if n in f.schema.names and n not in part_names
        ]
        if predicates and f.nstripes > 1:
            # stripe-granularity read with statistics gating
            # (GpuOrcScan.scala:853 + OrcFilters.scala analogue; pyarrow
            # reads per stripe, our orc_meta parses the stats footer)
            from .orc_meta import read_stripe_stats, stripe_survives

            stats = read_stripe_stats(path)
            if stats is not None:
                keep = [
                    i
                    for i in range(f.nstripes)
                    if stripe_survives(stats, i, predicates)
                ]
                if pruned_counter is not None and len(keep) < f.nstripes:
                    pruned_counter(f.nstripes - len(keep))
                for i in keep:
                    rb_s = f.read_stripe(i, columns=want)
                    for off in range(0, rb_s.num_rows, batch_rows):
                        yield out(rb_s.slice(off, batch_rows))
                return
        table = f.read(columns=want)
        for rb in table.to_batches(max_chunksize=batch_rows):
            yield out(rb)
    elif fmt == "csv":
        for rb in _read_csv(path, options).to_batches(max_chunksize=batch_rows):
            yield out(rb)
    else:
        raise ValueError(fmt)


def _conform(rb: pa.RecordBatch, target: pa.Schema) -> pa.RecordBatch:
    if rb.schema == target:
        return rb
    cols = []
    for i, f in enumerate(target):
        arr = rb.column(rb.schema.get_field_index(f.name))
        if arr.type != f.type:
            arr = arr.cast(f.type)
        cols.append(arr)
    return pa.RecordBatch.from_arrays(cols, schema=target)


class CpuFileScanExec(Exec):
    """File source scan (GpuFileSourceScanExec/GpuBatchScanExec analogue)."""

    def __init__(
        self,
        files: List[str],
        fmt: str,
        schema: Schema,
        options: dict,
        conf: TpuConf,
    ):
        super().__init__([])
        self.files = files
        self.fmt = fmt
        self._schema = schema
        self.options = options
        self.batch_rows = cfg.MAX_READER_BATCH_SIZE_ROWS.get(conf)
        self.coalesce_bytes = cfg.MAX_READER_BATCH_SIZE_BYTES.get(conf)
        conf_key = (
            cfg.ORC_READER_TYPE if fmt == "orc" else cfg.PARQUET_READER_TYPE
        )
        rt = options.get("readerType", conf_key.get(conf)).upper()
        if rt == "AUTO":
            # reference default: COALESCING locally, MULTITHREADED when any
            # path lives on a cloud scheme (RapidsConf.scala:651)
            schemes = {
                s.strip().lower()
                for s in cfg.CLOUD_SCHEMES.get(conf).split(",")
                if s.strip()
            }
            # URI schemes are case-insensitive (RFC 3986)
            is_cloud = any(
                "://" in f and f.split("://", 1)[0].lower() in schemes
                for f in files
            )
            rt = "MULTITHREADED" if is_cloud else "COALESCING"
        self.reader_type = rt
        self.num_threads = cfg.MULTITHREADED_READ_NUM_THREADS.get(conf)
        # pushed-down conjuncts (name, op, literal) — set by the planner
        self.predicates: list = list(options.get("__predicates", ()))
        self.part_schema, self._part_values = discover_partitions(files)
        self.bucket_spec = options.get("__bucket_spec")
        self.pruned_row_groups = 0
        self.pruned_files = 0
        self.pruned_buckets = 0
        self._prune_lock = threading.Lock()

    @property
    def output(self) -> Schema:
        return self._schema

    def _count_pruned(self, n: int):
        with self._prune_lock:
            self.pruned_row_groups += n

    def _surviving_files(self):
        """(path, partition values) pairs after partition-value and bucket
        pruning (bucket pruning: GpuFileSourceScanExec.scala:148-149 — when
        every bucket column carries an equality conjunct, matching rows can
        only live in the literals' bucket file)."""
        target = None
        if self.bucket_spec and self.predicates:
            from .bucketing import parse_bucket_id, target_bucket

            target = target_bucket(
                self.bucket_spec, self.predicates, self._schema
            )
        out = []
        for path, vals in zip(self.files, self._part_values):
            if self.predicates and not partition_value_survives(
                vals, self.part_schema, self.predicates
            ):
                self.pruned_files += 1
                continue
            if target is not None:
                b = parse_bucket_id(os.path.basename(path))
                if b is not None and b != target:
                    self.pruned_files += 1
                    self.pruned_buckets += 1
                    continue
            out.append((path, vals))
        return out

    def _file_iter(self, path: str, vals: dict):
        task.set_input_file(path)  # InputFileBlockHolder analogue
        yield from _iter_file(
            path,
            self.fmt,
            self._schema,
            self.options,
            self.batch_rows,
            self.part_schema,
            vals,
            self.predicates if self.fmt in ("parquet", "orc") else (),
            self._count_pruned,
        )

    def execute(self, ctx: ExecContext) -> PartitionSet:
        pairs = self._surviving_files()
        if self.reader_type == "MULTITHREADED":
            return self._execute_multithreaded(pairs)
        if self.reader_type == "COALESCING":
            return self._execute_coalescing(pairs)
        parts = []
        for path, vals in pairs:
            def make(path=path, vals=vals):
                return self._file_iter(path, vals)

            parts.append(make)
        if not parts:
            parts = [lambda: iter(())]
        return PartitionSet(parts)

    @property
    def num_partitions(self) -> int:
        """Upper bound on the partitions ``execute`` produces, counted
        before pruning (pruning only removes files, and counts them). The
        planner's merge-exchange decisions hang on one-vs-many
        (plan/planner.py _num_partitions_hint)."""
        pairs = list(zip(self.files, self._part_values))
        if self.reader_type == "COALESCING":
            return max(1, len(self._coalesced_groups(pairs)))
        return max(1, len(pairs))

    def _coalesced_groups(self, pairs) -> List[List[tuple]]:
        """Small files grouped by on-disk size into shared partitions until
        the reader byte target (MultiFileParquetPartitionReader's stitching,
        at file granularity)."""
        groups: List[List[tuple]] = []
        cur: List[tuple] = []
        cur_bytes = 0
        for path, vals in pairs:
            try:
                sz = os.path.getsize(path)
            except OSError:
                sz = self.coalesce_bytes
            if cur and cur_bytes + sz > self.coalesce_bytes:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append((path, vals))
            cur_bytes += sz
        if cur:
            groups.append(cur)
        return groups

    def _execute_coalescing(self, pairs) -> PartitionSet:
        groups = self._coalesced_groups(pairs)

        def make(group):
            def it():
                for path, vals in group:
                    yield from self._file_iter(path, vals)

            return it()

        parts = [lambda g=g: make(g) for g in groups]
        if not parts:
            parts = [lambda: iter(())]
        return PartitionSet(parts)

    def _execute_multithreaded(self, pairs) -> PartitionSet:
        """Background prefetch pool (MultiFileCloudParquetPartitionReader)."""
        pool = ThreadPoolExecutor(max_workers=self.num_threads)

        def make(path, vals):
            def thunk():
                fut = pool.submit(lambda: list(self._file_iter(path, vals)))

                def it():
                    task.set_input_file(path)
                    for rb in fut.result():
                        yield rb

                return it()

            return thunk

        parts = [make(p, v) for p, v in pairs]
        if not parts:
            parts = [lambda: iter(())]
        return PartitionSet(parts)

    def node_string(self):
        pred = f" pushed={self.predicates}" if self.predicates else ""
        return f"CpuFileScan {self.fmt} [{len(self.files)} files]{pred}"
