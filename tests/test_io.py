"""I/O layer tests — scans (3 formats), the plan-node write path, dynamic
partitioning, predicate pushdown / row-group pruning, COALESCING and
MULTITHREADED readers. Reference suites: ParquetScanSuite, OrcScanSuite,
CsvScanSuite, ParquetWriterSuite, and GpuParquetScan.scala:253,939,1358."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu.functions import col, sum as sum_
from spark_rapids_tpu.types import DOUBLE, INT, LONG, STRING

from data_gen import gen_table
from harness import assert_cpu_and_tpu_equal, cpu_session, tpu_session


def _data(n=500, seed=0):
    return gen_table([("x", LONG), ("y", DOUBLE), ("s", STRING)], n, seed=seed)


def _find_scan(plan):
    from spark_rapids_tpu.io.files import CpuFileScanExec

    if isinstance(plan, CpuFileScanExec):
        return plan
    for c in plan.children:
        f = _find_scan(c)
        if f is not None:
            return f
    return None


# ── write → read round trips ───────────────────────────────────────────────
@pytest.mark.parametrize("fmt", ["parquet", "orc", "csv"])
def test_write_read_round_trip(fmt, tmp_path):
    t = _data(300, seed=1)
    path = str(tmp_path / f"out_{fmt}")
    s = cpu_session()
    df = s.create_dataframe(t, num_partitions=3)
    kw = {}
    w = df.write.mode("overwrite")
    if fmt == "csv":
        w = w.option("header", "true")
    getattr(w, fmt)(path)
    # one part file per input partition — no driver-side funnel
    files = [
        f for f in os.listdir(path) if f.startswith("part-") and not f.startswith("_")
    ]
    assert len(files) == 3, files
    assert os.path.exists(os.path.join(path, "_SUCCESS"))

    def build(sess):
        r = sess.read
        if fmt == "csv":
            r = r.option("header", "true")
        df2 = getattr(r, fmt)(path)
        return df2.select(col("x"), col("y"), col("s"))

    assert_cpu_and_tpu_equal(build)

    # contents match the source table
    def canon(rows):
        import math

        def one(v):
            if isinstance(v, float) and math.isnan(v):
                return "nan"
            if fmt == "csv" and v == "":
                return None  # CSV can't distinguish empty from null (Spark
                # reads the default nullValue "" as null too)
            return v

        key = lambda r: tuple((v is None, str(v)) for v in r)
        return sorted((tuple(one(v) for v in r) for r in rows), key=key)

    got = canon(build(cpu_session()).collect())
    want = canon(
        zip(
            t.column("x").to_pylist(),
            t.column("y").to_pylist(),
            t.column("s").to_pylist(),
        )
    )
    assert got == want


def test_partitioned_write_and_read(tmp_path):
    rng = np.random.default_rng(3)
    t = pa.table(
        {
            "k": rng.integers(0, 4, 200),
            "x": rng.integers(-100, 100, 200),
            "s": [f"s{i % 7}" for i in range(200)],
        }
    )
    path = str(tmp_path / "pt")
    s = cpu_session()
    s.create_dataframe(t, num_partitions=2).write.mode("overwrite").partition_by(
        "k"
    ).parquet(path)
    dirs = sorted(d for d in os.listdir(path) if d.startswith("k="))
    assert dirs == ["k=0", "k=1", "k=2", "k=3"], dirs

    # read back: partition values are spliced from the directory names
    def build(sess):
        return sess.read.parquet(path).select(col("x"), col("s"), col("k"))

    assert_cpu_and_tpu_equal(build)
    rows = sorted(build(cpu_session()).collect())
    want = sorted(
        zip(t.column("x").to_pylist(), t.column("s").to_pylist(), t.column("k").to_pylist())
    )
    assert rows == want


def test_write_mode_error_raises(tmp_path):
    t = _data(20, seed=4)
    path = str(tmp_path / "dup")
    s = cpu_session()
    s.create_dataframe(t).write.parquet(path)
    with pytest.raises(FileExistsError):
        s.create_dataframe(t).write.parquet(path)
    s.create_dataframe(t).write.mode("overwrite").parquet(path)  # no raise


def test_overwrite_path_being_read_raises(tmp_path):
    """Spark: 'Cannot overwrite a path that is also being read from' — the
    source files must not be rmtree'd before the scan executes."""
    t = _data(20, seed=6)
    path = str(tmp_path / "self")
    s = cpu_session()
    s.create_dataframe(t).write.parquet(path)
    df = s.read.parquet(path)
    with pytest.raises(ValueError, match="also being read"):
        df.write.mode("overwrite").parquet(path)
    # the data survived the refused overwrite
    assert len(s.read.parquet(path).collect()) == 20


def test_write_stats_rows(tmp_path):
    t = _data(100, seed=5)
    path = str(tmp_path / "stats")
    s = cpu_session()
    stats = s.create_dataframe(t, num_partitions=2).write.mode("overwrite").parquet(path)
    assert stats.column("num_rows").to_pylist() and sum(
        stats.column("num_rows").to_pylist()
    ) == 100


# ── predicate pushdown / pruning ───────────────────────────────────────────
def test_row_group_pruning_skips_groups(tmp_path):
    n = 1000
    t = pa.table({"x": pa.array(np.arange(n)), "y": pa.array(np.arange(n) * 0.5)})
    f = str(tmp_path / "rg.parquet")
    papq.write_table(t, f, row_group_size=100)  # 10 row groups, sorted x

    s = tpu_session()
    df = s.read.parquet(f).filter(col("x") >= 900).agg(sum_(col("y")).alias("sy"))
    rows = df.collect()
    scan = _find_scan(s._last_plan)
    assert scan is not None
    assert scan.pruned_row_groups == 9, scan.pruned_row_groups
    assert rows == [(sum(i * 0.5 for i in range(900, 1000)),)]

    # differential: pruning must not change results
    def build(sess):
        return sess.read.parquet(f).filter(col("x") >= 900).select(col("y"))

    assert_cpu_and_tpu_equal(build)


def test_orc_stripe_pruning_skips_stripes(tmp_path):
    """Stripe-granularity ORC reads with statistics gating — the parquet
    row-group path's analogue (GpuOrcScan.scala:853 + OrcFilters.scala);
    stats come from our own footer parser (io/orc_meta.py) since pyarrow
    exposes stripe reads but not stripe statistics."""
    import pyarrow.orc as paorc

    n = 100_000
    t = pa.table(
        {"x": pa.array(np.arange(n)), "y": pa.array(np.arange(n) * 0.5)}
    )
    f = str(tmp_path / "st.orc")
    paorc.write_table(t, f, stripe_size=64 * 1024)
    nstripes = paorc.ORCFile(f).nstripes
    assert nstripes > 4  # multi-stripe premise

    s = tpu_session()
    df = s.read.orc(f).filter(col("x") >= n - 50).agg(sum_(col("y")).alias("sy"))
    rows = df.collect()
    scan = _find_scan(s._last_plan)
    assert scan is not None and scan.pruned_row_groups >= nstripes - 2, (
        scan.pruned_row_groups,
        nstripes,
    )
    assert rows == [(sum(i * 0.5 for i in range(n - 50, n)),)]

    # differential: pruning must not change results
    def build(sess):
        return sess.read.orc(f).filter(col("x") >= n - 50).select(col("y"))

    assert_cpu_and_tpu_equal(build)


def test_orc_stripe_pruning_string_stats(tmp_path):
    import pyarrow.orc as paorc

    n = 50_000
    t = pa.table(
        {
            "s": pa.array([f"k{i // 1000:03d}" for i in range(n)]),
            "v": pa.array(np.arange(n)),
        }
    )
    f = str(tmp_path / "sts.orc")
    paorc.write_table(t, f, stripe_size=64 * 1024)
    assert paorc.ORCFile(f).nstripes > 2

    def build(sess):
        return sess.read.orc(f).filter(col("s") == "k004").select(col("v"))

    assert_cpu_and_tpu_equal(build)
    s = tpu_session()
    rows = build(s).collect()
    assert len(rows) == 1000
    scan = _find_scan(s._last_plan)
    assert scan.pruned_row_groups > 0


def test_partition_value_file_pruning(tmp_path):
    t = pa.table({"k": [0] * 10 + [1] * 10 + [2] * 10, "x": list(range(30))})
    path = str(tmp_path / "pv")
    s = cpu_session()
    s.create_dataframe(t).write.mode("overwrite").partition_by("k").parquet(path)

    s2 = tpu_session()
    df = s2.read.parquet(path).filter(col("k") == 1).select(col("x"))
    rows = sorted(df.collect())
    scan = _find_scan(s2._last_plan)
    assert scan.pruned_files == 2, scan.pruned_files
    assert rows == [(i,) for i in range(10, 20)]


# ── reader strategies ──────────────────────────────────────────────────────
def test_coalescing_reader_groups_small_files(tmp_path):
    t = _data(400, seed=6)
    path = str(tmp_path / "many")
    s = cpu_session()
    s.create_dataframe(t, num_partitions=8).write.mode("overwrite").parquet(path)

    def build(sess):
        return (
            sess.read.option("readerType", "COALESCING")
            .parquet(path)
            .select(col("x"), col("y"))
        )

    assert_cpu_and_tpu_equal(build)
    # with a byte target far above the file sizes, all files share one task
    s3 = cpu_session()
    df = build(s3)
    plan = __import__(
        "spark_rapids_tpu.plan.planner", fromlist=["plan_physical"]
    ).plan_physical(df._plan, s3.conf)
    scan = _find_scan(plan)
    parts = scan.execute(None)
    assert len(parts.parts) == 1, len(parts.parts)


def test_multithreaded_reader(tmp_path):
    t = _data(300, seed=7)
    path = str(tmp_path / "mt")
    s = cpu_session()
    s.create_dataframe(t, num_partitions=4).write.mode("overwrite").parquet(path)

    def build(sess):
        return (
            sess.read.option("readerType", "MULTITHREADED")
            .parquet(path)
            .select(col("x"), col("y"), col("s"))
        )

    assert_cpu_and_tpu_equal(build)


@pytest.mark.parametrize("reader", ["PERFILE", "MULTITHREADED", "COALESCING"])
def test_aggregate_over_multi_partition_file_scan(reader, tmp_path):
    """A reader that yields one partition per file must get the merge
    exchange: the planner once took every file scan for ONE partition and
    aggregated each file on its own (eight partial counts as the answer)."""
    from spark_rapids_tpu.functions import count

    t = _data(400, seed=8)
    path = str(tmp_path / "agg")
    cpu_session().create_dataframe(t, num_partitions=4).write.mode(
        "overwrite"
    ).parquet(path)
    for sess in (cpu_session(), tpu_session()):
        got = (
            sess.read.option("readerType", reader)
            .parquet(path)
            .agg(count("*").alias("n"))
            .collect()
        )
        assert got == [(400,)], (reader, got)


# ── format specifics ───────────────────────────────────────────────────────
def test_csv_schema_option(tmp_path):
    from spark_rapids_tpu.types import Schema, StructField

    p = tmp_path / "x.csv"
    p.write_text("1,1.5,a\n2,2.5,b\n")
    schema = Schema(
        [
            StructField("a", LONG, True),
            StructField("b", DOUBLE, True),
            StructField("c", STRING, True),
        ]
    )
    s = cpu_session()
    rows = s.read.option("schema", schema).csv(str(p)).collect()
    assert rows == [(1, 1.5, "a"), (2, 2.5, "b")]


def test_orc_column_pruning_reads_subset(tmp_path):
    t = _data(100, seed=8)
    path = str(tmp_path / "o")
    cpu_session().create_dataframe(t).write.mode("overwrite").orc(path)

    def build(sess):
        return sess.read.orc(path).select(col("x"))

    assert_cpu_and_tpu_equal(build)


def test_partition_values_escaping_and_nan(tmp_path):
    """Special characters and NaN in partition values must round-trip
    (Spark's escapePathName/unescapePathName; r2 review findings)."""
    t = pa.table(
        {
            "k": pa.array(["a/b", "x=y", "plain", None]),
            "v": pa.array([1, 2, 3, 4]),
        }
    )
    path = str(tmp_path / "esc")
    s = cpu_session()
    s.create_dataframe(t).write.mode("overwrite").partition_by("k").parquet(path)
    rows = sorted(
        cpu_session().read.parquet(path).select(col("k"), col("v")).collect(),
        key=lambda r: r[1],
    )
    assert rows == [("a/b", 1), ("x=y", 2), ("plain", 3), (None, 4)]

    t2 = pa.table(
        {"k": pa.array([1.5, float("nan"), float("nan"), None]), "v": [1, 2, 3, 4]}
    )
    path2 = str(tmp_path / "nanp")
    s.create_dataframe(t2).write.mode("overwrite").partition_by("k").parquet(path2)
    got = cpu_session().read.parquet(path2).select(col("v")).collect()
    assert sorted(v for (v,) in got) == [1, 2, 3, 4]  # no NaN rows dropped


def test_no_pruning_on_float_columns(tmp_path):
    import pyarrow.parquet as papq2

    t = pa.table(
        {"x": pa.array([1.0, float("nan"), 2.0] * 10, type=pa.float64())}
    )
    f = str(tmp_path / "f.parquet")
    papq2.write_table(t, f, row_group_size=10)
    s = tpu_session()
    rows = s.read.parquet(f).filter(col("x") > 100.0).collect()
    # NaN is greatest: every NaN row matches despite finite stats
    assert len(rows) == 10
    scan = _find_scan(s._last_plan)
    assert scan.pruned_row_groups == 0


def test_reader_type_auto_selection(tmp_path):
    """AUTO (the default, like the reference): COALESCING for local paths,
    MULTITHREADED when a path scheme is in spark.rapids.cloudSchemes."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io.files import CpuFileScanExec
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.types import Schema, StructField, LONG

    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"x": [1, 2, 3]}), p)
    sch = Schema([StructField("x", LONG, True)])
    conf = TpuConf({})
    local = CpuFileScanExec([p], "parquet", sch, {}, conf)
    assert local.reader_type == "COALESCING"
    cloud = CpuFileScanExec(
        ["s3a://bucket/t.parquet"], "parquet", sch, {}, conf
    )
    assert cloud.reader_type == "MULTITHREADED"
    pinned = CpuFileScanExec(
        [p], "parquet", sch, {"readerType": "PERFILE"}, conf
    )
    assert pinned.reader_type == "PERFILE"


def test_alluxio_path_replacement(tmp_path):
    """spark.rapids.alluxio.pathsToReplace rewrites read-path prefixes
    before listing (RapidsConf.scala:929)."""
    import pyarrow.parquet as pq

    real = tmp_path / "mount"
    real.mkdir()
    pq.write_table(pa.table({"x": [1, 2, 3]}), str(real / "t.parquet"))
    s = tpu_session(
        {
            "spark.rapids.alluxio.pathsToReplace": f"s3://my-bucket->{real}",
        }
    )
    rows = sorted(s.read.parquet("s3://my-bucket/t.parquet").collect())
    assert rows == [(1,), (2,), (3,)]


# ── bucketed layout (GpuFileSourceScanExec.scala:148-149 analogue) ─────────
def test_bucketed_write_read_prunes(tmp_path):
    """bucketBy round trip: per-bucket files, sidecar spec, and whole-file
    bucket pruning under an equality filter — with a differential check
    against the unbucketed layout."""
    import glob

    t = pa.table({
        "k": pa.array(list(range(200)) * 2, type=pa.int64()),
        "s": pa.array([f"s{i % 37}" for i in range(400)]),
        "x": pa.array([float(i) for i in range(400)]),
    })
    path = str(tmp_path / "bk")
    flat = str(tmp_path / "flat")
    s = cpu_session()
    s.create_dataframe(t).write.mode("overwrite").bucket_by(8, "k").parquet(path)
    s.create_dataframe(t).write.mode("overwrite").parquet(flat)

    names = [os.path.basename(f) for f in glob.glob(os.path.join(path, "*.parquet"))]
    from spark_rapids_tpu.io.bucketing import parse_bucket_id, read_spec

    assert read_spec(path) == {"num_buckets": 8, "cols": ["k"]}
    buckets = {parse_bucket_id(n) for n in names}
    assert None not in buckets and len(buckets) > 1, names

    s2 = tpu_session()
    df = s2.read.parquet(path).filter(col("k") == 17).select(col("s"), col("x"))
    rows = sorted(df.collect())
    scan = _find_scan(s2._last_plan)  # before the flat read replaces it
    ref = sorted(
        s2.read.parquet(flat).filter(col("k") == 17).select(col("s"), col("x")).collect()
    )
    assert rows == ref and len(rows) == 2
    assert scan.bucket_spec is not None
    assert scan.pruned_buckets > 0, "no bucket files pruned"


def test_append_bucket_spec_mismatch_rejected(tmp_path):
    """Appends must agree with the existing bucket layout: a mismatched
    bucketBy (or bucketBy over unbucketed data, or unbucketed append over
    a bucketed table) would silently corrupt the sidecar spec and make
    bucket pruning return wrong results — the writer raises instead."""
    import pytest as _pytest

    t = pa.table({
        "k": pa.array(list(range(50)), type=pa.int64()),
        "x": pa.array([float(i) for i in range(50)]),
    })
    s = cpu_session()
    path = str(tmp_path / "bk")
    s.create_dataframe(t).write.mode("overwrite").bucket_by(4, "k").parquet(path)

    # different bucket count
    with _pytest.raises(ValueError, match="bucket spec mismatch"):
        s.create_dataframe(t).write.mode("append").bucket_by(8, "k").parquet(path)
    # different bucket columns
    with _pytest.raises(ValueError, match="bucket spec mismatch"):
        s.create_dataframe(t).write.mode("append").bucket_by(4, "x").parquet(path)
    # unbucketed append over a bucketed table
    with _pytest.raises(ValueError, match="unbucketed data to bucketed"):
        s.create_dataframe(t).write.mode("append").parquet(path)
    # bucketBy append over unbucketed data
    flat = str(tmp_path / "flat")
    s.create_dataframe(t).write.mode("overwrite").parquet(flat)
    with _pytest.raises(ValueError, match="without a bucket spec"):
        s.create_dataframe(t).write.mode("append").bucket_by(4, "k").parquet(flat)

    # the spec survived every rejected attempt
    from spark_rapids_tpu.io.bucketing import read_spec

    assert read_spec(path) == {"num_buckets": 4, "cols": ["k"]}

    # a MATCHING bucketed append is accepted and stays readable
    s.create_dataframe(t).write.mode("append").bucket_by(4, "k").parquet(path)
    s2 = tpu_session()
    rows = s2.read.parquet(path).filter(col("k") == 7).collect()
    assert len(rows) == 2  # one row per write


def test_bucketed_matches_hash_exchange_placement(tmp_path):
    """The writer's bucket id is the exchange's hash: repartition(n, k) and
    bucketBy(n, k) must agree on row placement (io/bucketing.py contract)."""
    import glob

    t = pa.table({"k": pa.array([1, 2, 3, 42, 1000, -7], type=pa.int64())})
    path = str(tmp_path / "bk2")
    s = cpu_session()
    s.create_dataframe(t).write.mode("overwrite").bucket_by(4, "k").parquet(path)
    from spark_rapids_tpu.io.bucketing import bucket_ids, parse_bucket_id
    from spark_rapids_tpu.types import LONG, Schema, StructField

    schema = Schema([StructField("k", LONG)])
    rb = pa.record_batch({"k": t.column("k").combine_chunks()})
    expect = bucket_ids(rb, schema, {"num_buckets": 4, "cols": ["k"]})
    got = {}
    for f in glob.glob(os.path.join(path, "*.parquet")):
        b = parse_bucket_id(os.path.basename(f))
        for v in papq.read_table(f).column("k").to_pylist():
            got[v] = b
    ks = t.column("k").to_pylist()
    assert got == {v: int(b) for v, b in zip(ks, expect)}
