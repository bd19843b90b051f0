"""Performance-attribution layer (ISSUE 9) — acceptance suite.

Covers the tentpole surfaces:

* host-overhead ledger — exclusive nested phase scopes, exhaustive
  decomposition on a real TPC-H q1+q6 run (sum of phases within 5% of
  wall), ranked bench-diag breakdown;
* HISTOGRAM metric kind — bucket/quantile/delta math, Prometheus
  ``_bucket/_sum/_count`` invariants;
* live scrape endpoint — /metrics + /healthz, and the concurrent-export
  contract: 8 threads running queries while scrapes stream, monotone
  counters between consecutive scrapes, bucket sums equal to _count;
* cross-process trace propagation — wire SpanContext round trip, loopback
  serve run merging client span → server query tree into one document,
  shuffle metadata-request trace tail;
* measured cost calibration — harvest/persist round trip, and the
  synthetic-table CBO flip with the weight source visible in explain
  (bit-identical planning when disabled or the file is absent);
* satellites — trace.droppedSpans, the dynamic-slug cardinality cap.
"""
from __future__ import annotations

import json
import os
import re
import threading
import urllib.request

import pyarrow as pa
import pytest

from spark_rapids_tpu.obs import ledger as OL
from spark_rapids_tpu.obs import metrics as OM
from spark_rapids_tpu.obs import trace as OT
from spark_rapids_tpu.functions import col, sum as sum_

from harness import tpu_session


# ── histogram kind ─────────────────────────────────────────────────────────


def test_histogram_buckets_sum_and_quantiles():
    h = OM.Histogram("latNs")
    for v in (1, 2, 3, 100, 1000, 10_000, 10_000, 1_000_000):
        h.observe(v)
    counts, total, n = h.state()
    assert n == 8 and sum(counts) == n
    assert total == 1 + 2 + 3 + 100 + 1000 + 10_000 + 10_000 + 1_000_000
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(0.99)
    # the p50 lands within the right log2 bucket's bounds (~values 100-1000)
    assert 64 <= h.quantile(0.5) <= 2048
    # timers feed histograms through the same add() shape
    with h.timed():
        pass
    assert h.state()[2] == 9


def test_histogram_delta_windows():
    h = OM.Histogram("winNs")
    h.observe(10)
    before = h.state()
    h.observe(1000)
    h.observe(2000)
    counts, total, n = OM.histogram_delta(h.state(), before)
    assert n == 2 and total == 3000 and sum(counts) == 2
    assert OM.quantile_from_counts(counts, n, 0.99) <= 2048


def test_histogram_prometheus_rendering():
    reg = OM.GLOBAL
    h = reg.histogram("kernel.compileHist")
    h.observe(5000)
    from spark_rapids_tpu.obs.export import prometheus_text

    text = prometheus_text()
    assert "# TYPE spark_rapids_tpu_kernel_compile_hist histogram" in text
    buckets = re.findall(
        r'spark_rapids_tpu_kernel_compile_hist_bucket\{le="([^"]+)"\} (\d+)',
        text,
    )
    assert buckets, "no _bucket series rendered"
    # cumulative counts are monotone and +Inf equals _count
    cum = [int(c) for _le, c in buckets]
    assert cum == sorted(cum)
    assert buckets[-1][0] == "+Inf"
    m_count = re.search(
        r"spark_rapids_tpu_kernel_compile_hist_count (\d+)", text
    )
    assert m_count and int(m_count.group(1)) == cum[-1]
    assert "spark_rapids_tpu_kernel_compile_hist_sum" in text


# ── dynamic-slug cardinality cap ───────────────────────────────────────────


def test_dynamic_slug_cap_overflows_to_other():
    prefix = "scheduler.cancelled.reason."
    saved_cap = OM._SLUG_CAP[0]
    saved_seen = OM._SLUG_SEEN.pop(prefix, None)
    overflow_before = OM.GLOBAL.counter("metrics.slugOverflow").value
    try:
        OM.set_slug_cap(3)
        names = {
            OM.dynamic_name(prefix, f"cause-{i}") for i in range(10)
        }
        assert prefix + "other" in names
        distinct = {n for n in names if not n.endswith(".other")}
        assert len(distinct) == 3
        assert OM.GLOBAL.counter("metrics.slugOverflow").value >= (
            overflow_before + 7
        )
        # an admitted slug keeps resolving to itself, never to 'other'
        assert OM.dynamic_name(prefix, "cause-0") == prefix + "cause_0"
    finally:
        OM._SLUG_CAP[0] = saved_cap
        if saved_seen is not None:
            OM._SLUG_SEEN[prefix] = saved_seen
        else:
            OM._SLUG_SEEN.pop(prefix, None)


# ── host-overhead ledger ───────────────────────────────────────────────────


def test_ledger_nested_scopes_are_exclusive():
    import time

    led = OL.PhaseLedger()
    led.wall_start()
    with led.scope("dispatch"):
        time.sleep(0.02)
        with led.scope("compile"):
            time.sleep(0.03)
        time.sleep(0.01)
    led.wall_stop()
    ns = led.snapshot()
    # the child subtracted itself out of the parent (exclusive scopes)
    assert ns["compile"] >= 25e6
    assert 20e6 <= ns["dispatch"] <= 45e6
    bd = led.breakdown()
    assert bd["wall_ms"] >= 55
    assert abs(sum(bd["phases_ms"].values()) - bd["wall_ms"]) <= 1.0


def test_ledger_timed_iter_bills_each_pull():
    led = OL.PhaseLedger()

    def gen():
        import time

        for i in range(3):
            time.sleep(0.005)
            yield i

    assert list(led.timed_iter("dispatch", gen())) == [0, 1, 2]
    assert led.snapshot()["dispatch"] >= 10e6


def test_ledger_module_hooks_are_noops_without_current():
    assert OL.current() is None
    with OL.phase("compile"):
        pass  # no ledger installed: shared no-op scope
    assert OL.phase("x") is OL.phase("y")


TPCH_LEDGER_QUERIES = (1, 6)


def test_tpch_ledger_exhaustive_and_ranked():
    """Acceptance: on a TPC-H q1+q6 run the phase decomposition is
    exhaustive — sum of phase durations (glue residual included) within
    5% of measured wall clock — and bench diag carries the ranked
    breakdown. Serial configuration (pipeline off, one task) so a
    wall-clock partition is well-defined."""
    from spark_rapids_tpu.tpch import gen_table, tpch_query
    from spark_rapids_tpu.tpch.datagen import TABLES

    tables = {name: gen_table(name, 0.003) for name in TABLES}
    s = tpu_session(
        {
            "spark.rapids.tpu.pipeline.enabled": False,
            "spark.rapids.sql.concurrentGpuTasks": 1,
        },
        strict=False,
    )

    def accessor(session):
        def t(name):
            return session.create_dataframe(tables[name], num_partitions=1)

        return t

    for q in TPCH_LEDGER_QUERIES:
        assert tpch_query(q, accessor(s)).collect()
        led = s._last_ledger
        assert led is not None
        bd = led.breakdown()
        wall = bd["wall_ms"]
        assert wall > 0
        phase_sum = sum(bd["phases_ms"].values())
        # exhaustive: phases (incl. the glue residual) partition the wall
        assert abs(phase_sum - wall) <= 0.05 * wall, (q, bd)
        # overlap-free in the serial config: measured phases fit the wall
        assert bd["parallel_overlap_ms"] <= 0.05 * wall, (q, bd)
        # the measured (non-residual) part is real work, not all residual
        assert bd["coverage_frac"] >= 0.5, (q, bd)
        # ranked: descending cost order
        vals = list(bd["phases_ms"].values())
        assert vals == sorted(vals, reverse=True)
        # the documented decomposition keys only
        assert set(bd["phases_ms"]) <= set(OL.PHASES), bd


def test_ledger_in_explain_and_artifact(tmp_path):
    s = tpu_session(strict=False)
    t = pa.table({"a": list(range(500)), "b": [float(i) for i in range(500)]})
    df = (
        s.create_dataframe(t, num_partitions=2)
        .filter(col("a") > 5)
        .group_by()
        .agg(sum_(col("b")).alias("s"))
    )
    assert df.collect()
    out = df.explain("metrics")
    assert "host-overhead ledger" in out and "wall" in out
    from spark_rapids_tpu.obs.export import query_artifact

    art = query_artifact(plan=s._last_plan, session=s)
    assert "ledger" in art and art["ledger"]["wall_ms"] > 0


def test_ledger_kill_switch():
    s = tpu_session({"spark.rapids.tpu.ledger.enabled": False}, strict=False)
    t = pa.table({"a": [1, 2, 3]})
    assert s.create_dataframe(t).filter(col("a") > 1).collect()
    assert getattr(s, "_last_ledger", None) is None


# ── live scrape endpoint ───────────────────────────────────────────────────


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode("utf-8")


def test_scrape_endpoint_serves_metrics_and_health():
    from spark_rapids_tpu.obs.scrape import ScrapeServer

    s = tpu_session(strict=False)
    t = pa.table({"a": list(range(100))})
    assert s.create_dataframe(t).filter(col("a") > 1).collect()
    with ScrapeServer(session=s, port=0) as srv:
        text = _get(f"http://{srv.host}:{srv.port}/metrics")
        assert "# TYPE spark_rapids_tpu_kernel_builds counter" in text
        assert "_bucket{le=" in text  # at least one histogram series
        health = json.loads(_get(f"http://{srv.host}:{srv.port}/healthz"))
        assert health["status"] == "ok" and health["live"] is True
        with pytest.raises(Exception):
            _get(f"http://{srv.host}:{srv.port}/nope")


def test_scrape_conf_starts_with_session():
    s = tpu_session(
        {"spark.rapids.tpu.metrics.httpPort": -1}, strict=False
    )
    srv = getattr(s, "_scrape_server", None)
    assert srv is not None and srv.port > 0
    try:
        assert "spark_rapids_tpu" in _get(
            f"http://{srv.host}:{srv.port}/metrics"
        )
    finally:
        srv.stop()


def _counter_values(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or "{" in line:
            continue
        parts = line.rsplit(" ", 1)
        if len(parts) == 2:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


def test_concurrent_queries_with_live_scrapes():
    """The satellite contract: Prometheus dumps + live scrapes while 8
    threads run queries — no exceptions, counters never regress between
    consecutive scrapes, histogram bucket sums equal _count."""
    from spark_rapids_tpu.obs.export import prometheus_text
    from spark_rapids_tpu.obs.scrape import ScrapeServer

    s = tpu_session(strict=False)
    t = pa.table({"a": list(range(2000)), "b": [float(i) for i in range(2000)]})

    def q():
        return (
            s.create_dataframe(t, num_partitions=2)
            .filter(col("a") > 10)
            .group_by()
            .agg(sum_(col("b")).alias("s"))
            .collect()
        )

    assert q()  # warm the kernels once
    errors: list = []
    stop = threading.Event()

    def worker():
        try:
            while not stop.is_set():
                assert q()
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    with ScrapeServer(session=s, port=0) as srv:
        for th in threads:
            th.start()
        url = f"http://{srv.host}:{srv.port}/metrics"
        prev: dict = {}
        counters = (
            "spark_rapids_tpu_kernel_cache_hits",
            "spark_rapids_tpu_scheduler_admitted",
        )
        for _ in range(12):
            text = _get(url)
            vals = _counter_values(text)
            for name in counters:
                assert vals.get(name, 0) >= prev.get(name, 0), name
            prev = vals
            # histogram invariant under concurrency: +Inf bucket == _count
            for base in re.findall(r"# TYPE (\S+) histogram", text):
                inf = re.search(
                    rf'{base}_bucket\{{le="\+Inf"\}} (\d+)', text
                )
                cnt = re.search(rf"{base}_count (\d+)", text)
                assert inf and cnt and inf.group(1) == cnt.group(1), base
            # the in-process dump path stays consistent too
            assert prometheus_text(session=s)
        stop.set()
        for th in threads:
            th.join(timeout=60)
    assert not errors, errors
    assert prev.get("spark_rapids_tpu_scheduler_admitted", 0) > 0


# ── cross-process trace propagation ────────────────────────────────────────


def test_span_context_wire_roundtrip():
    ctx = OT.SpanContext("abc123", 42, True)
    back = OT.SpanContext.from_wire(ctx.to_wire())
    assert back.trace_id == "abc123" and back.span_id == 42 and back.sampled
    assert OT.SpanContext.from_wire(None) is None
    assert OT.SpanContext.from_wire({}) is None
    assert OT.SpanContext.from_wire({"trace_id": "t"}).span_id is None


def test_shuffle_metadata_request_carries_trace_tail():
    from spark_rapids_tpu.shuffle import meta as M

    blocks = [M.BlockId(1, 2, 0, 4), M.BlockId(1, 3, 0, 4)]
    plain = M.pack_metadata_request(blocks)
    assert M.unpack_metadata_request(plain) == blocks
    assert M.unpack_metadata_trace(plain) is None
    wire = OT.SpanContext("deadbeef", 7).to_wire()
    tagged = M.pack_metadata_request(blocks, trace=wire)
    # old readers still see exactly the blocks; new readers see the tail
    assert M.unpack_metadata_request(tagged) == blocks
    tail = M.unpack_metadata_trace(tagged)
    assert tail == wire


def test_dropped_spans_counter_and_export_flag():
    before = OM.GLOBAL.counter("trace.droppedSpans").value
    tr = OT.Tracer(capacity=16)
    with OT.query_scope(tr, "q"):
        for i in range(40):
            with OT.span(f"s{i}"):
                pass
    assert tr.dropped == 41 - 16
    assert OM.GLOBAL.counter("trace.droppedSpans").value == before + tr.dropped
    doc = tr.to_chrome()
    assert doc["otherData"]["dropped_spans"] == tr.dropped
    assert doc["otherData"]["trace_id"] == tr.trace_id


def test_loopback_serve_trace_merges_into_one_tree(tmp_path):
    """Acceptance: a loopback serve run produces ONE coherent Perfetto
    tree — client span → server query root (shared trace id, remote
    parent = the client span) → operator spans chaining to the root."""
    from spark_rapids_tpu.serve import TpuServer, connect

    session = tpu_session(strict=False)
    session.create_or_replace_temp_view("r", session.range(0, 50_000))
    server = TpuServer(session, port=0)
    host, port = server.start()
    client_tracer = OT.Tracer(capacity=4096)
    try:
        with connect(host, port) as conn:
            with OT.query_scope(client_tracer, "client-session"):
                table = conn.sql(
                    "select count(*) c from r where id > 10"
                ).to_table()
        assert table.num_rows == 1
    finally:
        server.stop()

    server_tracer = getattr(session, "_last_tracer", None)
    assert server_tracer is not None
    assert server_tracer.trace_id == client_tracer.trace_id

    merged = OT.merge_chrome(
        client_tracer.to_chrome("client"), server_tracer.to_chrome("server")
    )
    path = tmp_path / "merged.trace.json"
    path.write_text(json.dumps(merged))
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    client_spans = [e for e in events if e["cat"] == "client"]
    assert client_spans, "client serve-query span missing"
    client_sid = client_spans[0]["args"]["span_id"]
    roots = [
        e for e in events if e["args"].get("remote_parent_id") is not None
    ]
    assert len(roots) == 1
    server_root = roots[0]
    assert server_root["cat"] == "query"
    assert server_root["args"]["remote_parent_id"] == client_sid
    assert server_root["args"]["trace_id"] == client_tracer.trace_id
    # operator spans chain to the server root (one coherent tree)
    by_sid = {e["args"]["span_id"]: e for e in events}
    ops = [e for e in events if e["cat"] == "operator"]
    assert ops

    def reaches(e, target):
        seen = set()
        while True:
            p = e["args"].get("parent_id")
            if p == target:
                return True
            if p is None or p in seen or p not in by_sid:
                return False
            seen.add(p)
            e = by_sid[p]

    root_sid = server_root["args"]["span_id"]
    assert all(reaches(e, root_sid) for e in ops)
    assert doc["otherData"]["trace_ids"] == [client_tracer.trace_id]


def test_prepared_statement_propagates_wire_trace():
    """EXECUTE_PREPARED carries the span context too: the server adopts
    the client's trace id (query root + queued spans record) even though
    the SHARED cached plan itself stays uninstrumented."""
    from spark_rapids_tpu.serve import TpuServer, connect

    session = tpu_session(strict=False)
    session.create_or_replace_temp_view("pr", session.range(0, 10_000))
    server = TpuServer(session, port=0)
    host, port = server.start()
    client_tracer = OT.Tracer(capacity=1024)
    try:
        with connect(host, port) as conn:
            stmt = conn.prepare("select count(*) c from pr where id > ?")
            with OT.query_scope(client_tracer, "client-prep"):
                assert conn.execute(stmt, [5]).to_table().num_rows == 1
    finally:
        server.stop()
    server_tracer = getattr(session, "_last_tracer", None)
    assert server_tracer is not None
    assert server_tracer.trace_id == client_tracer.trace_id
    cats = {s.cat for s in server_tracer.spans()}
    assert "query" in cats
    # the shared cached plan stayed uninstrumented: no per-operator wraps
    assert "operator" not in cats


# ── measured cost calibration ──────────────────────────────────────────────


def _calib_file(tmp_path, ops: dict) -> str:
    os.makedirs(str(tmp_path), exist_ok=True)
    path = str(tmp_path / "calib.json")
    with open(path, "w") as f:
        json.dump({"version": 1, "ops": ops}, f)
    from spark_rapids_tpu.obs import calibration as C

    C.invalidate(path)
    return path


def test_calibration_harvest_persists_measured_costs(tmp_path):
    from spark_rapids_tpu.obs import calibration as C

    path = str(tmp_path / "harvest.json")
    C.invalidate(path)
    s = tpu_session(
        {
            "spark.rapids.tpu.cbo.calibration.enabled": True,
            "spark.rapids.tpu.cbo.calibrationFile": path,
        },
        strict=False,
    )
    t = pa.table({"a": list(range(5000)), "b": [float(i) for i in range(5000)]})
    assert (
        s.create_dataframe(t, num_partitions=2)
        .filter(col("a") > 10)
        .group_by()
        .agg(sum_(col("b")).alias("s"))
        .collect()
    )
    assert os.path.exists(path)
    doc = json.load(open(path))
    device_ops = {
        op: e
        for op, e in doc["ops"].items()
        if "device_ns_per_row" in e and op.startswith("Tpu")
    }
    assert device_ops, doc
    for e in device_ops.values():
        assert e["device_ns_per_row"] > 0 and e["updates"] >= 1
    # a fresh load round-trips into usable weights
    C.invalidate(path)
    weights = C.load_weights(path)
    assert weights and all(isinstance(w, int) for w in weights.values())


def test_measured_weights_flip_unconversion_decision(tmp_path):
    """Acceptance: a synthetic calibration table flips the CBO island
    decision, the reason (with the measured source) shows in explain, and
    disabled/absent calibration is bit-identical to today."""
    t = pa.table({"a": list(range(100))})
    base_conf = {"spark.rapids.sql.optimizer.enabled": True}

    def build(s):
        return s.create_dataframe(t).filter(col("a") > 50)

    # today's behavior: the 2-weight project-free island unconverts
    s0 = tpu_session(base_conf, strict=False)
    assert len(build(s0).collect()) == 49
    baseline_tree = s0._last_plan.tree_string()
    assert "TpuFilter" not in baseline_tree

    # measured table says filter work is EXPENSIVE (3x the unit op):
    # island weight 3 >= transition cost 3 → stays on device
    keep = _calib_file(
        tmp_path / "keep",
        {
            "TpuProjectExec": {"device_ns_per_row": 10.0},
            "TpuFilterExec": {"device_ns_per_row": 30.0},
        },
    )
    s1 = tpu_session(
        {
            **base_conf,
            "spark.rapids.tpu.cbo.measuredWeights": True,
            "spark.rapids.tpu.cbo.calibrationFile": keep,
        },
        strict=False,
    )
    assert len(build(s1).collect()) == 49
    assert "TpuFilter" in s1._last_plan.tree_string()

    # measured table agrees the island is trivial → unconverted, with the
    # measured source + numbers in the explain reason
    drop = _calib_file(
        tmp_path / "drop",
        {
            "TpuProjectExec": {"device_ns_per_row": 10.0},
            "TpuFilterExec": {"device_ns_per_row": 10.0},
        },
    )
    s2 = tpu_session(
        {
            **base_conf,
            "spark.rapids.tpu.cbo.measuredWeights": True,
            "spark.rapids.tpu.cbo.calibrationFile": drop,
        },
        strict=False,
    )
    assert len(build(s2).collect()) == 49
    assert "TpuFilter" not in s2._last_plan.tree_string()
    reasons = [
        r
        for e in s2._last_overrides.explain
        for r in e.reasons
        if "cost-based optimizer" in r
    ]
    assert reasons and any(
        "measured weights" in r and "island" in r for r in reasons
    ), reasons

    # conf off or file absent: bit-identical planning vs the baseline
    s3 = tpu_session(
        {
            **base_conf,
            "spark.rapids.tpu.cbo.measuredWeights": True,
            "spark.rapids.tpu.cbo.calibrationFile": str(
                tmp_path / "does-not-exist.json"
            ),
        },
        strict=False,
    )
    assert len(build(s3).collect()) == 49
    assert s3._last_plan.tree_string() == baseline_tree
    s4 = tpu_session(base_conf, strict=False)
    assert len(build(s4).collect()) == 49
    assert s4._last_plan.tree_string() == baseline_tree
