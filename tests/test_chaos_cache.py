"""Chaos suite for the semantic result cache + concurrent subplan dedup
(ISSUE 19 acceptance): appends racing cached reads must never serve a torn
or impossible result, an owner killed mid-materialization must wake its
waiters into independent (correct) execution, and fault-injected spill IO
during cache admission must degrade to uncached behaviour — bit-identical
results throughout, balanced resources at exit (the autouse reswatch /
lockwatch harnesses in tests/conftest.py arm for every chaos-marked test,
and reswatch now audits ResultCache byte accounting and SubplanRegistry
orphaned-waiter state directly)."""
from __future__ import annotations

import threading

import pyarrow as pa
import pytest

from spark_rapids_tpu.obs.metrics import GLOBAL
from tests.harness import tpu_session

pytestmark = pytest.mark.chaos


def _table(version: int, rows: int = 512) -> pa.Table:
    # every row carries the version so a torn read (rows from two
    # versions) is detectable from the aggregate alone
    return pa.table(
        {
            "v": pa.array([version] * rows, type=pa.int64()),
            "a": pa.array(list(range(rows)), type=pa.int64()),
        }
    )


# ── appends racing cached reads ────────────────────────────────────────────


def test_view_replacement_racing_cached_reads():
    """Writer thread replaces a temp view N times while reader threads
    hammer a cached aggregate over it. Every observed result must be the
    exact result of SOME complete version (per-table invalidation means
    no read may mix versions or resurrect a dropped one), and once the
    writer stops, readers must converge on the final version."""
    session = tpu_session(
        {"spark.rapids.tpu.resultCache.enabled": True}, strict=False
    )
    versions = 12
    rows = 512
    session.create_dataframe(_table(0, rows)).create_or_replace_temp_view("t")

    # v is constant per version, so sum(v) = version * rows identifies
    # the version AND exposes a torn read as a non-multiple of rows
    valid = {v * rows for v in range(versions)}
    q = "SELECT sum(v) AS sv, count(*) AS n FROM t"
    errors: list = []
    observed: list = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                (sv, n), = session.sql(q).collect()
                observed.append(sv)
                if n != rows or sv not in valid:
                    errors.append(f"torn/impossible read: sum(v)={sv} n={n}")
                    return
        except Exception as e:  # noqa: BLE001 - chaos surface
            errors.append(repr(e))

    import time

    inv0 = GLOBAL.counter("cache.result.invalidations").value
    readers = [threading.Thread(target=reader) for _ in range(3)]
    for r in readers:
        r.start()
    try:
        for v in range(1, versions):
            # pace on reader progress so versions genuinely interleave
            # with cached reads (an unpaced writer finishes before the
            # first store and the race never happens)
            seen = len(observed)
            deadline = time.monotonic() + 10
            while len(observed) <= seen and time.monotonic() < deadline:
                time.sleep(0.005)
            session.create_dataframe(_table(v, rows)).create_or_replace_temp_view("t")
    finally:
        stop.set()
        for r in readers:
            r.join(timeout=60)
    assert not errors, errors
    assert observed, "readers never completed a query"
    # convergence: with the writer quiet the cache must serve the final
    # version (stale entries were invalidated per-table, not global-TTL'd)
    (sv, n), = session.sql(q).collect()
    assert (sv, n) == ((versions - 1) * rows, rows)
    # deterministic tail: the converged read above cached the final
    # version; one more replacement must invalidate that entry
    session.create_dataframe(_table(versions, rows)).create_or_replace_temp_view("t")
    assert GLOBAL.counter("cache.result.invalidations").value > inv0, (
        "view replacement never invalidated a cached entry"
    )
    (sv, n), = session.sql(q).collect()
    assert (sv, n) == (versions * rows, rows)


def test_writer_append_invalidates_cached_file_scan(tmp_path):
    """The ISSUE 19 fix satellite: an append through io/writer.py must
    bump the written path's per-table version so a cached file-scan
    result cannot be served stale — no window, the bump lands before the
    commit marker AND after it."""
    session = tpu_session(
        {"spark.rapids.tpu.resultCache.enabled": True}, strict=False
    )
    path = str(tmp_path / "t")
    session.create_dataframe(_table(1, 64)).write.mode("overwrite").parquet(path)

    def read_sum():
        session.read.parquet(path).create_or_replace_temp_view("ft")
        (sv, n), = session.sql(
            "SELECT sum(v) AS sv, count(*) AS n FROM ft"
        ).collect()
        return sv, n

    assert read_sum() == (64, 64)
    assert read_sum() == (64, 64)  # served (possibly) from cache
    session.create_dataframe(_table(2, 64)).write.mode("append").parquet(path)
    sv, n = read_sum()
    assert (sv, n) == (64 + 128, 128), (
        f"stale read after append: sum(v)={sv} rows={n} — the writer's "
        "version bump did not reach the result cache"
    )


def test_write_is_never_served_from_result_cache(tmp_path):
    """A write plan over an in-memory table is keyed by the table's id(),
    which a freed table hands on to the next: served from the cache, the
    second write landed no file (the cause of the stale read the test
    above saw once in a full run). Writing ONE table twice makes the keys
    collide every time."""
    session = tpu_session(
        {"spark.rapids.tpu.resultCache.enabled": True}, strict=False
    )
    path = str(tmp_path / "t")
    df = session.create_dataframe(_table(1, 64))
    df.write.mode("overwrite").parquet(path)
    df.write.mode("append").parquet(path)
    assert session.read.parquet(path).count() == 128


# ── owner killed mid-materialization ───────────────────────────────────────


def test_owner_killed_mid_materialization_waiters_recover():
    """The owner of a shared subplan abandons its stream after the first
    batch (cancellation mid-materialization); every waiter must wake into
    independent execution and produce the full, correct result — owner
    failure costs waiters latency, never correctness or a hang."""
    from spark_rapids_tpu.plan.physical import ExecContext

    session = tpu_session(
        {
            "spark.rapids.tpu.subplanDedup.enabled": True,
            "spark.rapids.tpu.subplanDedup.minCostNs": 0,
            "spark.sql.shuffle.partitions": 2,
        },
        strict=False,
    )
    rows = 4096
    session.create_dataframe(
        _table(7, rows), num_partitions=4
    ).create_or_replace_temp_view("t")
    df = session.sql("SELECT a, v FROM t WHERE a % 3 = 0")
    expect = df.to_arrow()

    reg = session._subplan_registry
    final_plan, _ctx = session._prepare_plan(df._plan)

    owner_started = threading.Event()
    release_owner = threading.Event()
    results: dict = {}
    errors: list = []

    def owner():
        ctx = ExecContext(session.conf, session)
        plan, lease = reg.prepare(session, final_plan, session.conf, "q-owner")
        try:
            ps = plan.execute(ctx)  # claims ownership, publishes shape
            it = ps.parts[0]()
            next(it, None)  # one batch into the stream, then die
            owner_started.set()
            release_owner.wait(30)
        finally:
            owner_started.set()  # even if execute itself raised
            lease.release()  # exiting FILLING → ABORTED, waiters wake

    def waiter(i):
        ctx = ExecContext(session.conf, session)
        plan, lease = reg.prepare(session, final_plan, session.conf, f"q-w{i}")
        try:
            ps = plan.execute(ctx)
            batches = [rb for part in ps.parts for rb in part()]
            results[i] = pa.Table.from_batches(batches, schema=expect.schema)
        except Exception as e:  # noqa: BLE001 - chaos surface
            errors.append(repr(e))
        finally:
            lease.release()

    to = threading.Thread(target=owner)
    to.start()
    assert owner_started.wait(30), "owner never claimed the entry"
    aborts0 = GLOBAL.counter("subplan.dedupAborts").value
    waiters = [threading.Thread(target=waiter, args=(i,)) for i in range(3)]
    for w in waiters:
        w.start()
    # give waiters a beat to reach the wait role, then kill the owner
    import time

    time.sleep(0.3)
    release_owner.set()
    to.join(timeout=60)
    for w in waiters:
        w.join(timeout=60)
    assert not errors, errors
    assert len(results) == 3, "a waiter hung after the owner died"
    for i, got in results.items():
        assert got.sort_by("a").equals(expect.sort_by("a")), (
            f"waiter {i} diverged after owner abort"
        )
    assert GLOBAL.counter("subplan.dedupAborts").value > aborts0
    assert reg.stats() == {"entries": 0, "bytes": 0, "pins": 0}
    assert reg._orphan_report() == []


# ── fault-injected spill IO during cache admission ─────────────────────────


def test_faulted_spill_io_during_admission_bit_identical():
    """A byte budget small enough to force every admission into the
    demote-to-disk path, with every 2nd spill write and read injected to
    fail: queries stay bit-identical to an uncached session, failed
    demotions drop entries (never corrupt them), and byte accounting
    stays balanced (reswatch's _orphan_report audit runs via the chaos
    fixture on top of the explicit check below)."""
    plain = tpu_session({}, strict=False)
    cached = tpu_session(
        {
            "spark.rapids.tpu.resultCache.enabled": True,
            "spark.rapids.tpu.resultCache.maxBytes": "48k",
            "spark.rapids.tpu.resultCache.maxEntries": 4,
            "spark.rapids.tpu.faults.enabled": True,
            "spark.rapids.tpu.faults.spillWriteErrorEveryN": 2,
            "spark.rapids.tpu.faults.spillReadErrorEveryN": 2,
        },
        strict=False,
    )
    rows = 2048
    for s in (plain, cached):
        s.create_dataframe(_table(3, rows)).create_or_replace_temp_view("t")

    queries = [
        f"SELECT sum(a) AS s, count(*) AS n FROM t WHERE a % {m} = 0"
        for m in range(2, 8)
    ]
    expected = {q: plain.sql(q).collect() for q in queries}
    # two passes: pass 1 populates + churns the LRU through the faulted
    # spill path; pass 2 mixes disk-tier read-backs (every 2nd injected
    # to fail → degrade to miss) with re-execution
    for _ in range(2):
        for q in queries:
            assert cached.sql(q).collect() == expected[q], q
    assert cached._result_cache._orphan_report() == []
    st = cached._result_cache.stats()
    assert st["mem_bytes"] >= 0 and st["disk_bytes"] >= 0
    assert GLOBAL.counter("cache.result.stores").value > 0
