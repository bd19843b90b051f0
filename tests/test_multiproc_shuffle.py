"""A REAL query across OS processes: map tasks in executor A serve shuffle
partitions to executor B over the TCP transport, driven through
TpuShuffleExchangeExec — not a protocol mock.

Reference: RapidsShuffleInternalManagerBase.scala:200 (manager routing),
UCX.scala:55 (executor-to-executor data plane), RapidsShuffleHeartbeatManager
(driver-mediated discovery). Here: shuffle/driver_service.py is the driver
control plane, shuffle/tcp.py the data plane; each executor process runs the
SAME plan, maps only its rank's input partitions, reduces only its rank's
output partitions, and fetches peer map output over real sockets.

The parent process is the 'driver': it hosts the coordination service,
spawns both executors, merges their partial results, and differentially
compares against a single-process CPU-engine run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.functions import col
from tests.harness import cpu_session

N_ROWS = 12_000
SEED = 77


def _table():
    rng = np.random.default_rng(SEED)
    return pa.table(
        {
            "k": rng.integers(0, 100, N_ROWS).astype(np.int64),
            "v": rng.integers(-50, 50, N_ROWS).astype(np.int64),
            "s": pa.array([f"g{i % 13}" for i in range(N_ROWS)]),
            "id": np.arange(N_ROWS, dtype=np.int64),
        }
    )


_CHILD = textwrap.dedent(
    """
    import json, sys
    import jax; jax.config.update("jax_platforms", "cpu")
    import numpy as np, pyarrow as pa
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.functions import col

    driver, rank, which = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    rng = np.random.default_rng({seed})
    n = {n_rows}
    t = pa.table({{
        "k": rng.integers(0, 100, n).astype(np.int64),
        "v": rng.integers(-50, 50, n).astype(np.int64),
        "s": pa.array([f"g{{i % 13}}" for i in range(n)]),
        "id": np.arange(n, dtype=np.int64),
    }})
    s = TpuSession({{
        "spark.rapids.sql.enabled": True,
        "spark.rapids.shuffle.manager.enabled": True,
        "spark.rapids.shuffle.multiproc.driver": driver,
        "spark.rapids.shuffle.multiproc.rank": rank,
        "spark.rapids.shuffle.multiproc.size": 2,
        "spark.sql.shuffle.partitions": 4,
        "spark.sql.adaptive.enabled": False,
    }})
    df = s.create_dataframe(t, num_partitions=4)
    if which == "agg":
        q = df.group_by("k", "s").agg(
            F.sum(col("v")).alias("sv"), F.count("*").alias("c")
        )
        first = sorted(map(tuple, q.collect()))
        out = q.collect()  # second query in the SAME session: shuffle ids
        # are namespaced per query, so no cross-query contamination
        assert sorted(map(tuple, out)) == first, "cross-query contamination"
    elif which == "join":  # aggregate joined to aggregate (two-stage shuffles)
        a = df.group_by("k").agg(F.sum(col("v")).alias("sv"))
        b = (
            df.filter(col("v") > 0)
            .group_by("k")
            .agg(F.count("*").alias("pc"))
            .with_column_renamed("k", "k2")
        )
        out = a.join(b, on=[("k", "k2")], how="left").collect()
    elif which == "sort":
        # ORDER BY = range exchange + per-partition sort. Every rank must
        # bucket with the SAME range bounds (gathered through the driver
        # service): per-rank bounds would route one key range to different
        # reduce partitions per mapping rank — a globally unsorted result.
        # (id makes the sort key total, so the parent can verify each
        # rank's output is contiguous slices of THE global order.)
        out = df.order_by(col("v").desc(), "id").collect()
    else:  # bcast: broadcast whose BUILD side contains an exchange — it
        # must run whole per executor (a rank-split build would broadcast
        # a partial table); the top-level aggregate still rank-splits
        small = (
            df.group_by("k").agg(F.max(col("v")).alias("mv"))
            .filter(col("mv") > 30)
            .with_column_renamed("k", "k2")
        )
        out = (
            df.join(F.broadcast(small), on=[("k", "k2")], how="inner")
            .group_by("s")
            .agg(F.count("*").alias("c"), F.sum(col("mv")).alias("sm"))
        ).collect()
    print("ROWS" + json.dumps([list(r) for r in out]), flush=True)
    # stay alive until the parent says every executor finished: a peer may
    # still be fetching this executor's map output over TCP (a real
    # executor outlives its own last task the same way)
    sys.stdin.read()
    """
)


def _run_multiproc(which: str, tmp_path, extra_env=None):
    """Returns (per_rank_rows, logs). Children hold their shuffle servers
    open until BOTH have produced results (parent closes stdin to release
    them) — exiting early would break a slower peer's fetch mid-stream."""
    from spark_rapids_tpu.shuffle.driver_service import DriverService

    svc = DriverService()
    addr = f"{svc.address[0]}:{svc.address[1]}"
    script = tmp_path / "executor_child.py"
    script.write_text(_CHILD.format(seed=SEED, n_rows=N_ROWS))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    env.update(extra_env or {})
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), addr, str(rank), which],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for rank in (0, 1)
    ]
    import threading
    import time as _time

    per_rank = [None, None]
    err_buf = [[], []]

    def reader(i, p):
        for ln in p.stdout:
            if ln.startswith("ROWS"):
                per_rank[i] = json.loads(ln[4:])
                return

    def drain_err(i, p):
        for ln in p.stderr:
            err_buf[i].append(ln)
            if len(err_buf[i]) > 400:
                del err_buf[i][:200]

    threads = [
        threading.Thread(target=reader, args=(i, p), daemon=True)
        for i, p in enumerate(procs)
    ] + [
        threading.Thread(target=drain_err, args=(i, p), daemon=True)
        for i, p in enumerate(procs)
    ]
    try:
        for t in threads:
            t.start()
        # under conftest's TEST_LIMIT_S, so that a child that never answers
        # fails here, with its stderr, and not at the limit without it
        deadline = _time.monotonic() + 240
        for t in threads[:2]:
            t.join(timeout=max(1, deadline - _time.monotonic()))
        for i, p in enumerate(procs):
            if per_rank[i] is None:
                raise AssertionError(
                    f"rank {i} produced no ROWS (rc={p.poll()}):\n"
                    f"{''.join(err_buf[i])[-4000:]}"
                )
        # both done: release the children, then collect exit statuses
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for i, p in enumerate(procs):
            p.wait(timeout=60)
            assert p.returncode == 0, (
                f"rank {i} failed:\n{''.join(err_buf[i])[-4000:]}"
            )
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        svc.close()
    return per_rank, ["".join(b) for b in err_buf]


@pytest.mark.parametrize("which", ["agg", "join", "bcast"])
def test_multiproc_query_over_tcp(which, tmp_path):
    per_rank, _logs = _run_multiproc(which, tmp_path)
    merged = per_rank[0] + per_rank[1]

    t = _table()
    cpu = cpu_session()
    df = cpu.create_dataframe(t, num_partitions=4)
    if which == "agg":
        expect = df.group_by("k", "s").agg(
            F.sum(col("v")).alias("sv"), F.count("*").alias("c")
        ).collect()
    elif which == "join":
        a = df.group_by("k").agg(F.sum(col("v")).alias("sv"))
        b = (
            df.filter(col("v") > 0)
            .group_by("k")
            .agg(F.count("*").alias("pc"))
            .with_column_renamed("k", "k2")
        )
        expect = a.join(b, on=[("k", "k2")], how="left").collect()
    else:
        small = (
            df.group_by("k").agg(F.max(col("v")).alias("mv"))
            .filter(col("mv") > 30)
            .with_column_renamed("k", "k2")
        )
        expect = (
            df.join(F.broadcast(small), on=[("k", "k2")], how="inner")
            .group_by("s")
            .agg(F.count("*").alias("c"), F.sum(col("mv")).alias("sm"))
        ).collect()

    got = sorted(tuple(r) for r in merged)
    want = sorted(tuple(r) for r in expect)
    assert len(got) == len(want), (
        f"{which}: merged rows {len(got)} vs single-process {len(want)}"
    )
    assert got == want, (
        f"{which}: first diffs: "
        f"{[p for p in zip(got, want) if p[0] != p[1]][:5]}"
    )


def test_multiproc_global_sort_shared_bounds(tmp_path):
    """ORDER BY across processes: the range exchange must gather ONE set of
    bounds via the driver service. With shared bounds, reduce partition p is
    exactly the p-th contiguous slice of the global order, so each rank's
    flat output (its owned pids, ascending) must decompose into contiguous
    slices of the single-process sorted result — per-rank bounds would mix
    key ranges inside a partition and break the decomposition."""
    per_rank, _logs = _run_multiproc("sort", tmp_path)

    t = _table()
    cpu = cpu_session()
    g = [
        tuple(r)
        for r in cpu.create_dataframe(t, num_partitions=4)
        .order_by(col("v").desc(), "id")
        .collect()
    ]
    flat = [[tuple(r) for r in rows] for rows in per_rank]
    assert sorted(flat[0] + flat[1]) == sorted(g)

    def lcp(xs, ref):
        n = 0
        while n < len(xs) and n < len(ref) and xs[n] == ref[n]:
            n += 1
        return n

    # reconstruct the 4 partition slices: rank0 owns pids {0,2}, rank1 {1,3}
    c1 = lcp(flat[0], g)
    c2 = lcp(flat[1], g[c1:])
    tail0, tail1 = flat[0][c1:], flat[1][c2:]
    p2_end = c1 + c2 + len(tail0)
    assert tail0 == g[c1 + c2 : p2_end], "rank0's 2nd slice not contiguous"
    assert tail1 == g[p2_end:], "rank1's 2nd slice not contiguous"


def test_multiproc_under_injected_dcn_latency(tmp_path):
    """The same two-process query under simulated DCN conditions: 25ms
    one-way frame latency (50ms request RTT) + a 200 MB/s bandwidth cap in
    the TCP transport (shuffle/tcp.py set_injection). Exercises the fetch
    throttle and bounce-buffer windowing against real waiting instead of
    loopback microseconds — the reference tests its client against a mocked
    transport the same way (RapidsShuffleClientSuite.scala)."""
    import time as _t

    t0 = _t.monotonic()
    per_rank, _logs = _run_multiproc(
        "agg",
        tmp_path,
        extra_env={
            "SRT_TCP_INJECT_LATENCY_MS": "25",
            "SRT_TCP_INJECT_BW_MBPS": "200",
        },
    )
    _ = _t.monotonic() - t0  # timing evidence lives in the unit test below
    merged = sorted(tuple(r) for r in per_rank[0] + per_rank[1])

    t = _table()
    cpu = cpu_session()
    expect = sorted(
        tuple(r)
        for r in cpu.create_dataframe(t, num_partitions=4)
        .group_by("k", "s")
        .agg(F.sum(col("v")).alias("sv"), F.count("*").alias("c"))
        .collect()
    )
    assert merged == expect


def test_tcp_injection_adds_latency_and_caps_bandwidth():
    """set_injection really shapes the link: every frame send pays the
    one-way latency and payload bytes serialize at the configured
    bandwidth; frames arrive intact."""
    import socket
    import threading
    import time as _t

    from spark_rapids_tpu.shuffle import tcp as T

    a, b = socket.socketpair()
    lock = threading.Lock()
    T.set_injection(latency_ms=20, bandwidth_mbps=1)
    try:
        payload = b"x" * 100_000  # 0.1s serialization at 1 MB/s
        n = 5
        t0 = _t.monotonic()
        for i in range(n):
            T._send_frame(a, lock, T._DATA, i, 0, payload)
            kind, tag, _seq, data, crc = T._recv_frame(b)
            assert kind == T._DATA and tag == i and len(data) == len(payload)
            # DATA frames carry the CRC32C of their payload (ISSUE 7)
            from spark_rapids_tpu.utils.checksum import frame_checksum

            assert crc == frame_checksum(data)
        elapsed = _t.monotonic() - t0
        # 5 frames x (20ms latency + 100ms serialization) = 0.6s floor
        assert elapsed >= 0.5, f"injection not applied: {elapsed:.3f}s"
    finally:
        T.set_injection()  # reset for the rest of the suite
        a.close()
        b.close()


def test_multiproc_results_are_split_across_executors(tmp_path):
    """Both executors must contribute rows (the reduce ownership split is
    real, not one process doing all the work)."""
    per_rank, _logs = _run_multiproc("agg", tmp_path)
    assert len(per_rank[0]) > 0 and len(per_rank[1]) > 0
    keys0 = {tuple(r[:2]) for r in per_rank[0]}
    keys1 = {tuple(r[:2]) for r in per_rank[1]}
    assert not (keys0 & keys1), "reduce partitions overlapped across executors"
