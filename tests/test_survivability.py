"""Service survivability (ISSUE 7) — tier-1 suite.

Covers the layer that keeps the service alive when work STOPS instead of
failing: the progress watchdog (stalled kernels/compiles cancelled within
stallTimeout + one beat interval, classified per site, permits released
through the normal admission exit), compile deadlines (a blown budget
force-opens the op's circuit breaker → CPU at the next planning pass),
deadline-aware load shedding with retry-after hints, graceful drain with
typed END/ERROR on every stream, protocol frame checksums, client
reconnect/half-open handling, and the permit-leak regression guard.
"""
from __future__ import annotations

import socket
import threading
import time

import pytest

from spark_rapids_tpu.obs.metrics import GLOBAL
from spark_rapids_tpu.sched import (
    QueryCancelledError,
    QueryOverloadedError,
    QueryQueueFull,
)
from spark_rapids_tpu.sched.estimate import CALIBRATION
from spark_rapids_tpu.serve import ServeError, TpuServer, connect
from spark_rapids_tpu.serve import protocol as P

from tests.harness import tpu_session


@pytest.fixture(scope="module", autouse=True)
def _no_leaks(serve_leak_guard):
    yield


def _poll(pred, timeout_s: float = 30.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ── progress watchdog ──────────────────────────────────────────────────────


def test_watchdog_cancels_stalled_kernel_and_frees_permits():
    """A launch that wedges (injected stall, no error raised) is cancelled
    by the watchdog within stallTimeout + one beat interval; the cancel
    unwinds through the normal admission exit, so permits return to 0 and
    the session keeps serving."""
    s = tpu_session(
        {
            "spark.rapids.tpu.watchdog.stallTimeout": 0.3,
            "spark.rapids.tpu.faults.enabled": True,
            "spark.rapids.tpu.faults.kernelStallEveryN": 1,
            "spark.rapids.tpu.faults.kernelStallMs": 1500,
        },
        strict=False,
    )
    from spark_rapids_tpu.functions import col

    stalls_before = GLOBAL.counter("watchdog.stalls").value
    t0 = time.monotonic()
    with pytest.raises(QueryCancelledError) as ei:
        s.range(0, 50_000).filter(col("id") % 7 != 0).collect()
    # cancelled (flagged) within stallTimeout + beat interval; the error
    # surfaces once the injected stall returns (~1.5s)
    assert time.monotonic() - t0 < 10.0
    assert ei.value.reason.startswith("stall:")
    assert GLOBAL.counter("watchdog.stalls").value > stalls_before
    _poll(lambda: s.scheduler.pool.in_use == 0, what="permits released")
    assert s.scheduler.state()["watchdog_running"]
    # the session survives: next query (injection off; watchdog off too —
    # a 0.3s stallTimeout is far below a legit cold XLA:CPU compile, which
    # is exactly why the conf doc says to keep it above the compile wall)
    s.set_conf("spark.rapids.tpu.faults.kernelStallEveryN", 0)
    s.set_conf("spark.rapids.tpu.watchdog.stallTimeout", 0)
    assert s.range(0, 10).count() == 10
    # per-site + per-reason Prometheus series
    from spark_rapids_tpu.obs.export import prometheus_text

    text = prometheus_text()
    assert "spark_rapids_tpu_watchdog_stalls_site_" in text
    assert "spark_rapids_tpu_scheduler_cancelled_reason_stall_" in text


def test_watchdog_classifies_compile_stall():
    """A wedged first-touch compile is classified as stall:compile — the
    explicit compile start/end beats label the phase."""
    s = tpu_session(
        {
            "spark.rapids.tpu.watchdog.stallTimeout": 0.3,
            "spark.rapids.tpu.faults.enabled": True,
            "spark.rapids.tpu.faults.compileDelayEveryN": 1,
            "spark.rapids.tpu.faults.compileDelayMs": 1500,
        },
        strict=False,
    )
    from spark_rapids_tpu.functions import col

    before = GLOBAL.counter("watchdog.stalls.site.compile").value
    with pytest.raises(QueryCancelledError) as ei:
        # a distinctive expression → a fresh kernel shape → a real
        # first-touch compile inside the admission window
        s.range(0, 1000).select(
            ((col("id") * 31 + 17) % 1009).alias("surv_compile_probe")
        ).collect()
    assert ei.value.reason == "stall:compile"
    assert GLOBAL.counter("watchdog.stalls.site.compile").value > before
    _poll(lambda: s.scheduler.pool.in_use == 0, what="permits released")


def test_watchdog_runs_periodic_evict_stale():
    """The watchdog thread sweeps shuffle heartbeat registries on the
    jittered period — dead peers vanish without any explicit heartbeat
    call, and the evicted_stale counter records it."""
    from spark_rapids_tpu.shuffle.heartbeat import ShuffleHeartbeatManager

    s = tpu_session(
        {
            "spark.rapids.tpu.watchdog.evictStalePeriod": 0.05,
            "spark.rapids.tpu.shuffle.heartbeatMaxAgeSeconds": 0.15,
        }
    )
    mgr = ShuffleHeartbeatManager()
    mgr.register_executor("doomed-peer", ("127.0.0.1", 1))
    before = GLOBAL.counter("shuffle.evictedStale").value
    # any admission configures + spawns the watchdog
    assert s.range(0, 10).count() == 10
    _poll(
        lambda: not mgr.all_executors()
        and GLOBAL.counter("shuffle.evictedStale").value > before,
        timeout_s=20.0,
        what="stale peer evicted by the watchdog sweep",
    )
    # the sweep is process-wide (evict_stale_all) and the scanner outlives the
    # test: left on, it evicts the executors of whatever file this worker runs
    # next at an age of 0.15 s (tests/test_multiproc_shuffle.py: KeyError
    # 'executor-1'). configure() reads the conf at the next admission.
    s.set_conf("spark.rapids.tpu.watchdog.evictStalePeriod", 0)
    assert s.range(0, 10).count() == 10
    assert s.scheduler.watchdog.evict_period_s == 0


# ── compile deadlines ──────────────────────────────────────────────────────


def test_compile_deadline_flips_op_to_cpu_via_breaker():
    """A compile over deadlineSeconds raises the typed error (never
    task-retried), force-opens the op's breaker, and the next run of the
    same query executes the op on CPU — correct results, reason in the
    explain output."""
    from spark_rapids_tpu.functions import col
    from spark_rapids_tpu.resilience import CompileDeadlineError

    def q(session, mul, mod):
        return session.range(0, 2000).select(
            ((col("id") * mul + 7) % mod).alias("surv_deadline_probe")
        )

    # warm the range/D2H kernels in the shared process-wide cache with a
    # DIFFERENT literal pair: the faulted session's only fresh compile is
    # then the probe projection itself (same output schema → same D2H key)
    base = tpu_session({}, strict=False)
    q(base, 7, 11).collect()

    s = tpu_session(
        {
            "spark.rapids.tpu.compile.deadlineSeconds": 0.2,
            "spark.rapids.tpu.faults.enabled": True,
            "spark.rapids.tpu.faults.compileDelayEveryN": 1,
            "spark.rapids.tpu.faults.compileDelayMs": 1200,
        },
        strict=False,
    )
    deadlines_before = GLOBAL.counter("kernel.compileDeadlines").value
    with pytest.raises(CompileDeadlineError):
        q(s, 131, 2027).collect()
    assert GLOBAL.counter("kernel.compileDeadlines").value > deadlines_before
    assert "ProjectExec" in s._breaker.state()["open"]
    # the tenant's retry (injection off — the wedge was the point) plans
    # the op on CPU via the open breaker and succeeds
    s.set_conf("spark.rapids.tpu.faults.compileDelayEveryN", 0)
    s.set_conf("spark.rapids.tpu.compile.deadlineSeconds", 0)
    got = q(s, 131, 2027).collect()
    assert got == q(base, 131, 2027).collect()
    reasons = [
        r for e in s._last_overrides.explain if not e.on_device
        for r in e.reasons
    ]
    assert any("circuit breaker" in r for r in reasons)


def test_compile_deadline_nested_first_touch_runs_inline():
    """A fused kernel's trace can enter another GuardedJit's first-touch
    compile (the reason _COMPILE_LOCK is an RLock). Under a deadline the
    locked region runs on a helper thread — a nested _call_with_deadline
    there must run inline on that same thread (the outer budget bounds
    the nest), not spawn a second helper that can never re-enter the
    RLock the first one holds."""
    from spark_rapids_tpu import kernels as K

    def inner():
        with K._COMPILE_LOCK:
            return "inner"

    def outer():
        with K._COMPILE_LOCK:
            # without the reentrancy shim this spawns a second helper
            # thread, deadlocks on the RLock, and burns the whole budget
            # into a spurious CompileDeadlineError
            return K._call_with_deadline(inner, 5.0)

    t0 = time.monotonic()
    assert K._call_with_deadline(outer, 5.0) == "inner"
    assert time.monotonic() - t0 < 4.0, "nested deadline scope re-joined"


# ── deadline-aware load shedding ───────────────────────────────────────────


def test_overload_shed_rejects_unmeetable_deadline_with_retry_after():
    """With the pool held and a queue formed, a query whose estimated
    wait + run exceeds its deadline is shed at admission: typed
    QueryOverloadedError, retry-after hint, per-reason Prometheus
    series."""
    s = tpu_session(
        {
            "spark.rapids.tpu.scheduler.permits": 1,
            "spark.rapids.tpu.scheduler.maxQueued": 4,
        }
    )
    final_plan, _ctx = s._prepare_plan(s.range(0, 100)._plan)
    CALIBRATION.reset()
    CALIBRATION.record(0, 0.5)  # recent queries took ~0.5s
    adm_a = s.scheduler.admit("surv-a", final_plan, s.conf)
    adm_a.__enter__()  # holds the whole pool (permits=1)
    b_done = threading.Event()

    def queue_b():
        with s.scheduler.admit("surv-b", final_plan, s.conf):
            pass
        b_done.set()

    t = threading.Thread(target=queue_b)
    t.start()
    try:
        _poll(lambda: s.scheduler.pool.queued == 1, what="b queued")
        shed_before = GLOBAL.counter("scheduler.shed").value
        conf_c = s.conf.set("spark.rapids.tpu.scheduler.queryTimeout", 0.05)
        with pytest.raises(QueryOverloadedError) as ei:
            s.scheduler.admit("surv-c", final_plan, conf_c)
        assert ei.value.retry_after_s > 0
        assert ei.value.reason == "deadline_unmeetable"
        assert GLOBAL.counter("scheduler.shed").value == shed_before + 1
        # queue-full rejections carry the same hint
        conf_d = s.conf.set("spark.rapids.tpu.scheduler.maxQueued", 1)
        e_done = threading.Event()
        errors: list = []

        def reject_d():
            try:
                with s.scheduler.admit("surv-d", final_plan, conf_d):
                    pass
            except QueryQueueFull as e:
                errors.append(e)
            e_done.set()

        t2 = threading.Thread(target=reject_d)
        t2.start()
        t2.join(timeout=30)
        assert errors and errors[0].retry_after_s > 0
    finally:
        adm_a.__exit__(None, None, None)
        t.join(timeout=30)
    assert b_done.is_set()
    from spark_rapids_tpu.obs.export import prometheus_text

    assert (
        "spark_rapids_tpu_scheduler_shed_reason_deadline_unmeetable"
        in prometheus_text()
    )
    CALIBRATION.reset()


# ── graceful drain / lifecycle ─────────────────────────────────────────────


def _mini_rig(extra_conf=None, warmup=None):
    s = tpu_session(
        {
            "spark.rapids.tpu.serve.streamBatchRows": 512,
            **(extra_conf or {}),
        },
        strict=False,
    )
    s.create_or_replace_temp_view("surv_mid", s.range(0, 120_000))
    # big enough that a stream can NEVER finish into loopback socket
    # buffers — in-flight means genuinely in flight
    s.create_or_replace_temp_view("surv_big", s.range(0, 3_000_000))
    server = TpuServer(s, port=0, warmup=warmup)
    server.start()
    return s, server


def test_drain_lets_inflight_finish_and_rejects_new_work(monkeypatch):
    s, server = _mini_rig()
    try:
        # Gate as in test_drain_timeout_cancels_with_shutdown_reason: the
        # stream's 640 KB can finish into loopback socket buffers, and the
        # drain then ends and closes conn2 before its request is refused
        # (seen once in five whole runs). Held after its first batch until
        # the mid-drain assertions are through, in flight means in flight.
        mid_drain_checked = threading.Event()
        real_stream = s.run_plan_stream

        def gated_stream(*a, **k):
            first = True
            for rb in real_stream(*a, **k):
                yield rb
                if first:
                    first = False
                    mid_drain_checked.wait(30)

        monkeypatch.setattr(s, "run_plan_stream", gated_stream)
        conn1 = connect(server.host, server.port)
        conn2 = connect(server.host, server.port)
        stream = conn1.sql("select id from surv_mid where id % 3 <> 0")
        it = iter(stream)
        next(it)  # in-flight
        drained: list = []
        dt = threading.Thread(
            target=lambda: drained.append(server.drain(timeout=30.0))
        )
        dt.start()
        _poll(lambda: server._draining.is_set(), what="drain begun")
        # new work on an existing connection answers the typed DRAINING
        # error naming the drain reason
        with pytest.raises(ServeError) as ei:
            conn2.sql("select 1 as x").to_table()
        assert ei.value.code == "DRAINING"
        assert ei.value.reason == "shutdown"
        assert ei.value.error_type == "ServerDrainingError"
        # STATUS stays answerable mid-drain and reports the lifecycle
        st = conn2.status()
        assert st["live"] and st["draining"] and not st["ready"]
        mid_drain_checked.set()
        # the in-flight stream finishes normally — typed END, no cut
        rows = sum(b.num_rows for b in it) + 512
        assert stream.rows == 80_000 and rows >= stream.rows
        dt.join(timeout=30)
        assert drained == [True]
        # listener closed: fresh connections are refused
        with pytest.raises(OSError):
            connect(server.host, server.port, timeout=2.0)
    finally:
        server.stop()


def test_drain_timeout_cancels_with_shutdown_reason(monkeypatch):
    s, server = _mini_rig()
    try:
        # Deterministic gating (this test used to flake): the stream is
        # held in-flight not by wall-clock read pacing (which raced the
        # 0.3s drain window — a fast machine could finish the whole query
        # before the deadline) but by a gate INSIDE the batch generator:
        # after the first batch it refuses to advance until drain has
        # actually cancelled something, observed via the drainCancelled
        # counter moving past its captured base. The next advance then
        # hits the query token's check and raises the typed cancellation.
        base = GLOBAL.counter("serve.drainCancelled").value
        real_stream = s.run_plan_stream

        def gated_stream(*a, **k):
            first = True
            for rb in real_stream(*a, **k):
                yield rb
                if first:
                    first = False
                    _poll(
                        lambda:
                            GLOBAL.counter("serve.drainCancelled").value
                            > base,
                        what="drain-deadline cancel",
                    )

        monkeypatch.setattr(s, "run_plan_stream", gated_stream)
        conn = connect(server.host, server.port)
        stream = conn.sql("select id from surv_big where id % 5 <> 0")
        it = iter(stream)
        next(it)
        got: list = []

        def consume():
            try:
                for _ in it:
                    pass
            except ServeError as e:
                got.append(e)

        ct = threading.Thread(target=consume)
        ct.start()
        clean = server.drain(timeout=0.3)
        ct.join(timeout=30)
        assert not clean
        assert got, "stream ended without a typed ERROR frame"
        assert got[0].error_type == "QueryCancelledError"
        assert got[0].reason == "shutdown"
        _poll(lambda: s.scheduler.pool.in_use == 0, what="permits released")
        assert GLOBAL.counter("serve.drainCancelled").value >= 1
    finally:
        server.stop()


def test_readiness_gates_on_warm_pool(monkeypatch):
    s = tpu_session({}, strict=False)
    s.create_or_replace_temp_view("surv_warm", s.range(0, 1000))
    real_prepare = s._prepare_plan

    def slow_prepare(lp):
        time.sleep(0.6)
        return real_prepare(lp)

    monkeypatch.setattr(s, "_prepare_plan", slow_prepare)
    server = TpuServer(
        s, port=0, warmup=["select count(*) as c from surv_warm"]
    )
    try:
        server.start()
        conn = connect(server.host, server.port)
        # not ready until the warm pool is primed...
        assert conn.status()["ready"] is False
        assert not server.is_ready()
        # ...then the readiness poll flips (the rolling-restart gate)
        assert conn.wait_ready(timeout=30.0)
        conn.close()
    finally:
        server.stop()


# ── permit/span leak regression (satellite) ────────────────────────────────


def test_worker_crash_between_admit_and_first_batch_releases_permits(
    monkeypatch,
):
    """The finally-scoped admission guard: a worker thread that dies
    between admission and the first batch must release its permits and
    unregister the query — the server answers a typed ERROR and keeps
    serving."""
    s, server = _mini_rig()
    try:
        def boom(final_plan, ctx, on_retry=None):
            raise RuntimeError("worker crashed before first batch")

        monkeypatch.setattr(s, "run_plan_stream", boom)
        with connect(server.host, server.port) as conn:
            with pytest.raises(ServeError, match="worker crashed"):
                conn.sql("select id from surv_mid").to_table()
            _poll(
                lambda: s.scheduler.pool.in_use == 0,
                what="permits released after worker crash",
            )
            assert s.active_queries() == {}
            monkeypatch.undo()
            # the guard released everything: the session still serves
            t = conn.sql("select count(*) as c from surv_mid").to_table()
            assert t.to_pydict() == {"c": [120_000]}
    finally:
        server.stop()


# ── chaos-harness hygiene ──────────────────────────────────────────────────


def test_fault_scope_refcounts_interleaved_concurrent_exits():
    """The serve path enters faults.scoped(session_injector) from one
    worker thread PER query, all sharing the session's injector. A plain
    save/restore would let interleaved exits resurrect a stale injector
    (A restores None while B still runs; B then restores A's injector —
    installed process-wide forever, so a chaos session's kernel stalls
    leak into every later session). The refcounted install must stay up
    for the last holder and drain to None after it."""
    from spark_rapids_tpu.resilience import FaultConfig, faults

    assert faults.active() is None
    inj = faults.FaultInjector(FaultConfig(kernel_stall_every_n=1))
    cm_a = faults.scoped(inj)
    cm_b = faults.scoped(inj)
    cm_a.__enter__()
    cm_b.__enter__()
    cm_a.__exit__(None, None, None)  # A exits while B still holds
    assert faults.active() is inj, "injector dropped under a live holder"
    cm_b.__exit__(None, None, None)
    assert faults.active() is None, "stale injector left installed"
    # a different injector shadows and restores (test-style nesting)
    other = faults.FaultInjector(FaultConfig())
    with faults.scoped(inj):
        with faults.scoped(other):
            assert faults.active() is other
        assert faults.active() is inj
    assert faults.active() is None


# ── protocol frame checksums (satellite) ───────────────────────────────────


def test_corrupt_frame_closes_connection_with_typed_error():
    s, server = _mini_rig()
    try:
        sock = socket.create_connection(
            (server.host, server.port), timeout=10
        )
        try:
            P.send_json(sock, P.HELLO, {"token": ""})
            P.expect_frame(sock, P.HELLO_OK)
            before = GLOBAL.counter("serve.corruptFrames").value
            body = b'{"sql": "select 1"}'
            # a frame whose checksum does not match its body
            sock.sendall(P._HEADER.pack(len(body), P.EXECUTE, 0xBAD) + body)
            with pytest.raises(ServeError) as ei:
                P.expect_frame(sock, P.RESULT)
            assert ei.value.error_type == "FrameCorruptError"
            assert GLOBAL.counter("serve.corruptFrames").value > before
            # the connection closes cleanly after the typed error
            with pytest.raises(P.ConnectionClosed):
                P.recv_frame(sock)
        finally:
            sock.close()
    finally:
        server.stop()


def test_frame_checksum_roundtrip_unit():
    from spark_rapids_tpu.utils.checksum import frame_checksum

    a, b = socket.socketpair()
    try:
        P.send_frame(a, P.BATCH, b"payload-bytes")
        ftype, body = P.recv_frame(b)
        assert ftype == P.BATCH and body == b"payload-bytes"
        assert frame_checksum(b"") == frame_checksum(bytes())
        assert frame_checksum(b"x") != frame_checksum(b"y")
    finally:
        a.close()
        b.close()


# ── client robustness (satellite) ──────────────────────────────────────────


def test_client_reconnects_for_new_queries_after_server_restart():
    s, server = _mini_rig()
    port = server.port
    conn = None
    server2 = None
    try:
        conn = connect(server.host, port)
        assert conn.sql("select 2 as x").to_table().to_pydict() == {"x": [2]}
        server.stop()
        # the dead socket surfaces on the next call...
        with pytest.raises((ServeError, P.ProtocolError, OSError)):
            conn.sql("select 3 as x").to_table()
        assert conn._dead
        # ...a restarted server on the same address serves the NEXT query
        # through the client's transparent redial
        server2 = TpuServer(s, host=server.host, port=port)
        server2.start()
        assert conn.sql("select 4 as x").to_table().to_pydict() == {"x": [4]}
    finally:
        if conn is not None:
            conn.close()
        if server2 is not None:
            server2.stop()
        server.stop()


def test_client_half_open_socket_times_out():
    """A server that accepts + greets then goes silent must not hang the
    client forever: op_timeout bounds the wait and marks the connection
    dead (the reconnect path's trigger)."""
    lst = socket.create_server(("127.0.0.1", 0))
    host, port = lst.getsockname()[:2]
    stop = threading.Event()

    def silent_server():
        lst.settimeout(5.0)
        try:
            sock, _ = lst.accept()
        except OSError:
            return
        try:
            P.recv_frame(sock)  # HELLO
            P.send_json(sock, P.HELLO_OK, {"tenant": "t", "pool": "p",
                                           "protocol": P.PROTOCOL_VERSION})
            stop.wait(10.0)  # then: silence (half-open)
        except P.ProtocolError:
            pass
        finally:
            sock.close()

    t = threading.Thread(target=silent_server)
    t.start()
    try:
        conn = connect(host, port, op_timeout=0.3)
        t0 = time.monotonic()
        with pytest.raises(socket.timeout):
            conn.sql("select 1").to_table()
        assert time.monotonic() - t0 < 5.0
        assert conn._dead
        conn.close()
    finally:
        stop.set()
        lst.close()
        t.join(timeout=10)
