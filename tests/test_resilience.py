"""Resilience-layer unit tests: OOM classification over cause chains, the
split-and-retry state machine, the CPU-fallback circuit breaker, heartbeat
liveness/eviction, shuffle fetch retry + issuer-thread shutdown, and the
spill disk-tier error paths.

Reference analogues: DeviceMemoryEventHandlerSuite (spill-retry),
RapidsShuffleClientSuite (fetch failure paths against mocked transports),
RapidsShuffleHeartbeatManagerTest."""
from __future__ import annotations

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import device_to_host, host_to_device
from spark_rapids_tpu.mem.spill import (
    BufferCatalog,
    SpillError,
    StorageTier,
    with_oom_retry,
)
from spark_rapids_tpu.resilience import (
    CircuitBreaker,
    FaultConfig,
    InjectedFault,
    RetryPolicy,
    faults,
    is_device_error,
    is_oom_error,
    run_once,
    run_with_retry,
    split_batch,
)
from spark_rapids_tpu.resilience import retry as R


@pytest.fixture(autouse=True)
def _reset_counters():
    R.reset()
    yield
    R.reset()


def _batch(n=100, seed=0):
    rng = np.random.default_rng(seed)
    rb = pa.record_batch(
        {
            "a": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
            "s": pa.array([f"val{i % 17}" for i in range(n)]),
        }
    )
    return host_to_device(rb)


def _rows(db):
    rb = device_to_host(db)
    return [tuple(c[i].as_py() for c in rb.columns) for i in range(rb.num_rows)]


# ── classification: the _is_oom false-negative fix ─────────────────────────


def test_oom_classified_through_cause_chain():
    """A clean top-level message wrapping a RESOURCE_EXHAUSTED cause must
    classify as OOM (the old top-level substring match returned False)."""
    inner = RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 8 GiB")
    try:
        raise RuntimeError("partition task failed") from inner
    except RuntimeError as outer:
        assert is_oom_error(outer)


def test_oom_classified_through_real_jax_wrappers():
    """A backend error (jax.errors.JaxRuntimeError on the installed jax)
    re-raised inside another exception must classify through the chain."""
    from jax.errors import JaxRuntimeError as XlaRuntimeError

    xla = XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory while allocating")
    try:
        try:
            raise xla
        except XlaRuntimeError:
            raise RuntimeError("jit failed")  # implicit __context__ link
    except RuntimeError as outer:
        assert is_oom_error(outer)
    # and a non-OOM XlaRuntimeError classifies as a device error instead
    try:
        raise RuntimeError("wrapped") from XlaRuntimeError("INTERNAL: mosaic bug")
    except RuntimeError as outer:
        assert not is_oom_error(outer)
        assert is_device_error(outer)


def test_non_oom_not_classified():
    assert not is_oom_error(ValueError("boom"))
    assert not is_device_error(ValueError("boom"))


def test_cause_cycle_terminates():
    a = RuntimeError("a")
    b = RuntimeError("b")
    a.__cause__, b.__cause__ = b, a
    assert not is_oom_error(a)  # must not hang or recurse forever


def test_with_oom_retry_recovers_wrapped_error():
    """mem/spill.py::with_oom_retry now classifies wrapped causes."""
    cat = BufferCatalog()
    h = cat.register(_batch())
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("task died") from RuntimeError(
                "RESOURCE_EXHAUSTED: oom"
            )
        return 7

    assert with_oom_retry(cat, flaky) == 7
    assert calls["n"] == 2 and cat.spill_count == 1
    assert R.report()["oom_retries"] == 1
    h.close()


# ── split-and-retry state machine ──────────────────────────────────────────


def test_split_batch_preserves_rows():
    db = _batch(100)
    want = _rows(db)
    lo, hi = split_batch(db)
    assert lo.capacity == db.capacity // 2 and hi.capacity == db.capacity // 2
    assert _rows(lo) + _rows(hi) == want


def test_run_with_retry_splits_to_fit():
    """A kernel that OOMs above a capacity threshold forces recursive
    halving; outputs must cover the batch in order and split_count > 0."""
    db = _batch(200)
    want = _rows(db)
    launches = []

    def kernel(b):
        launches.append(b.capacity)
        if b.capacity > 64:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return b

    policy = RetryPolicy(max_retries=0, split_enabled=True, min_split_rows=2)
    outs = list(run_with_retry(None, kernel, db, policy))
    got = [r for o in outs for r in _rows(o)]
    assert got == want
    assert all(o.capacity <= 64 for o in outs)
    assert R.report()["splits"] > 0


def test_run_with_retry_spills_before_splitting():
    cat = BufferCatalog()
    parked = cat.register(_batch(seed=3))
    db = _batch(100)
    calls = {"n": 0}

    def kernel(b):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: first launch")
        return b

    outs = list(run_with_retry(cat, kernel, db, RetryPolicy(max_retries=2)))
    assert len(outs) == 1 and _rows(outs[0]) == _rows(db)
    assert cat.spill_count >= 1  # the retry spilled the parked buffer
    assert R.report()["oom_retries"] == 1 and R.report()["splits"] == 0
    parked.close()


def test_run_with_retry_floor_reraises():
    db = _batch(100)

    def kernel(b):
        raise RuntimeError("RESOURCE_EXHAUSTED: always")

    policy = RetryPolicy(max_retries=0, split_enabled=True, min_split_rows=64)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        list(run_with_retry(None, kernel, db, policy))


def test_run_with_retry_non_oom_propagates_and_feeds_breaker():
    db = _batch(10)
    breaker = CircuitBreaker(threshold=2)

    def kernel(b):
        raise InjectedFault("kernel", "INTERNAL: bad kernel")

    for _ in range(2):
        with pytest.raises(InjectedFault):
            list(run_with_retry(None, kernel, db, op="ProjectExec",
                                breaker=breaker))
    assert breaker.is_open("ProjectExec")
    assert "circuit breaker open" in breaker.check("ProjectExec")
    assert R.report()["circuit_breaker_trips"] == 1


def test_run_once_never_splits():
    db = _batch(100)

    def kernel(b):
        raise RuntimeError("RESOURCE_EXHAUSTED: always")

    with pytest.raises(RuntimeError):
        run_once(None, kernel, db, RetryPolicy(max_retries=0))
    assert R.report()["splits"] == 0


# ── pipeline prefetcher opt-in: OOM pressure clamps the window ─────────────


def test_pipeline_clamps_window_under_oom_pressure():
    from spark_rapids_tpu.exec.pipeline import PipelinedIterator

    R._note_oom()  # recent OOM anywhere in the process

    class Item:
        def size_bytes(self):
            return 1

    produced = []

    def src():
        for i in range(16):
            produced.append(i)
            yield Item()

    pipe = PipelinedIterator(src(), depth=8)
    time.sleep(0.3)  # give the producer time to run ahead if it (wrongly) can
    # window clamped to 1: at most the in-flight item + one buffered
    assert len(produced) <= 2, produced
    for _ in range(16):
        next(pipe)
    with pytest.raises(StopIteration):
        next(pipe)
    pipe.close()


# ── spill disk-tier error paths ────────────────────────────────────────────


def _spill_to_disk(cat, h):
    cat.synchronous_spill(h.size_bytes)
    cat.host_limit = 0
    cat.synchronous_spill(0)
    assert cat.disk_bytes > 0


def test_disk_rematerialize_missing_file_names_buffer(tmp_path):
    import glob
    import os

    cat = BufferCatalog(spill_dir=str(tmp_path))
    h = cat.register(_batch())
    _spill_to_disk(cat, h)
    for f in glob.glob(str(tmp_path / "*")):
        os.unlink(f)
    with pytest.raises(SpillError) as ei:
        h.get_batch()
    msg = str(ei.value)
    assert f"buffer {h.id}" in msg and "DISK" in msg


def test_disk_rematerialize_corrupt_file_names_buffer(tmp_path):
    import glob

    cat = BufferCatalog(spill_dir=str(tmp_path))
    h = cat.register(_batch())
    _spill_to_disk(cat, h)
    (path,) = glob.glob(str(tmp_path / "*"))
    with open(path, "wb") as f:
        f.write(b"not a spill frame")
    with pytest.raises(SpillError) as ei:
        h.get_batch()
    msg = str(ei.value)
    assert f"buffer {h.id}" in msg and "DISK" in msg


def test_spill_write_error_degrades_to_host_tier(tmp_path):
    cat = BufferCatalog(spill_dir=str(tmp_path))
    h = cat.register(_batch())
    want = _rows(h.get_batch())
    h.unpin()
    cat.synchronous_spill(h.size_bytes)
    with faults.scoped(FaultConfig(spill_write_error_every_n=1)):
        cat.host_limit = 0
        cat.synchronous_spill(0)
    # write failed -> data stays at HOST (degraded, not lost)
    assert cat.disk_bytes == 0 and cat.host_bytes == h.size_bytes
    assert cat._buffers[h.id].tier == StorageTier.HOST
    assert R.report()["spill_write_errors"] == 1
    assert _rows(h.get_batch()) == want
    h.close()


# ── heartbeat liveness + eviction ──────────────────────────────────────────


def _manager_with_clock():
    from spark_rapids_tpu.shuffle.heartbeat import ShuffleHeartbeatManager

    clock = {"t": 0.0}
    return ShuffleHeartbeatManager(now_fn=lambda: clock["t"]), clock


def test_heartbeat_records_last_beat_and_evicts_stale():
    mgr, clock = _manager_with_clock()
    mgr.register_executor("e0", ("h", 1))
    mgr.register_executor("e1", ("h", 2))
    assert mgr.last_heartbeat("e0") == 0.0
    clock["t"] = 100.0
    mgr.executor_heartbeat("e1")
    assert mgr.evict_stale(30.0) == ["e0"]
    assert [e.executor_id for e in mgr.all_executors()] == ["e1"]
    # evicted peer is gone from later deltas until it actually re-registers
    assert mgr.executor_heartbeat("e1") == []
    assert R.report()["peers_evicted"] == 1


def test_evicted_peer_reappears_only_on_reregistration():
    mgr, clock = _manager_with_clock()
    mgr.register_executor("e0", ("h", 1))
    mgr.register_executor("e1", ("h", 2))
    clock["t"] = 50.0
    mgr.executor_heartbeat("e1")
    mgr.evict_stale(10.0)
    mgr.register_executor("e0", ("h", 9))  # restart with a new address
    delta = mgr.executor_heartbeat("e1")
    assert [p.executor_id for p in delta] == ["e0"]
    assert delta[0].address == ("h", 9)


def test_endpoint_sweeps_stale_peers_on_heartbeat():
    """spark.rapids.tpu.shuffle.heartbeatMaxAgeSeconds: the endpoint's
    heartbeat evicts quiet executors and drops them from its peer table."""
    from spark_rapids_tpu.shuffle.heartbeat import HeartbeatEndpoint

    mgr, clock = _manager_with_clock()
    mgr.register_executor("dead", ("h", 1))
    ep = HeartbeatEndpoint("live", mgr, ("h", 2), max_age_s=10.0)
    assert ep.peer("dead") is not None
    clock["t"] = 60.0
    ep.heartbeat()
    assert ep.peer("dead") is None
    assert [e.executor_id for e in mgr.all_executors()] == ["live"]


def test_registry_stays_bounded_across_evictions():
    mgr, clock = _manager_with_clock()
    for i in range(50):
        clock["t"] = float(i)
        mgr.register_executor(f"e{i}", ("h", i))
        evicted = mgr.evict_stale(5.0)
        assert all(int(e[1:]) < i - 5 for e in evicted)
    assert len(mgr._entries) <= 7  # compacted, not grown without bound


# ── shuffle client: retry, backoff, issuer-thread shutdown ─────────────────


from spark_rapids_tpu.shuffle import meta as M  # noqa: E402
from spark_rapids_tpu.shuffle.catalog import ShuffleReceivedBufferCatalog  # noqa: E402
from spark_rapids_tpu.shuffle.client import ShuffleClient, ShuffleFetchError  # noqa: E402
from spark_rapids_tpu.shuffle.transport import (  # noqa: E402
    REQ_METADATA,
    InflightThrottle,
    TransactionStatus,
    new_transaction,
)


class _MetaOnlyConnection:
    """Metadata succeeds; transfers are accepted but frames never arrive."""

    peer_executor_id = "deadpeer"

    def request(self, req_type, payload):
        tx = new_transaction()
        if req_type == REQ_METADATA:
            bm = M.BufferMeta(11, 4096, 4096, M.CODEC_NONE)
            tm = M.TableMeta(1, 0, 0, 0, 10, bm, b"")
            tx.complete(TransactionStatus.SUCCESS, M.pack_metadata_response([tm]))
        else:
            # transfer accepted (no rejected states), but frames never come
            tx.complete(TransactionStatus.SUCCESS, M.TransferResponse((0,)).pack())
        return tx

    def set_frame_handler(self, h):
        pass


def test_timed_out_fetch_leaves_no_live_threads():
    before = set(threading.enumerate())
    client = ShuffleClient(
        _MetaOnlyConnection(),
        ShuffleReceivedBufferCatalog(),
        throttle=InflightThrottle(1 << 20),
        fetch_timeout_s=0.3,
    )
    with pytest.raises(ShuffleFetchError):
        list(client.fetch_blocks([M.BlockId(1, 0, 0, 1)]))
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate() if t not in before and t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"fetch leaked threads: {leaked}"


class _FlakyMetadataConnection(_MetaOnlyConnection):
    """First metadata request errors; classic transient transport fault."""

    peer_executor_id = "flaky"

    def __init__(self):
        self.calls = 0

    def request(self, req_type, payload):
        if req_type == REQ_METADATA:
            self.calls += 1
            if self.calls == 1:
                tx = new_transaction()
                tx.complete(TransactionStatus.ERROR, error="connection reset")
                return tx
        return super().request(req_type, payload)


def test_metadata_retry_with_backoff():
    conn = _FlakyMetadataConnection()
    client = ShuffleClient(
        conn,
        ShuffleReceivedBufferCatalog(),
        throttle=InflightThrottle(1 << 20),
        fetch_timeout_s=0.3,
        max_retries=2,
        backoff_ms=5,
    )
    # metadata retried past the transient error; the (frame-less) transfer
    # then times out after its own retry budget — what matters here is the
    # first error did NOT surface and retries were counted
    with pytest.raises(ShuffleFetchError, match="timed out"):
        list(client.fetch_blocks([M.BlockId(1, 0, 0, 1)]))
    assert conn.calls == 2
    assert R.report()["fetch_retries"] >= 1


def test_fetch_failure_callback_drives_blacklist():
    from spark_rapids_tpu.mem.spill import BufferCatalog as BC
    from spark_rapids_tpu.shuffle.heartbeat import ShuffleHeartbeatManager
    from spark_rapids_tpu.shuffle.local import InProcessRegistry, InProcessTransport
    from spark_rapids_tpu.shuffle.manager import ShuffleEnv

    env = ShuffleEnv(
        "execL",
        InProcessTransport("execL", InProcessRegistry()),
        BC(),
        ShuffleHeartbeatManager(),
        blacklist_after=2,
    )
    env._on_fetch_result("peerZ", False)
    assert not env.blacklisted("peerZ")
    env._on_fetch_result("peerZ", False)
    assert env.blacklisted("peerZ")
    with pytest.raises(ShuffleFetchError, match="blacklisted"):
        env.client_to("peerZ")
    assert R.report()["peers_evicted"] == 1
    # success resets the count for other peers
    env._on_fetch_result("peerY", False)
    env._on_fetch_result("peerY", True)
    env._on_fetch_result("peerY", False)
    assert not env.blacklisted("peerY")


def test_throttle_acquire_cancellable():
    th = InflightThrottle(100)
    th.acquire(100)
    cancel = threading.Event()
    errs = []

    def waiter():
        from spark_rapids_tpu.shuffle.transport import FetchCancelled

        try:
            th.acquire(50, timeout=30.0, cancel=cancel)
        except FetchCancelled as e:
            errs.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    cancel.set()
    th.kick()
    t.join(timeout=2.0)
    assert not t.is_alive() and len(errs) == 1
    th.release(100)
    th.acquire(100, timeout=1.0)  # the cancelled waiter left no residue
    th.release(100)


# ── transport conf: handshake timeout ──────────────────────────────────────


def test_tcp_handshake_timeout_conf_driven():
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.shuffle.tcp import TcpTransport

    # registered conf with the historical 10s default
    assert cfg.SHUFFLE_HANDSHAKE_TIMEOUT_S.get(TpuConf({})) == 10.0
    conf = TpuConf({"spark.rapids.tpu.shuffle.handshakeTimeout": "0.25"})
    t = TcpTransport("hs", handshake_timeout_s=cfg.SHUFFLE_HANDSHAKE_TIMEOUT_S.get(conf))
    try:
        assert t.handshake_timeout_s == 0.25
        # a dialer that never sends HELLO is dropped after the deadline,
        # and the listener stays healthy for real peers
        import socket

        bad = socket.create_connection(t.address)
        time.sleep(0.6)
        t.register_address()
        t2 = TcpTransport("hs2")
        conn = t2.connect("hs")
        tx = conn.request(REQ_METADATA, b"")  # no handler -> error reply
        tx.wait(5.0)
        assert tx.status == TransactionStatus.ERROR
        bad.close()
        t2.shutdown()
    finally:
        t.shutdown()


# ── circuit breaker → planner fallback (session integration) ───────────────


def test_circuit_breaker_marks_op_cpu_fallback():
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.functions import col

    t = pa.table({"a": np.arange(64, dtype=np.int64)})
    s = TpuSession(
        {
            "spark.rapids.tpu.faults.enabled": True,
            "spark.rapids.tpu.faults.kernelErrorEveryN": 1,
            "spark.rapids.tpu.retry.circuitBreaker.threshold": 2,
            "spark.task.maxFailures": 3,
        }
    )

    def q():
        return s.create_dataframe(t).select((col("a") + 1).alias("b")).to_arrow()

    with pytest.raises(Exception):
        q()
    assert s._breaker.is_open("ProjectExec")
    # heal the faults; the op now plans CPU-side with the reason in explain
    s.set_conf("spark.rapids.tpu.faults.enabled", False)
    out = q()
    assert out.column("b").to_pylist() == list(range(1, 65))
    reasons = [
        r
        for e in s._last_overrides.explain
        if not e.on_device
        for r in e.reasons
    ]
    assert any("circuit breaker open" in r for r in reasons), reasons
