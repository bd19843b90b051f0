"""Shuffle subsystem tests — SURVEY §4 tier 2: the reference tests its
client/server protocol against mocked transports without a cluster
(RapidsShuffleClientSuite, RapidsShuffleServerSuite, WindowedBlockIteratorSuite,
RapidsShuffleHeartbeatManagerTest). Same strategy: the in-process and TCP
transports exercise the full metadata/transfer protocol in one process."""
from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import device_to_host, host_to_device
from spark_rapids_tpu.mem.spill import BufferCatalog
from spark_rapids_tpu.shuffle import meta as M
from spark_rapids_tpu.shuffle.bounce import (
    BounceBufferManager,
    BufferReceiveState,
    BufferSendState,
    windowed_blocks,
)
from spark_rapids_tpu.shuffle.compression import get_codec
from spark_rapids_tpu.shuffle.heartbeat import ShuffleHeartbeatManager
from spark_rapids_tpu.shuffle.local import InProcessRegistry, InProcessTransport
from spark_rapids_tpu.shuffle.manager import (
    MapOutputRegistry,
    ShuffleEnv,
    TpuShuffleManager,
)
from spark_rapids_tpu.shuffle.serializer import (
    deserialize_record_batch,
    serialize_record_batch,
)
from spark_rapids_tpu.shuffle.transport import InflightThrottle


def sample_rb(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return pa.record_batch(
        {
            "a": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
            "b": pa.array(rng.random(n)),
            "s": pa.array([f"v{int(i)}" for i in rng.integers(0, 50, n)]),
        }
    )


# ── wire metadata ──────────────────────────────────────────────────────────


def test_table_meta_roundtrip():
    bm = M.BufferMeta(7, 1234, 5678, M.CODEC_LZ4)
    tm = M.TableMeta(1, 2, 3, 0, 99, bm, b"schemabytes")
    data = M.pack_metadata_response([tm, tm])
    out = M.unpack_metadata_response(data)
    assert out == [tm, tm]


def test_metadata_request_roundtrip():
    blocks = [M.BlockId(1, 0, 0, 4), M.BlockId(1, 1, 2, 3)]
    assert M.unpack_metadata_request(M.pack_metadata_request(blocks)) == blocks


def test_transfer_messages_roundtrip():
    req = M.TransferRequest(0x1000, (5, 9, 11))
    assert M.TransferRequest.unpack(req.pack()) == req
    resp = M.TransferResponse((0, 0, 1))
    assert M.TransferResponse.unpack(resp.pack()) == resp


# ── codecs + serializer ────────────────────────────────────────────────────


@pytest.mark.parametrize("codec", ["none", "copy", "lz4", "zstd"])
def test_codec_roundtrip(codec):
    c = get_codec(codec)
    data = b"hello shuffle world " * 1000
    comp = c.compress(data)
    assert c.decompress(comp, len(data)) == data
    if codec in ("lz4", "zstd"):
        assert len(comp) < len(data)


def test_serializer_roundtrip():
    rb = sample_rb()
    codec = get_codec("lz4")
    payload, usize, cid = serialize_record_batch(rb, codec)
    bm = M.BufferMeta(1, len(payload), usize, cid)
    out = deserialize_record_batch(payload, bm)
    assert out.equals(rb)


# ── windowed blocks + bounce buffers ───────────────────────────────────────


def test_windowed_blocks_layout():
    windows = list(windowed_blocks([10, 3, 8], 8))
    # all bytes covered exactly once, in order, no window over 8 bytes
    total = sum(r.length for w in windows for r in w)
    assert total == 21
    for w in windows:
        assert sum(r.length for r in w) <= 8
    first = windows[0]
    assert first[0].block_index == 0 and first[0].length == 8


def test_bounce_pool_exhaustion():
    pool = BounceBufferManager(16, 2)
    a = pool.acquire()
    b = pool.acquire()
    with pytest.raises(TimeoutError):
        pool.acquire(timeout=0.05)
    b.close()
    c = pool.acquire(timeout=1.0)
    assert pool.free_count == 0
    a.close()
    c.close()
    assert pool.free_count == 2


def test_send_receive_state_roundtrip():
    payloads = [bytes(range(256)) * 10, b"x" * 5, b"" , b"tail" * 100]
    tags = [100, 200, 300, 400]
    pool = BounceBufferManager(64, 2)
    recv = BufferReceiveState({t: len(p) for t, p in zip(tags, payloads) if p})
    done = {}
    for tag, seq, frame in BufferSendState(payloads, tags, pool).frames():
        out = recv.on_frame(tag, seq, frame)
        if out is not None:
            done[tag] = out
    assert done[100] == payloads[0]
    assert done[200] == payloads[1]
    assert done[400] == payloads[3]
    assert recv.done


# ── throttle ───────────────────────────────────────────────────────────────


def test_throttle_blocks_and_orders():
    th = InflightThrottle(100)
    th.acquire(60)
    with pytest.raises(TimeoutError):
        th.acquire(60, timeout=0.05)
    th.release(60)
    th.acquire(60, timeout=1.0)
    th.release(60)
    # oversize request admitted alone
    th.acquire(1000, timeout=1.0)
    th.release(1000)
    assert th.inflight == 0


# ── heartbeats ─────────────────────────────────────────────────────────────


def test_heartbeat_gossip():
    mgr = ShuffleHeartbeatManager()
    assert mgr.register_executor("e0", ("h0", 1)) == []
    peers1 = mgr.register_executor("e1", ("h1", 2))
    assert [p.executor_id for p in peers1] == ["e0"]
    # e0 learns about e1 on its next heartbeat, exactly once
    new = mgr.executor_heartbeat("e0")
    assert [p.executor_id for p in new] == ["e1"]
    assert mgr.executor_heartbeat("e0") == []


# ── end-to-end: manager over in-process transport ──────────────────────────


def make_env(executor_id, registry, hb, codec="lz4"):
    store = BufferCatalog()
    transport = InProcessTransport(executor_id, registry)
    return ShuffleEnv(executor_id, transport, store, hb, codec=codec)


def test_manager_local_and_remote_read():
    reg = InProcessRegistry()
    hb = ShuffleHeartbeatManager()
    outputs = MapOutputRegistry()
    env_a = make_env("execA", reg, hb)
    env_b = make_env("execB", reg, hb)
    mgr_a = TpuShuffleManager(env_a, outputs)
    mgr_b = TpuShuffleManager(env_b, outputs)

    # map task on A writes 3 partitions
    rbs = [sample_rb(50, seed=i) for i in range(3)]
    writer = mgr_a.get_writer(shuffle_id=1, map_id=0, num_partitions=3)
    for p, rb in enumerate(rbs):
        writer.write(p, host_to_device(rb))
    status = writer.commit()
    assert all(s > 0 for s in status.sizes)

    # local read on A (zero-copy path)
    local = list(mgr_a.get_reader().read_partitions(1, 0, 1))
    assert len(local) == 1
    assert device_to_host(local[0]).equals(rbs[0])

    # remote read on B (metadata + transfer over the transport)
    got = list(mgr_b.get_reader().read_partitions(1, 1, 3))
    assert len(got) == 2
    out = sorted((device_to_host(b) for b in got), key=lambda r: r.num_rows)
    want = sorted(rbs[1:3], key=lambda r: r.num_rows)
    for o, w in zip(out, want):
        assert o.equals(w)

    mgr_a.unregister_shuffle(1)
    assert env_a.catalog.stats()["cached_batches"] == 0


def test_shuffle_output_survives_spill():
    """Map output must re-materialize identically after being spilled off
    the device tier (the spillable ShuffleBufferCatalog contract)."""
    reg = InProcessRegistry()
    hb = ShuffleHeartbeatManager()
    outputs = MapOutputRegistry()
    store = BufferCatalog()
    env = ShuffleEnv("execS", InProcessTransport("execS", reg), store, hb)
    mgr = TpuShuffleManager(env, outputs)
    rb = sample_rb(200, seed=7)
    w = mgr.get_writer(2, 0, 1)
    w.write(0, host_to_device(rb))
    w.commit()
    # force everything off-device, then read back
    store.synchronous_spill(1 << 40)
    assert store.device_bytes == 0
    got = list(mgr.get_reader().read_partitions(2, 0, 1))
    assert device_to_host(got[0]).equals(rb)


# ── end-to-end: TCP (DCN) transport ────────────────────────────────────────


def test_manager_over_tcp_transport():
    from spark_rapids_tpu.shuffle.tcp import TcpTransport

    hb = ShuffleHeartbeatManager()
    outputs = MapOutputRegistry()
    ta = TcpTransport("tcpA")
    tb = TcpTransport("tcpB")
    ta.register_address()
    tb.register_address()
    env_a = ShuffleEnv("tcpA", ta, BufferCatalog(), hb, codec="zstd", address=ta.address)
    env_b = ShuffleEnv("tcpB", tb, BufferCatalog(), hb, codec="zstd", address=tb.address)
    mgr_a = TpuShuffleManager(env_a, outputs)
    mgr_b = TpuShuffleManager(env_b, outputs)

    rbs = [sample_rb(300, seed=i + 10) for i in range(2)]
    w = mgr_a.get_writer(5, 0, 2)
    for p, rb in enumerate(rbs):
        w.write(p, host_to_device(rb))
    w.commit()

    got = list(mgr_b.get_reader().read_partitions(5, 0, 2))
    out = sorted((device_to_host(b) for b in got), key=lambda r: r.column(0)[0].as_py())
    want = sorted(rbs, key=lambda r: r.column(0)[0].as_py())
    for o, wnt in zip(out, want):
        assert o.equals(wnt)
    ta.shutdown()
    tb.shutdown()


# ── ICI device plane ───────────────────────────────────────────────────────


def test_ici_all_to_all_exchange():
    import jax

    from spark_rapids_tpu.parallel.distributed import make_mesh
    from spark_rapids_tpu.parallel.ici import (
        batch_to_global_leaves,
        build_ici_exchange,
        global_leaves_to_batches,
    )

    n = 4
    assert len(jax.devices()) >= n
    mesh = make_mesh(n)
    rng = np.random.default_rng(3)
    per = 64
    batches = [
        host_to_device(
            pa.record_batch(
                {
                    "k": pa.array(rng.integers(0, 1000, per // 2).astype(np.int64)),
                    "v": pa.array(rng.random(per // 2)),
                }
            ),
            capacity=per,
        )
        for _ in range(n)
    ]
    schema = batches[0].schema
    fn = build_ici_exchange(mesh, schema, [0])
    outs = fn(*batch_to_global_leaves(batches))
    result = global_leaves_to_batches(schema, outs, n)

    # row-set preserved
    before = []
    for b in batches:
        t = device_to_host(b)
        before.extend(zip(t.column(0).to_pylist(), t.column(1).to_pylist()))
    after = []
    for b in result:
        t = device_to_host(b)
        after.extend(zip(t.column(0).to_pylist(), t.column(1).to_pylist()))
    assert sorted(before) == sorted(after)

    # co-partitioned: equal keys land on the same chip
    key_chip = {}
    for chip, b in enumerate(result):
        for k in device_to_host(b).column(0).to_pylist():
            assert key_chip.setdefault(k, chip) == chip


# ── engine integration: exchange through the shuffle manager ───────────────


def test_query_with_shuffle_manager_enabled(session):
    """The same group-by must produce identical results when the exchange
    routes through the spillable shuffle catalog (manager path) as when it
    keeps buckets in-process (default path)."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.functions import col, sum as sum_

    rng = np.random.default_rng(11)
    table = pa.table(
        {
            "k": rng.integers(0, 20, 5000).astype(np.int64),
            "v": rng.random(5000),
        }
    )

    def q(s):
        return (
            s.create_dataframe(table, num_partitions=4)
            .group_by("k")
            .agg(sum_(col("v")).alias("s"))
            .collect()
        )

    base = sorted(q(TpuSession()))
    managed = sorted(q(TpuSession({"spark.rapids.shuffle.manager.enabled": True})))
    assert len(base) == len(managed) == 20
    for b, m in zip(base, managed):
        assert b[0] == m[0] and abs(b[1] - m[1]) < 1e-9


def test_concurrent_fetches_same_peer():
    """Two reduce tasks fetching from the same peer concurrently must not
    clobber each other's frame routing (tag-multiplexed client)."""
    import threading

    reg = InProcessRegistry()
    hb = ShuffleHeartbeatManager()
    outputs = MapOutputRegistry()
    env_a = make_env("ccA", reg, hb)
    env_b = make_env("ccB", reg, hb)
    mgr_a = TpuShuffleManager(env_a, outputs)
    mgr_b = TpuShuffleManager(env_b, outputs)

    rbs = [sample_rb(400, seed=i + 40) for i in range(4)]
    w = mgr_a.get_writer(9, 0, 4)
    for p, rb in enumerate(rbs):
        w.write(p, host_to_device(rb))
    w.commit()

    results = {}
    errors = []

    def fetch(part):
        try:
            got = list(mgr_b.get_reader().read_partitions(9, part, part + 1))
            results[part] = device_to_host(got[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=fetch, args=(p,)) for p in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for p in range(4):
        assert results[p].equals(rbs[p])
    assert env_b.throttle.inflight == 0


def test_server_reserializes_evicted_payload():
    """A transfer whose parked payload was evicted must rebuild it from the
    catalog rather than rejecting the buffer."""
    reg = InProcessRegistry()
    hb = ShuffleHeartbeatManager()
    outputs = MapOutputRegistry()
    env_a = make_env("evA", reg, hb)
    env_b = make_env("evB", reg, hb)
    mgr_a = TpuShuffleManager(env_a, outputs)
    mgr_b = TpuShuffleManager(env_b, outputs)
    rb = sample_rb(100, seed=99)
    w = mgr_a.get_writer(12, 0, 1)
    w.write(0, host_to_device(rb))
    w.commit()
    env_a.server.pending_limit_bytes = 0  # evict everything immediately
    got = list(mgr_b.get_reader().read_partitions(12, 0, 1))
    assert device_to_host(got[0]).equals(rb)
    assert env_a.server.pending_count() == 0


# ── failure modes: timeouts, fetch errors, throttle (verdict r1 #7) ────────


class _DeadConnection:
    """A client connection whose peer never answers (dead executor)."""

    def __init__(self):
        self.handler = None

    def request(self, req_type, payload):
        from spark_rapids_tpu.shuffle.transport import new_transaction

        return new_transaction()  # never completed

    def set_frame_handler(self, h):
        self.handler = h

    def close(self):
        pass


class _ErrConnection(_DeadConnection):
    """Metadata requests fail fast (peer raised)."""

    def request(self, req_type, payload):
        from spark_rapids_tpu.shuffle.transport import (
            TransactionStatus,
            new_transaction,
        )

        tx = new_transaction()
        tx.complete(TransactionStatus.ERROR, error="connection reset by peer")
        return tx


def test_fetch_timeout_surfaces_fetch_error():
    from spark_rapids_tpu.shuffle.catalog import ShuffleReceivedBufferCatalog
    from spark_rapids_tpu.shuffle.client import ShuffleClient, ShuffleFetchError

    client = ShuffleClient(
        _DeadConnection(), ShuffleReceivedBufferCatalog(), fetch_timeout_s=0.2
    )
    with pytest.raises(ShuffleFetchError, match="metadata"):
        list(client.fetch_blocks([M.BlockId(1, 0, 0, 1)]))


def test_fetch_error_propagates():
    from spark_rapids_tpu.shuffle.catalog import ShuffleReceivedBufferCatalog
    from spark_rapids_tpu.shuffle.client import ShuffleClient, ShuffleFetchError

    client = ShuffleClient(
        _ErrConnection(), ShuffleReceivedBufferCatalog(), fetch_timeout_s=0.2
    )
    with pytest.raises(ShuffleFetchError, match="connection reset"):
        list(client.fetch_blocks([M.BlockId(1, 0, 0, 1)]))


def test_transfer_stall_times_out_and_releases_throttle():
    """Metadata succeeds but the data frames never arrive: the fetch must
    raise within the timeout AND release its throttle reservation so later
    fetches are not starved (the claim-protocol cleanup path)."""
    from spark_rapids_tpu.shuffle.catalog import ShuffleReceivedBufferCatalog
    from spark_rapids_tpu.shuffle.client import ShuffleClient, ShuffleFetchError
    from spark_rapids_tpu.shuffle.transport import (
        TransactionStatus,
        new_transaction,
    )
    from spark_rapids_tpu.shuffle import REQ_METADATA

    class _MetaOnlyConnection(_DeadConnection):
        def request(self, req_type, payload):
            tx = new_transaction()
            if req_type == REQ_METADATA:
                bm = M.BufferMeta(11, 4096, 4096, M.CODEC_NONE)
                tm = M.TableMeta(1, 0, 0, 0, 10, bm, b"")
                tx.complete(
                    TransactionStatus.SUCCESS, M.pack_metadata_response([tm])
                )
            # transfer requests: accepted, but no frames ever delivered
            elif req_type is not None:
                tx.complete(TransactionStatus.SUCCESS, b"")
            return tx

    throttle = InflightThrottle(1 << 20)
    client = ShuffleClient(
        _MetaOnlyConnection(),
        ShuffleReceivedBufferCatalog(),
        throttle=throttle,
        fetch_timeout_s=0.3,
    )
    with pytest.raises(ShuffleFetchError):
        list(client.fetch_blocks([M.BlockId(1, 0, 0, 1)]))
    assert throttle.inflight == 0 or throttle.inflight() == 0


def test_heartbeat_registry_isolated_per_instance():
    """Two heartbeat managers never share peer tables (the suspected
    cross-test flake channel: module-level state would leak peers)."""
    hb1 = ShuffleHeartbeatManager()
    hb2 = ShuffleHeartbeatManager()
    hb1.register_executor("execA", ("127.0.0.1", 1))
    peers2 = hb2.register_executor("execB", ("127.0.0.1", 2))
    assert "execA" not in {p.executor_id for p in peers2}
    peers1 = hb1.register_executor("execC", ("127.0.0.1", 3))
    assert {p.executor_id for p in peers1} == {"execA"}


def test_ici_exchange_skew_escalates_capacity():
    """One key owning ~60% of all rows overflows a chip's receive bucket at
    the input capacity; the escalating exchange must deliver every row
    (reference: windowed sends never drop data — BufferSendState.scala)."""
    import jax

    from jax.sharding import Mesh
    from spark_rapids_tpu.parallel.ici import ici_exchange
    from spark_rapids_tpu.columnar.device import host_to_device
    from spark_rapids_tpu.types import Schema

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))
    n = 8
    rng = np.random.default_rng(11)
    batches = []
    total_rows = 0
    for chip in range(n):
        m = 96
        keys = np.where(rng.random(m) < 0.6, 7, rng.integers(0, 1000, m))
        rb = pa.record_batch({"k": pa.array(keys.astype(np.int64)),
                              "v": pa.array(rng.random(m))})
        batches.append(host_to_device(rb))
        total_rows += m
    schema = batches[0].schema
    out = ici_exchange(mesh, schema, [0], batches)
    assert sum(int(b.row_count()) for b in out) == total_rows
    # every hot-key row landed on exactly one chip
    hot = 0
    per_chip_hot = []
    for b in out:
        rb = device_to_host(b)
        ks = rb.column("k").to_pylist()
        c = sum(1 for k in ks if k == 7)
        per_chip_hot.append(c)
        hot += c
    want_hot = sum(
        1
        for b in batches
        for k in device_to_host(b).column("k").to_pylist()
        if k == 7
    )
    assert hot == want_hot
    assert sum(1 for c in per_chip_hot if c > 0) == 1
