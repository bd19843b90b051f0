"""Unit suite for the crash-safe persistent XLA executable store
(cache/xla_store.py) — ISSUE 11 tentpole.

The contract under test is defensive, not functional: a store that can be
corrupted, truncated, version-skewed, or half-written must degrade to a
fresh compile — never to a crash, and never to a wrong answer. Also
carries the utils/checksum.py parity satellite: the CRC stamps the store
(and both wire protocols) rely on must be input-representation-invariant
and match their reference polynomial on the selected implementation.
"""
from __future__ import annotations

import glob
import os
import struct
import subprocess
import sys
import threading
import time
import zlib

import jax
import numpy as np
import pytest

from spark_rapids_tpu import kernels as K
from spark_rapids_tpu.cache import xla_store as xc
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.obs.metrics import GLOBAL
from spark_rapids_tpu.resilience import faults as F
from spark_rapids_tpu.utils import checksum


@pytest.fixture()
def store(tmp_path):
    s = xc.XlaStore(str(tmp_path / "xc"), max_bytes=0, lock_timeout_s=2.0)
    yield s


@pytest.fixture()
def engine_store(tmp_path):
    """The process-global store, configured the way a session would."""
    conf = TpuConf({
        "spark.rapids.tpu.compileCache.enabled": True,
        "spark.rapids.tpu.compileCache.dir": str(tmp_path / "xc"),
    })
    s = xc.configure(conf)
    assert s is not None
    yield s
    xc.reset_for_tests()
    K.clear()


def _counter(name: str) -> int:
    return GLOBAL.counter(name).value


# ── container format: atomic write + load verification ──────────────────────

def test_put_load_roundtrip_and_lru_touch(store):
    digest = "d" * 64
    payload = os.urandom(4096)
    assert store.put(digest, payload)
    assert store.load(digest) == payload
    # a load touches mtime (the LRU signal)
    old = time.time() - 3600
    os.utime(store.entry_path(digest), (old, old))
    store.load(digest)
    assert os.stat(store.entry_path(digest)).st_mtime > old + 1800


def test_load_missing_is_a_plain_miss(store):
    assert store.load("e" * 64) is None


@pytest.mark.parametrize("cut", ["magic", "header", "payload", "empty"])
def test_truncation_at_every_boundary_quarantines(store, cut):
    """A torn write surviving the rename (or a filesystem lying about
    durability) must quarantine at LOAD time, whatever byte it died on."""
    digest = "a" * 64
    payload = os.urandom(1024)
    assert store.put(digest, payload)
    path = store.entry_path(digest)
    size = os.path.getsize(path)
    cut_at = {
        "magic": 4,                      # inside the magic
        "header": len(xc.MAGIC) + 20,    # inside the header JSON
        "payload": size - 100,           # inside the payload
        "empty": 0,
    }[cut]
    with open(path, "r+b") as f:
        f.truncate(cut_at)
    c0 = _counter("cache.xla.corrupt")
    assert store.load(digest) is None
    assert _counter("cache.xla.corrupt") == c0 + 1
    assert not os.path.exists(path), "damaged entry must leave the cache"
    assert len(os.listdir(store.quarantine_dir)) == 1


def test_bit_flip_in_payload_quarantines(store):
    digest = "b" * 64
    payload = os.urandom(2048)
    assert store.put(digest, payload)
    path = store.entry_path(digest)
    with open(path, "r+b") as f:
        f.seek(-300, os.SEEK_END)
        b = f.read(1)
        f.seek(-300, os.SEEK_END)
        f.write(bytes([b[0] ^ 0x01]))
    c0 = _counter("cache.xla.corrupt")
    assert store.load(digest) is None
    assert _counter("cache.xla.corrupt") == c0 + 1


def test_bit_flip_in_header_quarantines_without_parsing(store):
    digest = "c" * 64
    assert store.put(digest, os.urandom(512))
    path = store.entry_path(digest)
    with open(path, "r+b") as f:
        f.seek(len(xc.MAGIC) + 4 + 5)  # inside the header JSON
        b = f.read(1)
        f.seek(len(xc.MAGIC) + 4 + 5)
        f.write(bytes([b[0] ^ 0xFF]))
    assert store.load(digest) is None
    assert len(os.listdir(store.quarantine_dir)) == 1


def test_version_fence_is_a_silent_miss_never_a_load(store):
    """An entry written by 'different software' (stale-fence injection: a
    format this code does not write) silently misses: no quarantine, no corrupt count, and the
    payload is never parsed — the entry just ages out through LRU."""
    digest = "f" * 64
    inj = F.FaultInjector(F.FaultConfig(cache_stale_version_every_n=1))
    with F.scoped(inj):
        assert store.put(digest, os.urandom(256))
    assert inj.injected.get("cache_stale_version") == 1
    c0 = _counter("cache.xla.corrupt")
    assert store.load(digest) is None
    assert _counter("cache.xla.corrupt") == c0
    assert os.path.exists(store.entry_path(digest))
    assert not os.listdir(store.quarantine_dir)


def test_crash_before_rename_leaves_invisible_orphan(store):
    """The atomic-write protocol's worst crash point: fsynced temp file,
    no rename. The entry must not exist, loads must miss, and a boot
    whose writer pid is dead sweeps the orphan."""
    digest = "9" * 64
    inj = F.FaultInjector(F.FaultConfig(cache_crash_before_rename_every_n=1))
    with F.scoped(inj):
        assert store.put(digest, os.urandom(256)) is False
    assert store.load(digest) is None
    orphans = os.listdir(store.tmp_dir)
    assert len(orphans) == 1
    # our own pid is alive: the sweep must NOT touch an in-flight write
    assert store.sweep_tmp() == 0
    # a dead writer's orphan goes away (pid 2^22+ is not allocatable on
    # this kernel's default pid_max)
    dead = os.path.join(store.tmp_dir, f"{digest}.4999999.1.tmp")
    os.rename(os.path.join(store.tmp_dir, orphans[0]), dead)
    assert store.sweep_tmp() == 1
    assert not os.listdir(store.tmp_dir)


def test_eviction_is_oldest_first_and_spares_the_new_entry(store):
    store.max_bytes = 3000
    for i, age in enumerate((500, 400, 300, 200)):
        d = f"{i:x}" * 64
        assert store.put(d, bytes(1000))
        old = time.time() - age
        os.utime(store.entry_path(d), (old, old))
    e0 = _counter("cache.xla.evicted")
    new = "e" * 64
    assert store.put(new, bytes(1000))
    names = {n for n in os.listdir(store.root) if n.endswith(".xc")}
    assert new + ".xc" in names, "the just-written entry must survive"
    # oldest entries went first
    assert "0" * 64 + ".xc" not in names
    assert _counter("cache.xla.evicted") >= 2


# ── single-flight ───────────────────────────────────────────────────────────

def test_single_flight_blocks_second_acquirer(store):
    digest = "5" * 64
    holder_in = threading.Event()
    release = threading.Event()
    got_b = []

    def holder():
        with store.single_flight(digest) as got:
            assert got
            holder_in.set()
            release.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    assert holder_in.wait(5)
    store.lock_timeout_s = 0.2
    lt0 = _counter("cache.xla.lockTimeouts")
    with store.single_flight(digest) as got:
        got_b.append(got)
    release.set()
    t.join(5)
    assert got_b == [False], "second acquirer should time out, not hang"
    assert _counter("cache.xla.lockTimeouts") == lt0 + 1


def test_wedged_lock_holder_injection_times_out_then_proceeds(store):
    store.lock_timeout_s = 0.1
    inj = F.FaultInjector(F.FaultConfig(
        cache_lock_holder_every_n=1, cache_lock_holder_hold_ms=5000
    ))
    t0 = time.monotonic()
    with F.scoped(inj):
        with store.single_flight("6" * 64) as got:
            pass
    assert not got
    assert time.monotonic() - t0 < 3.0, "must give up at lockTimeout"


# ── stable digests ──────────────────────────────────────────────────────────

def test_digest_stable_and_value_sensitive():
    sig = (("treedef",), ((4,), "float32"))
    d1 = xc.digest_for(("project", 1, "a"), sig)
    d2 = xc.digest_for(("project", 1, "a"), sig)
    d3 = xc.digest_for(("project", 2, "a"), sig)
    assert d1 and d1 == d2
    assert d3 != d1


def test_digest_refuses_address_bearing_identity():
    assert xc.digest_for(("k", object()), ("s",)) is None


def test_digest_hashes_full_ndarray_buffer_not_its_elided_repr():
    """Two large literals whose reprs elide identically must NOT collide:
    a collision here would hand query A query B's executable."""
    a = np.zeros(100_000, dtype=np.int64)
    b = a.copy()
    b[50_000] = 1  # repr-elided middle — repr(a) == repr(b)
    assert repr(a) == repr(b)
    da = xc.digest_for(("k", a), ("s",))
    db = xc.digest_for(("k", b), ("s",))
    assert da and db and da != db


# ── the engine's source in the entry's name ─────────────────────────────────

def _as_source(monkeypatch, digest):
    """Run what follows as the checkout whose source digests to ``digest``
    (``raising=False``: at a commit without the function the tests below
    fail on what they assert, not on the patch)."""
    monkeypatch.setattr(xc, "source_digest", lambda: digest, raising=False)
    K.clear()


def test_digest_names_the_source(monkeypatch):
    key, sig = ("project", 1, "a"), (("treedef",), ((4,), "float32"))
    _as_source(monkeypatch, "a" * 64)
    da = xc.digest_for(key, sig)
    _as_source(monkeypatch, "b" * 64)
    db = xc.digest_for(key, sig)
    assert da and db and da != db
    _as_source(monkeypatch, "a" * 64)
    assert xc.digest_for(key, sig) == da


def test_two_checkouts_share_one_store_side_by_side(engine_store, monkeypatch):
    """The benchmark driver's case: a parent and a change alternate over one
    directory. Each side's entry stays on disk and loads only under its own
    source: neither evicts or overwrites the other's."""
    def make():
        return K.GuardedJit(lambda x: x * 3 + 1)

    x = np.arange(32, dtype=np.int64)
    ref = (x * 3 + 1).tolist()
    for side in ("a" * 64, "b" * 64):
        _as_source(monkeypatch, side)
        m0 = _counter("cache.xla.miss")
        assert np.asarray(K.kernel(("xc-shared", 1), make)(x)).tolist() == ref
        assert _counter("cache.xla.miss") == m0 + 1
    entries = sorted(glob.glob(os.path.join(engine_store.root, "*.xc")))
    assert len(entries) == 2
    before = [(e, open(e, "rb").read()) for e in entries]
    sources = {xc.XlaStore._parse(blob)[0]["source"] for _, blob in before}
    assert sources == {"a" * 64, "b" * 64}, "the header names the source"
    # second and later runs of both sides, in the driver's order: all hits
    s0 = _counter("cache.xla.stores")
    for side in ("a" * 64, "b" * 64, "b" * 64, "a" * 64):
        _as_source(monkeypatch, side)
        h0, m0 = _counter("cache.xla.hit"), _counter("cache.xla.miss")
        assert np.asarray(K.kernel(("xc-shared", 1), make)(x)).tolist() == ref
        assert _counter("cache.xla.hit") == h0 + 1
        assert _counter("cache.xla.miss") == m0
    assert _counter("cache.xla.stores") == s0, "nothing was written again"
    assert [(e, open(e, "rb").read()) for e in entries] == before


def test_changed_body_under_unchanged_key_compiles_anew(
    engine_store, monkeypatch
):
    """A kernel's body changes; its cache key and tag do not. The store an
    earlier checkout filled must not answer for it."""
    x = np.arange(32, dtype=np.int64)
    _as_source(monkeypatch, "a" * 64)
    old = K.kernel(("xc-body", 1), lambda: K.GuardedJit(lambda x: x * 3 + 1))
    assert np.asarray(old(x)).tolist() == (x * 3 + 1).tolist()
    _as_source(monkeypatch, "b" * 64)
    h0, m0 = _counter("cache.xla.hit"), _counter("cache.xla.miss")
    new = K.kernel(("xc-body", 1), lambda: K.GuardedJit(lambda x: x * 5 + 2))
    assert np.asarray(new(x)).tolist() == (x * 5 + 2).tolist()
    assert _counter("cache.xla.hit") == h0
    assert _counter("cache.xla.miss") == m0 + 1
    assert engine_store.stats()["entries"] == 2


def _tree(root, files):
    for rel, data in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(data)
    return str(root)


_TREE = {"__init__.py": b"", "ops/a.py": b"x = 1\n", "ops/b.py": b"y = 2\n"}


def test_tree_digest_is_stable(tmp_path):
    one = _tree(tmp_path / "one", _TREE)
    two = _tree(tmp_path / "two", dict(reversed(list(_TREE.items()))))
    assert xc._digest_tree(one) == xc._digest_tree(one) == xc._digest_tree(two)


@pytest.mark.parametrize("edit", ["byte", "rename", "move_bytes", "new_file"])
def test_tree_digest_changes_with_the_source(tmp_path, edit):
    files = dict(_TREE)
    if edit == "byte":
        files["ops/a.py"] = b"x = 2\n"
    elif edit == "rename":
        files["ops/c.py"] = files.pop("ops/a.py")
    elif edit == "move_bytes":  # the same bytes, split between files elsewhere
        files["ops/a.py"], files["ops/b.py"] = b"x = 1\ny", b" = 2\n"
    else:
        files["ops/d.py"] = b""
    assert xc._digest_tree(_tree(tmp_path / "a", _TREE)) != xc._digest_tree(
        _tree(tmp_path / "b", files)
    )


def test_tree_digest_ignores_pycache_and_other_files(tmp_path):
    extra = dict(_TREE)
    extra["ops/__pycache__/a.cpython-312.pyc"] = b"\x00" * 8
    extra["ops/__pycache__/stray.py"] = b"z = 3\n"
    extra["native/lib.so"] = b"\x7fELF"
    assert xc._digest_tree(_tree(tmp_path / "a", _TREE)) == xc._digest_tree(
        _tree(tmp_path / "b", extra)
    )


def test_source_digest_is_the_packages_and_the_same_in_another_process():
    import spark_rapids_tpu

    here = xc.source_digest()
    assert here and here == xc.source_digest()
    assert here == xc._digest_tree(os.path.dirname(spark_rapids_tpu.__file__))
    out = subprocess.run(
        [sys.executable, "-c",
         "from spark_rapids_tpu.cache import xla_store as xc;"
         "print(xc.source_digest())"],
        capture_output=True, text=True, timeout=240, check=True,
        cwd=os.path.dirname(os.path.dirname(spark_rapids_tpu.__file__)),
    )
    assert out.stdout.split()[-1] == here


@pytest.mark.parametrize("how", ["no_source", "unreadable", "missing"])
def test_unreadable_package_has_no_digest(tmp_path, how):
    root = tmp_path / "pkg"
    if how == "no_source":  # an import from an archive: a directory of .pyc
        _tree(root, {"__pycache__/a.pyc": b"\x00", "data.bin": b"\x01"})
    elif how == "unreadable":
        _tree(root, _TREE)
        os.symlink(str(root / "gone.py"), str(root / "ops" / "dangling.py"))
    assert xc._digest_tree(str(root)) is None


def test_without_a_source_digest_kernels_stay_memory_only(
    engine_store, monkeypatch
):
    _as_source(monkeypatch, None)
    assert xc.digest_for(("project", 1), ("s",)) is None
    x = np.arange(8, dtype=np.int64)
    s0, m0 = _counter("cache.xla.stores"), _counter("cache.xla.miss")
    g = K.kernel(("xc-nosrc", 1), lambda: K.GuardedJit(lambda x: x - 4))
    assert np.asarray(g(x)).tolist() == (x - 4).tolist()
    assert np.asarray(g(x)).tolist() == (x - 4).tolist()
    assert engine_store.stats()["entries"] == 0
    assert _counter("cache.xla.stores") == s0
    assert _counter("cache.xla.miss") == m0, "the store is never consulted"


# ── deserialize-failure breaker ─────────────────────────────────────────────

def test_repeated_deserialize_failures_trip_the_load_breaker(engine_store):
    digest = "7" * 64
    # a CRC-valid entry whose payload is NOT a pickled executable
    assert engine_store.put(digest, b"not a pickle at all")
    f0 = _counter("cache.xla.deserializeFailures")
    assert xc.load_executable(digest) is None
    assert _counter("cache.xla.deserializeFailures") == f0 + 1
    # the poison entry was quarantined so the rebuild cannot reload it
    assert not os.path.exists(engine_store.entry_path(digest))
    # two more strikes open the breaker: loads disabled for the process
    for i in (1, 2):
        d = str(i) * 64
        engine_store.put(d, b"poison")
        xc.load_executable(d)
    assert xc.loads_disabled()
    good = "8" * 64
    engine_store.put(good, b"payload")
    h0 = _counter("cache.xla.hit")
    assert xc.load_executable(good) is None, "breaker open: no loads"
    assert _counter("cache.xla.hit") == h0


# ── end-to-end through GuardedJit ───────────────────────────────────────────

def test_guarded_jit_roundtrip_and_corruption_rebuild(engine_store):
    """A fresh 'process' (cleared kernel cache) loads the published
    executable; a truncated entry quarantines and rebuilds; results stay
    bit-identical throughout."""
    def make():
        return K.GuardedJit(lambda x: x * 3 + 1)

    x = np.arange(32, dtype=np.int64)
    ref = (x * 3 + 1).tolist()
    g1 = K.kernel(("xc-e2e", 1), make)
    assert np.asarray(g1(x)).tolist() == ref
    assert engine_store.stats()["entries"] == 1

    K.clear()
    h0 = _counter("cache.xla.hit")
    g2 = K.kernel(("xc-e2e", 1), make)
    assert np.asarray(g2(x)).tolist() == ref
    assert _counter("cache.xla.hit") == h0 + 1

    entry = glob.glob(os.path.join(engine_store.root, "*.xc"))[0]
    with open(entry, "r+b") as f:
        f.truncate(os.path.getsize(entry) // 2)
    K.clear()
    c0 = _counter("cache.xla.corrupt")
    g3 = K.kernel(("xc-e2e", 1), make)
    assert np.asarray(g3(x)).tolist() == ref
    assert _counter("cache.xla.corrupt") == c0 + 1
    assert engine_store.stats()["entries"] == 1, "rebuild must republish"


def test_proving_failure_recovers_without_flock_self_contention(
    engine_store,
):
    """A fleet peer published a CRC-valid entry whose executable blows up
    on its proving run INSIDE the first-call single-flight. The fallback
    must quarantine and recompile while still holding the flight slot —
    re-entering the flock from the same process would self-contend and
    burn the whole lockTimeout under the compile lock."""
    def make():
        return K.GuardedJit(lambda x: x + 7)

    x = np.arange(8, dtype=np.int64)
    ref = (x + 7).tolist()
    g1 = K.kernel(("xc-prove", 1), make)
    assert np.asarray(g1(x)).tolist() == ref
    entry = glob.glob(os.path.join(engine_store.root, "*.xc"))[0]
    digest = os.path.basename(entry)[:-3]
    # a VALID executable for a different program (wrong shape/dtype):
    # deserializes fine, blows up only on its proving run with our args
    wrong = jax.jit(lambda y: y * 2.0).lower(
        jax.ShapeDtypeStruct((4,), np.float32)
    ).compile()
    payload = xc.serialize_executable(wrong)
    assert payload is not None
    assert engine_store.put(digest, payload)
    engine_store.lock_timeout_s = 30.0  # a re-entry bug would eat this
    K.clear()
    f0 = _counter("cache.xla.deserializeFailures")
    lt0 = _counter("cache.xla.lockTimeouts")
    t0 = time.monotonic()
    g2 = K.kernel(("xc-prove", 1), make)
    assert np.asarray(g2(x)).tolist() == ref
    assert time.monotonic() - t0 < 10.0, (
        "poison fallback burned the single-flight lockTimeout "
        "(flock re-entry self-contention)"
    )
    assert _counter("cache.xla.deserializeFailures") == f0 + 1
    assert _counter("cache.xla.lockTimeouts") == lt0
    assert engine_store.stats()["quarantined"] >= 1


def test_fleet_warm_single_flight_compiles_once(engine_store):
    """Two 'servers' (threads with separate GuardedJits over the same
    kernel identity) warm the same shape concurrently against one cache
    dir: the single-flight must make one compile+publish and one store
    load — the fleet cold-boot dedup warm() is documented to give."""
    spec = jax.ShapeDtypeStruct((32,), np.float64)
    gjs = [K.GuardedJit(lambda x: x * 1.5, store_key=("xc-fleet", 1))
           for _ in range(2)]
    s0 = _counter("cache.xla.stores")
    h0 = _counter("cache.xla.hit")
    threads = [threading.Thread(target=g.warm, args=(spec,)) for g in gjs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert _counter("cache.xla.stores") == s0 + 1, (
        "fleet warm published more than one entry for one shape"
    )
    assert _counter("cache.xla.hit") == h0 + 1, (
        "the second warmer should have loaded the first's publish"
    )


def test_warm_disk_hit_short_circuits_the_compile_lock(engine_store):
    """The satellite: a warm whose executable is a disk hit completes
    while ANOTHER thread holds the global compile serialization lock —
    warm restarts must not queue deserializations behind slow compiles."""
    spec = jax.ShapeDtypeStruct((16,), np.float64)

    def make():
        return K.GuardedJit(lambda x: x * 2.5)

    g1 = K.kernel(("xc-warmlock", 1), make)
    assert g1.warm(spec) is True  # compiles + publishes

    K.clear()
    g2 = K.kernel(("xc-warmlock", 1), make)
    lock_held = threading.Event()
    release = threading.Event()

    def hold_compile_lock():
        with K._COMPILE_LOCK:
            lock_held.set()
            release.wait(10)

    holder = threading.Thread(target=hold_compile_lock, daemon=True)
    holder.start()
    assert lock_held.wait(5)
    result: list = []
    worker = threading.Thread(target=lambda: result.append(g2.warm(spec)))
    worker.start()
    worker.join(5)
    release.set()
    holder.join(5)
    assert result == [True], (
        "a disk-hit warm blocked on the compile lock (or failed)"
    )


# ── utils/checksum.py parity satellite ──────────────────────────────────────

_FRAMES = [b"", b"\x00", b"abc", bytes(range(256)) * 7, os.urandom(4096)]


def test_frame_checksum_is_input_representation_invariant():
    """bytes / bytearray / memoryview of the same frame must stamp
    identically — both wire protocols hand the checksum whatever view the
    framing layer happens to hold."""
    for frame in _FRAMES:
        stamps = {
            checksum.frame_checksum(frame),
            checksum.frame_checksum(bytearray(frame)),
            checksum.frame_checksum(memoryview(bytes(frame))),
        }
        assert len(stamps) == 1
        stamp = stamps.pop()
        assert 0 <= stamp <= 0xFFFFFFFF
        assert stamp == checksum.frame_checksum(frame)  # deterministic


def _crc32c_reference(data: bytes) -> int:
    """Bit-by-bit CRC32C (Castagnoli, reflected poly 0x82F63B78) — the
    independent oracle the native implementation must match."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    return crc ^ 0xFFFFFFFF


def test_checksum_impl_matches_its_reference_polynomial():
    """Whichever implementation checksum.py selected at import must agree
    with an independent computation of ITS polynomial on the same frames:
    the zlib fallback with zlib.crc32, a native CRC32C with the bitwise
    Castagnoli reference. (The two polynomials are per-fleet constants —
    docs/operations.md — so cross-impl parity is parity-with-reference,
    not crc32==crc32c.)"""
    for frame in _FRAMES:
        got = checksum.frame_checksum(frame)
        if checksum.IMPL == "zlib-crc32":
            assert got == zlib.crc32(frame) & 0xFFFFFFFF
        else:
            assert got == _crc32c_reference(frame), checksum.IMPL


def test_entry_survives_checksum_impl_equivalence(store):
    """The store's on-disk CRC stamps verify through the same module that
    wrote them even for header-sized and payload-sized frames crossing
    the struct packing — a straight re-read of a just-written entry."""
    digest = "ab" * 32
    payload = os.urandom(8192)
    assert store.put(digest, payload)
    blob = open(store.entry_path(digest), "rb").read()
    header, parsed = xc.XlaStore._parse(blob)
    assert parsed == payload
    assert header["digest"] == digest
    (hlen,) = struct.unpack_from("<I", blob, len(xc.MAGIC))
    assert hlen == len(
        blob
    ) - len(xc.MAGIC) - 4 - 4 - len(payload) - 4
