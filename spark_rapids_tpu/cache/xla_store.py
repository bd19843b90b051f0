"""Crash-safe, multi-process, on-disk XLA executable store.

The compile wall is the engine's biggest latency lie (ROADMAP item 1):
every process boot re-pays 6–90s of first-touch XLA compiles per query
shape, so routine restarts — the defining event of a serving fleet — cost
minutes of cold latency. This store closes the wall the way the reference
ships pre-built cuDF kernels: ``kernels.GuardedJit`` serializes compiled
executables (JAX AOT ``lower(...).compile()`` + executable serialization)
and a restarted server deserializes them in milliseconds.

Robustness is the headline, not the cache. A store that can be corrupted,
version-skewed, or half-written must degrade to a fresh compile — never to
a crash, and never to a wrong answer:

- **Entry identity** is a SHA-256 over the engine's own source
  (``source_digest``) and a *stable structural fingerprint* of the
  kernel's cache key (the same structural identity discipline as
  ``plan/reuse.py::canonical_key``: frozen expression trees, schema
  signatures, batch geometry from the jit arg signature). Anything whose
  identity cannot be proven stable across processes (an ``id()``-bearing
  repr, an elided ndarray repr, a package whose source cannot be read)
  makes the kernel non-persistable — a false MISS is duplicate work; a
  false HIT would be a wrong executable.
- **Staleness is decided here and nowhere else.** An executable is served
  only to the source that compiled it: ``source_digest`` is part of the
  entry's NAME, so a kernel whose body changes under an unchanged cache
  key misses, and no call site carries a revision in its key tag (tags
  name kernels). In the name and not the header, because a mismatching
  header is a miss followed by an overwrite: two checkouts sharing one
  directory (a benchmark's parent and change, a fleet mid-upgrade) would
  evict each other's entries at every boot. Under distinct names their
  entries lie side by side and the older ones age out through the LRU.
  The whole package and not finer: a digest of each kernel's lowered
  text would be exact but needs the trace and the lowering this store
  exists to skip, and a per-module reachability digest is a second thing
  to keep right. The price is that any change to the package re-lowers
  every kernel once per checkout; jax's own cache (keyed by the HLO, so
  never stale) still serves the compile of every unchanged program that
  took long enough to be kept there.
- **Version fencing**: the entry header records format version,
  jax/jaxlib versions, backend platform and platform fingerprint. ANY
  mismatch is a silent miss — the payload is never even deserialized
  (deserialization is pickle; feeding it bytes written by a different
  software version is how caches turn into crash loops).
- **Atomic writes**: temp file in ``tmp/`` + fsync + ``os.replace``; a
  crash between temp and rename leaves an orphan that no load ever sees
  and a later boot sweeps (dead-pid detection).
- **Corruption quarantine**: CRC32C (utils/checksum.py) over header and
  payload; a bad entry moves to ``quarantine/`` (operator triage — see
  docs/operations.md), counts ``cache.xla.corrupt``, and the kernel
  rebuilds fresh.
- **Deserialize-failure breaker**: an entry that passes its CRC but fails
  to deserialize (or blows up on its first proving run) is quarantined,
  and repeated failures trip a PR-3 ``CircuitBreaker`` that disables
  loads for the rest of the process — a poisoned cache degrades the
  fleet to cold compiles, not to a retry storm.
- **Cross-process single-flight**: N servers sharing one cache dir take a
  per-entry ``flock`` while compiling, so each shape compiles once per
  fleet; ``flock`` dies with its holder, and a wedged holder is bounded
  by ``compileCache.lockTimeout`` (timeout → compile anyway; availability
  over dedup).
- **Bounded disk**: ``compileCache.maxBytes`` with mtime-LRU eviction
  (loads touch their entry's mtime).

Every failure path in this module is best-effort by design: the store is
an optimization layered UNDER the existing first-touch compile path, and
nothing here may fail a query.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import logging
import os
import re
import struct
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Optional

from ..obs import metrics as obs_metrics
from ..utils.checksum import frame_checksum

log = logging.getLogger(__name__)

#: on-disk container format revision — bump on any layout change
FORMAT_VERSION = 2
MAGIC = b"SRTXC01\n"
_ENTRY_EXT = ".xc"

_M_HIT = obs_metrics.GLOBAL.counter("cache.xla.hit")
_M_MISS = obs_metrics.GLOBAL.counter("cache.xla.miss")
_M_STORES = obs_metrics.GLOBAL.counter("cache.xla.stores")
_M_STORE_NS = obs_metrics.GLOBAL.timer("cache.xla.storeNs")
_M_LOAD_NS = obs_metrics.GLOBAL.timer("cache.xla.loadNs")
_M_EVICTED = obs_metrics.GLOBAL.counter("cache.xla.evicted")
_M_CORRUPT = obs_metrics.GLOBAL.counter("cache.xla.corrupt")
_M_DESER_FAIL = obs_metrics.GLOBAL.counter("cache.xla.deserializeFailures")
_M_LOCK_TIMEOUTS = obs_metrics.GLOBAL.counter("cache.xla.lockTimeouts")


# ── version fence ───────────────────────────────────────────────────────────

_FENCE: Optional[dict] = None


def fence() -> dict:
    """The version/platform fingerprint stamped into every entry header and
    compared EXACTLY on load. Computed once per process."""
    global _FENCE
    if _FENCE is None:
        import jax
        import jaxlib

        try:
            devs = jax.devices()
            dev = devs[0]
            backend = dev.platform
            platform_version = str(getattr(dev.client, "platform_version", ""))
            device_kind = str(getattr(dev, "device_kind", ""))
            n_devices = len(devs)
        except Exception:  # noqa: BLE001 - no backend = no fence = no store
            backend, platform_version, device_kind, n_devices = (
                "unknown", "", "", 0,
            )
        _FENCE = {
            "format": FORMAT_VERSION,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": backend,
            "platform_version": platform_version,
            "device_kind": device_kind,
            # sharded executables encode a device assignment; a store dir
            # must never hand an 8-chip binary to a 1-chip boot
            "device_count": n_devices,
        }
    return _FENCE


# ── the engine's source ─────────────────────────────────────────────────────

def _digest_tree(root: str) -> Optional[str]:
    """SHA-256 over the sorted relative paths and the bytes of every ``.py``
    file under ``root`` (``__pycache__`` skipped), or None when the tree
    cannot be read or holds no source (an import from an archive)."""
    paths = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                full = os.path.join(dirpath, name)
                rel = os.path.relpath(full, root).replace(os.sep, "/")
                paths.append((rel, full))
    if not paths:
        return None
    h = hashlib.sha256()
    try:
        for rel, full in sorted(paths):
            with open(full, "rb") as f:
                data = f.read()
            # lengths frame the stream: no two trees render alike
            h.update(f"{rel}\0{len(data)}\0".encode())
            h.update(data)
    except OSError:
        return None
    return h.hexdigest()


@functools.lru_cache(maxsize=1)
def source_digest() -> Optional[str]:
    """The digest of this package's source, read once per process: the part
    of an entry's name that ties an executable to the code that compiled
    it (module docstring). None when the source cannot be read; no kernel
    is persisted then."""
    import spark_rapids_tpu as package

    try:
        return _digest_tree(os.path.dirname(os.path.abspath(package.__file__)))
    except Exception:  # noqa: BLE001 - no source = no store
        return None


# ── stable structural fingerprint ───────────────────────────────────────────

class _Unstable(Exception):
    """The object's identity cannot be proven stable across processes."""


#: default-object reprs embed the instance address — never stable
_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


def _fingerprint(obj, out: list, depth: int = 0) -> None:
    """Append a stable byte rendering of ``obj`` to ``out``.

    Mirrors the comparability discipline of ``plan/reuse.py::_val_key``:
    primitives and frozen dataclasses (expression trees) render
    structurally; ndarrays hash their full buffer (a repr would ELIDE
    large literals — two different constants could collide, and a digest
    collision here means loading the wrong executable); anything else
    falls back to repr, rejected when it carries an address or an
    elision. Raising ``_Unstable`` anywhere disables the store for that
    kernel — a safe false miss."""
    if depth > 64:
        raise _Unstable("nesting too deep")
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        out.append(b"P" + repr(obj).encode())
        return
    if isinstance(obj, (tuple, list)):
        out.append(b"T(" if isinstance(obj, tuple) else b"L(")
        for x in obj:
            _fingerprint(x, out, depth + 1)
        out.append(b")")
        return
    if isinstance(obj, (set, frozenset)):
        # order-normalize: the same set must digest identically across
        # processes (iteration order is insertion/hash dependent)
        parts = []
        for x in obj:
            sub: list = []
            _fingerprint(x, sub, depth + 1)
            parts.append(b"".join(sub))
        out.append(b"S(" + b"".join(sorted(parts)) + b")")
        return
    if isinstance(obj, dict):
        out.append(b"D(")
        try:
            items = sorted(obj.items())
        except TypeError as e:
            raise _Unstable(f"unorderable dict keys: {e}") from None
        for k, v in items:
            _fingerprint(k, out, depth + 1)
            _fingerprint(v, out, depth + 1)
        out.append(b")")
        return
    if isinstance(obj, type):
        out.append(f"C{obj.__module__}.{obj.__qualname__}".encode())
        return
    import numpy as np

    if isinstance(obj, np.ndarray):
        out.append(
            b"A"
            + repr((obj.shape, str(obj.dtype))).encode()
            + hashlib.sha256(np.ascontiguousarray(obj).tobytes()).digest()
        )
        return
    if isinstance(obj, np.generic):
        out.append(b"S" + repr((str(obj.dtype), obj.item())).encode())
        return
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out.append(f"@{type(obj).__module__}.{type(obj).__qualname__}(".encode())
        for f in dataclasses.fields(obj):
            out.append(f.name.encode() + b"=")
            _fingerprint(getattr(obj, f.name), out, depth + 1)
        out.append(b")")
        return
    from ..types import Schema as _Schema

    if isinstance(obj, _Schema):
        # Schema's repr omits field NULLABILITY, but the jit pytree
        # metadata (and so the proving run) distinguishes it: digest the
        # StructFields structurally instead, or two kernels differing
        # only in nullable flags share an entry and quarantine-thrash it
        out.append(b"H(")
        for f in obj.fields:
            _fingerprint(f, out, depth + 1)
        out.append(b")")
        return
    r = repr(obj)
    if _ADDR_RE.search(r) or "..." in r:
        raise _Unstable(f"unstable repr for {type(obj).__name__}")
    out.append(b"R" + f"{type(obj).__module__}.{type(obj).__qualname__}:".encode()
               + r.encode())


def digest_for(key, sig) -> Optional[str]:
    """SHA-256 hex entry name for a kernel's (engine source, cache key, jit
    arg signature), or None when the source cannot be read or any component
    resists a stable rendering."""
    source = source_digest()
    if source is None:
        return None
    out: list = []
    try:
        _fingerprint((source, key, sig), out)
    except _Unstable:
        return None
    except Exception:  # noqa: BLE001 - identity failure = safe miss
        return None
    return hashlib.sha256(b"".join(out)).hexdigest()


# ── the store ───────────────────────────────────────────────────────────────

class XlaStore:
    """One cache directory: ``<root>/*.xc`` entries, ``tmp/`` staging,
    ``locks/`` single-flight files, ``quarantine/`` triage."""

    def __init__(self, root: str, max_bytes: int, lock_timeout_s: float):
        self.root = root
        self.max_bytes = max(0, int(max_bytes))
        self.lock_timeout_s = max(0.0, float(lock_timeout_s))
        self.tmp_dir = os.path.join(root, "tmp")
        self.lock_dir = os.path.join(root, "locks")
        self.quarantine_dir = os.path.join(root, "quarantine")
        for d in (root, self.tmp_dir, self.lock_dir, self.quarantine_dir):
            os.makedirs(d, exist_ok=True)
        self._tmp_seq = 0
        self._seq_lock = threading.Lock()
        self.sweep_tmp()

    # ── paths ───────────────────────────────────────────────────────────
    def entry_path(self, digest: str) -> str:
        return os.path.join(self.root, digest + _ENTRY_EXT)

    # ── load ────────────────────────────────────────────────────────────
    def load(self, digest: str) -> Optional[bytes]:
        """Verified payload bytes for ``digest``, or None (miss). Fence
        mismatch = silent miss; structural damage or CRC mismatch =
        quarantine + ``cache.xla.corrupt``. Never raises."""
        path = self.entry_path(digest)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        try:
            header, payload = self._parse(blob)
        except _Corrupt as e:
            self._quarantine(path, str(e))
            return None
        except Exception as e:  # noqa: BLE001 - unexpected = corrupt
            self._quarantine(path, f"unparseable entry: {e}")
            return None
        if header.get("fence") != fence():
            # version fencing: written by different software — silently
            # miss WITHOUT touching the payload (never a load attempt);
            # the stale entry ages out through LRU eviction
            return None
        try:
            os.utime(path)  # LRU touch
        except OSError:
            pass
        return payload

    @staticmethod
    def _parse(blob: bytes):
        if len(blob) < len(MAGIC) + 4 or not blob.startswith(MAGIC):
            raise _Corrupt("bad magic / truncated preamble")
        off = len(MAGIC)
        (hlen,) = struct.unpack_from("<I", blob, off)
        off += 4
        if hlen <= 0 or off + hlen + 4 > len(blob):
            raise _Corrupt("header overruns file")
        hbytes = blob[off:off + hlen]
        off += hlen
        (hcrc,) = struct.unpack_from("<I", blob, off)
        off += 4
        if frame_checksum(hbytes) != hcrc:
            raise _Corrupt("header CRC mismatch")
        try:
            header = json.loads(hbytes.decode("utf-8"))
        except Exception as e:
            raise _Corrupt(f"header JSON: {e}") from None
        plen = int(header.get("payload_len", -1))
        if plen < 0 or off + plen + 4 != len(blob):
            raise _Corrupt("payload length disagrees with file size")
        payload = blob[off:off + plen]
        (pcrc,) = struct.unpack_from("<I", blob, off + plen)
        if frame_checksum(payload) != pcrc:
            raise _Corrupt("payload CRC mismatch")
        return header, payload

    # ── store ───────────────────────────────────────────────────────────
    def put(self, digest: str, payload: bytes) -> bool:
        """Atomically publish ``payload`` under ``digest``: temp file +
        fsync + rename, then evict to the disk budget. Returns False (and
        cleans up) on any IO failure — a failed store is a future miss,
        nothing more."""
        from ..resilience import faults as _faults

        # `source` is for an operator's triage and is not compared: the
        # digest already holds it (module docstring)
        hdr = dict(fence=fence(), digest=digest, payload_len=len(payload),
                   created=int(time.time()), source=source_digest())
        if _faults.cache_stale_fence():
            # chaos: an entry written by "different software" — the load
            # path must fence it into a silent miss
            hdr["fence"] = dict(hdr["fence"], format=FORMAT_VERSION + 1_000_000)
        hbytes = json.dumps(hdr, sort_keys=True).encode("utf-8")
        blob = b"".join((
            MAGIC,
            struct.pack("<I", len(hbytes)),
            hbytes,
            struct.pack("<I", frame_checksum(hbytes)),
            payload,
            struct.pack("<I", frame_checksum(payload)),
        ))
        with self._seq_lock:
            self._tmp_seq += 1
            seq = self._tmp_seq
        tmp = os.path.join(self.tmp_dir, f"{digest}.{os.getpid()}.{seq}.tmp")
        final = self.entry_path(digest)
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            if _faults.cache_crash_before_rename():
                # chaos: the process "died" between temp and rename — the
                # orphan temp file must never serve a load and must be
                # swept by a later boot
                return False
            os.replace(tmp, final)
            self._fsync_dir(self.root)
        except OSError as e:
            log.debug("compile-cache put failed (ignored): %s", e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        damage = _faults.cache_post_write_damage()
        if damage == "truncate":
            try:
                with open(final, "r+b") as f:
                    f.truncate(max(len(MAGIC), len(blob) // 2))
            except OSError:
                pass
        elif damage == "corrupt":
            try:
                with open(final, "r+b") as f:
                    # flip a byte inside the payload region so the payload
                    # CRC — not the header parse — is what catches it
                    pos = len(blob) - 4 - max(1, len(payload) // 2)
                    f.seek(pos)
                    b = f.read(1)
                    f.seek(pos)
                    f.write(bytes([b[0] ^ 0xFF]))
            except OSError:
                pass
        _M_STORES.add(1)
        self.evict_to_budget(keep=final)
        return True

    @staticmethod
    def _fsync_dir(path: str) -> None:
        try:
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass

    # ── quarantine / eviction / sweeping ────────────────────────────────
    def _quarantine(self, path: str, reason: str) -> None:
        _M_CORRUPT.add(1)
        dst = os.path.join(
            self.quarantine_dir,
            f"{os.path.basename(path)}.{int(time.time() * 1e3)}",
        )
        try:
            os.replace(path, dst)
            log.warning(
                "compile-cache entry quarantined (%s): %s -> %s",
                reason, path, dst,
            )
        except OSError:
            try:
                os.unlink(path)
            except OSError:
                pass

    def quarantine_digest(self, digest: str, reason: str) -> None:
        """Quarantine an entry whose damage surfaced AFTER the CRC gate
        (deserialize failure, first-run blowup) so the rebuild's store
        consult cannot reload the same poison."""
        path = self.entry_path(digest)
        if os.path.exists(path):
            self._quarantine(path, reason)

    def evict_to_budget(self, keep: Optional[str] = None) -> int:
        """Oldest-mtime-first eviction down to ``max_bytes`` (0 = no
        bound). Loads touch mtime, so this approximates LRU. The entry
        just written (``keep``) is never the victim."""
        if self.max_bytes <= 0:
            return 0
        entries = []
        total = 0
        try:
            names = os.listdir(self.root)
        except OSError:
            return 0
        for name in names:
            if not name.endswith(_ENTRY_EXT):
                continue
            p = os.path.join(self.root, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, p))
            total += st.st_size
        evicted = 0
        for _mtime, size, p in sorted(entries):
            if total <= self.max_bytes:
                break
            if p == keep:
                continue
            try:
                os.unlink(p)
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            _M_EVICTED.add(evicted)
        return evicted

    def sweep_tmp(self) -> int:
        """Remove orphaned staging files: a crash between temp and rename
        leaves ``<digest>.<pid>.<seq>.tmp`` behind. A file whose writer
        pid is dead (or that is over a day old) is garbage."""
        removed = 0
        try:
            names = os.listdir(self.tmp_dir)
        except OSError:
            return 0
        now = time.time()
        for name in names:
            p = os.path.join(self.tmp_dir, name)
            pid = _writer_pid(name)
            if pid == os.getpid():
                continue
            if pid is not None and _pid_alive(pid):
                try:
                    if now - os.stat(p).st_mtime < 86400.0:
                        continue
                except OSError:
                    continue
            try:
                os.unlink(p)
                removed += 1
            except OSError:
                pass
        return removed

    # ── cross-process single-flight ─────────────────────────────────────
    @contextmanager
    def single_flight(self, digest: str):
        """Per-entry advisory ``flock`` so N processes sharing the dir
        compile a missing shape once. Yields True when the lock is held;
        a holder that outlives ``lock_timeout_s`` forfeits the dedup and
        the caller compiles anyway (``cache.xla.lockTimeouts``) — flock
        itself dies with its holder, so a CRASHED holder never blocks
        anyone past its own death."""
        from ..resilience import faults as _faults

        path = os.path.join(self.lock_dir, digest + ".lock")
        hold_ms = _faults.cache_lock_holder_ms()
        if hold_ms > 0:
            # chaos: a wedged peer holds this entry's lock from another fd
            # (flock contends across fds) and releases only after hold_ms
            self._wedge_lock(path, hold_ms)
        try:
            f = open(path, "ab")
        except OSError:
            yield False
            return
        got = False
        try:
            import fcntl

            deadline = time.monotonic() + self.lock_timeout_s
            while True:
                try:
                    fcntl.flock(f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
                    got = True
                    break
                except OSError:
                    if time.monotonic() >= deadline:
                        _M_LOCK_TIMEOUTS.add(1)
                        log.warning(
                            "compile-cache single-flight lock for %s held "
                            "past %.1fs; compiling without dedup",
                            digest[:12], self.lock_timeout_s,
                        )
                        break
                    time.sleep(0.05)
            yield got
        except ImportError:
            yield False
        finally:
            try:
                if got:
                    import fcntl

                    fcntl.flock(f.fileno(), fcntl.LOCK_UN)
            except OSError:
                pass
            finally:
                # close unconditionally — even a non-OSError out of the
                # unlock (or a cancellation landing there) must not
                # leak the lock-file fd
                f.close()

    @staticmethod
    def _wedge_lock(path: str, hold_ms: float) -> None:
        try:
            import fcntl

            # graft: ok(resource-lifecycle: flock on the next line raises
            # OSError only, and that handler closes wf — the unmatched-
            # exception edge the CFG also sees cannot fire in practice)
            wf = open(path, "ab")
        except OSError:
            return
        try:
            fcntl.flock(wf.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            # the entry is already locked — the wedge scenario is moot,
            # but the opened lock-file fd must not leak with it
            wf.close()
            return

        def _release():
            time.sleep(hold_ms / 1e3)
            try:
                wf.close()  # closing the fd releases the flock
            except OSError:
                pass

        threading.Thread(
            target=_release, name="srt-cache-wedge", daemon=True
        ).start()

    # ── reporting ───────────────────────────────────────────────────────
    def stats(self) -> dict:
        entries = bytes_total = quarantined = 0
        try:
            for name in os.listdir(self.root):
                if name.endswith(_ENTRY_EXT):
                    entries += 1
                    try:
                        bytes_total += os.stat(
                            os.path.join(self.root, name)
                        ).st_size
                    except OSError:
                        pass
            quarantined = len(os.listdir(self.quarantine_dir))
        except OSError:
            pass
        return {
            "dir": self.root,
            "entries": entries,
            "bytes": bytes_total,
            "max_bytes": self.max_bytes,
            "quarantined": quarantined,
        }


class _Corrupt(Exception):
    pass


def _writer_pid(tmp_name: str) -> Optional[int]:
    parts = tmp_name.split(".")
    if len(parts) >= 3:
        try:
            return int(parts[-3])
        except ValueError:
            return None
    return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except OSError:
        return True
    return True


# ── process-global configuration ────────────────────────────────────────────

_STORE: Optional[XlaStore] = None  # graft: guarded_by(_STORE_LOCK)
_STORE_LOCK = threading.Lock()

#: XLA:CPU deserializes through the same native loader the compiler uses —
#: serialize loads like compiles (the known concurrent-compile fragility),
#: so loads there go one at a time. They do NOT ride the kernel compile
#: lock: a disk hit must never queue behind a peer's 90s compile (the
#: warm-restart short-circuit).
_LOAD_LOCK = threading.Lock()


def default_dir() -> str:
    """``xc-<backend>`` under the root both compile caches share
    (kernels.compile_cache_root)."""
    from ..kernels import compile_cache_root

    return os.path.join(compile_cache_root(), "xc-" + fence()["backend"])


def configure(conf) -> Optional[XlaStore]:
    """(Re)build the process-global store from the session conf. Sessions
    share one store (like the kernel cache the store backs); reconfiguring
    with the same settings is a no-op. A store that was asked for and
    cannot be set up raises."""
    global _STORE
    from .. import config as cfg

    enabled = cfg.COMPILE_CACHE_ENABLED.get(conf)
    if (
        os.environ.get("SPARK_RAPIDS_TPU_NO_PERSISTENT_CACHE")
        and conf.get_raw(cfg.COMPILE_CACHE_ENABLED.key) is None
    ):
        # the test-env escape hatch (tests/conftest.py) keeps implicit
        # caching off; an EXPLICIT conf still wins — that is how the
        # store's own tests opt in
        enabled = False
    if not enabled:
        with _STORE_LOCK:
            _STORE = None
        return None
    root = cfg.COMPILE_CACHE_DIR.get(conf) or default_dir()
    max_bytes = cfg.COMPILE_CACHE_MAX_BYTES.get(conf)
    lock_timeout = cfg.COMPILE_CACHE_LOCK_TIMEOUT_S.get(conf)
    with _STORE_LOCK:
        s = _STORE
        if (
            s is not None
            and s.root == root
            and s.max_bytes == max_bytes
            and s.lock_timeout_s == lock_timeout
        ):
            return s
        _STORE = XlaStore(root, max_bytes, lock_timeout)
        return _STORE


def active_store() -> Optional[XlaStore]:
    # graft: ok(guarded-by: published-singleton snapshot read —
    # one ref load under the GIL; writers swap the whole object under
    # _STORE_LOCK and a stale snapshot is a cache miss, never corruption)
    return _STORE


# ── executable (de)serialization + the load-failure breaker ─────────────────

#: PR-3 circuit breaker over cache loads: repeated deserialize failures
#: (a systematically poisoned or version-confused cache that somehow
#: passes its CRCs) stop the engine consulting the store at all — degrade
#: to cold compiles, never to a failure loop. Threshold 3 like the
#: session breaker's default.
_LOAD_BREAKER_OP = "compileCache.load"
_LOAD_BREAKER = None
_LOAD_BREAKER_LOCK = threading.Lock()


def _load_breaker():
    global _LOAD_BREAKER
    if _LOAD_BREAKER is None:
        with _LOAD_BREAKER_LOCK:
            if _LOAD_BREAKER is None:
                from ..resilience.breaker import CircuitBreaker

                _LOAD_BREAKER = CircuitBreaker(threshold=3)
    return _LOAD_BREAKER


def loads_disabled() -> bool:
    b = _LOAD_BREAKER
    return b is not None and b.is_open(_LOAD_BREAKER_OP)


def record_load_failure(digest: Optional[str], err: BaseException) -> None:
    """A cache-loaded executable failed to deserialize or blew up on its
    proving run: quarantine the entry (the rebuild must not reload it),
    count it, and feed the breaker."""
    _M_DESER_FAIL.add(1)
    # graft: ok(guarded-by: published-singleton snapshot read —
    # one ref load under the GIL; writers swap the whole object under
    # _STORE_LOCK and a stale snapshot is a cache miss, never corruption)
    store = _STORE
    if store is not None and digest:
        store.quarantine_digest(digest, f"deserialize/proving failure: {err}")
    _load_breaker().record_failure(_LOAD_BREAKER_OP, err)


def load_executable(digest: Optional[str]):
    """Deserialized executable for ``digest``, or None. Counts
    ``cache.xla.hit``/``miss`` (a CRC-valid payload that fails to
    deserialize is a miss plus a ``deserializeFailures``)."""
    # graft: ok(guarded-by: published-singleton snapshot read —
    # one ref load under the GIL; writers swap the whole object under
    # _STORE_LOCK and a stale snapshot is a cache miss, never corruption)
    store = _STORE
    if store is None or not digest or loads_disabled():
        return None
    payload = store.load(digest)
    if payload is None:
        _M_MISS.add(1)
        return None
    try:
        with _M_LOAD_NS.timed():
            loaded = _deserialize(payload)
    except Exception as e:  # noqa: BLE001 - poison entry, never fatal
        record_load_failure(digest, e)
        _M_MISS.add(1)
        return None
    _M_HIT.add(1)
    return loaded


def _deserialize(payload: bytes):
    import pickle

    import jax
    from jax.experimental import serialize_executable as _se

    ser, in_tree, out_tree, device_ids = pickle.loads(payload)
    # load onto the devices the executable was compiled for: the default is
    # every device of the backend, which a one-device program cannot take
    by_id = {d.id: d for d in jax.devices()}
    devices = [by_id[i] for i in device_ids]
    cpu = fence()["backend"] == "cpu"
    with _LOAD_LOCK if cpu else nullcontext():
        return _se.deserialize_and_load(
            ser, in_tree, out_tree, execution_devices=devices
        )


def serialize_executable(compiled) -> Optional[bytes]:
    """Payload bytes for a compiled executable, or None when this
    executable resists serialization (some lowerings legitimately do).
    Callers on XLA:CPU invoke this under the kernel compile lock — the
    native serializer shares the compiler's thread-unsafety there."""
    try:
        import pickle

        from jax.experimental import serialize_executable as _se

        ser, in_tree, out_tree = _se.serialize(compiled)
        device_ids = [
            d.id for d in compiled.runtime_executable().local_devices()
        ]
        return pickle.dumps(
            (ser, in_tree, out_tree, device_ids),
            protocol=pickle.HIGHEST_PROTOCOL,
        )
    except Exception as e:  # noqa: BLE001 - skip persisting, keep serving
        log.debug("executable not serializable (ignored): %s", str(e)[:200])
        return None


def store_executable(digest: Optional[str], payload: Optional[bytes]) -> bool:
    # graft: ok(guarded-by: published-singleton snapshot read —
    # one ref load under the GIL; writers swap the whole object under
    # _STORE_LOCK and a stale snapshot is a cache miss, never corruption)
    store = _STORE
    if store is None or not digest or payload is None:
        return False
    try:
        with _M_STORE_NS.timed():
            return store.put(digest, payload)
    except Exception as e:  # noqa: BLE001
        log.debug("compile-cache store failed (ignored): %s", e)
        return False


def reset_for_tests() -> None:
    """Drop the process-global store and breaker (test isolation)."""
    global _STORE, _LOAD_BREAKER
    with _STORE_LOCK:
        _STORE = None
    with _LOAD_BREAKER_LOCK:
        _LOAD_BREAKER = None
