"""Semantic result cache — bounded LRU of completed query results.

The dashboard fleet re-executes identical queries over slowly-changing
tables; prepared statements (PR 6) already skip parse/plan/compile, so
the remaining per-EXECUTE cost is the physical plan itself. This cache
closes that gap: a completed query's Arrow batches are stored under the
full *result identity* — ``canonical_key(final_plan)`` (bound params are
literals in the plan), the session conf fingerprint, and the per-table
data version of every table read (``cache/keys.py``) — and a later
identical query streams them back through the exact same
``run_plan_stream`` / serve-FETCH surface *without* touching scheduler
admission.

Bounded three ways, all from conf at use time (runtime-tunable):

* ``spark.rapids.tpu.resultCache.maxBytes`` — in-memory footprint. The
  same figure is reserved against the host spill budget through
  :meth:`mem/spill.py::BufferCatalog.host_reserve`, so cached results
  compete with spilled device buffers instead of hiding from the memory
  ledger.
* the same ``maxBytes`` again for the **disk tier**: LRU entries demoted
  from memory persist as Arrow IPC files in the spill directory (writes
  and reads pass the ``resilience/faults`` spill-IO points — the chaos
  hooks); a failed spill write silently drops the entry, never the query.
* ``spark.rapids.tpu.resultCache.maxEntries`` — entry count across both
  tiers.

Consistency: keys embed table versions, so a *completed* write never
serves stale hits; a write RACING an execution is caught by
re-fingerprinting at admission (``admit`` rejects when any read table's
version moved since lookup), and writes also push invalidation eagerly
through :meth:`invalidate_table` so dead entries free budget immediately.

Locking: ``_lock`` (session-caches tier) guards the entry map and byte
counters. All IO and all ``BufferCatalog`` accounting (mem tier — LOWER
than this lock in ``analysis/lock_order.py``) happens outside it: victims
are chosen under the lock, serialized/released outside it, and the
transition is committed by re-checking membership under the lock.
"""
from __future__ import annotations

import os
import threading
import uuid
from collections import OrderedDict
from typing import List, Optional, Tuple

from ..obs import metrics as obs_metrics
from ..resilience import faults
from . import keys as cache_keys

_M = obs_metrics.GLOBAL

_MEM = "mem"
_SPILLING = "spilling"
_DISK = "disk"


class _Entry:
    """One cached result. Owned by the cache map; fields other than
    ``tier``/``path`` are write-once at insert and safe to read once the
    entry has been popped (the holder then owns it exclusively)."""

    __slots__ = ("key", "batches", "nbytes", "read_keys", "tier", "path")

    def __init__(self, key, batches, nbytes, read_keys):
        self.key = key
        self.batches = batches
        self.nbytes = nbytes
        self.read_keys = read_keys
        self.tier = _MEM
        self.path: Optional[str] = None


def key_for(session, final_plan, params=()) -> Tuple[Optional[tuple], tuple]:
    """Result-cache key for a prepared physical plan, or ``(None, ())``
    when the plan is not canonicalizable (structural identity would be
    meaningless) — callers treat None as cache-off for this query."""
    from ..plan import reuse

    # a write is an effect, not a result: served from the cache it would
    # land no file. Its in-memory source is keyed by id(), which a freed
    # table hands on to the next one, so two writes can share a key
    if type(final_plan).__name__ == "CpuWriteFilesExec":
        return None, ()
    try:
        ckey = reuse.canonical_key(final_plan)
    except Exception:
        return None, ()
    read_keys = cache_keys.plan_read_keys(session, final_plan)
    fp = cache_keys.result_fingerprint(session, read_keys)
    return (ckey, tuple(params), fp), read_keys


class ResultCache:
    """Bounded mem+disk LRU of completed query results, accounted against
    the host spill budget through a session-lifetime ``BufferCatalog``."""

    def __init__(self, conf, catalog=None):
        self._conf = conf
        if catalog is None:
            from ..mem.spill import BufferCatalog

            catalog = BufferCatalog.from_conf(conf)
        self._catalog = catalog
        self._lock = threading.Lock()
        #: key -> _Entry, LRU order (oldest first)
        self._entries: "OrderedDict" = OrderedDict()  # graft: guarded_by(_lock)
        self._mem_bytes = 0  # graft: guarded_by(_lock)
        self._disk_bytes = 0  # graft: guarded_by(_lock)
        self._hits = 0  # graft: guarded_by(_lock)
        self._misses = 0  # graft: guarded_by(_lock)
        self._spill_dir: Optional[str] = None  # graft: guarded_by(_lock)

    # ── conf knobs (read per call so runtime set_conf applies) ──────────
    def _max_bytes(self) -> int:
        from .. import config as cfg

        return cfg.RESULT_CACHE_MAX_BYTES.get(self._conf)

    def _max_entries(self) -> int:
        from .. import config as cfg

        return cfg.RESULT_CACHE_MAX_ENTRIES.get(self._conf)

    # ── lookup ──────────────────────────────────────────────────────────
    def get(self, key) -> Optional[List]:
        """Cached batch list for ``key`` (the exact stored RecordBatch
        objects for memory hits; an IPC round-trip for disk hits), or
        None. A disk entry whose file fails to read back (injected IO
        fault, pruned spill dir) degrades to a miss and is dropped."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.tier == _SPILLING:
                # a mid-demotion entry has no stable home; miss rather
                # than block the hot path on the spiller's IO
                self._misses += 1
                self._publish_locked()
                _M.counter("cache.result.misses").add(1)
                return None
            self._entries.move_to_end(key)
            if e.tier == _MEM:
                self._hits += 1
                self._publish_locked()
                _M.counter("cache.result.hits").add(1)
                return list(e.batches)
            path, nbytes = e.path, e.nbytes
        # disk tier: IO outside the lock
        batches = _read_ipc(path)
        if batches is not None:
            with self._lock:
                self._hits += 1
                self._publish_locked()
            _M.counter("cache.result.hits").add(1)
            return batches
        dropped = False
        with self._lock:
            cur = self._entries.get(key)
            if cur is e and cur.tier == _DISK:
                del self._entries[key]
                self._disk_bytes -= nbytes
                dropped = True
            self._misses += 1
            self._publish_locked()
        _M.counter("cache.result.misses").add(1)
        if dropped:
            self._catalog.disk_release(nbytes)
            _unlink(path)
        return None

    # ── admission ───────────────────────────────────────────────────────
    def admit(self, session, key, read_keys, batches) -> bool:
        """Store a completed result. Rejects (False) when the entry alone
        exceeds maxBytes, the host budget refuses the reservation, or any
        read table's version moved since the key was fingerprinted (a
        write raced this execution — caching would publish a result that
        is neither fully-old nor fully-new)."""
        nbytes = sum(rb.nbytes for rb in batches)
        max_bytes = self._max_bytes()
        if nbytes > max_bytes:
            return False
        if cache_keys.result_fingerprint(session, read_keys) != key[2]:
            _M.counter("cache.result.invalidations").add(1)
            return False
        if not self._catalog.host_reserve(nbytes):
            return False
        e = _Entry(key, list(batches), nbytes, tuple(read_keys))
        victims: List[_Entry] = []
        with self._lock:
            if key in self._entries:
                # another thread of the same dashboard fleet raced us
                # here with an identical result; keep the incumbent
                self._publish_locked()
                dup = True
            else:
                dup = False
                self._entries[key] = e
                self._mem_bytes += nbytes
                _M.counter("cache.result.stores").add(1)
                victims = self._pick_victims_locked()
                self._publish_locked()
        if dup:
            self._catalog.host_release(nbytes)
            return True
        self._settle_victims(victims)
        return True

    def _pick_victims_locked(self) -> List[_Entry]:
        """LRU victims to demote/drop so the budgets hold again. Memory
        overflow marks entries SPILLING (still resident, invisible to
        hits) for the caller to serialize outside the lock; entry-count
        and disk overflow pop entries outright."""
        max_bytes, max_entries = self._max_bytes(), self._max_entries()
        victims: List[_Entry] = []
        for k in list(self._entries):
            if len(self._entries) <= max_entries:
                break
            e = self._entries.pop(k)
            if e.tier == _DISK:
                self._disk_bytes -= e.nbytes
            else:
                self._mem_bytes -= e.nbytes
            e.key = None  # mark dropped for _settle_victims
            victims.append(e)
            _M.counter("cache.result.evictions").add(1)
        if self._mem_bytes > max_bytes:
            for e in list(self._entries.values()):
                if self._mem_bytes <= max_bytes:
                    break
                if e.tier != _MEM or not e.batches:
                    # empty results hold no bytes; demoting them frees
                    # nothing and an empty IPC stream has no schema
                    continue
                e.tier = _SPILLING
                self._mem_bytes -= e.nbytes
                victims.append(e)
        return victims

    def _settle_victims(self, victims: List[_Entry]) -> None:
        """Outside the lock: release dropped victims' budget; serialize
        SPILLING victims to disk and commit (or drop them when the write
        fails / the disk tier is itself over budget)."""
        for e in victims:
            if e.key is None:  # dropped outright by _pick_victims_locked
                if e.tier == _DISK:
                    self._catalog.disk_release(e.nbytes)
                    _unlink(e.path)
                else:
                    self._catalog.host_release(e.nbytes)
                continue
            path = None
            if self._disk_bytes_now() + e.nbytes <= self._max_bytes():
                path = _write_ipc(self._dir(), e.batches)
            committed = False
            with self._lock:
                cur = self._entries.get(e.key)
                if cur is e and e.tier == _SPILLING:
                    if path is not None:
                        e.tier, e.path, e.batches = _DISK, path, None
                        self._disk_bytes += e.nbytes
                        committed = True
                    else:
                        del self._entries[e.key]
                        _M.counter("cache.result.spillDrops").add(1)
                self._publish_locked()
            # whether committed to disk or dropped (or invalidated while
            # we wrote), the memory reservation ends here
            self._catalog.host_release(e.nbytes)
            if committed:
                self._catalog.disk_reserve(e.nbytes)
                _M.counter("cache.result.spills").add(1)
            elif path is not None:
                _unlink(path)

    # ── invalidation ────────────────────────────────────────────────────
    def invalidate_table(self, written_key: str) -> int:
        """Drop every entry whose read set intersects a written table key
        (exact for views, directory containment for paths). Called by
        ``cache/keys.py::bump_table_version`` on every write path."""
        dropped: List[_Entry] = []
        with self._lock:
            for k in list(self._entries):
                e = self._entries[k]
                if any(
                    cache_keys.keys_related(rk, written_key)
                    for rk in e.read_keys
                ):
                    del self._entries[k]
                    if e.tier == _DISK:
                        self._disk_bytes -= e.nbytes
                    else:
                        self._mem_bytes -= e.nbytes
                    dropped.append(e)
            self._publish_locked()
        for e in dropped:
            if e.tier == _DISK:
                self._catalog.disk_release(e.nbytes)
                _unlink(e.path)
            else:
                self._catalog.host_release(e.nbytes)
        if dropped:
            _M.counter("cache.result.invalidations").add(len(dropped))
        return len(dropped)

    def clear(self) -> None:
        with self._lock:
            dropped = list(self._entries.values())
            self._entries.clear()
            self._mem_bytes = 0
            self._disk_bytes = 0
            self._publish_locked()
        for e in dropped:
            if e.tier == _DISK:
                self._catalog.disk_release(e.nbytes)
                _unlink(e.path)
            else:
                self._catalog.host_release(e.nbytes)

    # ── introspection ───────────────────────────────────────────────────
    def stats(self) -> dict:
        with self._lock:
            total = self._hits + self._misses
            return {
                "entries": len(self._entries),
                "mem_bytes": self._mem_bytes,
                "disk_bytes": self._disk_bytes,
                "hits": self._hits,
                "misses": self._misses,
                "hit_ratio": (self._hits / total) if total else 0.0,
            }

    def _orphan_report(self) -> List[str]:
        """Internal-invariant violations for reswatch's exit check."""
        out: List[str] = []
        with self._lock:
            mem = sum(
                e.nbytes for e in self._entries.values() if e.tier == _MEM
            )
            disk = sum(
                e.nbytes for e in self._entries.values() if e.tier == _DISK
            )
            stuck = sum(
                1 for e in self._entries.values() if e.tier == _SPILLING
            )
            if mem != self._mem_bytes:
                out.append(
                    f"result-cache mem bytes drifted: accounted "
                    f"{self._mem_bytes} != resident {mem}"
                )
            if disk != self._disk_bytes:
                out.append(
                    f"result-cache disk bytes drifted: accounted "
                    f"{self._disk_bytes} != resident {disk}"
                )
            if stuck:
                out.append(
                    f"result-cache has {stuck} entries stuck mid-spill"
                )
            if self._mem_bytes < 0 or self._disk_bytes < 0:
                out.append(
                    f"result-cache negative byte counter "
                    f"(mem={self._mem_bytes}, disk={self._disk_bytes})"
                )
        return out

    # ── internals ───────────────────────────────────────────────────────
    def _publish_locked(self) -> None:
        """Refresh the exported gauges from state the caller holds
        ``_lock`` over (every mutation path ends here)."""
        _M.gauge("cache.result.bytes").set(self._mem_bytes)
        _M.gauge("cache.result.diskBytes").set(self._disk_bytes)
        _M.gauge("cache.result.entries").set(len(self._entries))
        total = self._hits + self._misses
        if total:
            _M.gauge("cache.result.hitRatio").set(
                int(1000 * self._hits / total)
            )

    def _disk_bytes_now(self) -> int:
        with self._lock:
            return self._disk_bytes

    def _dir(self) -> str:
        with self._lock:
            d = self._spill_dir
        if d is None:
            d = os.path.join(self._catalog._dir(), "result_cache")
            os.makedirs(d, exist_ok=True)
            with self._lock:
                self._spill_dir = d
        return d


def _write_ipc(dirname: str, batches) -> Optional[str]:
    """Serialize a batch list to one Arrow IPC stream file; None on any
    failure (including the injected spill-write fault)."""
    import pyarrow as pa

    path = os.path.join(dirname, f"r{uuid.uuid4().hex}.arrow")
    try:
        faults.on_spill_write()
        with pa.OSFile(path, "wb") as sink:
            with pa.ipc.new_stream(sink, batches[0].schema) as writer:
                for rb in batches:
                    writer.write_batch(rb)
        return path
    except Exception:
        _unlink(path)
        return None


def _read_ipc(path: Optional[str]) -> Optional[List]:
    import pyarrow as pa

    if path is None:
        return None
    try:
        faults.on_spill_read()
        with pa.OSFile(path, "rb") as src:
            with pa.ipc.open_stream(src) as reader:
                return [rb for rb in reader]
    except Exception:
        return None


def _unlink(path: Optional[str]) -> None:
    if path is None:
        return
    try:
        os.unlink(path)
    except OSError:
        pass
