"""TpuServer — the threaded Arrow-IPC SQL endpoint over a TpuSession.

The network seam the north star needs: where the reference lives inside a
running SparkSession (an in-JVM plugin boundary), a TPU-resident engine
serves remote clients directly, so the PR-5 scheduler pools, PR-4 metrics,
and PR-3 resilience stack finally have a wire to face. One server wraps
ONE session; every client connection gets a handler thread and every
query rides the session's existing machinery:

- **auth → tenant → pool**: the HELLO token maps to a tenant and its
  fair-share scheduler pool (``spark.rapids.tpu.serve.tenants``); the
  query is admitted under that pool (``QueryScheduler.admit(pool=…)``),
  so admission control, weights, deadlines, and queue backpressure all
  apply per tenant with no conf mutation on the shared session;
- **prepared statements** (``serve/prepared.py``): PREPARE parses once,
  EXECUTE_PREPARED/BIND resolve a compiled plan from the LRU keyed by
  canonicalized statement + parameters + batch geometry — a hit never
  re-enters the planner;
- **streaming results**: batches flow to the client as they land
  (``session.run_plan_stream``), re-chunked to
  ``spark.rapids.tpu.serve.streamBatchRows`` so CANCEL has boundaries to
  act on; between frames the server polls the socket, so a mid-stream
  CANCEL (or a vanished client) cancels the query through its token —
  permits release through the normal admission exit, and the
  ``scheduler.cancelled.reason.*`` series says why;
- **observability**: connection/query/prepared/stream counters land in
  the process metric registry (``serve.*`` catalog slice), so the
  Prometheus export carries the server story next to the engine's;
- **subscriptions** (ISSUE 20): SUBSCRIBE registers a live query with
  the session's :class:`live.LiveRuntime`; the refresh worker fans
  epoch-stamped updates into a per-connection sink (:class:`_ConnSubs`),
  and the handler thread — the only thread that ever writes this socket —
  drains them onto the wire as UPDATE trains between commands. A slow
  consumer's queue collapses to one fresh snapshot
  (``spark.rapids.tpu.live.subscriber.maxPending``); drain() refuses new
  SUBSCRIBEs and proactively sheds existing ones with
  ``UNSUBSCRIBED {reason: "draining"}`` so dashboards fail over.
"""
from __future__ import annotations

import base64
import itertools
import logging
import select
import socket
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, Optional

import pyarrow as pa

from .. import config as cfg
from ..columnar import ipc
from ..obs import metrics as obs_metrics
from ..sched import (
    QueryCancelledError,
    QueryOverloadedError,
    QueryQueueFull,
    SchedulerError,
)
from ..sql.parser import SqlError
from . import protocol as P
from .prepared import PreparedPlanCache, PreparedStatement

_M = obs_metrics.GLOBAL
_log = logging.getLogger(__name__)


class _ClientGone(Exception):
    """The client socket died mid-stream (disconnect-as-cancellation)."""


class ServerDrainingError(RuntimeError):
    """New work refused because the server is draining (``drain()`` /
    SIGTERM); the ERROR frame carries code=DRAINING and the drain reason
    so clients fail over instead of retrying this endpoint."""

    def __init__(self, message: str, reason: str = "shutdown"):
        super().__init__(message)
        self.reason = reason


class _Tenant:
    __slots__ = ("name", "pool")

    def __init__(self, name: str, pool: str = "default"):
        self.name = name
        self.pool = pool


def parse_tenant_spec(spec: Optional[str]) -> Dict[str, _Tenant]:
    """``"token:tenant:pool,…"`` → token → tenant mapping (pool defaults
    to 'default'); empty spec = open access."""
    out: Dict[str, _Tenant] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        if len(bits) < 2 or not bits[0] or not bits[1]:
            continue
        out[bits[0]] = _Tenant(bits[1], bits[2] if len(bits) > 2 else "default")
    return out


def _metric_slug(name: str) -> str:
    return obs_metrics.metric_slug(name, fallback="anon")


#: served-query latency distributions (HISTOGRAM kind — Prometheus
#: _bucket/_sum/_count): the real replacement for raw-sample percentile
#: lists; latency_samples remains only as a bounded debugging window
_M_WAIT_HIST = _M.histogram("serve.queryWaitHist")
_M_RUN_HIST = _M.histogram("serve.queryRunHist")
_M_TOTAL_HIST = _M.histogram("serve.queryTotalHist")


class _PendingQuery:
    """A planned-but-not-yet-streamed query (between EXECUTE/BIND and its
    FETCH): the compiled plan + execution context, plus an early-cancel
    flag for CANCELs that land before admission mints a token."""

    __slots__ = ("query_id", "final_plan", "ctx", "cancelled_reason",
                 "cache_hit", "traceable", "wire_trace")

    def __init__(self, query_id: str, final_plan, ctx, cache_hit: bool = False,
                 traceable: bool = True, wire_trace=None):
        self.query_id = query_id
        self.final_plan = final_plan
        self.ctx = ctx
        self.cancelled_reason: Optional[str] = None
        self.cache_hit = cache_hit
        # span instrumentation wraps the plan's methods in place, so only
        # per-query plan instances may be traced — prepared-cache plans
        # are SHARED across executions and must stay unwrapped
        self.traceable = traceable
        # inbound SpanContext (obs/trace.py) from the EXECUTE/BIND frame:
        # the client's trace id + parent span id + sampled bit — the
        # Dapper propagation that merges client and server trees
        self.wire_trace = wire_trace


class _ConnSubs:
    """Per-connection subscription state: the sink the LiveRuntime's
    refresh worker fans :class:`live.LiveUpdate` objects into, plus the
    per-subscription pending queues the handler thread drains onto the
    wire. ``offer()`` only enqueues (called off-thread, never blocks and
    never touches the socket); every frame write stays on the handler
    thread, so UPDATE trains can never interleave with command replies.

    Slow consumers: a queue past ``spark.rapids.tpu.live.subscriber.
    maxPending`` collapses — pending epochs are dropped and one fresh
    snapshot is resent instead (the subscriber sees every version's
    EFFECT, not every version). Epoch filtering in ``next_delivery``
    makes redundant deliveries (handshake races, post-collapse stragglers)
    harmless: anything at or below the last epoch put on the wire is
    skipped."""

    def __init__(self, max_pending: int):
        self._lock = threading.Lock()
        #: read by the runtime's fan-out and the reswatch orphan report
        self.closed = False
        self._max_pending = max(1, max_pending)
        self._qid_of: Dict[str, str] = {}  # graft: guarded_by(_lock)
        self._by_qid: Dict[str, list] = {}  # graft: guarded_by(_lock)
        self._pending: Dict[str, deque] = {}  # graft: guarded_by(_lock)
        self._collapsed: set = set()  # graft: guarded_by(_lock)
        self._last_epoch: Dict[str, int] = {}  # graft: guarded_by(_lock)
        #: updates fanned out between the runtime registering this sink
        #: and SUBSCRIBE_OK minting the sub_id land here; register() moves
        #: them into the real queue (the epoch filter drops duplicates of
        #: the initial snapshot)
        self._early: Dict[str, deque] = {}  # graft: guarded_by(_lock)

    def register(self, sub_id: str, qid: str) -> None:
        with self._lock:
            self._qid_of[sub_id] = qid
            self._by_qid.setdefault(qid, []).append(sub_id)
            self._pending[sub_id] = deque(self._early.pop(qid, ()))
            self._last_epoch[sub_id] = -1

    def drop(self, sub_id: str) -> None:
        with self._lock:
            qid = self._qid_of.pop(sub_id, None)
            if qid is not None:
                lst = self._by_qid.get(qid, [])
                if sub_id in lst:
                    lst.remove(sub_id)
                if not lst:
                    self._by_qid.pop(qid, None)
            self._pending.pop(sub_id, None)
            self._collapsed.discard(sub_id)
            self._last_epoch.pop(sub_id, None)

    def offer(self, upd) -> None:
        """Enqueue one refresh delivery (refresh-worker thread)."""
        with self._lock:
            if self.closed:
                return
            subs = self._by_qid.get(upd.qid)
            if not subs:
                dq = self._early.setdefault(upd.qid, deque(maxlen=4))
                dq.append(upd)
                return
            for sub_id in subs:
                if sub_id in self._collapsed:
                    continue  # the snapshot resend already covers it
                dq = self._pending.get(sub_id)
                if dq is None:
                    continue
                dq.append(upd)
                if len(dq) > self._max_pending:
                    _M.counter("live.updates.collapsed").add(len(dq))
                    dq.clear()
                    self._collapsed.add(sub_id)

    def active(self) -> bool:
        with self._lock:
            return bool(self._qid_of)

    def sub_ids(self) -> list:
        with self._lock:
            return list(self._qid_of)

    def next_delivery(self):
        """One deliverable ``(sub_id, qid, update-or-None)`` — None means
        collapsed (resend a fresh snapshot) — or None when nothing is
        ready. Handler thread only."""
        with self._lock:
            while self._collapsed:
                sub_id = self._collapsed.pop()
                qid = self._qid_of.get(sub_id)
                if qid is not None:
                    return sub_id, qid, None
            for sub_id, dq in self._pending.items():
                while dq:
                    upd = dq.popleft()
                    if upd.epoch <= self._last_epoch.get(sub_id, -1):
                        continue
                    return sub_id, self._qid_of.get(sub_id), upd
            return None

    def mark_sent(self, sub_id: str, epoch: int) -> None:
        with self._lock:
            if sub_id in self._last_epoch:
                self._last_epoch[sub_id] = max(
                    self._last_epoch[sub_id], epoch
                )

    def last_epoch(self, sub_id: str) -> int:
        with self._lock:
            return self._last_epoch.get(sub_id, -1)


class TpuServer:
    """Threaded socket front-end over one :class:`TpuSession`.

    ``start()`` binds and returns ``(host, port)`` (port 0 → ephemeral,
    the test/bench mode); ``stop()`` cancels in-flight served queries,
    closes every connection, and releases the port. Usable as a context
    manager."""

    def __init__(
        self,
        session,
        host: Optional[str] = None,
        port: Optional[int] = None,
        warmup: Optional[list] = None,
    ):
        self.session = session
        conf = session.conf
        self.host = host if host is not None else cfg.SERVE_HOST.get(conf)
        self.port = port if port is not None else cfg.SERVE_PORT.get(conf)
        self.tenants = parse_tenant_spec(cfg.SERVE_TENANTS.get(conf))
        self.prepared = PreparedPlanCache(session)
        self._qids = itertools.count(1)
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._conns: set = set()  # graft: guarded_by(_conn_lock)
        self._handler_threads: set = set()  # graft: guarded_by(_conn_lock)
        self._conn_lock = threading.Lock()
        self._stopping = threading.Event()
        # ── survivability state ─────────────────────────────────────────
        #: drain(): stop accepting, finish in-flight, then cancel
        self._draining = threading.Event()
        self._drain_reason = "shutdown"
        #: readiness: set once the warm pool is primed (immediately when
        #: no warmup statements exist) — the rolling-restart gate
        self._ready = threading.Event()
        #: SQL statements planned+precompiled before ready flips; the
        #: conf (spark.rapids.tpu.serve.warmupStatements) supplies them
        #: when the constructor doesn't
        raw_warm = cfg.SERVE_WARMUP_STATEMENTS.get(conf) or ""
        self._warmup = list(warmup) if warmup else [
            s.strip() for s in raw_warm.split(";") if s.strip()
        ]
        self._warmup_thread: Optional[threading.Thread] = None
        #: per-statement warmup progress surfaced in STATUS so a caller
        #: waiting on readiness can distinguish "still compiling
        #: statement k of n" from "hung" (updated only by the warmup
        #: thread; plain assignments — readers take a snapshot)
        self._warmup_progress = {
            "total": len(self._warmup),
            "done": 0,
            "failed": 0,
            "current": None,
        }
        #: in-flight FETCH streams (drain waits on these)
        self._inflight = 0  # graft: guarded_by(_inflight_cond)
        self._inflight_cond = threading.Condition()
        #: per-tenant connection / in-flight-query occupancy (the caps
        #: that stop one tenant wedging the accept loop for everyone)
        self._tenant_conns: Dict[str, int] = {}  # graft: guarded_by(_conn_lock)
        self._tenant_inflight: Dict[str, int] = {}  # graft: guarded_by(_inflight_cond)
        #: (tenant, wait_s, run_s) per served query — a bounded window
        #: for debugging (aggregate totals live in serve.*)
        self.latency_samples: deque = deque(maxlen=8192)
        #: failover dedup window: client-generated dedup keys recently
        #: seen, bounded LRU sized by serve.failover.dedupWindow. A key
        #: seen again is a failover replay of a query this server already
        #: answered once (counted, for attribution; the engine is
        #: side-effect-free, so re-execution is safe either way)
        self._dedup_seen: OrderedDict = OrderedDict()  # graft: guarded_by(_dedup_lock)
        self._dedup_lock = threading.Lock()

    # ── lifecycle ───────────────────────────────────────────────────────
    def start(self) -> tuple:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((self.host, self.port))
            sock.listen(128)
            self.host, self.port = sock.getsockname()[:2]
        except BaseException:
            # a failed bind/listen (port taken) must not leak the fd
            sock.close()
            raise
        self._sock = sock
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="tpu-serve-accept", daemon=True
        )
        self._accept_thread.start()
        if self._warmup:
            self._warmup_thread = threading.Thread(
                target=self._run_warmup, name="tpu-serve-warmup", daemon=True
            )
            self._warmup_thread.start()
        else:
            self._ready.set()
        # live scrape endpoint (obs/scrape.py): /metrics + /healthz with
        # this server's readiness folded in; no-op unless
        # spark.rapids.tpu.metrics.httpPort asks for it (idempotent when
        # the session already started one)
        from ..obs.scrape import ensure_scrape

        ensure_scrape(self.session, serve_server=self)
        _log.info("serving on %s:%d", self.host, self.port)
        return self.host, self.port

    def _run_warmup(self) -> None:
        """Prime the precompile warm pool: plan every warmup statement
        (session._prepare_plan runs the kernel pre-compilation pass), then
        flip readiness. A failed statement logs and is skipped — a typo
        must not hold the server not-ready forever."""
        for i, text in enumerate(self._warmup):
            if self._stopping.is_set() or self._draining.is_set():
                return
            self._warmup_progress = dict(
                self._warmup_progress, current=text[:120],
            )
            try:
                df = self.session.sql(text)
                self.session._prepare_plan(df._plan)
                self._warmup_progress = dict(
                    self._warmup_progress,
                    done=self._warmup_progress["done"] + 1,
                )
            except Exception:  # noqa: BLE001 - warmup is best-effort
                _log.warning("warmup statement failed: %r", text[:120],
                             exc_info=True)
                self._warmup_progress = dict(
                    self._warmup_progress,
                    failed=self._warmup_progress["failed"] + 1,
                )
        self._warmup_progress = dict(self._warmup_progress, current=None)
        self._ready.set()
        _log.info("warm pool primed (%d statements); server READY",
                  len(self._warmup))

    def is_ready(self) -> bool:
        """Readiness for traffic: warm pool primed and not draining (the
        STATUS ``ready`` field operators roll restarts on)."""
        return (
            self._ready.is_set()
            and not self._draining.is_set()
            and not self._stopping.is_set()
        )

    def drain(self, timeout: Optional[float] = None,
              reason: str = "shutdown") -> bool:
        """Graceful shutdown: stop accepting connections, answer new work
        with a typed DRAINING error, let in-flight streams finish up to
        ``timeout`` (default ``spark.rapids.tpu.serve.drainTimeout``),
        then cancel the stragglers with ``reason`` — every stream still
        ends with a typed END/ERROR frame. Returns True when all
        in-flight work finished without cancellation. Idempotent; called
        by the SIGTERM handler."""
        if timeout is None:
            timeout = cfg.SERVE_DRAIN_TIMEOUT_S.get(self.session.conf)
        first = not self._draining.is_set()
        self._drain_reason = reason
        self._draining.set()
        if first:
            _M.gauge("serve.draining").set(1)
            _log.info("draining (timeout %.1fs, reason %r)", timeout, reason)
        self._close_listener()  # stop accepting; handler conns live on
        deadline = time.monotonic() + max(0.0, timeout)
        with self._inflight_cond:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._inflight_cond.wait(min(remaining, 0.1))
            clean = self._inflight == 0
        if not clean:
            n = self.session.cancel_all(reason)
            _M.counter("serve.drainCancelled").add(n)
            _log.warning(
                "drain timeout: cancelled %d in-flight queries (%s)",
                n, reason,
            )
            # the cancelled streams unwind to their typed ERROR frames;
            # give them one bounded window to do so
            with self._inflight_cond:
                end = time.monotonic() + 5.0
                while self._inflight > 0 and time.monotonic() < end:
                    self._inflight_cond.wait(0.1)
        self.stop()
        return clean

    def stop(self) -> None:
        self._stopping.set()
        self._ready.clear()
        # the draining gauge is per-server state in a process-wide
        # registry: a stopped server must not pin it at 1
        _M.gauge("serve.draining").set(0)
        self._close_listener()
        with self._conn_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        # join handler threads so post-stop() session state (exported
        # traces, ledgers, leak checks) is fully settled — a client that
        # raced its END frame otherwise reads it mid-unwind (GIL-schedule
        # dependent on small boxes). kill() deliberately skips this.
        with self._conn_lock:
            handlers = list(self._handler_threads)
            self._handler_threads.clear()
        me = threading.current_thread()
        for t in handlers:
            if t is not me:
                t.join(timeout=5)

    def kill(self) -> None:
        """Crash simulation (the failover chaos hook): drop the listener
        and every client socket on the floor — no drain window, no typed
        END/ERROR frames. Clients observe a bare transport death
        mid-stream, exactly the signal ``ResultStream`` fails over on."""
        self._stopping.set()
        self._ready.clear()
        _M.gauge("serve.draining").set(0)
        self._close_listener()
        with self._conn_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass

    def __enter__(self) -> "TpuServer":
        self.start()
        return self

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def _close_listener(self) -> None:
        """Close the listening socket AND unblock the accept thread: a
        plain close() leaves a thread blocked in accept() holding the
        kernel listener alive (in-flight syscalls pin the file), so a
        'drained' server would silently keep accepting — shutdown() makes
        the blocked accept return immediately."""
        sock = self._sock
        if sock is None:
            return
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            sock.close()
        except OSError:
            pass

    # ── accept / connection handling ────────────────────────────────────
    def _accept_loop(self) -> None:
        while not self._stopping.is_set() and not self._draining.is_set():
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return  # listener closed by stop()/drain()
            if self._stopping.is_set() or self._draining.is_set():
                # raced the shutdown: never serve a post-drain connection
                try:
                    conn.close()
                except OSError:
                    pass
                return
            t = threading.Thread(
                target=self._handle_conn,
                args=(conn, addr),
                name=f"tpu-serve-{addr[0]}:{addr[1]}",
                daemon=True,
            )
            with self._conn_lock:
                # track for stop()'s join; prune finished handlers so a
                # long-lived server doesn't accumulate dead thread objects
                self._handler_threads = {
                    h for h in self._handler_threads if h.is_alive()
                }
                self._handler_threads.add(t)
            t.start()

    def _handle_conn(self, sock: socket.socket, addr) -> None:
        with self._conn_lock:
            over = len(self._conns) >= cfg.SERVE_MAX_CONNECTIONS.get(
                self.session.conf
            )
            if not over:
                self._conns.add(sock)
            n_conns = len(self._conns)
        if over:
            _M.counter("serve.connectionsRejected").add(1)
            try:
                P.send_json(
                    sock, P.ERROR,
                    {"type": "ConnectionLimit", "code": "OVERLOADED",
                     "retry_after_s": self.session.scheduler.retry_after_hint(),
                     "error": "server connection limit reached"},
                )
            except OSError:
                pass
            sock.close()
            return
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        _M.gauge("serve.connectionsActive").set(n_conns)
        tenant: Optional[_Tenant] = None
        tenant_counted = False
        pending: Dict[str, _PendingQuery] = {}
        # prepared statements are CONNECTION-scoped (the Flight SQL session
        # model): dropped with the connection, so a churning client fleet
        # cannot grow the registry without bound — cross-client sharing
        # happens at the plan-cache layer (canonical keys), not here
        statements: Dict[str, PreparedStatement] = {}
        # live subscriptions are connection-scoped too: this is the sink
        # the refresh worker fans updates into; the loop below drains it
        subs = _ConnSubs(
            cfg.LIVE_SUBSCRIBER_MAX_PENDING.get(self.session.conf)
        )
        try:
            tenant = self._hello(sock)
            if tenant is None:
                return
            # per-tenant connection cap: one tenant's connection storm is
            # refused at HELLO time, before it can occupy handler threads
            cap = cfg.SERVE_MAX_CONNECTIONS_PER_TENANT.get(self.session.conf)
            with self._conn_lock:
                held = self._tenant_conns.get(tenant.name, 0)
                if cap > 0 and held >= cap:
                    over_tenant = True
                else:
                    over_tenant = False
                    self._tenant_conns[tenant.name] = held + 1
                    tenant_counted = True
            if over_tenant:
                _M.counter("serve.connectionsRejected").add(1)
                P.send_json(
                    sock, P.ERROR,
                    {"type": "ConnectionLimit", "code": "OVERLOADED",
                     "retry_after_s":
                         self.session.scheduler.retry_after_hint(),
                     "error": f"tenant {tenant.name!r} is at its "
                              f"connection limit ({cap})"},
                )
                return
            while not self._stopping.is_set():
                if subs.active():
                    # subscription mode: the blocking recv becomes a short
                    # select so the handler thread can interleave pending
                    # UPDATE trains with inbound commands — it is the only
                    # thread that ever writes this socket
                    if self._draining.is_set():
                        self._shed_subs(sock, subs, self._drain_reason)
                        continue
                    try:
                        self._pump_updates(sock, subs)
                        readable, _, _ = select.select([sock], [], [], 0.05)
                    except (OSError, ValueError):
                        return
                    if not readable:
                        continue
                try:
                    ftype, body = P.recv_frame(sock)
                except P.FrameCorruptError as e:
                    # the typed corrupt-frame close: name the cause on the
                    # way out, then drop the connection — nothing after a
                    # bad checksum can be trusted
                    self._send_error(sock, e)
                    return
                except (P.ConnectionClosed, OSError):
                    return
                if ftype == P.BYE:
                    return
                try:
                    self._dispatch(sock, tenant, pending, statements,
                                   subs, ftype, body)
                except _ClientGone:
                    return
                except P.ProtocolError:
                    raise
                except Exception as e:  # noqa: BLE001 - per-command errors
                    # answered as ERROR frames; the connection (and the
                    # session behind it) keeps serving subsequent queries
                    self._send_error(sock, e)
        except _ClientGone:
            # the client vanished while we were answering it (e.g. died
            # mid-UPDATE train and the ERROR reply failed too): plain
            # teardown, the finally below reaps its subscriptions
            _log.debug("connection %s vanished mid-reply", addr)
        except (P.ProtocolError, OSError) as e:
            _log.debug("connection %s closed: %s", addr, e)
        finally:
            # a vanished client must not leave queued-but-unfetched work
            for pq in pending.values():
                pq.cancelled_reason = "client disconnect"
            # … nor orphaned subscriptions: the runtime frees the shared
            # query's state when the last subscriber leaves
            subs.closed = True
            rt = self.session._live_runtime
            if rt is not None:
                for sub_id in subs.sub_ids():
                    try:
                        rt.unsubscribe(sub_id)
                    except Exception:  # noqa: BLE001 - teardown best-effort
                        _log.debug("unsubscribe %s failed", sub_id,
                                   exc_info=True)
                    subs.drop(sub_id)
            with self._conn_lock:
                self._conns.discard(sock)
                if tenant_counted and tenant is not None:
                    n = self._tenant_conns.get(tenant.name, 1) - 1
                    if n <= 0:
                        self._tenant_conns.pop(tenant.name, None)
                    else:
                        self._tenant_conns[tenant.name] = n
                n_conns = len(self._conns)
            _M.gauge("serve.connectionsActive").set(n_conns)
            try:
                sock.close()
            except OSError:
                pass

    def _hello(self, sock: socket.socket) -> Optional[_Tenant]:
        # slow-loris connects: a dribbling (or silent) HELLO holds only
        # this handler thread, and only until the deadline
        sock.settimeout(max(0.05, cfg.SERVE_HELLO_TIMEOUT_S.get(
            self.session.conf
        )))
        try:
            ftype, body = P.recv_frame(sock)
        except (P.ConnectionClosed, OSError, socket.timeout):
            return None
        finally:
            sock.settimeout(None)
        if ftype != P.HELLO:
            P.send_json(
                sock, P.ERROR,
                {"type": "ProtocolError", "error": "first frame must be HELLO"},
            )
            return None
        info = P.decode_json(body)
        token = info.get("token") or ""
        if self.tenants:
            tenant = self.tenants.get(token)
            if tenant is None:
                _M.counter("serve.connectionsRejected").add(1)
                P.send_json(
                    sock, P.ERROR,
                    {"type": "AuthError", "error": "unknown auth token"},
                )
                return None
        else:
            tenant = _Tenant("anonymous", "default")
        _M.counter("serve.connections").add(1)
        P.send_json(
            sock, P.HELLO_OK,
            {
                "tenant": tenant.name,
                "pool": tenant.pool,
                "protocol": P.PROTOCOL_VERSION,
                "server": "spark-rapids-tpu",
                # advertised readiness budget: wait_ready() with no
                # explicit timeout polls this long — conf-sized so a
                # cold boot's worst-case compile fits inside it
                "ready_timeout_s": cfg.SERVE_READY_TIMEOUT_S.get(
                    self.session.conf
                ),
            },
        )
        return tenant

    # ── command dispatch ────────────────────────────────────────────────
    def _dispatch(self, sock, tenant, pending, statements, subs,
                  ftype, body) -> None:
        if self._draining.is_set() and ftype in (
            P.EXECUTE, P.PREPARE, P.BIND, P.EXECUTE_PREPARED, P.FETCH,
            P.SUBSCRIBE,
        ):
            # drain contract: no NEW work once draining; STATUS and CANCEL
            # stay answerable so operators can watch the drain complete
            raise ServerDrainingError(
                f"server is draining ({self._drain_reason}); no new "
                "queries are accepted",
                reason=self._drain_reason,
            )
        if ftype == P.EXECUTE:
            self._cmd_execute(sock, tenant, pending, P.decode_json(body))
        elif ftype == P.PREPARE:
            self._cmd_prepare(sock, tenant, statements, P.decode_json(body))
        elif ftype in (P.BIND, P.EXECUTE_PREPARED):
            self._cmd_bind(sock, tenant, pending, statements,
                           P.decode_json(body))
        elif ftype == P.FETCH:
            self._cmd_fetch(sock, tenant, pending, P.decode_json(body))
        elif ftype == P.CANCEL:
            self._cmd_cancel(sock, pending, subs, P.decode_json(body))
        elif ftype == P.STATUS:
            self._cmd_status(sock, tenant)
        elif ftype == P.SUBSCRIBE:
            self._cmd_subscribe(sock, tenant, subs, P.decode_json(body))
        else:
            raise P.ProtocolError(
                f"unexpected frame {P.FRAME_NAMES.get(ftype, ftype)}"
            )

    def _next_qid(self) -> str:
        return f"srv-{next(self._qids)}"

    def _send_result(self, sock, pq: _PendingQuery) -> None:
        schema = pa.schema(
            [(f.name, f.data_type.to_arrow()) for f in pq.final_plan.output]
        )
        P.send_json(
            sock, P.RESULT,
            {
                "query_id": pq.query_id,
                "columns": [f.name for f in pq.final_plan.output],
                "schema": base64.b64encode(
                    ipc.schema_to_bytes(schema)
                ).decode("ascii"),
                "cache_hit": pq.cache_hit,
            },
        )

    def _note_dedup(self, key: Optional[str]) -> None:
        """Record a client dedup key; a repeat is a failover replay of a
        query already answered once (by this server or a dead peer that
        shared the client). Counted for attribution — the engine is pure,
        so re-executing is correct; at-most-once delivery is the CLIENT's
        job (it skips the frames it already yielded)."""
        if not key:
            return
        window = cfg.SERVE_FAILOVER_DEDUP_WINDOW.get(self.session.conf)
        if window <= 0:
            return
        with self._dedup_lock:
            if key in self._dedup_seen:
                self._dedup_seen.move_to_end(key)
                replay = True
            else:
                self._dedup_seen[key] = True
                replay = False
                while len(self._dedup_seen) > window:
                    self._dedup_seen.popitem(last=False)
        if replay:
            _M.counter("serve.dedupReplays").add(1)

    def _cmd_execute(self, sock, tenant, pending, req) -> None:
        from ..obs.trace import SpanContext

        sql_text = req.get("sql") or ""
        params = req.get("params")
        self._note_dedup(req.get("dedup_key"))
        df = self.session.sql(sql_text, params=params)
        final_plan, ctx = self.session._prepare_plan(df._plan)
        pq = _PendingQuery(
            self._next_qid(), final_plan, ctx,
            wire_trace=SpanContext.from_wire(req.get("trace")),
        )
        pending[pq.query_id] = pq
        self._send_result(sock, pq)

    def _cmd_prepare(self, sock, tenant, statements, req) -> None:
        from ..sql import parse

        sql_text = req.get("sql") or ""
        ast = parse(sql_text)
        stmt = PreparedStatement(
            self.prepared.next_statement_id(), sql_text, ast, tenant.name
        )
        statements[stmt.statement_id] = stmt
        _M.counter("serve.preparedStatements").add(1)
        P.send_json(
            sock, P.PREPARE_OK,
            {"statement_id": stmt.statement_id, "n_params": stmt.n_params},
        )

    def _cmd_bind(self, sock, tenant, pending, statements, req) -> None:
        sid = req.get("statement_id") or ""
        stmt = statements.get(sid)
        if stmt is None:
            raise SqlError(f"unknown statement_id {sid!r}")
        self._note_dedup(req.get("dedup_key"))
        from ..obs.trace import SpanContext

        final_plan, ctx, hit = self.prepared.resolve(
            stmt, req.get("params") or []
        )
        pq = _PendingQuery(
            self._next_qid(), final_plan, ctx, cache_hit=hit, traceable=False,
            wire_trace=SpanContext.from_wire(req.get("trace")),
        )
        pending[pq.query_id] = pq
        self._send_result(sock, pq)

    def _cmd_cancel(self, sock, pending, subs, req) -> None:
        sub_id = req.get("subscription_id")
        if sub_id:
            # CANCEL with a subscription_id = unsubscribe (valid any time,
            # including between a train's frames — the handler thread only
            # reads commands at train boundaries, so no interleaving)
            rt = self.session._live_runtime
            found = bool(rt is not None and rt.unsubscribe(sub_id))
            subs.drop(sub_id)
            if found:
                _M.counter("serve.cancels").add(1)
            P.send_json(sock, P.UNSUBSCRIBED,
                        {"subscription_id": sub_id, "found": found})
            return
        qid = req.get("query_id") or ""
        found = False
        pq = pending.get(qid)
        if pq is not None and pq.cancelled_reason is None:
            pq.cancelled_reason = "client cancel"
            found = True
        # already admitted (queued or mid-stream on another thread): flag
        # through the scheduler registry — reason reaches the metrics
        found = self.session.cancel(qid, reason="client cancel") or found
        if found:
            _M.counter("serve.cancels").add(1)
        P.send_json(sock, P.CANCEL_OK, {"query_id": qid, "found": found})

    def _cmd_status(self, sock, tenant) -> None:
        with self._inflight_cond:
            inflight = self._inflight
        P.send_json(
            sock, P.STATUS_OK,
            {
                "tenant": tenant.name,
                "pool": tenant.pool,
                # lifecycle for operators: live is this process answering
                # at all; ready gates traffic shifting (warm pool primed,
                # not draining) — the rolling-restart contract
                "live": True,
                "ready": self.is_ready(),
                "draining": self._draining.is_set(),
                # warmup progress: "compiling statement k of n" vs "hung"
                # is exactly the distinction a restart orchestrator needs
                # while ready=false
                "warmup": dict(self._warmup_progress),
                "ready_timeout_s": cfg.SERVE_READY_TIMEOUT_S.get(
                    self.session.conf
                ),
                "inflight": inflight,
                "active": self.session.active_queries(),
                "scheduler": self.session.scheduler.state(),
                "serve": _M.view("serve.", strip=False),
                "prepared_cache": self.prepared.stats(),
                "result_cache": self.session._result_cache.stats(),
                "subplan_dedup": self.session._subplan_registry.stats(),
                # live-analytics slice (ISSUE 20): table versions,
                # maintained queries (class + fallback reason + epoch),
                # subscriber count, state bytes, and the live.* metric
                # catalog slice; null until session.live is first touched
                "live_analytics": (
                    dict(
                        self.session._live_runtime.status(),
                        metrics=_M.view("live.", strip=False),
                    )
                    if self.session._live_runtime is not None
                    else None
                ),
            },
        )

    # ── the subscription stream ─────────────────────────────────────────
    def _cmd_subscribe(self, sock, tenant, subs, req) -> None:
        sql_text = req.get("sql") or ""
        # session.live raises a typed RuntimeError when
        # spark.rapids.tpu.live.enabled is off — answered as an ERROR
        # frame like any per-command failure; the connection survives
        rt = self.session.live
        desc = rt.subscribe(sql_text, subs)
        sub_id = desc["subscription_id"]
        subs.register(sub_id, desc["qid"])
        P.send_json(
            sock, P.SUBSCRIBE_OK,
            {
                "subscription_id": sub_id,
                "query_id": desc["qid"],
                "mode": desc["mode"],
                "reason": desc["reason"],
                "epoch": desc["epoch"],
            },
        )
        snap = desc["snapshot"]
        if snap is not None:
            # the initial state, as a regular UPDATE train so the client
            # reads one uniform stream; a just-seeded or quiet query may
            # legitimately have nothing newer afterwards
            self._send_update_train(
                sock, sub_id, desc["epoch"], "snapshot", snap
            )
            subs.mark_sent(sub_id, desc["epoch"])
            _M.counter("live.updates.sent").add(1)

    def _pump_updates(self, sock, subs) -> None:
        """Drain every deliverable subscription update onto the wire
        (handler thread). A collapsed slow consumer gets one fresh
        snapshot instead of its dropped epochs; if that snapshot is
        unavailable (demoted state lost its file), the resend is skipped —
        the reseeding refresh fans out a new update anyway."""
        while True:
            item = subs.next_delivery()
            if item is None:
                return
            sub_id, qid, upd = item
            if upd is None:
                rt = self.session._live_runtime
                q = rt.query(qid) if rt is not None else None
                snap = q.snapshot() if q is not None else None
                if snap is None:
                    continue
                epoch, table = snap
                if epoch <= subs.last_epoch(sub_id):
                    continue
                self._send_update_train(
                    sock, sub_id, epoch, "snapshot", table
                )
            else:
                self._send_update_train(
                    sock, sub_id, upd.epoch, upd.kind, upd.table,
                    incremental=upd.incremental, reason=upd.reason,
                )
                epoch = upd.epoch
            subs.mark_sent(sub_id, epoch)
            _M.counter("live.updates.sent").add(1)

    def _send_update_train(self, sock, sub_id: str, epoch: int, kind: str,
                           table: pa.Table, incremental: bool = True,
                           reason: Optional[str] = None) -> None:
        """One epoch-stamped UPDATE train: JSON header, the payload
        re-chunked as BATCH frames, UPDATE_END. Counted in-flight so
        ``drain()`` waits for a train mid-write exactly as it does for a
        FETCH stream. An empty payload still carries one zero-row batch —
        the client needs the schema."""
        max_rows = max(1, cfg.SERVE_STREAM_BATCH_ROWS.get(self.session.conf))
        with self._inflight_cond:
            self._inflight += 1
        try:
            hdr = {
                "subscription_id": sub_id,
                "epoch": epoch,
                "kind": kind,
                "rows": table.num_rows,
                "incremental": incremental,
            }
            if reason:
                hdr["reason"] = reason
            P.send_json(sock, P.UPDATE, hdr)
            batches = [
                rb for rb in table.to_batches(max_chunksize=max_rows)
                if rb.num_rows
            ]
            if not batches:
                sch = table.schema
                batches = [pa.RecordBatch.from_arrays(
                    [pa.array([], type=f.type) for f in sch], schema=sch,
                )]
            for rb in batches:
                payload = ipc.write_batch(rb)
                P.send_frame(sock, P.BATCH, payload)
                _M.counter("serve.streamedBatches").add(1)
                _M.counter("serve.streamedBytes").add(len(payload))
            P.send_json(sock, P.UPDATE_END,
                        {"subscription_id": sub_id, "epoch": epoch})
        finally:
            with self._inflight_cond:
                self._inflight -= 1
                self._inflight_cond.notify_all()

    def _shed_subs(self, sock, subs, reason: str) -> None:
        """Drain contract for subscriptions: proactively unsubscribe every
        live subscription and tell the client why, so dashboard clients
        re-subscribe against a peer instead of waiting on a dead wire."""
        rt = self.session._live_runtime
        for sub_id in subs.sub_ids():
            if rt is not None:
                rt.unsubscribe(sub_id)
            subs.drop(sub_id)
            try:
                P.send_json(sock, P.UNSUBSCRIBED,
                            {"subscription_id": sub_id, "reason": reason})
            except OSError:
                return

    # ── the fetch stream ────────────────────────────────────────────────
    def _cmd_fetch(self, sock, tenant, pending, req) -> None:
        qid = req.get("query_id") or ""
        pq = pending.pop(qid, None)
        if pq is None:
            raise SqlError(f"unknown or already-fetched query_id {qid!r}")
        cap = cfg.SERVE_MAX_INFLIGHT_PER_TENANT.get(self.session.conf)
        with self._inflight_cond:
            held = self._tenant_inflight.get(tenant.name, 0)
            if cap > 0 and held >= cap:
                pending[qid] = pq  # still fetchable once the tenant drains
                # counted once in _send_error when the OVERLOADED frame
                # actually goes out — not here too
                raise QueryOverloadedError(
                    f"tenant {tenant.name!r} is at its in-flight query "
                    f"limit ({cap}); retry after the hint",
                    retry_after_s=self.session.scheduler.retry_after_hint(),
                    reason="tenant_inflight",
                )
            self._tenant_inflight[tenant.name] = held + 1
            self._inflight += 1
        try:
            self._fetch_stream(sock, tenant, pq, qid)
        finally:
            with self._inflight_cond:
                self._inflight -= 1
                n = self._tenant_inflight.get(tenant.name, 1) - 1
                if n <= 0:
                    self._tenant_inflight.pop(tenant.name, None)
                else:
                    self._tenant_inflight[tenant.name] = n
                self._inflight_cond.notify_all()

    def _fetch_stream(self, sock, tenant, pq: _PendingQuery, qid: str) -> None:
        _M.counter("serve.queries").add(1)
        _M.counter(
            obs_metrics.dynamic_name(
                "serve.tenant.", tenant.name, ".queries", fallback="anon"
            )
        ).add(1)
        max_rows = max(1, cfg.SERVE_STREAM_BATCH_ROWS.get(self.session.conf))
        t0 = time.perf_counter_ns()
        rows = 0
        batches = 0
        # served queries ride the session's obs + chaos envelopes exactly
        # like in-process collect(): sampled span tracing (EXECUTE-path
        # plans only — see _PendingQuery.traceable) and the session's
        # fault-injection scope, so trace artifacts and faults.* confs
        # work identically for wire traffic
        from ..obs import trace as obs_trace
        from ..resilience import faults as _faults

        wire = pq.wire_trace
        if (
            wire is not None
            and wire.sampled
            and cfg.TRACE_PROPAGATE.get(self.session.conf)
        ):
            # the client's sampled bit IS the trace decision (Dapper):
            # adopt its trace id and parent this query tree under the
            # client span so both exports merge into one coherent tree.
            # Prepared statements propagate too — only the per-node plan
            # instrumentation below is skipped for them (cached plans are
            # SHARED; the query root + queued + module-level spans still
            # record), so a traced client's prepared executions never
            # leave an orphan client span
            tracer = obs_trace.Tracer(
                capacity=cfg.TRACE_BUFFER_SPANS.get(self.session.conf),
                trace_id=wire.trace_id,
                remote_parent=wire.span_id,
            )
        else:
            tracer = (
                self.session._maybe_tracer(pq.ctx.query_seq)
                if pq.traceable
                else None
            )
        if tracer is not None and pq.traceable:
            obs_trace.instrument_plan(pq.final_plan, tracer)
        led = getattr(pq.ctx, "ledger", None)
        if led is not None:
            led.wall_start()  # second wall window: prepare was the first
        lease = None
        try:
            if pq.cancelled_reason:
                raise QueryCancelledError(
                    f"query {qid} cancelled before fetch: "
                    f"{pq.cancelled_reason}",
                    reason=pq.cancelled_reason,
                )
            # semantic result cache (cache/results.py): an identical
            # completed query streams its cached batches HERE — before
            # scheduler admission; a hit costs no scheduler state at all
            rkey, rkeys = None, ()
            if cfg.RESULT_CACHE_ENABLED.get(self.session.conf):
                from ..cache import results as _rcache

                rkey, rkeys = _rcache.key_for(self.session, pq.final_plan)
                if rkey is not None:
                    # faults scope covers the disk-tier read-back (the
                    # chaos harness's spill-read injection point)
                    with _faults.scoped(self.session._fault_injector):
                        hit = self.session._result_cache.get(rkey)
                    if hit is not None:
                        self._stream_cached(
                            sock, tenant, qid, hit, max_rows, t0
                        )
                        return
            # concurrent subplan dedup (cache/subplan.py): wrap shareable
            # subtrees for single-flight execution across in-flight
            # queries; admission keeps keying off the original plan
            exec_plan, lease = self.session._subplan_registry.prepare(
                self.session, pq.final_plan, self.session.conf, qid
            )
            rec: "list | None" = [] if rkey is not None else None
            rec_bytes = 0
            rec_cap = cfg.RESULT_CACHE_MAX_BYTES.get(self.session.conf)
            with _faults.scoped(self.session._fault_injector), \
                    obs_trace.query_scope(tracer, f"query-{qid}", {"qid": qid}):
                with self.session._scheduler.admit(
                    qid, pq.final_plan, self.session.conf,
                    tracer=tracer, pool=tenant.pool,
                ) as adm:
                    pq.ctx.cancel_token = adm.token
                    if led is not None:
                        led.add("queue_wait", adm.queue_wait_ns)
                    if pq.cancelled_reason:  # raced the admission
                        adm.token.cancel(pq.cancelled_reason)
                    for rb in self.session.run_plan_stream(
                        exec_plan, pq.ctx
                    ):
                        if rec is not None:
                            # tee the pre-rechunk stream for cache
                            # admission; an over-budget result stops
                            # recording, never the stream
                            rec_bytes += rb.nbytes
                            if rec_bytes > rec_cap:
                                rec = None
                            else:
                                rec.append(rb)
                        for chunk in _rechunk(rb, max_rows):
                            self._send_batch(sock, adm.token, chunk)
                            rows += chunk.num_rows
                            batches += 1
                            self._poll_cancel(sock, adm.token)
                    adm.token.check()  # a cancel that raced the final batch
                    if rec is not None:
                        # commit only after the full stream survived the
                        # final cancel check; admission re-fingerprints,
                        # so an append that raced this stream rejects it
                        self.session._result_cache.admit(
                            self.session, rkey, rkeys, rec
                        )
                    wait_ms = adm.queue_wait_ns / 1e6
                    run_ms = (time.perf_counter_ns() - t0) / 1e6 - wait_ms
                    P.send_json(
                        sock, P.END,
                        {
                            "query_id": qid,
                            "rows": rows,
                            "batches": batches,
                            "wait_ms": round(wait_ms, 3),
                            "run_ms": round(max(0.0, run_ms), 3),
                        },
                    )
            _M.timer("serve.queryWaitNs").add(adm.queue_wait_ns)
            run_ns = time.perf_counter_ns() - t0 - adm.queue_wait_ns
            _M.timer("serve.queryRunNs").add(max(0, run_ns))
            # the distribution series (log2-bucket histograms): what a
            # p50/p95/p99 of the server's own wait and run time is read from
            _M_WAIT_HIST.observe(adm.queue_wait_ns)
            _M_RUN_HIST.observe(max(0, run_ns))
            _M_TOTAL_HIST.observe(adm.queue_wait_ns + max(0, run_ns))
            self.latency_samples.append(
                (tenant.name, adm.queue_wait_ns / 1e9, max(0, run_ns) / 1e9)
            )
        except _ClientGone:
            _M.counter("serve.queryErrors").add(1)
            raise
        except Exception as e:  # noqa: BLE001 - reported as ERROR frame
            # (cancellations were already counted at their initiation
            # site — _cmd_cancel, _poll_cancel, or _send_batch)
            _M.counter("serve.queryErrors").add(1)
            self._send_error(sock, e, query_id=qid)
        finally:
            if lease is not None:
                lease.release()
            if led is not None:
                led.wall_stop()
                self.session._last_ledger = led
            if tracer is not None:
                self.session._export_trace(
                    tracer, pq.final_plan, pq.ctx.query_seq, ledger=led
                )
            self.session._leak_check(pq.ctx)

    def _stream_cached(
        self, sock, tenant, qid: str, hit, max_rows: int, t0: int
    ) -> None:
        """Stream a result-cache hit to the client: same wire framing,
        rechunking, cancel polling, and latency bookkeeping as a cold
        stream, but with zero scheduler involvement (no admission, no
        queue wait — the hit's wait time IS 0). A fresh CancelToken keeps
        client-side CANCEL working mid-stream."""
        from ..sched import CancelToken

        token = CancelToken(query_id=qid)
        rows = 0
        batches = 0
        for rb in hit:
            if rb.num_rows == 0:
                continue  # wire protocol never carries empty batches
            for chunk in _rechunk(rb, max_rows):
                self._send_batch(sock, token, chunk)
                rows += chunk.num_rows
                batches += 1
                self._poll_cancel(sock, token)
        token.check()  # a cancel that raced the final batch
        run_ns = max(0, time.perf_counter_ns() - t0)
        P.send_json(
            sock, P.END,
            {
                "query_id": qid,
                "rows": rows,
                "batches": batches,
                "wait_ms": 0.0,
                "run_ms": round(run_ns / 1e6, 3),
                "cache_hit": True,
            },
        )
        _M.timer("serve.queryRunNs").add(run_ns)
        _M_WAIT_HIST.observe(0)
        _M_RUN_HIST.observe(run_ns)
        _M_TOTAL_HIST.observe(run_ns)
        self.latency_samples.append((tenant.name, 0.0, run_ns / 1e9))

    def _send_batch(self, sock, token, rb: pa.RecordBatch) -> None:
        from ..obs import ledger as obs_ledger
        from ..resilience.watchdog import stall_phase

        # wire IPC encoding bills the query ledger's 'serialize' phase
        # (the handler thread carries the stream's current ledger)
        with obs_ledger.phase("serialize"):
            payload = ipc.write_batch(rb)
        send_timeout = cfg.SERVE_SEND_TIMEOUT_S.get(self.session.conf)
        try:
            # phase 'client' + a bounded send: a reader that stopped
            # draining its socket (slow loris) classifies as a CLIENT
            # stall on the watchdog and times out here — its query
            # cancels and the permits free, instead of a forever-blocked
            # sendall pinning the tenant's capacity
            with stall_phase("client", token=token):
                if send_timeout > 0:
                    sock.settimeout(send_timeout)
                try:
                    P.send_frame(sock, P.BATCH, payload)
                finally:
                    if send_timeout > 0:
                        sock.settimeout(None)
        except OSError:
            # disconnect-as-cancellation: the admission context releases
            # the permits as the typed error unwinds, and the
            # scheduler.cancelled.reason.client_disconnect series records
            # why (the satellite's distinguishable-cancel contract)
            token.cancel("client disconnect")
            _M.counter("serve.cancels").add(1)
            try:
                token.check()
            except QueryCancelledError as e:
                raise e from None
            raise _ClientGone()  # token already tripped by someone else
        _M.counter("serve.streamedBatches").add(1)
        _M.counter("serve.streamedBytes").add(len(payload))

    def _poll_cancel(self, sock, token) -> None:
        """Between BATCH frames, look for an inbound CANCEL (the client may
        send it while still reading the stream — the socket is full
        duplex). EOF here means the client vanished."""
        try:
            readable, _, _ = select.select([sock], [], [], 0)
        except (OSError, ValueError):
            token.cancel("client disconnect")
            return
        if not readable:
            return
        try:
            ftype, body = P.recv_frame(sock)
        except (P.ConnectionClosed, OSError):
            token.cancel("client disconnect")
            _M.counter("serve.cancels").add(1)
            return
        if ftype == P.CANCEL:
            token.cancel("client cancel")
            _M.counter("serve.cancels").add(1)
        elif ftype == P.BYE:
            token.cancel("client disconnect")
            _M.counter("serve.cancels").add(1)
        else:
            raise P.ProtocolError(
                f"unexpected {P.FRAME_NAMES.get(ftype, ftype)} mid-stream "
                "(only CANCEL is valid while fetching)"
            )

    def _send_error(self, sock, e: Exception, query_id: Optional[str] = None):
        info = {
            "type": type(e).__name__,
            "error": str(e)[:2000],
        }
        if isinstance(e, (QueryCancelledError, SchedulerError,
                          ServerDrainingError)):
            info["reason"] = getattr(e, "reason", "") or ""
        if isinstance(e, (QueryQueueFull, QueryOverloadedError)):
            # the typed overload contract: a machine-readable code plus a
            # computed retry-after, so clients back off instead of
            # hammering a saturated scheduler (visible server-side as the
            # scheduler.shed.reason.* / scheduler.rejected series)
            info["code"] = "OVERLOADED"
            info["retry_after_s"] = (
                getattr(e, "retry_after_s", 0.0)
                or self.session.scheduler.retry_after_hint()
            )
            _M.counter("serve.overloaded").add(1)
        elif isinstance(e, ServerDrainingError):
            info["code"] = "DRAINING"
        if query_id is not None:
            info["query_id"] = query_id
        try:
            P.send_json(sock, P.ERROR, info)
        except OSError:
            raise _ClientGone() from None


def _rechunk(rb: pa.RecordBatch, max_rows: int):
    if rb.num_rows <= max_rows:
        yield rb
        return
    off = 0
    # graft: ok(cancel-beat: zero-copy slicing of one already-materialized
    # host batch; the _fetch_stream send loop around it beats per frame)
    while off < rb.num_rows:
        yield rb.slice(off, min(max_rows, rb.num_rows - off))
        off += max_rows
