"""Module-level jitted-kernel cache — compile once per query *shape*, not per
query execution.

The reference relies on cuDF's pre-compiled kernel library: planning a query
never compiles GPU code, so running the same query twice costs the same both
times. The TPU engine compiles its kernels with XLA at first use instead —
which is only acceptable if compiled kernels are reused across `collect()`
calls. Exec instances are rebuilt per query (session._execute), so jitted
closures must NOT live on exec instances; they live here, keyed by the
semantic identity of the kernel:

    (kernel kind, bound expression tree(s), schema signature, static config)

Bound expressions are frozen dataclasses (hashable by structure — expr/base),
and schemas/types are value objects, so the key is a plain tuple. XLA's own
per-function tracing cache then handles shape/dtype specialization beneath
each entry (capacity bucketing keeps that logarithmic).

A persistent on-disk compilation cache (enable_persistent_cache) additionally
reuses XLA binaries across *processes* — the analogue of shipping cuDF's
pre-built kernels. Reference framing: SURVEY.md §7 "recompilation management"
(the #1 perf trap); RapidsConf.scala has no analogue because cuDF never
recompiles.
"""
from __future__ import annotations

import os
import threading
from typing import Callable

import jax

from .obs import ledger as obs_ledger
from .obs import metrics as obs_metrics
from .obs import trace as obs_trace

_LOCK = threading.Lock()
_KERNELS: dict = {}

# typed process metrics (obs/metrics.py catalog) replacing the old module
# counters: compile-vs-execute attribution, cache behavior, precompiles
_M_BUILDS = obs_metrics.GLOBAL.counter("kernel.builds")
_M_CACHE_HITS = obs_metrics.GLOBAL.counter("kernel.cacheHits")
_M_WARMS = obs_metrics.GLOBAL.counter("kernel.warms")
_M_WARM_NS = obs_metrics.GLOBAL.timer("kernel.warmTimeNs")
_M_FIRST_CALLS = obs_metrics.GLOBAL.counter("kernel.firstCalls")
_M_COMPILE_NS = obs_metrics.GLOBAL.timer("kernel.compileTimeNs")
_M_COMPILE_HIST = obs_metrics.GLOBAL.histogram("kernel.compileHist")
_M_KEY_PASSES = obs_metrics.GLOBAL.counter("sort.keyPasses")
_M_KEY_PASSES_UNPACKED = obs_metrics.GLOBAL.counter("sort.keyPassesUnpacked")
_M_GATHER_PLANES = obs_metrics.GLOBAL.counter("gather.planes")
_M_GATHER_LAUNCHES = obs_metrics.GLOBAL.counter("gather.launches")


def kernel(key: tuple, builder: Callable):
    """Return the cached kernel for ``key``, building it on first use.

    ``builder`` returns the (usually jitted) callable; it must close over
    nothing whose lifetime matters — everything semantic belongs in the key.
    The key doubles as the kernel's PERSISTENT identity: the on-disk
    executable store (cache/xla_store.py) digests it together with each
    call's arg signature, so a restarted process deserializes yesterday's
    binaries instead of recompiling.
    """
    fn = _KERNELS.get(key)
    if fn is None:
        with _LOCK:
            fn = _KERNELS.get(key)
            if fn is None:
                fn = builder()
                _adopt_store_key(fn, key)
                _KERNELS[key] = fn
                _M_BUILDS.add(1)
                return fn
    _M_CACHE_HITS.add(1)
    return fn


def _adopt_store_key(fn, key: tuple) -> None:
    """Attach the kernel-cache key as the persistent store identity of the
    GuardedJit behind ``fn`` (directly, or one wrapper deep — the
    _ErrorCheckingKernel shape). GuardedJits built without a key stay
    memory-only: no stable identity, no disk entry."""
    gj = fn if isinstance(fn, GuardedJit) else getattr(fn, "_fn", None)
    if isinstance(gj, GuardedJit) and gj._store_key is None:
        gj._store_key = key


# Reentrant: tracing one kernel may invoke another GuardedJit (e.g. a fused
# kernel built from cached sub-kernels); a plain lock would self-deadlock.
_COMPILE_LOCK = threading.RLock()

#: sentinel returned by GuardedJit._prove_loaded when a cache-loaded
#: executable blew up its proving run (the caller falls back to a fresh
#: compile; a kernel result can never BE this object)
_PROVE_FAILED = object()

# ── compile deadline (spark.rapids.tpu.compile.deadlineSeconds) ─────────────
# Process-global like the kernel cache itself: the session stamps it at init
# and on set_conf; 0 disables. Boxed so readers never race a rebind.
_COMPILE_DEADLINE_S = [0.0]
_M_COMPILE_DEADLINES = obs_metrics.GLOBAL.counter("kernel.compileDeadlines")


def set_compile_deadline(seconds: float) -> None:
    """Install the first-touch compile budget (0 disables)."""
    _COMPILE_DEADLINE_S[0] = max(0.0, float(seconds))


# ── shape-bucket lattice ────────────────────────────────────────────────────
# Compile-geometry policy: batch capacities round up to a pow-2 lattice with
# this floor (columnar/device.py bucket_capacity reads it), so one cached
# executable serves every batch geometry inside a bucket. Process-global
# like the kernel cache whose entry count it bounds: the session stamps it
# at init and on set_conf (spark.rapids.tpu.shapeBuckets.*). Boxed so
# readers never race a rebind. The floor never drops below 8 (MIN_CAPACITY
# — the lattice degenerates to plain pow-2-of-row-count bucketing there).
_SHAPE_BUCKET_FLOOR = [8]


def set_shape_bucket_floor(rows: int) -> None:
    """Install the lattice floor, rounded up to a power of two (>= 8)."""
    f = 8
    while f < min(int(rows), 1 << 24):
        f <<= 1
    _SHAPE_BUCKET_FLOOR[0] = f


def shape_bucket_floor() -> int:
    return _SHAPE_BUCKET_FLOOR[0]


#: set on the deadline helper thread: a NESTED first-touch compile inside
#: the guarded region (a fused kernel tracing into a cached sub-kernel's
#: first call) must run inline there — the outer budget already bounds the
#: whole nest, and a second helper thread could never re-enter the RLock
#: the helper holds
_DEADLINE_TLS = threading.local()


def _call_with_deadline(fn, deadline_s: float):
    """Run ``fn()`` — the locked first-touch trace+compile region — under
    a wall-clock budget. Without a budget this is a plain call. With one,
    the region runs on a helper thread (big stack: LLVM recursion), which
    acquires _COMPILE_LOCK ITSELF so nested first-touch compiles re-enter
    the RLock on that same thread; a join past the deadline raises the
    typed CompileDeadlineError while the orphan daemon finishes (XLA
    exposes no compile cancellation). The orphan keeps holding
    _COMPILE_LOCK until its compile returns, so the hazard window after a
    blown budget is the orphan's remaining compile — acceptable for the
    pathological case the deadline exists to cut, and exactly why the
    deadline defaults off on single-tenant use."""
    if deadline_s <= 0 or getattr(_DEADLINE_TLS, "active", False):
        return fn()
    from .resilience.watchdog import CompileDeadlineError
    from .utils.threads import start_big_stack_thread

    box: list = []

    def run():
        _DEADLINE_TLS.active = True
        try:
            box.append(("ok", fn()))
        except BaseException as e:  # noqa: BLE001 - re-raised on the caller
            box.append(("err", e))
        finally:
            _DEADLINE_TLS.active = False

    t = start_big_stack_thread(run, "srt-compile-deadline")
    t.join(timeout=deadline_s)
    if not box:
        _M_COMPILE_DEADLINES.add(1)
        raise CompileDeadlineError(
            f"first-touch kernel compile exceeded its budget of "
            f"{deadline_s:g}s (spark.rapids.tpu.compile.deadlineSeconds); "
            "abandoning the compile and flipping the op to CPU via the "
            "circuit breaker"
        )
    kind, val = box[0]
    if kind == "err":
        raise val
    return val


def _args_sig(args) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        treedef,
        tuple(
            (tuple(x.shape), str(x.dtype)) if hasattr(x, "shape") else repr(x)
            for x in leaves
        ),
    )


class GuardedJit:
    """``jax.jit`` wrapper that serializes first-time compilations.

    The session runs partition tasks on a thread pool; concurrent XLA-CPU
    compilations from those worker threads segfault once enough compiled
    state has accumulated (deterministic SIGSEGV inside
    ``backend_compile_and_load`` on full-suite runs). First call per input
    signature takes a global compile lock; the compiled fast path stays
    lock-free."""

    __slots__ = ("_fn", "_seen", "_warmed", "_store_key", "_loaded",
                 "_unproven", "_digests", "_on_launch")

    def __init__(self, fn, store_key: tuple | None = None, on_launch=None):
        self._fn = jax.jit(fn)
        #: called as ``on_launch(sig, args)`` at every call, with the arg
        #: signature this call computes anyway (host-side launch counters)
        self._on_launch = on_launch
        self._seen = set()
        self._warmed = set()
        #: persistent identity for the on-disk executable store — the
        #: kernel-cache key (kernel()); None = memory-only kernel
        self._store_key = store_key
        #: sig -> AOT Compiled executable (disk-cache loads AND fresh AOT
        #: compiles); takes precedence over the jit fast path so a loaded
        #: binary serves every call without re-tracing
        self._loaded: dict = {}
        #: sigs whose loaded executable has not yet survived one real
        #: call — a blowup there is treated as cache poison, not a query
        #: failure (see _proving_call)
        self._unproven: set = set()
        #: sig -> digest memo (digesting walks the whole key; do it once)
        self._digests: dict = {}

    def _store_digest(self, sig):
        if self._store_key is None:
            return None
        if sig in self._digests:
            return self._digests[sig]
        from .cache import xla_store as _xc

        d = _xc.digest_for(self._store_key, sig)
        if len(self._digests) > 128:
            self._digests.clear()
        self._digests[sig] = d
        return d

    def warm(self, *args) -> bool:
        """Pre-compilation (the tentpole's compile-warm pass): lower +
        compile against ``args`` — usually jax.ShapeDtypeStruct pytrees —
        WITHOUT executing, retaining the AOT executable so the first real
        call runs it directly. The binary also lands in the persistent
        executable store (cache/xla_store.py), the TPU analogue of cuDF
        shipping pre-built kernels — and when the store already HOLDS this
        signature, the warm short-circuits to a deserialization BEFORE
        touching the global compile lock, so a warm restart never queues
        disk hits behind a slow compile.

        Fresh compiles are serialized through the global compile lock on
        XLA:CPU (the known concurrent-compile SIGSEGV); on other backends
        warms run concurrently, bounded by the precompile pool. Returns
        False when the signature was already compiled or warmed."""
        sig = _args_sig(args)
        if sig in self._seen or sig in self._warmed or sig in self._loaded:
            return False
        from .cache import xla_store as _xc

        digest = (
            self._store_digest(sig) if _xc.active_store() is not None else None
        )
        if digest is not None:
            loaded = _xc.load_executable(digest)
            if loaded is not None:
                self._loaded[sig] = loaded
                self._unproven.add(sig)
                self._warmed.add(sig)
                return True
        with obs_ledger.phase("compile"), _M_WARM_NS.timed():
            if jax.default_backend() == "cpu":
                with _COMPILE_LOCK:
                    # graft: ok(lock-order: the compile lock EXISTS to
                    # serialize XLA:CPU compiles (concurrent-compile
                    # SIGSEGV) — compiling under it is the design, and
                    # the deadline helper owns the lock on its own
                    # thread so a blown budget cannot wedge it)
                    compiled, from_store = self._warm_compile(args, digest)
            else:
                compiled, from_store = self._warm_compile(args, digest)
        self._loaded[sig] = compiled
        self._warmed.add(sig)
        if from_store:
            self._unproven.add(sig)
        else:
            _M_WARMS.add(1)
        return True

    def _warm_compile(self, args, digest):
        """The warm-miss slow path (under _COMPILE_LOCK on XLA:CPU).
        Publishing compiles take the cross-process single-flight lock so
        a FLEET cold boot — N servers warming the same statements against
        one cache dir — compiles each shape once; once the flight slot is
        ours the store is re-checked (a peer may have published while we
        waited). Returns (executable, came_from_store)."""
        from .cache import xla_store as _xc

        store = _xc.active_store() if digest is not None else None
        if store is None:
            return self._fn.lower(*args).compile(), False
        with store.single_flight(digest):
            loaded = _xc.load_executable(digest)
            if loaded is not None:
                return loaded, True
            compiled = self._fn.lower(*args).compile()
            # the native executable SERIALIZER shares the compiler's
            # thread-unsafety on XLA:CPU — the caller holds the compile
            # lock around this whole helper there
            payload = _xc.serialize_executable(compiled)
            if payload is not None:
                _xc.store_executable(digest, payload)
            return compiled, False

    def __call__(self, *args):
        from .resilience import faults as _faults

        if _faults._ACTIVE is not None:
            # chaos harness: synthetic RESOURCE_EXHAUSTED on the Nth launch
            # (spark.rapids.tpu.faults.deviceOomEveryN) — surfaces exactly
            # where a real allocation failure would, so the retry/spill/
            # split machinery above this call is what recovers it
            _faults.on_kernel_launch()
            # wedged-device simulation (kernelStallEveryN): the launch
            # SLEEPS instead of failing — nothing here recovers it; the
            # progress watchdog's stall cancel is what the chaos suite
            # asserts on
            _faults.on_kernel_stall()
        sig = _args_sig(args)
        if self._on_launch is not None:
            self._on_launch(sig, args)
        loaded = self._loaded.get(sig)
        if loaded is not None:
            if sig in self._unproven:
                return self._proving_call(loaded, sig, args)
            if sig not in self._seen:
                # _seen doubles as "this signature has executed" for the
                # precompile pass's warm-hit accounting
                self._seen.add(sig)
            return loaded(*args)
        if sig in self._seen:
            return self._fn(*args)

        def locked_first():
            # lock acquisition INSIDE the deadline scope: under a budget
            # this whole region runs on the helper thread, so nested
            # first-touch compiles (fused kernels tracing into cached
            # sub-kernels) re-enter the RLock on the thread that holds it
            with _COMPILE_LOCK:
                out = self._first_call(args, sig)
                self._seen.add(sig)
                return out

        deadline = _COMPILE_DEADLINE_S[0]
        if deadline <= 0:
            return locked_first()
        from .resilience import watchdog as _wd

        # phase-label the caller thread too: it blocks in join() for up
        # to the budget, and a watchdog stall there is a compile stall.
        # The LEDGER scope also lives here, on the caller: the helper
        # thread has no current ledger (thread-locals don't ride along),
        # and the caller's join-wait IS the compile's wall-clock cost —
        # billing it here keeps 'compile' honest under a deadline and
        # avoids double-counting against the caller's open 'dispatch'
        with _wd.stall_phase("compile"), obs_ledger.phase("compile"):
            return _call_with_deadline(locked_first, deadline)

    def _prove_loaded(self, loaded, sig, digest, args):
        """First real run of a cache-loaded executable. A blowup here that
        is neither a device OOM (the retry machinery's jurisdiction) nor
        an injected fault is a bad deserialization in disguise — the entry
        is quarantined (so no path can reload it) and ``_PROVE_FAILED``
        is returned for the caller to fall back to a fresh compile: a
        poisoned cache can cost latency but never a query."""
        self._loaded[sig] = loaded
        self._unproven.add(sig)
        try:
            out = loaded(*args)
        except Exception as e:  # noqa: BLE001 - classify, then decide
            from .cache import xla_store as _xc
            from .resilience import faults as _faults
            from .resilience import retry as _retry

            if isinstance(e, _faults.InjectedFault) or _retry.is_oom_error(e):
                raise
            self._loaded.pop(sig, None)
            self._unproven.discard(sig)
            self._warmed.discard(sig)
            self._seen.discard(sig)
            _xc.record_load_failure(digest, e)
            return _PROVE_FAILED
        self._unproven.discard(sig)
        self._seen.add(sig)
        return out

    def _proving_call(self, loaded, sig, args):
        """The __call__-fast-path proving wrapper (warm-loaded sigs). On
        poison, re-enter __call__: no flock is held HERE, so the re-entry
        may safely take the single-flight again — the quarantine above
        guarantees it misses and compiles fresh."""
        out = self._prove_loaded(loaded, sig, self._store_digest(sig), args)
        if out is _PROVE_FAILED:
            return self.__call__(*args)
        return out

    def _first_call(self, args, sig=None):
        """First execution per signature. With the persistent executable
        store active, this consults the disk under a cross-process
        single-flight lock (N servers sharing a cache dir compile each
        shape once) before compiling; a miss compiles AOT and publishes
        the serialized binary. Transient compile errors retry with
        backoff; anything else — a Mosaic (pallas) compile failure
        included — raises to the caller. Runs under _COMPILE_LOCK."""
        import logging
        import time

        from .cache import xla_store as _xc

        log = logging.getLogger(__name__)
        store = _xc.active_store() if sig is not None else None
        digest = self._store_digest(sig) if store is not None else None
        if digest is None:
            store = None
        if store is not None:
            with store.single_flight(digest):
                # re-check under the lock: a fleet peer may have published
                # this entry while we waited for the flight slot
                loaded = _xc.load_executable(digest)
                if loaded is not None:
                    out = self._prove_loaded(loaded, sig, digest, args)
                    if out is not _PROVE_FAILED:
                        return out
                    # poison (quarantined above): compile fresh while we
                    # STILL hold the flight slot — re-entering
                    # single_flight here would self-contend (flock
                    # conflicts across fds within one process) and burn
                    # the whole lockTimeout under _COMPILE_LOCK
                return self._first_compile(args, sig, digest, log)
        return self._first_compile(args, sig, None, log)

    def _first_compile(self, args, sig, digest, log):
        import time

        from .cache import xla_store as _xc
        from .resilience import watchdog as _wd

        attempts = 4
        i = 0
        # once per first execution — retry attempts accumulate compile
        # TIME but are not more first calls
        _M_FIRST_CALLS.add(1)

        while True:
            try:
                def attempt():
                    from .resilience import faults as _faults

                    if _faults._ACTIVE is not None:
                        # chaos harness: injected compile delay (inside the
                        # deadline scope so compile.deadlineSeconds can cut
                        # it) and transient compile failure on the Nth
                        # first-touch compile — recovered by the retry loop
                        _faults.on_kernel_compile()
                    if digest is None:
                        return self._fn(*args), None
                    # AOT path: keep the Compiled stage so it can be
                    # serialized into the store; the serializer runs here
                    # — under _COMPILE_LOCK — because on XLA:CPU it
                    # shares the compiler's thread-unsafety
                    compiled = self._fn.lower(*args).compile()
                    payload = _xc.serialize_executable(compiled)
                    # register BEFORE the first run: the binary is valid
                    # even if this batch OOMs — the retry's re-entry must
                    # reuse it, not recompile
                    self._loaded[sig] = compiled
                    return compiled(*args), payload

                # the compile is a long legitimate beat gap: the stall
                # phase stamps beats at entry/exit and labels a watchdog
                # cancel 'stall:compile' instead of blaming the launch
                # (the deadline join, when one is armed, lives in
                # __call__ — this runs on the helper thread there)
                t_compile = time.perf_counter_ns()
                try:
                    with _wd.stall_phase("compile"), \
                            obs_trace.span("xla-compile", "kernel"), \
                            obs_ledger.phase("compile"), \
                            _M_COMPILE_NS.timed():
                        out, payload = attempt()
                finally:
                    _M_COMPILE_HIST.observe(
                        time.perf_counter_ns() - t_compile
                    )
                if payload is not None:
                    # disk IO outside the timed compile scope; the
                    # single-flight flock (when held) spans this publish
                    _xc.store_executable(digest, payload)
                return out
            except Exception as e:  # noqa: BLE001 - classify, then re-raise
                msg = str(e)
                transient = any(
                    k in msg
                    for k in ("DEADLINE", "UNAVAILABLE")
                )
                i += 1
                if not transient or i >= attempts:
                    raise
                log.warning(
                    "kernel compile failed (attempt %d/%d), retrying: %s",
                    i,
                    attempts,
                    msg[:160],
                )
                # injected faults back off nominally — chaos runs assert on
                # recovery, not on real compile pacing
                time.sleep(0.02 if "fault injection" in msg else 2.0 * i)

    def _cache_size(self):
        cs = getattr(self._fn, "_cache_size", None)
        return cs() if callable(cs) else 0


def guarded_jit(fn) -> GuardedJit:
    return GuardedJit(fn)


def jit_kernel(key: tuple, make_fn: Callable):
    """Shorthand: cache ``GuardedJit(make_fn())`` under ``key``."""
    return kernel(key, lambda: GuardedJit(make_fn()))


class _LaunchCounter:
    """``on_launch`` hook of a kernel whose program sorts by packed keys or
    gathers planes (the aggregate, sort, window, join-pair, exchange-slice and
    shrink kernels). Each launch adds the program's sort passes to
    ``sort.keyPasses``, what two passes a uint64 radix word would have run to
    ``sort.keyPassesUnpacked``, the planes it hands to ``ops/gather.py``'s
    ``gather_planes`` to ``gather.planes`` and the gathers that issues to
    ``gather.launches``. All are static per input signature (dtypes, plane
    widths, capacities), so they are read once per signature from one
    abstract trace — an executable loaded from the store is never traced —
    and a launch costs a lookup and four counter adds, no device sync."""

    __slots__ = ("_raw", "_passes", "_gathers")

    def __init__(self, raw):
        self._raw = raw
        self._passes: dict = {}
        self._gathers: dict = {}

    def __call__(self, sig, args) -> None:
        passes = self._passes.get(sig)
        if passes is None:
            from .ops.gather import counting_gathers
            from .ops.sortkeys import counting_passes

            with counting_passes() as count, counting_gathers() as gathers:
                jax.eval_shape(self._raw, *args)
            passes = self._passes[sig] = tuple(count)
            self._gathers[sig] = tuple(gathers)
        planes, launches = self._gathers[sig]
        _M_KEY_PASSES.add(passes[0])
        _M_KEY_PASSES_UNPACKED.add(passes[1])
        _M_GATHER_PLANES.add(planes)
        _M_GATHER_LAUNCHES.add(launches)


def counted_kernel(key: tuple, make_fn: Callable):
    """``jit_kernel`` for a program that sorts by packed keys or gathers
    planes: its launches feed the ``sort.keyPasses*`` and ``gather.*``
    counters."""

    def build():
        raw = make_fn()
        return GuardedJit(raw, on_launch=_LaunchCounter(raw))

    return kernel(key, build)


def schema_key(schema) -> tuple:
    """Hashable identity of a Schema (names participate: they are pytree aux
    metadata on DeviceBatch, so two name-sets are two trace entries)."""
    return tuple((f.name, f.data_type, f.nullable) for f in schema)


def build_count() -> int:
    """Distinct kernels built so far (monotonic; cache misses)."""
    return _M_BUILDS.value


def warm_count() -> int:
    """Pre-compilations performed so far (monotonic; GuardedJit.warm)."""
    return _M_WARMS.value


def precompile_worthwhile() -> bool:
    """Whether warming ahead of execution can pay: compiles overlap on
    non-CPU backends, and the persistent caches (jax's HLO cache and the
    executable store) carry warmed binaries to later processes. On
    XLA:CPU with both caches disabled, a warm is the SAME serial compile
    the first touch would do — pure waste — so the default-on precompile
    pass skips itself there (an explicitly set
    spark.rapids.tpu.precompile.enabled=true overrides)."""
    try:
        if jax.default_backend() != "cpu":
            return True
    except Exception:
        return False
    if _PERSISTENT_ENABLED:
        return True
    from .cache import xla_store as _xc

    return _xc.active_store() is not None


def precompile(specs: list, parallelism: int = 0) -> dict:
    """Warm a batch of kernels concurrently on a small compile pool.

    ``specs`` is ``[(kernel, abstract_args_tuple)]`` where each kernel
    exposes ``warm`` (GuardedJit or a wrapper forwarding to one). On the
    CPU backend the pool collapses to one worker — GuardedJit.warm takes
    the global compile lock there anyway (the concurrent-compile SIGSEGV),
    so extra workers would only contend. Failures never propagate:
    pre-compilation is an optimization, first touch retains its own
    error handling (transient-compile retries)."""
    stats = {"warmed": 0, "skipped": 0, "failed": 0}
    if not specs:
        return stats
    try:
        backend = jax.default_backend()
    except Exception:
        return stats
    import logging

    log = logging.getLogger(__name__)

    def one(spec):
        kernel, args = spec
        try:
            return "warmed" if kernel.warm(*args) else "skipped"
        except Exception as e:  # noqa: BLE001 - warm is best-effort
            log.debug("kernel precompile failed (ignored): %s", str(e)[:200])
            return "failed"

    workers = 1 if backend == "cpu" else (parallelism or min(4, len(specs)))
    if workers <= 1:
        for s in specs:
            stats[one(s)] += 1
        return stats
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers) as pool:
        for outcome in pool.map(one, specs):
            stats[outcome] += 1
    return stats


def trace_count() -> int:
    """Total jit specializations across cached kernels — grows only when a
    kernel is traced/compiled for a new shape signature. Flat between two
    identical queries ⇔ zero recompilation."""
    total = 0
    for fn in _KERNELS.values():
        cs = getattr(fn, "_cache_size", None)
        if callable(cs):
            try:
                total += cs()
            except Exception:
                pass
    return total


def clear() -> None:
    _KERNELS.clear()


_PERSISTENT_ENABLED = False

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache"
)


def compile_cache_root() -> str:
    """The one directory both compile caches live under: where
    ``JAX_COMPILATION_CACHE_DIR`` points when it is set, else the fixed
    git-ignored ``.cache/`` of this checkout. The path is part of jax's
    cache key, so it never carries a pid, a time or a temporary name."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def enable_persistent_cache() -> None:
    """Turn on JAX's on-disk compilation cache so separate processes
    (restarts, benchmark runs, chip calls) reuse XLA executables. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax has already put its cache there
    and the directory is left alone; unset, it goes to ``<root>/jax``. A
    cache that cannot be set up raises."""
    global _PERSISTENT_ENABLED
    if _PERSISTENT_ENABLED:
        return
    if os.environ.get("SPARK_RAPIDS_TPU_NO_PERSISTENT_CACHE"):
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = os.path.join(compile_cache_root(), "jax")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # The cache singleton binds its directory at the FIRST compile —
        # which may already have happened (import-time jnp work), so the
        # config update alone would be ignored. Re-point the singleton.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    _PERSISTENT_ENABLED = True
