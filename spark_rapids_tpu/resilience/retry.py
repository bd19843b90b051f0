"""OOM retry state machine — spill, retry, then recursively split.

Reference: DeviceMemoryEventHandler.scala:42-69 (RMM alloc-failure →
synchronous spill → retry) plus the split-and-retry escalation the
reference grew for work that genuinely does not fit (GpuOutOfCoreSortIterator
/ the RmmRapidsRetryIterator family: spill first, then halve the input and
retry each half). PJRT has no allocation callback, so both live here as a
wrapper at the kernel launch site:

    launch ──OOM──▶ spill everything spillable ──▶ retry      (× maxRetries)
        └─still OOM──▶ split batch in half ──▶ recurse on each half
              └─at the min-rows floor──▶ re-raise (task retry / query fail)

Splitting is sound only for operators whose output over ``concat(a, b)``
equals ``concat(output(a), output(b))`` — project, filter, the partial
update aggregate, and the probe side of a hash join. Those operators opt in
by routing their per-batch launches through ``run_with_retry``; everything
else uses the non-splitting ``run_once`` (spill-retry only, the old
``with_oom_retry`` contract).

Classification walks the full ``__cause__``/``__context__`` chain instead of
string-matching the top-level message: jax re-wraps backend errors
(``jax.errors.JaxRuntimeError`` with the ``XlaRuntimeError`` as its cause),
so a top-level-only match silently misses wrapped RESOURCE_EXHAUSTED.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Iterator, Optional

from . import faults

log = logging.getLogger(__name__)

_OOM_TOKENS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_DEVICE_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError")


# ── classification ──────────────────────────────────────────────────────────


def walk_causes(err: BaseException) -> Iterator[BaseException]:
    """The exception and its cause/context chain (cycle- and depth-guarded).
    ``__cause__`` (explicit ``raise ... from``) wins over the implicit
    ``__context__`` at each link, matching traceback rendering."""
    seen: set[int] = set()
    e: Optional[BaseException] = err
    while e is not None and id(e) not in seen and len(seen) < 16:
        seen.add(id(e))
        yield e
        e = e.__cause__ if e.__cause__ is not None else e.__context__


def is_oom_error(err: BaseException) -> bool:
    """Device allocation failure anywhere in the cause chain — the
    recoverable class (spill / split / retry)."""
    for e in walk_causes(err):
        if isinstance(e, faults.InjectedFault) and e.kind == "oom":
            return True
        if isinstance(e, MemoryError):
            return True
        s = str(e)
        if any(tok in s for tok in _OOM_TOKENS):
            return True
    return False


def is_device_error(err: BaseException) -> bool:
    """Non-OOM device/kernel failure anywhere in the cause chain — the
    class the CPU-fallback circuit breaker counts."""
    for e in walk_causes(err):
        if isinstance(e, faults.InjectedFault) and e.kind == "kernel":
            return True
        if type(e).__name__ in _DEVICE_ERROR_TYPES:
            return True
    return False


# ── retry counters (the profiling diag block) ──────────────────────────────
# Counters live in the process-wide typed registry (obs/metrics.py) under
# the ``resilience.`` prefix; ``report()`` is a registry view. The catalog
# pre-registers the well-known names so a healthy run still exports the
# full series set at zero.

from ..obs.metrics import GLOBAL as _REGISTRY  # noqa: E402

_METRICS_LOCK = threading.Lock()
_LAST_OOM: Optional[float] = None  # time.monotonic of the last observed OOM


def record(name: str, n: int = 1) -> None:
    _REGISTRY.counter("resilience." + name).add(n)


def report() -> dict:
    """Cumulative process-wide resilience counters (profiling) —
    a view over the registry's ``resilience.`` slice."""
    return _REGISTRY.view("resilience.")


def reset() -> None:
    global _LAST_OOM
    _REGISTRY.reset("resilience.")
    with _METRICS_LOCK:
        _LAST_OOM = None


def _note_oom() -> None:
    global _LAST_OOM
    with _METRICS_LOCK:
        _LAST_OOM = time.monotonic()


def oom_pressure(window_s: float = 30.0) -> bool:
    """Whether an OOM was handled recently — consumers that buffer ahead
    (the pipeline prefetcher) clamp their windows while this holds."""
    last = _LAST_OOM
    return last is not None and (time.monotonic() - last) < window_s


# ── policy ─────────────────────────────────────────────────────────────────


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    max_retries: int = 2
    split_enabled: bool = True
    min_split_rows: int = 1024

    @classmethod
    def from_conf(cls, conf) -> "RetryPolicy":
        from .. import config as cfg

        return cls(
            max_retries=cfg.RETRY_OOM_MAX_RETRIES.get(conf),
            split_enabled=cfg.RETRY_OOM_SPLIT_ENABLED.get(conf),
            min_split_rows=cfg.RETRY_OOM_MIN_SPLIT_ROWS.get(conf),
        )


DEFAULT_POLICY = RetryPolicy()


# ── batch splitting ────────────────────────────────────────────────────────


def split_batch(batch):
    """(lo, hi) halves of a DeviceBatch at half its (power-of-two) capacity.
    Live rows occupy the prefix [0, num_rows), so lo takes rows [0, cap/2)
    and hi rows [cap/2, cap); each half's tail validity is re-masked so
    padding rows stay inert. One cached fused kernel per (schema, cap)."""
    import jax.numpy as jnp

    from .. import kernels as K
    from ..columnar.device import DeviceBatch, dc_replace
    from ..ops.gather import gather_batch

    cap = batch.capacity
    half = cap // 2
    assert half >= 1, "cannot split a capacity-1 batch"

    def make():
        def _split(b):
            iota = jnp.arange(half, dtype=jnp.int32)
            lo_n = jnp.clip(b.num_rows, 0, half).astype(jnp.int32)
            hi_n = jnp.clip(b.num_rows - half, 0, half).astype(jnp.int32)
            lo = gather_batch(b, iota, lo_n)
            hi = gather_batch(b, half + iota, hi_n)

            def mask(sb, n):
                live = iota < n
                cols = [
                    dc_replace(c, validity=c.validity & live) for c in sb.columns
                ]
                return DeviceBatch(sb.schema, cols, n)

            return mask(lo, lo_n), mask(hi, hi_n)

        return _split

    fn = K.jit_kernel(("oom_split", batch.schema, cap), make)
    return fn(batch)


# ── the state machine ──────────────────────────────────────────────────────


def _spill_all(catalog) -> int:
    try:
        return catalog.synchronous_spill(catalog.device_bytes)
    except Exception:  # spilling is best-effort recovery, never the error
        return 0


def _batch_size(batch) -> int:
    sb = getattr(batch, "size_bytes", None)
    if callable(sb):
        try:
            return int(sb())
        except Exception:
            return 0
    return 0


def _handle_non_oom(err, op, breaker) -> None:
    """Feed the circuit breaker on non-OOM device failures (the caller
    re-raises). A blown compile deadline force-opens in one step: the op
    already cost the tenant its whole compile budget once."""
    from .watchdog import CompileDeadlineError

    if breaker is None or not op:
        return
    for e in walk_causes(err):
        if isinstance(e, CompileDeadlineError):
            breaker.force_open(op, e)
            return
    if is_device_error(err):
        breaker.record_failure(op, err)


def _label_launch(op: Optional[str]) -> None:
    """Stamp the op signature as the current token's stall-phase detail so
    a watchdog-detected launch stall names the op it wedged in (and feeds
    that op's circuit breaker). One attribute write; the next op
    overwrites it."""
    if not op:
        return
    from .watchdog import current as _wd_current

    tok = _wd_current()
    if tok is not None:
        tok.phase_detail = op


def run_once(catalog, fn: Callable, batch, policy: Optional[RetryPolicy] = None,
             op: Optional[str] = None, breaker=None):
    """Spill-and-retry WITHOUT splitting (operators whose kernel is not
    distributive over row ranges: final/merge aggregates, sorts)."""
    policy = policy or DEFAULT_POLICY
    _label_launch(op)
    attempt = 0
    while True:
        try:
            if faults._ACTIVE is not None:
                faults.on_batch_launch(_batch_size(batch))
                with faults.recoverable():
                    return fn(batch)
            return fn(batch)
        except Exception as e:  # noqa: BLE001 - classified below
            if not is_oom_error(e):
                _handle_non_oom(e, op, breaker)
                raise
            _note_oom()
            if catalog is None or attempt >= policy.max_retries:
                raise
            attempt += 1
            record("oom_retries")
            log.warning(
                "device OOM at %s (attempt %d/%d): spilling %d bytes and retrying",
                op or "kernel", attempt, policy.max_retries, catalog.device_bytes,
            )
            _spill_all(catalog)


def run_with_retry(catalog, fn: Callable, batch,
                   policy: Optional[RetryPolicy] = None,
                   op: Optional[str] = None, breaker=None) -> Iterator:
    """Yield ``fn`` outputs covering ``batch`` in row order, escalating
    OOMs: spill-retry up to ``policy.max_retries``, then recursively halve
    down to the ``min_split_rows`` floor. The caller must accept MULTIPLE
    output batches per input batch — that is the splittable-operator
    contract."""
    policy = policy or DEFAULT_POLICY
    _label_launch(op)
    attempt = 0
    while True:
        try:
            if faults._ACTIVE is not None:
                faults.on_batch_launch(_batch_size(batch))
                with faults.recoverable():
                    out = fn(batch)
            else:
                out = fn(batch)
        except Exception as e:  # noqa: BLE001 - classified below
            if not is_oom_error(e):
                _handle_non_oom(e, op, breaker)
                raise
            _note_oom()
            if catalog is not None and attempt < policy.max_retries:
                attempt += 1
                record("oom_retries")
                log.warning(
                    "device OOM at %s (attempt %d/%d): spilling %d bytes "
                    "and retrying",
                    op or "kernel", attempt, policy.max_retries,
                    catalog.device_bytes,
                )
                _spill_all(catalog)
                continue
            cap = getattr(batch, "capacity", 0)
            floor = max(2, policy.min_split_rows)
            if not policy.split_enabled or cap <= floor:
                raise
            record("splits")
            log.warning(
                "device OOM at %s persists after spills: splitting batch "
                "(capacity %d -> 2x%d) and retrying each half",
                op or "kernel", cap, cap // 2,
            )
            lo, hi = split_batch(batch)
            yield from run_with_retry(catalog, fn, lo, policy, op, breaker)
            yield from run_with_retry(catalog, fn, hi, policy, op, breaker)
            return
        yield out
        return
