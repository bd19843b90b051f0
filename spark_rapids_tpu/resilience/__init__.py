"""Unified resilience subsystem — graceful degradation under memory
pressure, transport faults, and kernel failures (ISSUE 3; reference:
DeviceMemoryEventHandler.scala spill-retry, FetchFailedException stage
retry, per-node CPU fallback).

Four pillars:

* ``retry``   — OOM classification (cause-chain walk), the spill → retry →
  split-in-half state machine splittable operators opt into, and the
  process-wide resilience counters the query artifact reports.
* ``breaker`` — CPU-fallback circuit breaker: repeated non-OOM device
  failures per op signature flip that op to CPU for the session.
* ``faults``  — deterministic, seeded fault injection (device OOM, compile
  failure, spill-disk IO errors, transport frame drop/delay) behind
  ``spark.rapids.tpu.faults.*``; drives the chaos suite.
* shuffle fault recovery lives with the shuffle code it protects
  (``shuffle/client.py`` retry/backoff, ``shuffle/heartbeat.py`` liveness
  + eviction, ``shuffle/tcp.py`` reconnect) but reports through
  ``retry.record`` so one counter block covers the whole layer.

See docs/fault-tolerance.md.
"""
from __future__ import annotations

from .breaker import CircuitBreaker
from .faults import FaultConfig, InjectedFault
from .watchdog import CompileDeadlineError, Watchdog, WatchdogStallError
from .retry import (
    RetryPolicy,
    is_device_error,
    is_oom_error,
    oom_pressure,
    record,
    report,
    reset,
    run_once,
    run_with_retry,
    split_batch,
    walk_causes,
)

__all__ = [
    "CircuitBreaker",
    "CompileDeadlineError",
    "FaultConfig",
    "InjectedFault",
    "Watchdog",
    "WatchdogStallError",
    "RetryPolicy",
    "is_device_error",
    "is_oom_error",
    "oom_pressure",
    "record",
    "report",
    "reset",
    "run_once",
    "run_with_retry",
    "split_batch",
    "walk_causes",
]
