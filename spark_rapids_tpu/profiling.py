"""Profiling facade — stable public entry points over the obs/ subsystem.

Historically this module owned the whole observability story (NvtxWithMetrics
analogue: jax.profiler traces + ad-hoc per-node metrics and three bespoke
report functions). PR 4 moved the machinery into the unified subsystem:

- typed metric registries      → :mod:`spark_rapids_tpu.obs.metrics`
- hierarchical span tracing    → :mod:`spark_rapids_tpu.obs.trace`
- reports/exporters            → :mod:`spark_rapids_tpu.obs.export`

Everything importable from here before PR 4 still is — ``walk``,
``instrument_plan``, ``query_trace``, ``metrics_report``,
``pipeline_report``, ``resilience_report``, ``device_host_breakdown`` —
now as thin shims, so rigs and tests written against the old surface
keep working. What stays native here is the jax.profiler integration
(XPlane/TensorBoard capture + the block-until-ready opTime debug mode),
which is TPU-runtime-specific rather than part of the portable obs layer.
"""
from __future__ import annotations

import time

import jax

from .obs.export import (  # noqa: F401  (public re-exports)
    device_host_breakdown,
    metrics_report,
    pipeline_report,
    resilience_report,
    walk,
)
from .plan.physical import Exec, ExecContext, PartitionSet


def _wrap_partitions(node: Exec, pset: PartitionSet) -> PartitionSet:
    """Per-partition: annotate the trace with the node name and attribute
    blocked device time per produced batch to the node's opTime metric."""
    from .obs.metrics import MetricKind

    op_time = node.metric("opTime", "DEBUG", MetricKind.NANOS)
    batches_m = node.metric("opOutputBatches", "DEBUG")
    name = type(node).__name__

    def make(t):
        def it():
            for db in t():
                t0 = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(name):
                    jax.block_until_ready(db)
                op_time.add(time.perf_counter_ns() - t0)
                batches_m.add(1)
                yield db

        return it

    return PartitionSet([make(t) for t in pset.parts])


def instrument_plan(plan: Exec) -> None:
    """Instance-level wrap of every node's ``execute`` so its output
    partitions block-and-time per batch. Wall-clock spent blocking at node
    X = device work that finished between X-1's sync and X's sync = X's own
    kernels (the pipeline is serialized by the syncs themselves)."""
    for node in walk(plan):
        if getattr(node, "_profiled", False):
            continue
        orig = node.execute

        def execute(ctx: ExecContext, _orig=orig, _node=node):
            return _wrap_partitions(_node, _orig(ctx))

        node.execute = execute  # type: ignore[method-assign]
        node._profiled = True  # type: ignore[attr-defined]


class query_trace:
    """Context manager: wrap one query execution in a jax.profiler trace
    dump when a path is configured (else no-op). This is the XPlane/
    TensorBoard capture; the portable span trace is obs/trace.py."""

    def __init__(self, path: str | None):
        self.path = path or None
        self._cm = None

    def __enter__(self):
        if self.path:
            self._cm = jax.profiler.trace(self.path)
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            return self._cm.__exit__(*exc)
        return False
