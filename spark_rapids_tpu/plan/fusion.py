"""Whole-stage fusion — collapse operator chains into one XLA program.

The reference accelerator owns the physical plan, so it owns execution
granularity too (PAPER.md); this pass spends that ownership. The per-op
execution model launches one jitted program per project/filter node per
batch, so a chain of n operators pays n dispatches and n rounds of glue
for a batch (their share of a query: not measured on the chip). A
*stage* is a maximal chain of adjacent device row-operators whose bodies
are pure expression evaluation; fusing the chain stitches their expression
trees end-to-end inside ONE jitted function, so a batch pays one dispatch
(and its consumer one device sync) per stage instead of per operator.

Fusion boundaries (anything else breaks the chain):

* only ``TpuProjectExec`` / ``TpuFilterExec`` fuse — their kernels are
  pure ``DeviceBatch -> DeviceBatch`` functions with identical launch
  plumbing (``exec/task.run_device``);
* task-dependent expressions never fuse: ``run_device`` accumulates
  ``row_base`` from the *stage input* batch, which would be wrong for an
  expression that was supposed to see a post-filter batch;
* expressions with ANSI error sites never fuse: their kernels' error
  channel raises at the precise batch, and fusing would re-order the check
  against the in-stage filter's compaction;
* chains cap at ``spark.rapids.tpu.fusion.maxOps`` to bound trace+compile
  time of the single program.

Single-op "chains" stay unfused — the parent-side fusions that already
exist (``TpuHashAggregateExec._fused_child`` folding an immediate filter,
the exchange's scatter-side filter fusion) keep first claim on lone
filters, so this pass composes with them instead of competing.

The fused kernel rides ``kernels.kernel`` under a structural key — the
same frozen-expression identity ``plan/reuse.py`` canonical keys use — so
``GuardedJit`` and the persistent xla_store (PR 11) cache whole stages
exactly like single operators, and the shape-bucket lattice keeps the
per-stage executable count logarithmic.
"""
from __future__ import annotations

from typing import List, Tuple

import jax.numpy as jnp

from .. import config as cfg
from .. import kernels as K
from ..columnar.device import DeviceBatch, dc_replace
from ..config import TpuConf
from ..exec import task
from ..exec.tpu import (
    TpuFilterExec,
    TpuProjectExec,
    _ErrorCheckingKernel,
    _expr_has_error_site,
    val_to_column,
)
from ..expr.base import Ctx
from ..ops.gather import compact
from .physical import Exec, ExecContext, PartitionSet


def _op_key(op: Exec) -> tuple:
    """Semantic identity of one fused step — the same (kind, bound exprs,
    schema) tuple the standalone kernels key on, so a stage's kernel key is
    the concatenation of its steps' identities."""
    if isinstance(op, TpuProjectExec):
        return ("project", tuple(op.exprs), op.output)
    assert isinstance(op, TpuFilterExec)
    return ("filter", op.condition)


def stage_signature(fused: tuple) -> str:
    """The circuit-breaker key for one fused chain. Per-STAGE, not the
    class-wide \"StageExec\": one pathological fused program must not
    condemn every other stage in the plan to the fallback path. Process-
    local like the breaker itself (``hash`` randomization is fine — the
    signature never leaves this process)."""
    return f"StageExec:{hash(('stage',) + fused) & 0xFFFFFFFF:08x}"


def stage_kernel(fused: tuple):
    """One jitted program evaluating every step of ``fused`` in sequence.

    Steps with error sites are excluded by the fusion guard, so the error
    vector is statically empty — the ``_ErrorCheckingKernel`` wrapper then
    never syncs, and exists only to keep the ``(batch, tvals) -> batch``
    calling convention (and ``warm`` passthrough) identical to the per-op
    kernels ``run_device`` drives."""

    def make():
        def _stage(batch: DeviceBatch, tvals):
            for step in fused:
                c = Ctx.for_device(batch, task=tvals)
                if step[0] == "project":
                    _, exprs, schema = step
                    cols = [
                        val_to_column(c, e.eval(c), e.data_type) for e in exprs
                    ]
                    live = batch.row_mask()
                    cols = [
                        dc_replace(col, validity=col.validity & live)
                        for col in cols
                    ]
                    batch = DeviceBatch(schema, cols, batch.num_rows)
                else:
                    _, condition = step
                    v = condition.eval(c)
                    keep = c.broadcast_bool(v.data) & v.full_valid(c)
                    batch = compact(batch, keep)
            return batch, jnp.zeros((0,), dtype=bool)

        return _ErrorCheckingKernel(K.GuardedJit(_stage), [])

    return K.kernel(("stage",) + fused, make)


class StageExec(Exec):
    """A fused pipeline stage: ``ops`` (bottom-up) executed as one program.

    ``fused`` — the tuple of step identities — is a *public* attribute on
    purpose: ``plan/reuse.py`` canonical keys derive structural identity
    from public attributes, so two plans with the same fused chain share
    exchange reuse and the per-plan run-calibration bucket exactly like
    their unfused forms would."""

    def __init__(self, ops: List[Exec], child: Exec):
        super().__init__([child])
        self._ops = list(ops)
        self._schema = ops[-1].output
        self.fused: Tuple[tuple, ...] = tuple(_op_key(op) for op in ops)
        self._needs_task = False
        self._fn = stage_kernel(self.fused)
        # per-stage breaker identity: kernel failures recorded under THIS
        # signature open the breaker for this chain only; the next planning
        # pass rebuilds it unfused (fuse_stages' fallback) while other
        # stages keep fusing
        self.breaker_op = stage_signature(self.fused)

    @property
    def output(self):
        return self._schema

    @property
    def is_device(self) -> bool:
        return True

    def execute(self, ctx: ExecContext) -> PartitionSet:
        fn = self._fn

        def run(it):
            # splittable exactly like its constituent ops: every step is a
            # row-local map/compact, so concat(a, b) commutes with the stage
            return task.run_device(
                fn, it, False, catalog=ctx.catalog,
                policy=ctx.retry_policy, op=self.breaker_op,
                breaker=ctx.breaker, token=ctx.cancel_token,
            )

        return self.children[0].execute(ctx).map_partitions(run)

    def node_string(self):
        names = []
        for op in self._ops:
            names.append(op.node_string())
        return f"Stage({len(self._ops)}) [" + " -> ".join(names) + "]"


def _fusable(node: Exec) -> bool:
    if isinstance(node, TpuProjectExec):
        return not node._needs_task and not any(
            _expr_has_error_site(e) for e in node.exprs
        )
    if isinstance(node, TpuFilterExec):
        return not node._needs_task and not _expr_has_error_site(
            node.condition
        )
    return False


def fuse_stages(plan: Exec, conf: TpuConf, breaker=None) -> tuple:
    """(fused plan, number of stages formed). Walks top-down, replacing
    every maximal chain of >= 2 fusable nodes with a ``StageExec``; all
    other nodes are rebuilt via ``with_new_children`` (fresh metric
    registries, the standard rewrite currency).

    Breaker-aware (graceful degradation, not wholesale surrender): a chain
    whose ``stage_signature`` the circuit breaker has opened — its fused
    kernel failed repeatedly — is rebuilt as the unfused per-op chain
    instead of a StageExec. Each op then runs (and fails) under its OWN
    breaker key, so a genuinely bad operator degrades one more step to
    per-op CPU via the overrides pass, while its innocent chain-mates keep
    running on device."""
    if not cfg.FUSION_ENABLED.get(conf):
        return plan, 0
    max_ops = max(2, cfg.FUSION_MAX_OPS.get(conf))
    count = 0

    def unfuse(chain, below: Exec) -> Exec:
        from ..obs.metrics import GLOBAL as _obs

        _obs.counter("fusion.breakerFallbacks").add(1)
        rebuilt = below
        for op in reversed(chain):  # deepest first, original node on top
            rebuilt = op.with_new_children([rebuilt])
        return rebuilt

    def walk(node: Exec) -> Exec:
        nonlocal count
        if _fusable(node):
            chain = [node]
            cur = node.children[0]
            while len(chain) < max_ops and _fusable(cur):
                chain.append(cur)
                cur = cur.children[0]
            if len(chain) >= 2:
                fused = tuple(_op_key(op) for op in reversed(chain))
                if breaker is not None and breaker.is_open(
                    stage_signature(fused)
                ):
                    return unfuse(chain, walk(cur))
                count += 1
                return StageExec(list(reversed(chain)), walk(cur))
        return node.with_new_children([walk(c) for c in node.children])

    return walk(plan), count
