"""Exchange/subplan reuse (plan/reuse.py) — the ReuseExchange analogue.

Reference: GpuExec.doCanonicalize (GpuExec.scala:251-276) + Spark's
ReuseExchange rule. A self-joined aggregate must materialize its exchange
ONCE; results stay differentially equal to the CPU engine.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.functions import col
from tests.harness import cpu_session, tpu_session, _normalize, _values_equal


def _table(n=4000):
    rng = np.random.default_rng(3)
    return pa.table(
        {
            "k": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.integers(-100, 100, n).astype(np.int64),
        }
    )


def _self_join_agg(s, t):
    df = s.create_dataframe(t, num_partitions=2)
    agg = df.group_by("k").agg(F.sum(col("v")).alias("s"))
    right = agg.with_column_renamed("k", "k2").with_column_renamed("s", "s2")
    return agg.join(right, on=[("k", "k2")]).select("k", "s", "s2")


def test_self_join_aggregate_reuses_exchange(monkeypatch):
    from spark_rapids_tpu.exec.tpu import TpuShuffleExchangeExec

    calls = []
    orig = TpuShuffleExchangeExec._execute_impl

    def counting(self, ctx):
        calls.append(id(self))
        return orig(self, ctx)

    monkeypatch.setattr(TpuShuffleExchangeExec, "_execute_impl", counting)

    from spark_rapids_tpu.obs import metrics

    t = _table()
    s = tpu_session()
    reused_before = metrics.GLOBAL.snapshot()["exchange.reused"]
    rows_t = _self_join_agg(s, t).collect()
    assert s._last_reused_exchanges >= 1, "no exchange was deduplicated"
    # the process-wide counter says the same
    reused = metrics.GLOBAL.snapshot()["exchange.reused"] - reused_before
    assert reused == s._last_reused_exchanges
    # the shared node's pipeline ran exactly once
    assert len(calls) == len(set(calls)), (
        "a reused exchange executed its pipeline more than once"
    )

    rows_c = _self_join_agg(cpu_session(), t).collect()
    rows_t, rows_c = _normalize(rows_t, True), _normalize(rows_c, True)
    assert len(rows_t) == len(rows_c)
    for rt, rc in zip(rows_t, rows_c):
        for vt, vc in zip(rt, rc):
            assert _values_equal(vt, vc, False), (rt, rc)


def test_reuse_respects_kill_switch():
    t = _table(500)
    s = tpu_session({"spark.sql.exchange.reuse": "false"})
    _self_join_agg(s, t).collect()
    assert s._last_reused_exchanges == 0


def test_distinct_subtrees_not_merged():
    """Different aggregate expressions ⇒ different canonical keys."""
    t = _table(500)
    s = tpu_session()
    df = s.create_dataframe(t, num_partitions=2)
    a1 = df.group_by("k").agg(F.sum(col("v")).alias("s"))
    a2 = (
        df.group_by("k")
        .agg(F.max(col("v")).alias("m"))
        .with_column_renamed("k", "k2")
    )
    rows = a1.join(a2, on=[("k", "k2")]).select("k", "s", "m").collect()
    # sum vs max pipelines differ above the scan: scan-level exchange (none
    # here) aside, the two partial-agg exchanges must NOT merge
    kset = {r[0] for r in rows}
    got = {r[0]: (r[1], r[2]) for r in rows}
    import collections

    expect_s = collections.defaultdict(int)
    expect_m = collections.defaultdict(lambda: -(10**9))
    ks = t.column("k").to_pylist()
    vs = t.column("v").to_pylist()
    for k, v in zip(ks, vs):
        expect_s[k] += v
        expect_m[k] = max(expect_m[k], v)
    assert kset == set(expect_s)
    for k in kset:
        assert got[k] == (expect_s[k], expect_m[k])


def test_reuse_under_aqe_differential():
    """Shared exchanges revert to identity partitions under AQE; results
    must stay correct with adaptive enabled."""
    t = _table()
    conf = {"spark.sql.adaptive.enabled": "true"}
    rows_t = _self_join_agg(tpu_session(conf), t).collect()
    rows_c = _self_join_agg(cpu_session(), t).collect()
    rows_t, rows_c = _normalize(rows_t, True), _normalize(rows_c, True)
    assert len(rows_t) == len(rows_c)
    for rt, rc in zip(rows_t, rows_c):
        assert rt == rc
