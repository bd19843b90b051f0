"""Recompilation regression tests.

The reference never compiles GPU code at query time (cuDF ships pre-built
kernels); the TPU engine's equivalent guarantee is: running the same query
shape twice builds ZERO new kernels and triggers ZERO new XLA traces on the
second run (kernels.py module cache). This was round 1's #1 perf bug — every
``collect()`` rebuilt exec instances and recompiled every kernel.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import kernels as K


def _lineitem(n: int) -> pa.Table:
    rng = np.random.default_rng(7)
    return pa.table(
        {
            "flag": pa.array(
                np.asarray(["A", "N", "R"], dtype=object)[rng.integers(0, 3, n)]
            ),
            "qty": rng.integers(1, 51, n).astype(np.float64),
            "price": (rng.random(n) * 1e5).round(2),
            "ship": rng.integers(8000, 12000, n).astype(np.int32),
        }
    )


def _q1ish(session, table):
    from spark_rapids_tpu.functions import avg, col, count, sum as sum_

    df = session.create_dataframe(table, num_partitions=4)
    return (
        df.filter(col("ship") <= 11000)
        .group_by("flag")
        .agg(
            sum_(col("qty")).alias("sum_qty"),
            avg(col("price")).alias("avg_price"),
            count("*").alias("n"),
        )
    )


def test_second_collect_compiles_nothing():
    tpu = TpuSession({"spark.rapids.sql.enabled": True})
    table = _lineitem(1000)
    _q1ish(tpu, table).collect()  # builds + compiles every kernel once
    builds0, traces0 = K.build_count(), K.trace_count()
    r2 = _q1ish(tpu, table).collect()
    assert K.build_count() == builds0, "second collect built new kernels"
    assert K.trace_count() == traces0, "second collect re-traced a kernel"
    assert len(r2) == 3


def test_fresh_session_reuses_kernels():
    """A NEW session running the same query shape also compiles nothing —
    kernels are process-global, not session-scoped (the analogue of cuDF's
    shared kernel library)."""
    table = _lineitem(1000)
    _q1ish(TpuSession({"spark.rapids.sql.enabled": True}), table).collect()
    builds0, traces0 = K.build_count(), K.trace_count()
    _q1ish(TpuSession({"spark.rapids.sql.enabled": True}), table).collect()
    assert K.build_count() == builds0
    assert K.trace_count() == traces0


def test_sort_and_join_kernels_cached():
    tpu = TpuSession({"spark.rapids.sql.enabled": True})
    from spark_rapids_tpu.functions import col

    t = _lineitem(500)
    dim = pa.table({"flag": ["A", "N", "R"], "name": ["aa", "nn", "rr"]})

    def q():
        left = tpu.create_dataframe(t, num_partitions=2)
        right = tpu.create_dataframe(dim)
        return left.join(right, on="flag").sort("qty", "flag").limit(50)

    q().collect()
    builds0, traces0 = K.build_count(), K.trace_count()
    q().collect()
    assert K.build_count() == builds0
    assert K.trace_count() == traces0


def _enable_cache_recording(monkeypatch):
    """Run enable_persistent_cache with the test environment's kill switch
    lifted, recording (not applying) what it would set on jax."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    updates = {}
    monkeypatch.delenv("SPARK_RAPIDS_TPU_NO_PERSISTENT_CACHE")
    monkeypatch.setattr(K, "_PERSISTENT_ENABLED", False)
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setattr(cc, "reset_cache", lambda: None)
    K.enable_persistent_cache()
    return updates


def test_cache_placement_follows_jax_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's cache stays where the variable
    put it (no directory set in code) and the store resolves under it."""
    from spark_rapids_tpu.cache import xla_store as xc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = _enable_cache_recording(monkeypatch)
    assert "jax_compilation_cache_dir" not in updates
    assert xc.default_dir() == str(tmp_path / ("xc-" + xc.fence()["backend"]))


def test_cache_placement_defaults_inside_checkout(monkeypatch):
    """Unset: both caches resolve to the fixed .cache/ of the checkout."""
    import os

    from spark_rapids_tpu.cache import xla_store as xc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache"
    )
    updates = _enable_cache_recording(monkeypatch)
    assert updates["jax_compilation_cache_dir"] == os.path.join(root, "jax")
    assert xc.default_dir() == os.path.join(root, "xc-" + xc.fence()["backend"])
