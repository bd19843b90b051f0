"""Pallas string kernels (ops/pallas_strings.py) — differential against the
python oracle and the XLA window-gather path. These tests pass
``interpret=True``; tests/test_chip_compile.py compiles the kernel through
Mosaic for the chip."""
from __future__ import annotations

import numpy as np
import pytest

from spark_rapids_tpu.ops import pallas_strings as PS


def _pack(strs, W):
    n = len(strs)
    data = np.zeros((n, W), np.uint8)
    lens = np.zeros(n, np.int32)
    for i, s in enumerate(strs):
        b = s.encode()[:W]
        lens[i] = len(b)
        data[i, : len(b)] = np.frombuffer(b, np.uint8)
    return data, lens


@pytest.mark.parametrize("pat", [b"a", b"ab", b"abc", b"xyzw"])
@pytest.mark.parametrize("W", [16, 64, 130])
def test_match_starts_interpret_matches_oracle(pat, W):
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    alphabet = "abxyz"
    strs = [
        "".join(rng.choice(list(alphabet), size=rng.integers(0, W)))
        for _ in range(300)
    ] + ["", "a", "ab", "abc", "abcabcabc", "aab" * 10]
    data, lens = _pack(strs, W)
    got = np.asarray(
        PS.match_starts(jnp.asarray(data), jnp.asarray(lens), pat, interpret=True)
    )
    ref = PS.match_starts_np_reference(data, lens, pat)
    assert (got == ref).all()


def test_match_starts_row_padding():
    """n not divisible by the block size: pad rows are dropped."""
    import jax.numpy as jnp

    data, lens = _pack(["abc"] * 7, 16)
    got = np.asarray(
        PS.match_starts(jnp.asarray(data), jnp.asarray(lens), b"bc", interpret=True)
    )
    assert got.shape == (7, 16)
    assert got[:, 1].all() and got[:, 0].sum() == 0


def test_match_starts_agrees_with_xla_path():
    """The engine's _match_starts XLA fallback and the pallas kernel give
    the same mask (the contract Contains/Like/locate/split depend on)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.expr.base import Ctx
    from spark_rapids_tpu.expr import strings as S

    rng = np.random.default_rng(12)
    strs = ["".join(rng.choice(list("abc,"), size=rng.integers(0, 40))) for _ in range(200)]
    data, lens = _pack(strs, 48)

    class FakeCtx:
        xp = jnp
        n = len(strs)
        is_device = True

    PS.set_enabled(False)
    try:
        xla = np.asarray(
            S._match_starts(FakeCtx, jnp.asarray(data), jnp.asarray(lens), b"ab")
        )
    finally:
        PS.set_enabled(True)
    pallas = np.asarray(
        PS.match_starts(jnp.asarray(data), jnp.asarray(lens), b"ab", interpret=True)
    )
    assert (xla == pallas).all()


def test_engine_dispatch_reaches_pallas(monkeypatch):
    """The in-engine dispatch (strings.py:_match_starts → pallas) must fire
    inside the jitted kernels — this is trace-time dispatch, so the gate
    must not inspect Tracers (regression: usable_for once probed
    arr.devices(), which raises on Tracers, silently killing the path)."""
    import pyarrow as pa

    from spark_rapids_tpu.functions import col, count

    calls = {"n": 0}
    real = PS.match_starts

    def spy(data, lengths, pat):
        # the engine never asks for the interpreter; this CPU test does
        calls["n"] += 1
        return real(data, lengths, pat, interpret=True)

    monkeypatch.setattr(PS, "_backend_is_tpu", lambda: True)
    monkeypatch.setattr(PS, "match_starts", spy)
    from harness import cpu_session, tpu_session

    # long strings so the padded plane buckets to W >= 128 (the gate
    # rejects narrow planes where the XLA gather is already cheap)
    t = pa.table(
        {
            "s": [
                "x" * 90 + "apple" + "y" * 10,
                "z" * 100,
                "apple" + "q" * 100,
                "",
                "m" * 64 + "pineapple",
            ]
            * 10
        }
    )
    dev = tpu_session({})
    got = (
        dev.create_dataframe(t)
        .filter(col("s").contains("app"))
        .agg(count("*").alias("c"))
        .collect()
    )
    assert calls["n"] >= 1, "pallas dispatch never fired inside the engine"
    cpu = cpu_session({})
    exp = (
        cpu.create_dataframe(t)
        .filter(col("s").contains("app"))
        .agg(count("*").alias("c"))
        .collect()
    )
    assert got == exp


def test_gate_off_uses_xla(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(PS, "_backend_is_tpu", lambda: True)
    assert not PS.usable_for(jnp.zeros((4, 8), jnp.uint8))  # narrow plane
    assert PS.usable_for(jnp.zeros((4, 128), jnp.uint8))
    PS.set_enabled(False)
    try:
        assert not PS.usable_for(jnp.zeros((4, 8), jnp.uint8))
    finally:
        PS.set_enabled(True)


def test_mosaic_compile_error_propagates():
    """A Mosaic compile failure raises to the caller: no re-trace through
    the XLA lowering, no switch flipped for the process."""
    import jax.numpy as jnp

    from spark_rapids_tpu import kernels as K

    traces = []

    def fn(x):
        traces.append(1)
        raise RuntimeError("Mosaic failed to compile TPU kernel: forced")

    with pytest.raises(RuntimeError, match="Mosaic failed"):
        K.GuardedJit(fn)(jnp.zeros(4))
    assert len(traces) == 1
    assert PS.ENABLED
