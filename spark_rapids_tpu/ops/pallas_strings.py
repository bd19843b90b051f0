"""Pallas TPU kernels for the padded-byte string plane.

The byte-matrix string layout ([n, W] u8 + lengths) makes substring search
the hot string op (`like '%p%'`, contains, locate, split all ride
``match_starts``). The pure-XLA path materializes an ``[n, S, L]`` window
gather — at 2M rows × W=128 × L=16 that is a multi-GB intermediate in HBM.
This Pallas kernel (pallas_guide.md playbook) keeps each row block resident
in VMEM and computes the match mask with L shifted compares — no windows
ever hit HBM, and the whole search is ONE fused kernel regardless of W.

Used on the TPU backend when ``spark.rapids.sql.pallas.enabled`` (default
on); off the TPU, and with the switch off, the engine takes the XLA
lowering. Differential-tested against the XLA path in tests/test_pallas.py
(``interpret=True`` on CPU) and compiled for the chip in
tests/test_chip_compile.py.
"""
from __future__ import annotations

import numpy as np

ENABLED = True  # conf gate (spark.rapids.sql.pallas.enabled)

_BLOCK_ROWS = 256


def set_enabled(flag: bool) -> None:
    global ENABLED
    ENABLED = bool(flag)


def _backend_is_tpu() -> bool:
    # NOTE: must not inspect the ARRAY — inside jax.jit (where every engine
    # call site lives) the data is a Tracer with no .devices(); the backend
    # is a process-level fact and trace-safe
    import jax

    return jax.default_backend() == "tpu"


def usable_for(data) -> bool:
    """Pallas path applies: enabled, TPU backend, 2-D byte plane whose
    width fills whole 128-lane vregs (narrow planes fail Mosaic
    legalization AND are exactly where the XLA gather is cheap)."""
    return (
        ENABLED
        and getattr(data, "ndim", 0) == 2
        and not isinstance(data, np.ndarray)  # host numpy stays host-side
        and data.shape[1] >= 128
        and data.shape[1] % 128 == 0
        and _backend_is_tpu()
    )


def match_starts(data, lengths, pat: bytes, interpret: bool = False):
    """bool[n, W]: ``pat`` matches starting at each byte position — the
    Pallas twin of expr/strings.py:_match_starts (bit-identical contract:
    matches must FIT inside the row's length)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n, W = data.shape
    L = len(pat)
    if L == 0 or L > W:
        return jnp.zeros((n, W), dtype=bool)

    def kernel(x_ref, len_ref, o_ref):
        x = x_ref[...].astype(jnp.int32)
        lens = len_ref[...].astype(jnp.int32)
        B = x.shape[0]
        m = jnp.ones((B, W), jnp.bool_)
        for t, byte in enumerate(pat):
            # static roll: W stays constant so every shift is one vreg
            # permute; positions past W-L are killed by the fit mask below
            shifted = x if t == 0 else jnp.roll(x, -t, axis=1)
            m = m & (shifted == byte)
        pos = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
        m = m & ((pos + L) <= lens)
        o_ref[...] = m.astype(jnp.int8)

    B = _BLOCK_ROWS
    lens2 = lengths.reshape(-1, 1).astype(jnp.int32)
    # grid = ceil(n/B): Mosaic masks the ragged final block itself — no
    # padded copy of the whole byte plane (capacities are usually
    # power-of-two bucketed so the ragged case is rare anyway)
    # the package enables x64, so a literal 0 in an index map would be an
    # i64 next to the i32 grid index — Mosaic refuses the mixed return
    def row_block(i):
        return i, jnp.int32(0)

    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, B),),
        in_specs=[
            pl.BlockSpec((B, W), row_block),
            pl.BlockSpec((B, 1), row_block),
        ],
        out_specs=pl.BlockSpec((B, W), row_block),
        out_shape=jax.ShapeDtypeStruct((n, W), jnp.int8),
        interpret=interpret,
    )(data, lens2)
    return out.astype(bool)


def match_starts_np_reference(data: np.ndarray, lengths: np.ndarray, pat: bytes) -> np.ndarray:
    """Oracle for tests: per-row python find loop."""
    n, W = data.shape
    out = np.zeros((n, W), dtype=bool)
    p = np.frombuffer(pat, dtype=np.uint8)
    L = len(p)
    if L == 0 or L > W:
        return out
    for i in range(n):
        ln = int(lengths[i])
        for j in range(0, ln - L + 1):
            if (data[i, j : j + L] == p).all():
                out[i, j] = True
    return out
