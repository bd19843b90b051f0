"""ops/scan.py segscan against a per-row python loop."""
from __future__ import annotations

import numpy as np
import pytest

from spark_rapids_tpu.ops.scan import segscan


def _loop(vals, starts, op):
    out = np.empty_like(vals)
    for i, v in enumerate(vals):
        out[i] = v if starts[i] or i == 0 else op(out[i - 1], v)
    return out


@pytest.mark.parametrize("n", [1, 2, 3, 64, 1000, 1025])
@pytest.mark.parametrize(
    "op,np_op,dtype",
    [("add", np.add, np.int64), ("maximum", np.maximum, np.int32),
     ("minimum", np.minimum, np.float64), ("add", np.add, np.float64)],
)
def test_segscan_matches_loop(n, op, np_op, dtype):
    import jax.numpy as jnp

    rng = np.random.default_rng(n)
    vals = (rng.random(n) * 100).astype(dtype)
    starts = rng.random(n) < 0.15
    got = np.asarray(
        segscan(jnp.asarray(vals), jnp.asarray(starts), getattr(jnp, op))
    )
    want = _loop(vals, starts, np_op)
    if dtype is np.float64 and op == "add":
        # the scan adds in a different order than the loop
        np.testing.assert_allclose(got, want, rtol=1e-12)
    else:
        assert (got == want).all()


def test_segscan_without_a_leading_start():
    """Rows before the first start accumulate from row 0."""
    import jax.numpy as jnp

    vals = np.arange(1, 9, dtype=np.int64)
    starts = np.zeros(8, bool)
    starts[5] = True
    got = np.asarray(segscan(jnp.asarray(vals), jnp.asarray(starts), jnp.add))
    assert got.tolist() == [1, 3, 6, 10, 15, 6, 13, 21]
