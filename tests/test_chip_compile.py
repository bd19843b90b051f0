"""Ahead-of-time compiles for the real chip, with no chip attached.

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``v5e:2x2``). Interpret mode cannot see what
Mosaic refuses — the Pallas kernel here passed every interpret test while
the chip's compiler rejected its index maps — so these compiles guard the
kernels of the main path at real widths. A compile that passes is not a
chip run.

Everything that touches libtpu lives inside the module-scoped fixtures of
THIS file: only the xdist worker that is handed the file loads the library.
"""
from __future__ import annotations

import os

import pytest

from spark_rapids_tpu.ops import pallas_strings as PS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "n,W", [(512, 128), (1000, 128), (1 << 20, 128), (1 << 20, 256)]
)
def test_match_starts_compiles_for_v5e(one_chip, n, W):
    """The widths the engine can hand the kernel (usable_for: W a multiple
    of 128), a full-size plane and a ragged final block."""
    import jax
    import jax.numpy as jnp

    data = jax.ShapeDtypeStruct((n, W), jnp.uint8, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda d, ln: PS.match_starts(d, ln, b"special"))
        .lower(data, lens)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_segscan_compiles_for_v5e_at_full_capacity(one_chip):
    """The grouped aggregates' scan at the capacity q1 reaches at SF 1
    (2^23 rows). Its ``lax.associative_scan`` form made the chip's compiler
    work in proportion to the array — minutes at this size; the loop form
    compiles in seconds, and this keeps it so."""
    import time

    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.scan import segscan

    n = 1 << 23
    vals = jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip)
    starts = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    t0 = time.perf_counter()
    jax.jit(lambda v, s: segscan(v, s, jnp.add)).lower(vals, starts).compile()
    assert time.perf_counter() - t0 < 60


def test_concat_compiles_for_v5e_at_q6_shapes(one_chip):
    """The scan's merge as q6 runs it at SF 1: 8 file batches of 2^20 rows
    (three doubles and a date, each with its validity) into one of 2^23,
    every plane a block copy at a traced offset."""
    import jax

    from spark_rapids_tpu.columnar.device import abstract_batch
    from spark_rapids_tpu.ops.concat import _concat_impl
    from spark_rapids_tpu.types import DATE, DOUBLE, Schema, StructField

    schema = Schema(
        [
            StructField("l_quantity", DOUBLE),
            StructField("l_extendedprice", DOUBLE),
            StructField("l_discount", DOUBLE),
            StructField("l_shipdate", DATE),
        ]
    )
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        abstract_batch(schema, 1 << 20),
    )
    compiled = (
        jax.jit(lambda bs: _concat_impl(list(bs), 1 << 23))
        .lower((batch,) * 8)
        .compile()
    )
    text = compiled.as_text()
    assert "scatter" not in text
    assert "dynamic-update-slice" in text


def test_ungrouped_sum_compiles_for_v5e(one_chip, monkeypatch):
    """q6's final step — sum(l_extendedprice * l_discount) with no keys —
    at a capacity of 65,536 rows. ops/bits.py asks jax.default_backend()
    and would take its CPU branch here; steer it to the chip's."""
    import jax
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.device import DeviceColumn, host_to_device
    from spark_rapids_tpu.ops.aggregate import group_aggregate
    from spark_rapids_tpu.types import DOUBLE

    cap = 65536
    host = host_to_device(
        pa.record_batch(
            {
                "l_extendedprice": pa.array(np.zeros(cap)),
                "l_discount": pa.array(np.zeros(cap)),
            }
        )
    )
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        host,
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def q6_sum(b):
        price, disc = b.columns
        rev = DeviceColumn(
            DOUBLE, price.data * disc.data, price.validity & disc.validity
        )
        _, aggs, n_groups = group_aggregate(b, [], [rev], ["sum"])
        return aggs[0].data, aggs[0].validity, n_groups

    jax.jit(q6_sum).lower(batch).compile()


def test_window_rank_compiles_for_v5e_at_q67_shapes(one_chip, monkeypatch):
    """TPC-DS q67's window as the chip runs it at SF 1: rank() over
    (partition by a string, order by a double descending) on a batch of 2^20
    rows with five padded string planes. The window kernel had never been
    lowered for a TPU before PR 27."""
    import jax

    from spark_rapids_tpu.columnar.device import abstract_batch
    from spark_rapids_tpu.exec.tpu_window import _make_window_kernel
    from spark_rapids_tpu.expr.base import BoundReference
    from spark_rapids_tpu.expr.windows import Rank, WindowExpression, WindowOrder, WindowSpec
    from spark_rapids_tpu.types import DOUBLE, INT, LONG, STRING, Schema, StructField

    names = ("i_category", "i_class", "i_brand", "i_product_name", "d_year",
             "d_qoy", "d_moy", "s_store_id", "sumsales")
    types = (STRING,) * 4 + (LONG,) * 3 + (STRING, DOUBLE)
    child = Schema([StructField(n, t) for n, t in zip(names, types)])
    out = Schema(list(child.fields) + [StructField("rk", INT, False)])
    category, sumsales = BoundReference(0, STRING), BoundReference(8, DOUBLE)
    spec = WindowSpec((category,), (WindowOrder(sumsales, False),))
    fn = _make_window_kernel(
        (category,), ((sumsales, False, False),),
        (("rk", WindowExpression(Rank(), spec)),), out, child,
    )
    assert fn.__name__ == "_window"  # the trace reads jit__window
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        abstract_batch(child, 1 << 20, {0: 16, 1: 32, 2: 16, 3: 16, 7: 32}),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(fn).lower(batch).compile()
    # what the program holds while it runs, beside its 0.2 GB of input
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
