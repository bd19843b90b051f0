"""TPC-H end-to-end: all 22 queries differential, device vs CPU engine.

The reference's closest analogue is its nightly SQL battery + mortgage ETL
suite (integration_tests qa_nightly_sql.py, mortgage/Benchmarks.scala); the
TPC-H rig itself is this framework's own (BASELINE.md's north star is
TPC-shaped). Tiny scale factor keeps the suite fast; the benchmark
(benchmark/run.py) runs q1, q3 and q6 at SF 1 on the chip.
"""
from __future__ import annotations

import pytest

from spark_rapids_tpu.tpch import QUERIES, gen_table, tpch_query, write_tables
from tests.harness import cpu_session, tpu_session, _normalize, _values_equal

SF = 0.003


@pytest.fixture(scope="module")
def tables():
    from spark_rapids_tpu.tpch.datagen import TABLES

    return {name: gen_table(name, SF) for name in TABLES}


def _accessor(session, tables, partitions=2):
    def t(name):
        n = partitions if tables[name].num_rows > 1000 else 1
        return session.create_dataframe(tables[name], num_partitions=n)

    return t


# Q11's threshold fraction is 0.0001/SF per spec — at SF=0.003 no part
# clears it, so tests use the SF-1 fraction to keep the result non-empty
# (the differential comparison is what matters here, not the spec value).
Q11_SF = 1.0


# Q2/Q15's min/max-match filters compare float64 aggregates against float64
# rows: equal on a single engine, but cross-engine float-sum ordering can
# differ, so compare approximately everywhere and skip none.
@pytest.mark.parametrize("n", sorted(QUERIES))
def test_tpch_differential(n, tables):
    cpu = cpu_session()
    # 2 shuffle partitions: exchanges still multi-partition, but the per-query
    # kernel-compile fanout stays affordable for a 22-query parametrization
    tpu = tpu_session({"spark.sql.shuffle.partitions": 2})
    rows_c = tpch_query(n, _accessor(cpu, tables), sf=Q11_SF).collect()
    rows_t = tpch_query(n, _accessor(tpu, tables), sf=Q11_SF).collect()
    # full-device-placement evidence at zero extra cost: the only nodes off
    # device may be source scans (host Arrow decode is the v1 I/O design,
    # SURVEY §7); reasons are kept for diagnosis
    bad = [
        (e.node, e.reasons)
        for e in tpu._last_overrides.explain
        if not e.on_device and not e.node.startswith("CpuScan")
    ]
    assert not bad, f"q{n} compute fallbacks: {bad}"
    rows_c, rows_t = _normalize(rows_c, True), _normalize(rows_t, True)
    assert len(rows_c) == len(rows_t), (
        f"q{n}: row count cpu={len(rows_c)} tpu={len(rows_t)}\n"
        f"cpu={rows_c[:5]}\ntpu={rows_t[:5]}"
    )
    for i, (cr, tr) in enumerate(zip(rows_c, rows_t)):
        for j, (cv, tv) in enumerate(zip(cr, tr)):
            assert _values_equal(cv, tv, approx_float=True), (
                f"q{n} row {i} col {j}: cpu={cv!r} tpu={tv!r}"
            )


def test_tpch_parquet_roundtrip(tmp_path, tables):
    """Scan-from-disk path: write SF tables as multi-file Parquet, read them
    back through the DataFrameReader, run Q6 + Q3 differentially."""
    root = str(tmp_path / "tpch")
    write_tables(root, SF, files_per_table=3)

    def t_for(session):
        def t(name):
            return session.read.parquet(f"{root}/{name}")

        return t

    for n in (6, 3, 1):
        rows_c = tpch_query(n, t_for(cpu_session())).collect()
        rows_t = tpch_query(n, t_for(tpu_session())).collect()
        rows_c, rows_t = _normalize(rows_c, True), _normalize(rows_t, True)
        assert len(rows_c) == len(rows_t)
        for cr, tr in zip(rows_c, rows_t):
            for cv, tv in zip(cr, tr):
                assert _values_equal(cv, tv, approx_float=True), (n, cr, tr)


def test_tpch_nonempty_results(tables):
    """Guard the generator's selectivity: every query must return rows at
    tiny SF (an empty result would make the differential test vacuous)."""
    cpu = cpu_session()
    empty_ok = {20, 21}  # tight multi-way EXISTS chains at SF<0.01
    for n in sorted(QUERIES):
        rows = tpch_query(n, _accessor(cpu, tables), sf=Q11_SF).collect()
        if n not in empty_ok:
            assert rows, f"q{n} returned no rows at SF={SF}"


