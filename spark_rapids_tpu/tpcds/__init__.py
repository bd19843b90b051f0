"""TPC-DS rig: scalable generator + the 99-query battery as SQL text.

The north-star workload (BASELINE.json: TPC-DS SF1000, 99 queries; SURVEY §7
step 10). The reference repo's only in-tree rig is the mortgage ETL battery
(integration_tests/.../mortgage/Benchmarks.scala); this module exceeds that
shape: dsdgen-shaped deterministic generator, every query from (sql-parsed)
text, differential tests (tests/test_tpcds.py).
"""
from .datagen import TABLES, gen_table, register_tables, write_tables
from .queries_sql import ALL as QUERY_IDS
from .queries_sql import tpcds_sql

__all__ = [
    "TABLES",
    "gen_table",
    "register_tables",
    "write_tables",
    "QUERY_IDS",
    "tpcds_sql",
]
