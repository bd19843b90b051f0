"""Checks of what the TPC-DS configuration added to the yardstick, run by hand
and in rehearsal (not tier-1; ``selftest.py`` covers the rest):

    python3 benchmark/selftest_tpcds.py            # everything, about a minute on the CPU
    python3 benchmark/selftest_tpcds.py quick      # no run of the engine, seconds

quick: the generator gives the same tables for the same seed and other tables
for another; ``min_bytes`` and ``window_min_bytes`` of q67 against hand counts
and the Arrow buffers; the reference against a second, independent computation
(pandas); the float32 control comes out as not correct on three seeds; the four
new metric readers find nothing to read in the small recorded trace, which has
no ``jit__window`` module, and say ``None``.

full adds, on the CPU backend at SF 0.01: a traced rehearsal of the new batch
cell end to end reads correct by the comparison alone and reports
``window_calls.batch`` 1; the same run with a rank altered where ``collect()``
produces it reads not correct.
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import run as harness
import trace_reduce
from compare import answer_gap

HERE = harness.HERE
SF, SEED = 0.01, 2147485001
CELL = "batch_ds_q67_rollup_rank"
FAILED = []


def check(name: str, ok: bool, detail="") -> None:
    print(("ok   " if ok else "FAIL ") + name + (f"  {detail}" if detail else ""), flush=True)
    if not ok:
        FAILED.append(name)


def tables_at(seed: int) -> dict:
    import tpcds_datagen

    root = os.path.join(HERE, ".data", f"sf{SF:g}-seed{seed}")
    paths = tpcds_datagen.ensure_tables(root, SF, seed, tpcds_datagen.TABLES)
    paths.pop("_generated")
    return paths


def test_generator() -> None:
    import pyarrow as pa
    import pyarrow.compute as pc

    import tpcds_datagen as g

    def head(t: str, seed: int) -> pa.Table:
        draws = g.DRAW[t](SF, seed)
        return g.ARROW[t](draws, 0, min(50, draws["rows"]))

    a = {t: head(t, SEED) for t in g.TABLES}
    b = {t: head(t, SEED) for t in g.TABLES}
    c = {t: head(t, SEED + 1) for t in g.TABLES}
    check("generator: the same seed gives the same tables", all(a[t].equals(b[t]) for t in a))
    check("generator: another seed gives other sales, items and stores",
          not any(a[t].equals(c[t]) for t in ("store_sales", "item", "store")))
    check("generator: every column of the four tables",
          [a[t].num_columns for t in g.TABLES] == [23, 28, 22, 29],
          [a[t].num_columns for t in g.TABLES])
    check("generator: row counts at SF 1",
          [g.n_rows(t, 1.0) for t in g.TABLES] == [2_880_000, 73_049, 18_000, 12])
    check("generator: business ids are char(16), d_date_sk the julian day number",
          set(pc.utf8_length(a["store"]["s_store_id"]).to_pylist()) == {16}
          and a["date_dim"]["d_date_sk"][0].as_py() == 2415022
          and a["date_dim"]["d_date"][0].as_py().isoformat() == "1900-01-02",
          a["store"]["s_store_id"][0])
    d = g.draw_date_dim(SF, SEED)
    jan2000 = d["month_seq"][(d["year"] == 2000) & (d["moy"] == 1)]
    check("generator: d_month_seq 1200 is January 2000", set(jan2000) == {1200}, set(jan2000))
    check("generator: a seed past 32 signed bits is taken",
          isinstance(g.ARROW["store"](g.DRAW["store"](SF, 2**31 + 7), 0, 2), pa.Table))


def test_min_bytes(paths) -> None:
    import pyarrow.parquet as pq

    q = harness.load_module("queries", "ds_q67")
    sf1 = {"store_sales": 2_880_000, "date_dim": 73_049, "item": 18_000, "store": 12}
    # by hand: 5 columns of 8 bytes over store_sales, 5 over date_dim, item's key
    # and four strings (9.9 + 15.9 + 18.625 + 15.4 with offsets), store's key and id
    hand = 2_880_000 * 40 + 73_049 * 40 + 18_000 * (8 + 59.825) + 12 * (8 + 20)
    check("min_bytes q67 at SF 1 is the hand count, about 119.3 MB",
          abs(q.min_bytes(sf1, 0) - hand) < 1 and abs(hand / 1e6 - 119.3) < 0.1, q.min_bytes(sf1, 0))
    row = 9.9 + 15.9 + 18.625 + 15.4 + 3 * 8 + 20 + 8  # eight keys and sumsales
    check("min_bytes q67 adds the rows returned with their rank",
          abs(q.min_bytes(sf1, 100) - q.min_bytes(sf1, 0) - 100 * (row + 4)) < 1)
    leaf, total = [0] * 8 + [1 << 20], [1] + [0] * 8
    check("window_min_bytes: a leaf row in, the row and its rank out",
          q.window_min_bytes(leaf) == int((1 << 20) * (2 * row + 4))
          and abs(q.window_min_bytes(leaf) / 1e6 - 238.7) < 0.1, q.window_min_bytes(leaf))
    # the grand total: five string offsets, three null numbers, the sum
    check("window_min_bytes: a rolled-up key is its offset or its slot",
          q.window_min_bytes(total) == 2 * (5 * 4 + 3 * 8 + 8) + 4
          and q.window_min_bytes([1, 1] + [0] * 7) == int(2 * 52 + 4 + 2 * (52 + 5.9) + 4))
    rows, arrow = {}, 0
    for table, cols in q.COLUMNS.items():
        t = pq.read_table(paths[table], columns=list(cols)).combine_chunks()
        rows[table] = t.num_rows
        for c in t.columns:
            buffers = [b for b in c.chunk(0).buffers() if b is not None]
            arrow += sum(b.size for b in buffers[-2:]) if str(c.type) == "string" \
                else buffers[-1].size
    check(f"min_bytes q67 is the Arrow size of its columns (SF {SF:g})",
          abs(q.min_bytes(rows, 0) - arrow) <= 0.01 * arrow, (q.min_bytes(rows, 0), arrow))


def independent(paths, dms: int) -> list:
    """The same answer by another road: pandas merges, one groupby per level,
    pandas' own rank and sort."""
    import pandas as pd
    import pyarrow.parquet as pq

    q = harness.load_module("queries", "ds_q67")
    t = {n: pq.read_table(paths[n], columns=list(q.COLUMNS[n])).to_pandas() for n in q.TABLES}
    dd = t["date_dim"]
    j = (t["store_sales"].merge(dd[(dd.d_month_seq >= dms) & (dd.d_month_seq <= dms + 11)],
                                left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(t["store"], left_on="ss_store_sk", right_on="s_store_sk")
         .merge(t["item"], left_on="ss_item_sk", right_on="i_item_sk"))
    j["v"] = (j.ss_sales_price * j.ss_quantity).fillna(0.0)
    keys = list(q.KEYS)
    parts = []
    for depth in range(len(keys) + 1):
        g = (j.groupby(keys[:depth]).v.sum().reset_index() if depth
             else pd.DataFrame({"v": [j.v.sum()]}))
        for k in keys[depth:]:
            g[k] = None
        parts.append(g[keys + ["v"]])
    r = pd.concat(parts, ignore_index=True)
    r["rk"] = r.groupby("i_category", dropna=False).v.rank(method="min", ascending=False).astype(int)
    r = r[r.rk <= 100].sort_values(keys + ["v", "rk"], na_position="last").head(100)
    return [tuple(None if pd.isna(x) else (int(x) if k.startswith("d_") else x)
                  for k, x in zip(keys, row[:8])) + (float(row[8]), int(row[9]))
            for row in r.itertuples(index=False)]


def test_reference(paths) -> None:
    q = harness.load_module("queries", "ds_q67")
    ref = q.reference(harness.table_reader(paths), q.DEFAULT_PARAMS)
    names = list(q.RESULT_COLUMNS)
    wrong, gap = answer_gap(names, independent(paths, 1200), names, ref)
    check("reference q67 agrees with the independent computation",
          not wrong and gap < 1e-12, (wrong, gap))
    ranks = [r[-1] for r in ref]
    check("reference q67: 100 rows of the first category, ranks with ties and gaps",
          len(ref) == 100 and len({r[0] for r in ref}) == 1 and len(set(ranks)) < len(ranks)
          and max(ranks) <= 100, (len(ref), sorted(ranks)[-3:]))


def test_control() -> None:
    import control

    for seed in (SEED, SEED + 1, SEED + 2):
        r = control.control_gaps(CELL, seed, rehearse=True)
        for key, (wrong, gap) in r["gaps"].items():
            check(f"float32 control of {CELL} seed {seed} comes out not correct",
                  wrong or gap > r["limits"]["float_rel_gap"], (wrong, gap))


def test_metric_readers(paths) -> None:
    run = harness.Run()
    run.trace = trace_reduce.reduce_trace(os.path.join(HERE, "testdata", "tiny_tpu.xplane.pb"))
    run.traced_requests = [(0.0, 1.0, True, "ds_q67:{}")]
    run.requests = list(run.traced_requests)
    run.peaks = {"hbm_gbps": 819.0}
    names = ("window_ms.batch", "window_roofline.batch", "rollup_agg_ms.batch", "window_calls.batch")
    got = {n: harness.load_module("metrics", n).read(run) for n in names}
    check("the new readers say None on a trace without their modules and a program "
          "without their counters (the parent)", all(v is None for v in got.values()), got)
    run.trace = dict(run.trace, modules=[["jit__window", 0.5], ["jit__sort", 2.0],
                                         ["jit__aggregate", 1.0], ["jit_fn", 9.0]])
    run.counters_before = {"window.calls": 3}
    run.counters_after = {"window.calls": 5}
    run.requests = run.requests * 2
    q = harness.load_module("queries", "ds_q67")
    import ds_q67  # the copy the readers import: the reference leaves its count there

    ds_q67.LEVEL_ROWS = None
    got = harness.load_module("metrics", "window_roofline.batch").read(run)
    check("window_roofline says None until a reference has counted the window's rows",
          got is None, got)
    q.reference(harness.table_reader(paths), q.DEFAULT_PARAMS)
    levels, _ = q.rollup_levels(harness.table_reader(paths), q.DEFAULT_PARAMS)
    got = {n: harness.load_module("metrics", n).read(run) for n in names}
    want_share = 100.0 * q.window_min_bytes([len(g) for g, _ in levels]) / 819e9 / 0.5
    check("the new readers on made-up modules and counters",
          got["window_ms.batch"] == 500.0 and got["rollup_agg_ms.batch"] == 1000.0
          and got["window_calls.batch"] == 1.0
          and abs(got["window_roofline.batch"] - want_share) < 1e-9, got)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    check("the new batch cell lists the four new metrics and the batch family",
          set(names) <= listed and "query_roofline.batch" in listed, sorted(listed))


def drive(trace: int, seconds=2.0):
    args = harness.parse_args(["--workload", CELL, "--seed", str(SEED), "--seconds",
                               str(seconds), "--trace", str(trace), "--rehearse"])
    h = harness.Harness(args, require_tpu=False)
    return h, h.go()


def test_rehearsal() -> None:
    h, result = drive(trace=1)
    check("clean traced rehearsal of the new batch cell: the comparison says correct",
          h.compared_ok and result["compared"]["rows_wrong"]["value"] == 0, result["compared"])
    check("rehearsal never prints correct",
          result["correct"] is False and result["device"]["platform"] == "cpu")
    m = result["metrics"]
    check("traced rehearsal reports the counter metric, and no device metric off a TPU",
          m.get("window_calls.batch", {}).get("value") == 1.0 and "window_ms.batch" not in m
          and "window_roofline.batch" not in m and m["compiles_in_window.batch"]["value"] == 0,
          sorted(m))

    from spark_rapids_tpu.session import DataFrame

    real_collect = DataFrame.collect

    def rank_off(rows):
        out = [list(r) for r in rows]
        out[-1][-1] += 1
        return [tuple(r) for r in out]

    DataFrame.collect = lambda self: rank_off(real_collect(self))
    try:
        h, result = drive(trace=0)
    finally:
        DataFrame.collect = real_collect
    check("a rank off by one where collect() produces it: not correct",
          not h.compared_ok and result["compared"]["rows_wrong"]["value"] >= 1, result["compared"])


def main() -> int:
    quick = sys.argv[1:] == ["quick"]
    test_generator()
    paths = tables_at(SEED)
    test_min_bytes(paths)
    test_reference(paths)
    test_control()
    test_metric_readers(paths)
    if not quick:
        test_rehearsal()
    print(f"{len(FAILED)} failed" + (": " + "; ".join(FAILED) if FAILED else ""))
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
