"""Checks of the yardstick itself, run by hand and in rehearsal (not tier-1):

    python3 benchmark/selftest.py            # everything, a few minutes on the CPU
    python3 benchmark/selftest.py quick      # no run of the engine, seconds

quick: the trace reduction on the small recorded trace and on made-up
intervals; ``min_bytes`` of q1/q3/q6 against the table shapes; the plain
references against a second, independent computation and pinned answers at
SF 0.01; a workload file with an unknown key is refused; the float32 control
comes out as not correct on three seeds.

full adds, on the CPU backend at SF 0.01 with the harness's look for a chip
skipped: a clean run of a batch and of the served cell reads correct by the
comparison alone; the same runs with an answer altered where it is produced
(a float nudged by 1e-6, a count off by one, a row dropped) read not correct;
a workload file with an open-loop rate drives the served path.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"

import run as harness
import trace_reduce
from compare import answer_gap, compare, verdict

HERE = harness.HERE
SF, SEED = 0.01, 20261001
FAILED = []


def check(name: str, ok: bool, detail="") -> None:
    print(("ok   " if ok else "FAIL ") + name + (f"  {detail}" if detail else ""), flush=True)
    if not ok:
        FAILED.append(name)


# ── trace reduction ──────────────────────────────────────────────────────
def test_intervals() -> None:
    merged = trace_reduce.union([(0, 1), (0.5, 2), (3, 4), (3.2, 3.4), (5, 5)])
    check("union merges overlaps and drops empties", merged == [[0, 2], [3, 4]], merged)
    check("length", trace_reduce.length(merged) == 3)
    g = trace_reduce.gaps(merged, -1, 6)
    check("gaps are the complement", g == [(-1, 0), (2, 3), (4, 6)], g)
    spans = [(1.9, 3.1, "collect"), (3.9, 4.2, "between_queries")]
    labels = [trace_reduce.label_gap(x, spans) for x in g]
    check("gaps take the label that covers most of them",
          labels == ["unattributed", "collect", "between_queries"], labels)


def test_recorded_trace() -> None:
    path = os.path.join(HERE, "testdata", "tiny_tpu.xplane.pb")
    r = trace_reduce.reduce_trace(path)
    # recorded by record_trace.py on a TPU v5 lite: three calls of one small
    # jitted program with 50 ms of sleep after each, inside bench:window
    check("recorded trace: one device plane", r["devices"] == 1, r["devices"])
    check("recorded trace: spans", r["spans"] == {"collect": 3, "between_queries": 3}, r["spans"])
    check("recorded trace: window holds three sleeps", 0.15 < r["window_s"] < 1.0, r["window_s"])
    check("recorded trace: busy is above 0 and under the window minus the sleeps",
          0 < r["busy_s"] < r["window_s"] - 0.14, (r["busy_s"], r["window_s"]))
    check("recorded trace: the jit module is named",
          any("tiny_step" in n for n, _ in r["modules"]), r["modules"])
    check("recorded trace: module time is about busy time",
          abs(sum(s for _, s in r["modules"]) - r["busy_s"]) < 0.2 * r["busy_s"] + 1e-4,
          (r["modules"], r["busy_s"]))
    sleeps = [s for label, s in r["gaps"] if label == "between_queries"]
    check("recorded trace: three idle gaps of about 50 ms between queries",
          len(sleeps) == 3 and all(0.045 < s < 0.08 for s in sleeps), r["gaps"])


# ── queries: min_bytes and plain references ──────────────────────────────
def tables_at(seed: int) -> dict:
    import datagen

    root = os.path.join(HERE, ".data", f"sf{SF:g}-seed{seed}")
    paths = datagen.ensure_tables(root, SF, seed, datagen.TABLES)
    paths.pop("_generated")
    return paths


def test_min_bytes(paths) -> None:
    import pyarrow.parquet as pq

    sf1 = {"lineitem": 6_001_215, "orders": 1_500_000, "customer": 150_000}
    for name, want_mb in (("q6", 168), ("q1", 276), ("q3", 207)):
        q = harness.load_module("queries", name)
        got = q.min_bytes(sf1, 0) / 1e6
        check(f"min_bytes {name} at SF 1 is about {want_mb} MB", abs(got - want_mb) < 1.5, got)
        # against the Arrow buffers of the referenced columns as generated
        rows, arrow = {}, 0
        for table, cols in q.COLUMNS.items():
            t = pq.read_table(paths[table], columns=list(cols)).combine_chunks()
            rows[table] = t.num_rows
            for c in t.columns:
                buffers = [b for b in c.chunk(0).buffers() if b is not None]
                # validity bitmaps are not counted: the generator writes no nulls
                arrow += sum(b.size for b in buffers[-2:] if str(c.type) == "string") or \
                    buffers[-1].size
        check(f"min_bytes {name} is the Arrow size of its columns (SF {SF:g})",
              abs(q.min_bytes(rows, 0) - arrow) <= 0.01 * arrow, (q.min_bytes(rows, 0), arrow))


def independent(name: str, paths) -> list:
    """The same answers by another road: pandas over whole tables."""
    import pandas as pd
    import pyarrow.parquet as pq

    li = pq.read_table(paths["lineitem"]).to_pandas()
    if name == "q6":
        d = pd.to_datetime(li.l_shipdate)
        k = li[(d >= "1994-01-01") & (d < "1995-01-01") & (li.l_discount >= 0.05)
               & (li.l_discount <= 0.07) & (li.l_quantity < 24)]
        return [((k.l_extendedprice * k.l_discount).sum(),)]
    if name == "q1":
        k = li[pd.to_datetime(li.l_shipdate) <= "1998-09-02"].copy()
        k["dp"] = k.l_extendedprice * (1 - k.l_discount)
        k["ch"] = k.dp * (1 + k.l_tax)
        g = k.groupby(["l_returnflag", "l_linestatus"]).agg(
            a=("l_quantity", "sum"), b=("l_extendedprice", "sum"), c=("dp", "sum"),
            d=("ch", "sum"), e=("l_quantity", "mean"), f=("l_extendedprice", "mean"),
            g=("l_discount", "mean"), n=("l_quantity", "size")).reset_index()
        return [tuple(r) for r in g.itertuples(index=False)]
    cu = pq.read_table(paths["customer"]).to_pandas()
    od = pq.read_table(paths["orders"]).to_pandas()
    j = cu[cu.c_mktsegment == "BUILDING"].merge(od, left_on="c_custkey", right_on="o_custkey")
    j = j[pd.to_datetime(j.o_orderdate) < "1995-03-15"].merge(
        li[pd.to_datetime(li.l_shipdate) > "1995-03-15"], left_on="o_orderkey",
        right_on="l_orderkey")
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"]).rev.sum().reset_index()
    g = g.sort_values(["rev", "o_orderdate"], ascending=[False, True]).head(10)
    return [(r.l_orderkey, r.rev, r.o_orderdate, r.o_shippriority) for r in g.itertuples()]


#: answers at SF 0.01, seed 20261001, checked by hand: q6's revenue summed row
#: by row in exact decimal arithmetic (752295.6143), q1's groups counted row by
#: row in plain Python
PINNED = {"q6": 752295.6143, "q1_counts": [14318, 393, 29943, 14578], "q3_rows": 10}


def test_references(paths) -> None:
    read = harness.table_reader(paths)
    for name in ("q6", "q1", "q3"):
        q = harness.load_module("queries", name)
        ref = q.reference(read, q.DEFAULT_PARAMS)
        wrong, gap = answer_gap(list(q.RESULT_COLUMNS), independent(name, paths),
                                list(q.RESULT_COLUMNS), ref)
        check(f"reference {name} agrees with the independent computation",
              not wrong and gap < 1e-12, (wrong, gap))
        if name == "q6":
            check("reference q6 is the pinned answer",
                  abs(ref[0][0] - PINNED["q6"]) < 1e-6, ref[0][0])
        if name == "q1":
            check("reference q1 has the pinned groups",
                  [r[-1] for r in ref] == PINNED["q1_counts"], [r[-1] for r in ref])
        if name == "q3":
            check("reference q3 is a top-10 in order",
                  len(ref) == PINNED["q3_rows"]
                  and all(a[1] >= b[1] for a, b in zip(ref, ref[1:])), len(ref))


def test_unknown_key() -> None:
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "w.json")
        good = harness.load_json(os.path.join(HERE, "workloads", "batch_q6_scan.json"))
        for bad in ({**good, "config": "tpch_sf1_parquet"}, {**good, "queries": [{"name": "q6", "wieght": 2}]},
                    {**good, "driver": "open"}):
            with open(path, "w") as f:
                json.dump(bad, f)
            try:
                harness.load_workload("w", path)
                check("a workload file with an unknown key is refused", False, bad)
            except ValueError as e:
                check("a workload file with an unknown key is refused", True, str(e)[:60])


def test_control() -> None:
    import control

    for cell in ("batch_q1_agg", "batch_q3_join", "batch_q6_scan", "served_q6_resident"):
        for seed in (SEED, SEED + 1, SEED + 2):
            r = control.control_gaps(cell, seed, rehearse=True)
            for key, (wrong, gap) in r["gaps"].items():
                check(f"float32 control of {cell} seed {seed} comes out not correct",
                      wrong or gap > r["limits"]["float_rel_gap"], gap)


def test_verdict() -> None:
    limits = {"rows_wrong": 0, "float_rel_gap": 1e-9}
    ok, _ = verdict(compare([], {}), limits)
    check("no answer compared is not correct", not ok)


# ── full: the harness with the timed path broken underneath ──────────────
def drive(cell: str, trace: int = 0, workload_path=None, seconds=2.0):
    args = harness.parse_args(["--workload", cell, "--seed", str(SEED), "--seconds",
                               str(seconds), "--trace", str(trace), "--rehearse"])
    h = harness.Harness(args, require_tpu=False, workload_path=workload_path)
    result = h.go()
    return h, result


def nudge_float(rows):
    out = [list(r) for r in rows]
    for r in out:
        for j, v in enumerate(r):
            if isinstance(v, float):
                r[j] = v * (1 + 1e-6)
                return [tuple(x) for x in out]
    raise AssertionError("no float to nudge")


def count_off_by_one(rows):
    out = [list(r) for r in rows]
    out[0][-1] += 1
    return [tuple(x) for x in out]


def test_faults() -> None:
    h, result = drive("batch_q1_agg", trace=1)
    check("clean batch run: the comparison says correct", h.compared_ok, result["compared"])
    check("rehearsal never prints correct", result["correct"] is False
          and result["device"]["platform"] == "cpu")
    check("traced rehearsal reports every per-layer metric but the roofline",
          set(result["metrics"]) == {"plan_ms.batch", "h2d_ms.batch", "compiles_in_window.batch",
                                     "device_idle_pct.batch"}, sorted(result["metrics"]))
    h, result = drive("served_q6_resident")
    check("clean served run: the comparison says correct", h.compared_ok, result["compared"])
    check("served run reports its end-to-end metrics",
          set(result["metrics"]) == {"served_qps", "request_p95_ms", "setup_s"},
          sorted(result["metrics"]))

    from spark_rapids_tpu.serve.client import ResultStream
    from spark_rapids_tpu.session import DataFrame

    real_collect, real_to_table = DataFrame.collect, ResultStream.to_table
    faults = (
        ("batch_q1_agg", "a count off by one", count_off_by_one),
        ("batch_q6_scan", "a float nudged by 1e-6", nudge_float),
        ("batch_q3_join", "a row dropped", lambda rows: rows[:-1]),
    )
    for cell, what, fault in faults:
        DataFrame.collect = lambda self, f=fault: f(real_collect(self))
        try:
            h, result = drive(cell)
        finally:
            DataFrame.collect = real_collect
        check(f"{cell} with {what} where collect() produces it: not correct",
              not h.compared_ok, result["compared"])

    def altered_table(self):
        import pyarrow as pa

        t = real_to_table(self)
        return pa.table({n: [v * (1 + 1e-6) for v in t.column(n).to_pylist()]
                         for n in t.column_names})

    calls = {"n": 0}

    def every_other(self):
        calls["n"] += 1
        return altered_table(self) if calls["n"] % 2 else real_to_table(self)

    ResultStream.to_table = every_other
    try:
        h, result = drive("served_q6_resident")
    finally:
        ResultStream.to_table = real_to_table
    check("served_q6_resident with every other answer nudged by 1e-6 on the client's wire: "
          "not correct", not h.compared_ok, result["compared"])


def test_open_loop() -> None:
    w = harness.load_json(os.path.join(HERE, "workloads", "served_q6_resident.json"))
    w.update({"rate_qps": 5, "clients": 2, "think_ms": 0})
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "open.json")
        with open(path, "w") as f:
            json.dump(w, f)
        h, result = drive("served_q6_resident", workload_path=path, seconds=3.0)
    check("an open-loop rate in a workload file alone drives the served path",
          h.compared_ok and 10 <= result["attempted"] <= 15, result["attempted"])
    a, b = harness.arrivals(5, 3.0, 1), harness.arrivals(5, 3.0, 2)
    check("every seed has the same arrival gaps in another order",
          a != b and abs(len(a) - len(b)) <= 2, (len(a), len(b)))


def main() -> int:
    quick = sys.argv[1:] == ["quick"]
    test_intervals()
    test_recorded_trace()
    paths = tables_at(SEED)
    test_min_bytes(paths)
    test_references(paths)
    test_unknown_key()
    test_control()
    test_verdict()
    if not quick:
        test_faults()
        test_open_loop()
    print(f"{len(FAILED)} failed" + (": " + "; ".join(FAILED) if FAILED else ""))
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
