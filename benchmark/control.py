"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place and computed in float32, the nearest
precision below the float64 the configurations state. It has to come out as
not correct: its float gap against the float64 reference has to pass the
configuration's limit, on every seed.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 [--rehearse]

Host only (numpy over the cell's Parquet files at the cell's own scale; data
is generated where a run has not left it). The benchmark's own runs never run
this; ``selftest.py`` keeps it as a test at the rehearsal scale.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run as harness
from compare import answer_gap


def control_gaps(workload: str, seed: int, rehearse: bool) -> dict:
    """{query key: (wrong, gap)} of the float32 control against the float64
    reference, for each query and parameter set of the cell."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.cell_entry(bench, workload)
    w = harness.load_workload(cell["traffic"])
    config = harness.load_config(cell["config"])
    queries = {q["name"]: harness.load_module("queries", q["name"]) for q in w["queries"]}
    _, paths, _ = harness.cell_tables(config, queries, seed, rehearse)
    read = harness.table_reader(paths)
    out = {}
    for name, params in harness.query_block(w, queries):
        q = queries[name]
        want = q.reference(read, params)
        got = q.reference(read, params, dtype=np.float32)
        out[harness.key_of(name, params)] = answer_gap(
            list(q.RESULT_COLUMNS), got, list(q.RESULT_COLUMNS), want
        )
    return {"limits": config["limits"], "gaps": out}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    passed_as_correct = 0
    for seed in a.seeds:
        r = control_gaps(a.workload, seed, a.rehearse)
        limit = r["limits"]["float_rel_gap"]
        for key, (wrong, gap) in r["gaps"].items():
            fails = bool(wrong) or gap > limit
            passed_as_correct += not fails
            print(json.dumps({
                "workload": a.workload, "seed": seed, "query": key, "control": "float32",
                "rows_wrong": bool(wrong), "float_rel_gap": gap, "limit": limit,
                "comes_out_not_correct": fails,
            }), flush=True)
    return 1 if passed_as_correct else 0


if __name__ == "__main__":
    sys.exit(main())
