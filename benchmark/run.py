"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it owns the chip. Everything particular to a cell is data that
this file finds by name: the cell and its metrics in ``BENCHMARK.json``, the
traffic mix in ``workloads/<traffic>.json``, the deployment in
``configs/<configuration>.json``, each query (builder, SQL text, plain
reference, ``min_bytes``) in ``queries/<query>.py`` and each per-layer metric
in ``metrics/<metric>.py``. No cell, query or kernel is named here.

The run: set-up (device, data from ``--seed``, session, warm-up of the cell's
own queries), the measured window, then — with the window closed, the peak
memory read and the program's state dropped — the plain reference over the
same files and the comparison that decides ``correct``. The last line of
standard output is the result; the numbers compared stand beside their limits
there (key ``compared``, last) and as the last lines of standard error.

Off a TPU the run fails without a result. ``--rehearse`` runs the same code on
the CPU backend at the configuration's ``rehearse_scale_factor`` and always
says ``"correct": false``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import queue
import random
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(HERE, "queries"))

WORKLOAD_KEYS = {
    "driver": None, "queries": None, "why": "",
    "clients": 1, "think_ms": 0.0, "rate_qps": None, "warmup_runs": 1,
    "trace_queries": 1, "trace_seconds": 3.0, "trace_after_seconds": 1.0,
}
QUERY_KEYS = {"name": None, "weight": 1, "params": None}
CONFIG_KEYS = {
    "source", "deployment", "loader", "tables_generator", "scale_factor",
    "rehearse_scale_factor", "files_per_table", "conf", "recorded_defaults",
    "assumed", "reduced", "guarantees", "limits",
}
LOADERS = ("parquet", "resident")
DRIVERS = ("batch", "served")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ── data files ───────────────────────────────────────────────────────────
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py``, or for a split metric such as ``x_ms.batch``
    the reader it shares with its siblings, ``<kind>/x_ms.py``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, kind, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{stem.replace('.', '_')}", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {HERE}")


def load_workload(name: str, path: str | None = None) -> dict:
    raw = load_json(path or os.path.join(HERE, "workloads", name + ".json"))
    unknown = sorted(set(raw) - set(WORKLOAD_KEYS))
    if unknown:
        raise ValueError(f"workload {name}: unknown keys {unknown}; known: {sorted(WORKLOAD_KEYS)}")
    w = {**WORKLOAD_KEYS, **raw}
    for key in ("driver", "queries"):
        if not w[key]:
            raise ValueError(f"workload {name}: key {key!r} is required")
    if w["driver"] not in DRIVERS:
        raise ValueError(f"workload {name}: driver {w['driver']!r} is not one of {DRIVERS}")
    queries = []
    for q in w["queries"]:
        unknown = sorted(set(q) - set(QUERY_KEYS))
        if unknown:
            raise ValueError(f"workload {name}: unknown query keys {unknown}")
        q = {**QUERY_KEYS, **q}
        if not q["name"] or int(q["weight"]) < 1:
            raise ValueError(f"workload {name}: a query needs a name and a weight of 1 or more")
        queries.append(q)
    w["queries"] = queries
    if w["driver"] == "batch" and (w["clients"] != 1 or w["rate_qps"]):
        raise ValueError(f"workload {name}: the batch driver is one closed-loop stream")
    return w


def load_config(name: str) -> dict:
    c = load_json(os.path.join(HERE, "configs", name + ".json"))
    unknown = sorted(set(c) - CONFIG_KEYS)
    if unknown:
        raise ValueError(f"configuration {name}: unknown keys {unknown}")
    if c.get("loader") not in LOADERS:
        raise ValueError(f"configuration {name}: loader {c.get('loader')!r} is not one of {LOADERS}")
    return c


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {name!r}")


def metrics_for(bench: dict, section: str, cell: str) -> list:
    """Metrics of ``section`` that this cell reports: those that list it, and
    those that list nothing (per-layer: where the moved metric is reported)."""
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        if section == "per_layer":
            return reports(end_to_end[m["moves"]])
        return True

    return [m for m in bench[section] if reports(m)]


# ── traffic: one general generator ───────────────────────────────────────
def query_block(workload: dict, queries: dict) -> list:
    """One block of the mix: each query ``weight`` times, its parameter list
    cycling. Every seed sends the same blocks, in another order."""
    block = []
    for q in workload["queries"]:
        plist = q["params"] or [queries[q["name"]].DEFAULT_PARAMS]
        for i in range(int(q["weight"]) * len(plist)):
            block.append((q["name"], plist[i % len(plist)]))
    return block


def schedule(block: list, seed: int, stream: int):
    """Endless (query, params) for one stream: the block, reshuffled each time."""
    rng = random.Random(seed * 1000003 + stream)
    while True:
        order = list(block)
        rng.shuffle(order)
        yield from order


def arrivals(rate_qps: float, seconds: float, seed: int) -> list:
    """Open loop: due times in [0, seconds). The gaps are the quantiles of
    the exponential distribution with mean 1/rate — the same set for every
    seed — in an order drawn from the seed."""
    n = max(1, int(math.ceil(rate_qps * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_qps for i in range(n)]
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        if t >= seconds:
            break
        out.append(t)
    return out


def cell_tables(config: dict, queries: dict, seed: int, rehearse: bool) -> tuple:
    """(scale factor, {table: directory}, generated now?) of the Parquet data
    the cell's queries read, made from the seed where an earlier run of the
    same seed has not left it."""
    gen = importlib.import_module(config["tables_generator"])
    sf = config["rehearse_scale_factor" if rehearse else "scale_factor"]
    tables = sorted({t for q in queries.values() for t in q.TABLES})
    root = os.path.join(HERE, ".data", f"sf{sf:g}-seed{seed}")
    paths = gen.ensure_tables(root, sf, seed, tables, int(config["files_per_table"]))
    return sf, paths, paths.pop("_generated")


def key_of(name: str, params: dict) -> str:
    return name + ":" + json.dumps(params, sort_keys=True)


def table_reader(paths: dict):
    """``read(table, columns)`` → numpy arrays by column, from the Parquet
    files under ``paths[table]``; what the plain references compute from.
    Dates come as days since 1970-01-01, strings as numpy unicode."""
    import numpy as np
    import pyarrow.parquet as pq

    cache = {}

    def read(table: str, columns: list) -> dict:
        got = cache.get((table, tuple(columns)))
        if got is None:
            schema = pq.ParquetDataset(paths[table]).schema
            strings = [c for c in columns if str(schema.field(c).type) == "string"]
            # strings come as Parquet's own dictionary pages: few distinct values
            t = pq.read_table(paths[table], columns=columns, read_dictionary=strings)
            got = {}
            for c in columns:
                col = t.column(c)
                if c in strings:
                    d = col.unify_dictionaries().combine_chunks()
                    words = np.asarray(d.dictionary.to_pylist(), dtype=str)
                    got[c] = words[d.indices.to_numpy()]
                elif str(col.type).startswith("date32"):
                    got[c] = col.cast("int32").to_numpy()
                else:
                    got[c] = col.to_numpy()
            cache[(table, tuple(columns))] = got
        return got

    return read


# ── the run ──────────────────────────────────────────────────────────────
class Run:
    """What a per-layer metric's ``read(run)`` may look at."""

    def __init__(self):
        self.counters_before = {}   # obs.metrics.GLOBAL.snapshot() at the window's start
        self.counters_after = {}    # ... and at its end
        self.ledgers = []           # PhaseLedger.snapshot() (ns by phase) of the window's queries
        self.requests = []          # (start_s, end_s, ok, key) on this process's clock
        self.trace = None           # trace_reduce.reduce_trace(...) of the traced part
        self.traced_requests = []   # the requests that ended inside the traced part
        self.min_bytes = {}         # key -> bytes the query must touch
        self.peaks = {}             # this device's row of peaks.json
        self.window_s = 0.0

    def counter_delta(self, name: str) -> int:
        return self.counters_after.get(name, 0) - self.counters_before.get(name, 0)


def percentile(values: list, q: float) -> float:
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


class Harness:
    def __init__(self, args, require_tpu: bool = True, workload_path: str | None = None):
        self.args = args
        self.require_tpu = require_tpu
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        self.cell = cell_entry(self.bench, args.workload)
        self.workload = load_workload(self.cell["traffic"], workload_path)
        self.config = load_config(self.cell["config"])
        self.queries = {
            q["name"]: load_module("queries", q["name"]) for q in self.workload["queries"]
        }
        self.block = query_block(self.workload, self.queries)
        self.run = Run()
        self.answers = []   # (key, names, rows) of every request of the window
        self.failed = 0
        self.attempted = 0
        self.server = None
        self._lock = threading.Lock()
        self._ledgers = {}  # id -> PhaseLedger of the window's queries, each once

    # ── set-up ───────────────────────────────────────────────────────────
    def device(self) -> dict:
        # both compile caches at one fixed place inside the checkout, unless
        # the machine already names one
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".cache", "benchmark-xla")
        )
        os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
        if self.args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(2, ROOT)
        import jax
        import spark_rapids_tpu  # noqa: F401 - a checkout without the program fails here, before any work

        devs = jax.devices()
        d = devs[0]
        line = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
        if d.platform != "tpu" and self.require_tpu and not self.args.rehearse:
            raise SystemExit(f"no TPU: jax reports platform {d.platform!r}")
        if len(devs) < int(self.cell["chips"]):
            raise SystemExit(f"the cell asks for {self.cell['chips']} chips, jax reports {len(devs)}")
        peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if d.platform == "tpu":
            if d.device_kind not in peaks:
                raise SystemExit(f"device kind {d.device_kind!r} is not in benchmark/peaks.json")
            self.run.peaks = peaks[d.device_kind]
        self.jax, self.dev = jax, d
        return line

    def data(self) -> None:
        self.sf, self.paths, self.generated = cell_tables(
            self.config, self.queries, self.args.seed, self.args.rehearse
        )
        import pyarrow.parquet as pq

        self.rows = {
            t: sum(
                pq.ParquetFile(os.path.join(d, f)).metadata.num_rows
                for f in sorted(os.listdir(d))
            )
            for t, d in self.paths.items()
        }

    def session_up(self) -> None:
        from spark_rapids_tpu import TpuSession

        self.session = s = TpuSession(dict(self.config["conf"]))
        if self.config["loader"] == "parquet":
            self.table = lambda name: s.read.parquet(self.paths[name])
        else:  # resident: read once, kept as an in-memory table (CACHE TABLE)
            import pyarrow.parquet as pq

            held = {
                name: s.create_dataframe(pq.read_table(path))
                for name, path in self.paths.items()
            }
            self.table = held.__getitem__
        if self.workload["driver"] == "served":
            from spark_rapids_tpu.serve import TpuServer, connect

            for name in self.paths:
                self.table(name).create_or_replace_temp_view(name)
            self.server = TpuServer(s, host="127.0.0.1", port=0)
            self.address = self.server.start()
            self.connect = connect

    def counters(self) -> dict:
        from spark_rapids_tpu.obs import metrics

        return dict(metrics.GLOBAL.snapshot())

    def recorded_defaults(self) -> dict:
        from spark_rapids_tpu import config

        entries = config.registry()
        return {
            key: entries[key].get(self.session.conf) if key in entries else "no such key"
            for key in self.config.get("recorded_defaults", [])
        }

    # ── the timed calls ──────────────────────────────────────────────────
    def collect_once(self, name: str, params: dict) -> tuple:
        """The batch entry: build the DataFrame, collect host rows."""
        df = self.queries[name].dataframe(self.table, params)
        names = list(df.columns)
        return names, df.collect()

    def request_once(self, conn, name: str, params: dict) -> tuple:
        """The served entry: SQL text over the wire, rows as the client got them."""
        t = conn.sql(self.queries[name].sql(params)).to_table()
        cols = [c.to_pylist() for c in t.columns]
        return list(t.column_names), [tuple(c[i] for c in cols) for i in range(t.num_rows)]

    def warm_up(self) -> None:
        distinct = {key_of(n, p): (n, p) for n, p in self.block}
        runs = int(self.workload["warmup_runs"])
        if self.workload["driver"] == "batch":
            for n, p in distinct.values():
                for _ in range(runs):
                    self.collect_once(n, p)
        else:
            with self.connect(*self.address) as conn:
                for n, p in distinct.values():
                    for _ in range(runs):
                        self.request_once(conn, n, p)

    def note(self, t0, t1, ok, name, params, names, rows) -> None:
        key = key_of(name, params)
        led = getattr(self.session, "_last_ledger", None)
        with self._lock:
            self.attempted += 1
            self.run.requests.append((t0, t1, ok, key))
            if ok:
                self.answers.append((key, names, rows))
            else:
                self.failed += 1
            if led is not None:
                self._ledgers[id(led)] = led

    def start_trace(self) -> None:
        self.trace_dir = os.path.join(ROOT, ".cache", "benchmark-trace", f"{os.getpid()}")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window_span = self.jax.profiler.TraceAnnotation("bench:window")
        self._window_span.__enter__()
        self._traced_from = time.perf_counter()

    def stop_trace(self) -> None:
        self._traced_to = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def drive_batch(self) -> None:
        seconds, span = self.args.seconds, self.jax.profiler.TraceAnnotation
        stream = schedule(self.block, self.args.seed, 0)
        to_trace = int(self.workload["trace_queries"]) if self.args.trace else 0
        if to_trace:
            self.start_trace()
        start = time.perf_counter()
        self.window_start = start
        while time.perf_counter() - start < seconds:
            name, params = next(stream)
            t0 = time.perf_counter()
            try:
                with span("bench:collect"):
                    names, rows = self.collect_once(name, params)
                ok = True
            except Exception as e:  # noqa: BLE001 - a failed query is counted, not hidden
                log(f"query {name} failed: {type(e).__name__}: {e}"[:2000])
                names, rows, ok = [], [], False
            t1 = time.perf_counter()
            with span("bench:between_queries"):
                self.note(t0, t1, ok, name, params, names, rows)
                if to_trace and self.attempted == to_trace:
                    self.stop_trace()
                    to_trace = 0
        if to_trace:
            self.stop_trace()
        self.window_end = max([r[1] for r in self.run.requests], default=start)

    def drive_served(self) -> None:
        w, seconds = self.workload, self.args.seconds
        span = self.jax.profiler.TraceAnnotation
        think = float(w["think_ms"]) / 1e3
        due = queue.Queue() if w["rate_qps"] else None
        go = threading.Event()
        start_box = []

        def client(i: int) -> None:
            stream = schedule(self.block, self.args.seed, i)
            with self.connect(*self.address) as conn:
                go.wait()
                start = start_box[0]
                while True:
                    if due is not None:
                        t_due = due.get()
                        if t_due is None:
                            return
                        t0 = start + t_due  # a late start is the request's wait
                    else:
                        if time.perf_counter() - start >= seconds:
                            return
                        t0 = time.perf_counter()
                    name, params = next(stream)
                    try:
                        with span("bench:client_wait"):
                            names, rows = self.request_once(conn, name, params)
                        ok = True
                    except Exception as e:  # noqa: BLE001 - counted as failed and as the worst
                        log(f"request {name} failed: {type(e).__name__}: {e}"[:2000])
                        names, rows, ok = [], [], False
                    self.note(t0, time.perf_counter(), ok, name, params, names, rows)
                    if think:
                        time.sleep(think)

        threads = [
            threading.Thread(target=client, args=(i,), name=f"bench-client-{i}", daemon=True)
            for i in range(int(w["clients"]))
        ]
        for t in threads:
            t.start()
        time.sleep(0.2)  # connections made before the window opens
        start = time.perf_counter()
        start_box.append(start)
        self.window_start = start
        go.set()
        if due is not None:
            def feed():
                for t_due in arrivals(float(w["rate_qps"]), seconds, self.args.seed):
                    wait = start + t_due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    due.put(t_due)
                for _ in threads:
                    due.put(None)

            feeder = threading.Thread(target=feed, name="bench-arrivals", daemon=True)
            feeder.start()
        if self.args.trace:
            time.sleep(min(float(w["trace_after_seconds"]), seconds / 4))
            self.start_trace()
            time.sleep(min(float(w["trace_seconds"]), seconds / 2))
            self.stop_trace()
        # an answer that comes late is late, not wrong: wait a minute past the
        # close; one that has not come by then never came, and counts as failed
        deadline = start + seconds + 60.0
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        never = sum(t.is_alive() for t in threads)
        if never:
            log(f"{never} requests had no answer a minute after the window closed")
            with self._lock:
                self.attempted += never
                self.failed += never
        self.window_end = max([r[1] for r in self.run.requests], default=start)

    # ── after the window ─────────────────────────────────────────────────
    def references(self) -> dict:
        read = table_reader(self.paths)
        out = {}
        for key in {k for k, _, _ in self.answers}:
            name, params = key.split(":", 1)
            q = self.queries[name]
            out[key] = (list(q.RESULT_COLUMNS), q.reference(read, json.loads(params)))
        return out

    def end_to_end(self) -> dict:
        r = self.run
        done = [x for x in r.requests if x[2]]
        window = self.window_end - self.window_start
        out = {"setup_s": self.setup_s}
        if done:
            out["query_s"] = window / len(done)
            out["served_qps"] = len(done) / window
            times = [x[1] - x[0] for x in done]
            worst = max(times + [x[1] - x[0] for x in r.requests if not x[2]])
            times += [worst] * (len(r.requests) - len(done))
            out["request_p95_ms"] = percentile(times, 0.95) * 1e3
        return out

    def go(self) -> dict:
        import compare
        import trace_reduce

        a, run = self.args, self.run
        device = self.device()
        log(f"device {device}")
        self.data()
        log(f"data sf {self.sf:g} seed {a.seed}: rows {self.rows}, generated {self.generated},"
            f" {time.perf_counter() - T0:.1f}s since start")
        self.session_up()
        self.warm_up()
        log(f"warm-up done, {time.perf_counter() - T0:.1f}s since start")
        # warm-up's answers and ledgers are not the window's
        self.answers, self._ledgers, run.requests = [], {}, []
        self.attempted = self.failed = 0
        gc.collect()
        run.counters_before = self.counters()
        self.setup_s = time.perf_counter() - T0
        (self.drive_batch if self.workload["driver"] == "batch" else self.drive_served)()
        run.counters_after = self.counters()
        run.window_s = self.window_end - self.window_start
        run.ledgers = [led.snapshot() for led in self._ledgers.values()]
        stats = self.dev.memory_stats() or {}
        device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
        extras = {
            "window_s": run.window_s,
            "conf_defaults": self.recorded_defaults(),
            "data_generated": self.generated,
            "cache": {k: run.counters_after.get(k, 0) for k in
                      ("cache.xla.hit", "cache.xla.miss", "cache.xla.stores", "kernel.firstCalls")},
        }
        # the program's state goes before the reference runs
        if self.server is not None:
            self.server.stop()
        self.session = self.table = self.server = None
        gc.collect()

        t_ref = time.perf_counter()
        refs = self.references()
        numbers = compare.compare(self.answers, refs)
        extras["reference_s"] = time.perf_counter() - t_ref
        correct, compared = compare.verdict(numbers, self.config["limits"])
        if self.failed:
            correct = False
        compared["requests_failed"] = {"value": self.failed, "limit": 0}

        for key, _, rows in self.answers:
            if key not in run.min_bytes:
                name = key.split(":", 1)[0]
                run.min_bytes[key] = self.queries[name].min_bytes(self.rows, len(rows))
        if a.trace:
            try:
                run.trace = trace_reduce.reduce_trace(
                    trace_reduce.find_xplane(self.trace_dir),
                    host_xla_as_device=a.rehearse,
                )
                run.traced_requests = [
                    x for x in run.requests
                    if x[2] and self._traced_from <= x[1] <= self._traced_to
                ]
            finally:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
            metrics = {}
            for m in metrics_for(self.bench, "per_layer", a.workload):
                value = load_module("metrics", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            tr = run.trace
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            breakdown = {
                "device_ops": [["module " + n, s] for n, s in tr["modules"][:5]]
                + [["op " + n, s] for n, s in tr["ops"][:5]],
                "idle_gaps": tr["gaps"][:10],
            }
            extras["trace_spans"] = tr["spans"]
        else:
            values = self.end_to_end()
            metrics = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in metrics_for(self.bench, "end_to_end", a.workload)
                if m["name"] in values
            }
            breakdown = None
        on_tpu = device["platform"] == "tpu"
        result = {
            "correct": bool(correct and on_tpu and not a.rehearse),
            "attempted": self.attempted,
            "failed": self.failed,
            "metrics": metrics,
            "device": device,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["workload"] = a.workload
        result["seed"] = a.seed
        result["extras"] = extras
        result["compared"] = compared
        self.compared_ok = correct  # what the comparison alone said
        return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend at the rehearsal scale; never correct")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    h = Harness(args)
    result = h.go()
    sys.stdout.flush()
    for name, c in result["compared"].items():
        log(f"compared {name}: {json.dumps(c)}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
