"""The quickest proof that the engine still starts on the chip.

One process, one chip: TPC-H at ``--sf 1`` generated from ``--seed`` as
multi-file Parquet, then q6/q1/q3 through ``TpuSession`` (strict mode: an
operator that falls back to the CPU engine fails the phase), a ``contains``
filter that reaches the Pallas kernel, and q6/q1 as SQL text through
``serve/`` — every answer compared with the CPU-oracle session (exact for
non-floats, relative 1e-6 for floats). One JSON object per phase; the last
line is ``{"ok": ..., "device": {...}}`` and says ``"ok": true`` only from a
TPU. ``--chips 4`` runs the mesh phase and its one-device comparison, and no
other. ``--rehearse`` runs the later phases on the CPU at ``--sf 0.01``; the
last line then says ``"ok": false`` and the exit code is non-zero.

Timings are printed for orientation and are not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TABLES_SERVED = ("lineitem", "orders", "customer")


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def rows_differ(got, want) -> str:
    """'' when the two row lists agree: same rows up to order, non-floats
    exactly, floats to a relative 1e-6 (device and oracle sum in different
    orders)."""
    if len(got) != len(want):
        return f"row count {len(got)} vs {len(want)}"

    def key(row):
        # floats quantized to 5 significant digits so a summation-order
        # difference cannot pair unrelated rows
        return tuple(
            (v is None, "f", float(f"{v:.5g}")) if isinstance(v, float)
            and not math.isnan(v) else (v is None, type(v).__name__, repr(v))
            for v in row
        )

    for rg, rw in zip(sorted(got, key=key), sorted(want, key=key)):
        for j, (g, w) in enumerate(zip(rg, rw)):
            if isinstance(g, float) and isinstance(w, float):
                if not (
                    g == w
                    or (math.isnan(g) and math.isnan(w))
                    or abs(g - w) <= 1e-6 * max(abs(g), abs(w), 1.0)
                ):
                    return f"float {g} vs {w} (col {j})"
            elif g != w:
                return f"{g!r} vs {w!r} (col {j})"
    return ""


def table_rows(t) -> list:
    return [tuple(r.values()) for r in t.to_pylist()]


def counter(name: str) -> int:
    from spark_rapids_tpu.obs import metrics

    return metrics.GLOBAL.counter(name).value


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failed = []
        self.answers = {}  # query name -> device rows (phase 3)

    def phase(self, name: str, fn) -> bool:
        t0 = time.perf_counter()
        try:
            line = fn() or {}
            ok = not line.get("error")
        except Exception as e:  # noqa: BLE001 - a phase reports, the run fails
            import traceback

            traceback.print_exc()
            line, ok = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
        if not ok:
            self.failed.append(name)
        emit({"phase": name, "ok": ok,
              "seconds": round(time.perf_counter() - t0, 3), **line})
        return ok

    # ── phases ──────────────────────────────────────────────────────────
    def device(self) -> dict:
        import jax

        devs = jax.devices()
        d = devs[0]
        self.device_line = {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(devs),
        }
        out = dict(self.device_line)
        out["bytes_limit"] = (d.memory_stats() or {}).get("bytes_limit")
        want = self.args.chips
        if d.platform == "tpu":
            if not out["bytes_limit"]:
                out["error"] = "device reports no bytes_limit"
        elif not self.args.rehearse:
            out["error"] = f"no TPU: jax reports platform {d.platform!r}"
        if len(devs) < want:
            out["error"] = f"need {want} devices, jax reports {len(devs)}"
        return out

    def native(self) -> dict:
        """Rebuild the host library from the tracked sources: a copied
        checkout can flatten mtimes and leave a stale build looking fresh."""
        shutil.rmtree(os.path.join(HERE, "native", "build"), ignore_errors=True)
        from spark_rapids_tpu import native

        plane = "native" if native.available() else "python-fallback"
        out = {"plane": plane, "gxx": shutil.which("g++")}
        if plane != "native" and out["gxx"]:
            out["error"] = "g++ is on this machine but the native plane did not build"
        return out

    def data(self) -> dict:
        import pyarrow.parquet as pq

        from spark_rapids_tpu.tpch.datagen import TABLES, write_tables

        a = self.args
        root = os.path.join(a.data_dir, f"sf{a.sf:g}-seed{a.seed}")
        shutil.rmtree(root, ignore_errors=True)
        self.paths = write_tables(root, a.sf, files_per_table=8, seed=a.seed)
        rows = {
            name: sum(
                pq.ParquetFile(os.path.join(self.paths[name], f)).metadata.num_rows
                for f in sorted(os.listdir(self.paths[name]))
            )
            for name in TABLES
        }
        return {"sf": a.sf, "seed": a.seed, "root": root, "rows": rows}

    def device_session(self, extra: dict | None = None):
        from spark_rapids_tpu import TpuSession

        return TpuSession({
            "spark.rapids.sql.enabled": True,
            # an operator that falls back to the CPU engine fails the phase
            "spark.rapids.sql.test.enabled": True,
            **(extra or {}),
        })

    def oracle_session(self):
        from spark_rapids_tpu import TpuSession

        return TpuSession({"spark.rapids.sql.enabled": False})

    def reader(self, session):
        return lambda name: session.read.parquet(self.paths[name])

    def timed_twice(self, build) -> tuple:
        """(rows, cold_s, warm_s, compiles_in_warm_run); collect() ends in
        materialised host rows."""
        t0 = time.perf_counter()
        rows = build().collect()
        cold = time.perf_counter() - t0
        before = counter("kernel.firstCalls")
        t0 = time.perf_counter()
        build().collect()
        warm = time.perf_counter() - t0
        return rows, cold, warm, counter("kernel.firstCalls") - before

    def batch(self) -> dict:
        from spark_rapids_tpu.tpch.queries import tpch_query

        self.dev, self.oracle = self.device_session(), self.oracle_session()
        out, errors = {}, []
        for q in (6, 1, 3):
            rows, cold, warm, compiles = self.timed_twice(
                lambda: tpch_query(q, self.reader(self.dev), sf=self.args.sf)
            )
            want = tpch_query(q, self.reader(self.oracle), sf=self.args.sf).collect()
            diff = rows_differ(rows, want)
            self.answers[q] = rows
            out[f"q{q}"] = {
                "rows": len(rows),
                "cold_s": round(cold, 3),
                "warm_s": round(warm, 3),
                "warm_compiles": compiles,
                "matches_oracle": not diff,
            }
            if diff:
                errors.append(f"q{q}: {diff}")
            if not rows:
                errors.append(f"q{q}: no rows")
        out["cache"] = self.cache_line()
        if errors:
            out["error"] = "; ".join(errors)
        return out

    def cache_line(self) -> dict:
        from spark_rapids_tpu import kernels as K

        return {
            "root": K.compile_cache_root(),
            "xla_store_hit": counter("cache.xla.hit"),
            "xla_store_miss": counter("cache.xla.miss"),
            "xla_store_stores": counter("cache.xla.stores"),
            "first_call_compiles": counter("kernel.firstCalls"),
        }

    def pallas(self) -> dict:
        """No TPC-H plan reaches the Pallas kernel, so run one that does:
        o_comment is up to 90 bytes, its byte plane is W=128. The session
        of this phase keeps the executable store off, so that the stage is
        traced in every run — one loaded from the store never is, and the
        trace is where the dispatch into the kernel can be seen."""
        import jax

        from spark_rapids_tpu.cache import xla_store
        from spark_rapids_tpu.functions import col, count
        from spark_rapids_tpu.ops import pallas_strings as PS

        real, seen = PS.match_starts, []
        on_tpu = jax.default_backend() == "tpu"

        def spy(data, lengths, pat):
            # trace-time: one call per compiled stage that holds the kernel.
            # Off the TPU (rehearsal only) the same kernel runs interpreted.
            seen.append(tuple(data.shape))
            return real(data, lengths, pat, interpret=not on_tpu)

        def query(session):
            return (
                self.reader(session)("orders")
                .filter(col("o_comment").contains("special"))
                .agg(count("*").alias("n"))
            )

        saved = PS.match_starts, PS._backend_is_tpu
        PS.match_starts = spy
        if not on_tpu:
            PS._backend_is_tpu = lambda: True
        try:
            traced = self.device_session(
                {"spark.rapids.tpu.compileCache.enabled": False}
            )
            rows, cold, warm, compiles = self.timed_twice(lambda: query(traced))
        finally:
            PS.match_starts, PS._backend_is_tpu = saved
            xla_store.configure(self.dev.conf)  # the store is process-wide
        diff = rows_differ(rows, query(self.oracle).collect())
        out = {
            "count": rows[0][0] if rows else None,
            "cold_s": round(cold, 3),
            "warm_s": round(warm, 3),
            "warm_compiles": compiles,
            "matches_oracle": not diff,
            "kernel_traces": len(seen),
            "kernel_shapes": sorted(set(seen)),
            "interpreted": not on_tpu,
        }
        if seen and on_tpu:
            # the program the stage held, compiled alone at the same shape
            import jax.numpy as jnp

            n, w = seen[0]
            text = (
                jax.jit(lambda d, ln: real(d, ln, b"special"))
                .lower(
                    jax.ShapeDtypeStruct((n, w), jnp.uint8),
                    jax.ShapeDtypeStruct((n,), jnp.int32),
                )
                .compile()
                .as_text()
            )
            out["tpu_custom_call"] = "tpu_custom_call" in text
            if not out["tpu_custom_call"]:
                out["error"] = "no tpu_custom_call in the compiled kernel"
        if not seen:
            out["error"] = "the Pallas kernel was never dispatched"
        elif diff:
            out["error"] = diff
        return out

    def serve(self) -> dict:
        from spark_rapids_tpu.serve import TpuServer, connect
        from spark_rapids_tpu.tpch.sql_queries import tpch_sql

        for name in TABLES_SERVED:
            self.reader(self.dev)(name).create_or_replace_temp_view(name)
        server = TpuServer(self.dev, host="127.0.0.1", port=0)
        host, port = server.start()
        out, errors = {}, []
        try:
            with connect(host, port) as conn:
                for q in (6, 1):
                    times = []
                    for _ in range(self.args.serve_requests):
                        t0 = time.perf_counter()
                        got = table_rows(conn.sql(tpch_sql(q, self.args.sf)).to_table())
                        times.append(round(time.perf_counter() - t0, 3))
                        diff = rows_differ(got, self.answers[q])
                        if diff:
                            errors.append(f"q{q}: {diff}")
                    out[f"q{q}"] = {"rows": len(got), "request_s": times}
        finally:
            server.stop()
        out["requests"] = 2 * self.args.serve_requests
        if errors:
            out["error"] = "; ".join(errors[:3])
        return out

    def mesh(self) -> dict:
        """q1 and q3 with mesh mode on over ``--chips`` devices against the
        one-device answers; per-device bytes after a scan show whether
        everything landed on the first device."""
        import jax

        from spark_rapids_tpu.functions import count
        from spark_rapids_tpu.parallel import mesh as pmesh
        from spark_rapids_tpu.tpch.queries import tpch_query

        n = self.args.chips
        # one scan partition per file: the default reader stitches files up
        # to 1 GiB into one partition, and one partition has no exchange
        per_file = {"spark.rapids.sql.format.parquet.reader.type": "PERFILE"}
        one = self.device_session(per_file)
        mesh = self.device_session({
            "spark.rapids.sql.mesh.enabled": True,
            "spark.rapids.sql.mesh.size": n,
            **per_file,
        })

        def device_bytes(key):
            return [
                (d.memory_stats() or {}).get(key) for d in jax.devices()[:n]
            ]

        exchanges = []
        real_exchange = pmesh.mesh_exchange

        def counting_exchange(mc, *a, **kw):
            exchanges.append(mc.n)
            return real_exchange(mc, *a, **kw)

        out, errors = {"devices": n}, []
        pmesh.mesh_exchange = counting_exchange
        try:
            scanned = self.reader(mesh)("lineitem").agg(count("*").alias("n")).collect()
            out["lineitem_rows"] = scanned[0][0]
            out["bytes_in_use_after_scan"] = device_bytes("bytes_in_use")
            out["peak_bytes_after_scan"] = device_bytes("peak_bytes_in_use")
            # a four-chip call is dear: what is known so far survives a cut
            emit({"phase": "mesh", "step": "scan", **out})
            for q in (1, 3):
                t0 = time.perf_counter()
                got = tpch_query(q, self.reader(mesh), sf=self.args.sf).collect()
                mesh_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                want = tpch_query(q, self.reader(one), sf=self.args.sf).collect()
                one_s = time.perf_counter() - t0
                diff = rows_differ(got, want)
                out[f"q{q}"] = {
                    "rows": len(got),
                    "mesh_cold_s": round(mesh_s, 3),
                    "one_device_cold_s": round(one_s, 3),
                    "matches_one_device": not diff,
                }
                if diff:
                    errors.append(f"q{q}: {diff}")
                if not got:
                    errors.append(f"q{q}: no rows")
                emit({"phase": "mesh", "step": f"q{q}", **out[f"q{q}"],
                      "mesh_exchanges": len(exchanges)})
        finally:
            pmesh.mesh_exchange = real_exchange
        out["mesh_exchanges"] = len(exchanges)
        out["bytes_in_use_after_queries"] = device_bytes("bytes_in_use")
        out["peak_bytes_after_queries"] = device_bytes("peak_bytes_in_use")
        peaks = out["peak_bytes_after_scan"]
        if all(isinstance(b, int) for b in peaks) and not all(peaks[1:]):
            errors.append(f"the scan put nothing on devices past the first: {peaks}")
        if not exchanges or set(exchanges) != {n}:
            errors.append(f"exchanges over the {n}-device mesh: {exchanges}")
        if errors:
            out["error"] = "; ".join(errors)
        return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; 0.01 with --rehearse)")
    ap.add_argument("--seed", type=int, default=19980802)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh phase and its comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the later phases off the TPU; never ends ok")
    ap.add_argument("--serve-requests", type=int, default=3)
    ap.add_argument("--data-dir", default=os.path.join(HERE, "chip_smoke_data"))
    args = ap.parse_args()
    if args.sf is None:
        args.sf = 0.01 if args.rehearse else 1.0

    s = Smoke(args)
    phases = [("device", s.device), ("native", s.native), ("data", s.data)]
    if args.chips == 4:
        phases.append(("mesh", s.mesh))
    else:
        phases += [("batch", s.batch), ("pallas", s.pallas), ("serve", s.serve)]
    for name, fn in phases:
        if not s.phase(name, fn):
            break  # later phases build on this one
    device = getattr(s, "device_line", {"platform": None, "kind": None, "count": 0})
    ok = not s.failed and device["platform"] == "tpu"
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
