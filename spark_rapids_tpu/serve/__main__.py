"""Standalone server entry point: ``python -m spark_rapids_tpu.serve``.

Builds one TpuSession, optionally loads the TPC-H demo catalog as temp
views (``--tpch-sf``), and serves until interrupted. Conf keys pass
through ``--conf k=v`` (repeatable) exactly as TpuSession takes them.

Lifecycle (docs/operations.md): SIGTERM (and Ctrl-C) triggers
``server.drain()`` — stop accepting, let in-flight streams finish up to
``spark.rapids.tpu.serve.drainTimeout``, cancel stragglers with reason
'shutdown' — so a rolling restart never cuts a stream without a typed
END/ERROR frame. ``--warm-tpch`` precompiles TPC-H q1/q6 before the
server reports ready (STATUS ``ready`` field; readiness-gate restarts
on it).
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.serve",
        description="Arrow-IPC SQL endpoint over a TpuSession",
    )
    ap.add_argument("--host", default=None, help="bind interface "
                    "(default: spark.rapids.tpu.serve.host)")
    ap.add_argument("--port", type=int, default=None,
                    help="bind port, 0 = ephemeral "
                    "(default: spark.rapids.tpu.serve.port)")
    ap.add_argument("--tenants", default=None,
                    help="auth spec token:tenant:pool,… "
                    "(spark.rapids.tpu.serve.tenants)")
    ap.add_argument("--tpch-sf", type=float, default=0.0,
                    help="register the TPC-H tables at this scale factor "
                    "as temp views (demo catalog)")
    ap.add_argument("--warm-tpch", action="store_true",
                    help="precompile TPC-H q1/q6 before reporting ready "
                    "(requires --tpch-sf)")
    ap.add_argument("--conf", action="append", default=[],
                    metavar="K=V", help="session conf entry (repeatable)")
    args = ap.parse_args(argv)

    conf = {}
    for kv in args.conf:
        k, _, v = kv.partition("=")
        conf[k] = v
    if args.tenants is not None:
        conf["spark.rapids.tpu.serve.tenants"] = args.tenants

    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.serve import TpuServer

    session = TpuSession(conf)
    if args.tpch_sf > 0:
        from spark_rapids_tpu.tpch.datagen import TABLES, gen_table

        for name in TABLES:
            table = gen_table(name, args.tpch_sf)
            session.create_dataframe(table).create_or_replace_temp_view(name)
            print(f"registered {name}: {table.num_rows} rows", file=sys.stderr)

    warmup = None
    if args.warm_tpch and args.tpch_sf > 0:
        from spark_rapids_tpu.tpch.sql_queries import tpch_sql

        warmup = [tpch_sql(1, sf=1.0), tpch_sql(6, sf=1.0)]

    server = TpuServer(session, host=args.host, port=args.port,
                       warmup=warmup)
    host, port = server.start()
    print(f"spark-rapids-tpu serving on {host}:{port}", file=sys.stderr)

    # SIGTERM = graceful drain (the rolling-restart path): in-flight
    # streams finish (or cancel with reason 'shutdown' at drainTimeout),
    # every stream still ends with a typed END/ERROR frame
    stop = threading.Event()

    def on_sigterm(_sig, _frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_sigterm)
    try:
        while not stop.is_set():
            time.sleep(0.5)
        print("SIGTERM: draining", file=sys.stderr)
        server.drain(reason="shutdown")
    except KeyboardInterrupt:
        print("interrupt: draining", file=sys.stderr)
        server.drain(reason="shutdown")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
