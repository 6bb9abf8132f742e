"""CLI entry: ``python -m greptimedb_tpu.cli <subcommand>``.

Mirrors the reference binary's role subcommands (src/cmd/src/bin/greptime.rs:
standalone/cli) for the roles that exist this round, plus data export/
import (reference src/cli/src/data/) and an interactive SQL shell.
"""

from __future__ import annotations

import argparse
import json
import sys


def cmd_standalone(args) -> int:
    import jax

    from greptimedb_tpu.compile.xla_cache import configure_xla_cache
    from greptimedb_tpu.servers import HttpServer
    from greptimedb_tpu.standalone import GreptimeDB
    from greptimedb_tpu.storage.region import RegionOptions
    from greptimedb_tpu.utils.config import load_options

    opts = load_options(args.config)
    if args.data_home:
        opts.storage.data_home = args.data_home
    if args.http_addr:
        opts.http.addr = args.http_addr
    if opts.device.platform:
        jax.config.update("jax_platforms", opts.device.platform)
    configure_xla_cache()
    db = GreptimeDB(
        opts.storage.data_home,
        region_options=RegionOptions(
            flush_threshold_bytes=opts.storage.flush_threshold_mb << 20,
            compaction_window_ms=opts.storage.compaction_window_hours * 3600_000,
            compaction_trigger_files=opts.storage.compaction_trigger_files,
            wal_enabled=opts.wal.provider != "noop",
            wal_sync=opts.wal.sync,
        ),
        cache_capacity_bytes=opts.storage.cache_capacity_gb << 30,
        ingest_quota_bytes=(opts.memory.ingest_quota_mb << 20) or None,
        ingest_quota_policy=opts.memory.ingest_policy,
    )
    if opts.default_timezone and opts.default_timezone != "UTC":
        db.set_timezone(opts.default_timezone)
    if opts.slow_query.threshold_ms > 0:
        db.slow_query_threshold_ms = opts.slow_query.threshold_ms
    if opts.auth.users:
        from greptimedb_tpu.utils.auth import StaticUserProvider

        db.user_provider = StaticUserProvider.from_lines(
            [str(u) for u in opts.auth.users]
        )
    from greptimedb_tpu.utils.tls import TlsConfig, context_from_config

    def _tls_ctx(o):
        return context_from_config(
            TlsConfig(cert_path=o.tls_cert_path or None,
                      key_path=o.tls_key_path or None,
                      mode=o.tls_mode),
            opts.storage.data_home,
        )

    host, port = opts.http.addr.rsplit(":", 1)
    servers = []
    try:
        http_ctx = _tls_ctx(opts.http)
        srv = HttpServer(db, host=host, port=int(port),
                         ssl_context=http_ctx)
        srv.start()
        servers.append(srv)
        extra = []
        if opts.mysql.enable:
            from greptimedb_tpu.servers.mysql import MysqlServer

            mh, mp = opts.mysql.addr.rsplit(":", 1)
            mysql_srv = MysqlServer(
                db, host=mh, port=int(mp),
                ssl_context=_tls_ctx(opts.mysql),
                tls_require=opts.mysql.tls_mode == "require")
            mysql_srv.start()
            servers.append(mysql_srv)
            extra.append(f"mysql://{mh}:{mysql_srv.port}")
        if opts.postgres.enable:
            from greptimedb_tpu.servers.postgres import PostgresServer

            ph, pp = opts.postgres.addr.rsplit(":", 1)
            pg_srv = PostgresServer(
                db, host=ph, port=int(pp),
                ssl_context=_tls_ctx(opts.postgres),
                auth_mode=opts.postgres.auth_mode,
                tls_require=opts.postgres.tls_mode == "require")
            pg_srv.start()
            servers.append(pg_srv)
            extra.append(f"postgres://{ph}:{pg_srv.port}")
        scheme = "https" if http_ctx is not None else "http"
        print("greptimedb-tpu standalone listening on "
              f"{scheme}://{host}:{srv.port}"
              + (" " + " ".join(extra) if extra else "")
              + f" (data_home={opts.storage.data_home}, devices={jax.devices()})")
        import signal
        import threading

        stop = threading.Event()
        signal.signal(signal.SIGINT, lambda *a: stop.set())
        signal.signal(signal.SIGTERM, lambda *a: stop.set())
        stop.wait()
    finally:
        # protocol servers drain before the database closes under them
        for s in reversed(servers):
            s.stop()
        # graceful shutdown: flush dirty regions so the clean restart
        # replays O(hot-tail) instead of the full log (ISSUE 9)
        db.close(flush=True)
    return 0


def cmd_datanode(args) -> int:
    """Datanode role process: regions behind Arrow Flight (reference
    src/cmd/src/datanode.rs + src/datanode/src/region_server.rs)."""
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from greptimedb_tpu.compile.xla_cache import configure_xla_cache
    from greptimedb_tpu.rpc.datanode import serve

    configure_xla_cache()

    serve(args.node_id, args.data_home, host=args.host, port=args.port,
          managed=args.managed, remote_wal_dir=args.remote_wal_dir)
    return 0


def cmd_frontend(args) -> int:
    """Frontend role process: stateless HTTP SQL router over remote
    datanodes + a shared metadata store (reference
    src/cmd/src/frontend.rs)."""
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from greptimedb_tpu.rpc.frontend import serve_frontend

    host, port = args.http_addr.rsplit(":", 1)
    serve_frontend(args.kvstore, args.datanode or [],
                   host=host, port=int(port))
    return 0


def cmd_kvstore(args) -> int:
    """Shared metadata-store role process (etcd/RDS analog: an
    SqliteKv-backed Flight service every metasrv/frontend can point at;
    reference src/common/meta/src/kv_backend/{etcd,rds})."""
    from greptimedb_tpu.rpc.kvservice import serve

    serve(args.path, host=args.host, port=args.port)
    return 0


def cmd_meta(args) -> int:
    """Metadata snapshot/restore (reference greptime cli metadata
    snapshot, src/cli/src/metadata/snapshot.rs): dump the entire typed
    kv key-space to a JSON file, or load one back."""
    import base64

    from greptimedb_tpu.meta.kv import FileKv

    kv_path = f"{args.data_home}/metadata/kv.json"
    kv = FileKv(kv_path)
    if args.action == "snapshot":
        entries = [
            {"k": k, "v": base64.b64encode(v).decode()}
            for k, v in kv.range("")
        ]
        with open(args.file, "w") as f:
            json.dump({"version": 1, "entries": entries}, f)
        print(f"snapshot: {len(entries)} keys -> {args.file}")
        return 0
    with open(args.file) as f:
        snap = json.load(f)
    # REPLACE the key-space (a merge would resurrect post-snapshot drops)
    kv.bulk_replace(
        {e["k"]: base64.b64decode(e["v"]) for e in snap["entries"]}
    )
    print(f"restore: {len(snap['entries'])} keys <- {args.file}")
    return 0


def cmd_gc(args) -> int:
    """Orphaned-object GC sweep over a data home."""
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(args.data_home)
    try:
        deleted = db.regions.gc(grace_seconds=args.grace_seconds)
        print(f"gc: deleted {len(deleted)} orphaned objects")
        for p in deleted:
            print(f"  {p}")
    finally:
        db.close()
    return 0


def cmd_sql(args) -> int:
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(args.data_home)
    try:
        if args.execute:
            res = db.sql(args.execute)
            _print_result(res)
            return 0
        # interactive shell
        print("greptimedb-tpu sql shell (end statements with ;, \\q to quit)")
        buf: list[str] = []
        while True:
            try:
                prompt = "greptime> " if not buf else "      ...> "
                line = input(prompt)
            except EOFError:
                break
            if line.strip() in ("\\q", "exit", "quit"):
                break
            buf.append(line)
            if line.rstrip().endswith(";"):
                stmt = "\n".join(buf)
                buf = []
                try:
                    _print_result(db.sql(stmt))
                except Exception as e:  # noqa: BLE001
                    print(f"ERROR: {e}")
    finally:
        db.close()
    return 0


def _print_result(res) -> None:
    if not res.column_names:
        print(f"OK, {res.affected_rows} rows affected")
        return
    widths = [
        max(len(str(n)), *(len(str(r[i])) for r in res.rows)) if res.rows else len(str(n))
        for i, n in enumerate(res.column_names)
    ]
    line = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
    print(line)
    print("|" + "|".join(f" {n:<{w}} " for n, w in zip(res.column_names, widths)) + "|")
    print(line)
    for r in res.rows:
        print("|" + "|".join(f" {str(v):<{w}} " for v, w in zip(r, widths)) + "|")
    print(line)
    print(f"{len(res.rows)} rows in set")


def cmd_export(args) -> int:
    """Data export (reference greptime cli data export): per-table parquet +
    a metadata manifest."""
    import os

    import pyarrow.parquet as pq

    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(args.data_home)
    os.makedirs(args.output_dir, exist_ok=True)
    manifest = {"version": 1, "databases": {}}
    try:
        for dbname in db.catalog.list_databases():
            manifest["databases"][dbname] = []
            for t in db.catalog.list_tables(dbname):
                region = db._region_of(f"{dbname}.{t.name}")
                host = region.scan_host()
                import numpy as np
                import pyarrow as pa

                cols = {}
                for c in t.schema:
                    arr = host[c.name]
                    cols[c.name] = pa.array(
                        arr.astype(object) if arr.dtype == object else arr,
                        type=c.to_arrow().type,
                    )
                table = pa.table(cols)
                path = os.path.join(args.output_dir, f"{dbname}.{t.name}.parquet")
                pq.write_table(table, path)
                manifest["databases"][dbname].append({
                    "table": t.name, "schema": t.schema.to_dict(),
                    "rows": table.num_rows, "file": os.path.basename(path),
                })
                print(f"exported {dbname}.{t.name}: {table.num_rows} rows")
        with open(os.path.join(args.output_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
    finally:
        db.close()
    return 0


def cmd_import(args) -> int:
    import os

    import pyarrow.parquet as pq

    from greptimedb_tpu.datatypes.schema import Schema
    from greptimedb_tpu.standalone import GreptimeDB

    db = GreptimeDB(args.data_home)
    try:
        with open(os.path.join(args.input_dir, "manifest.json")) as f:
            manifest = json.load(f)
        for dbname, tables in manifest["databases"].items():
            db.catalog.create_database(dbname, if_not_exists=True)
            for entry in tables:
                schema = Schema.from_dict(entry["schema"])
                info = db.catalog.create_table(
                    dbname, entry["table"], schema, if_not_exists=True
                )
                if info is not None:
                    db.regions.create_region(info.region_ids[0], schema)
                table = pq.read_table(os.path.join(args.input_dir, entry["file"]))
                region = db._region_of(f"{dbname}.{entry['table']}")
                data = {}
                for c in schema:
                    col = table.column(c.name)
                    if c.dtype.is_string_like:
                        data[c.name] = col.to_pylist()
                    elif c.dtype.is_timestamp:
                        data[c.name] = col.to_numpy(zero_copy_only=False).astype("int64")
                    else:
                        data[c.name] = col.to_numpy(zero_copy_only=False)
                if table.num_rows:
                    region.write(data)
                print(f"imported {dbname}.{entry['table']}: {table.num_rows} rows")
    finally:
        db.close()
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="greptime-tpu",
                                description="TPU-native observability database")
    sub = p.add_subparsers(dest="command", required=True)

    ps = sub.add_parser("standalone", help="run the standalone server")
    ps.add_argument("action", choices=["start"])
    ps.add_argument("-c", "--config", help="TOML config file")
    ps.add_argument("--data-home")
    ps.add_argument("--http-addr")
    ps.set_defaults(fn=cmd_standalone)

    pd = sub.add_parser("datanode", help="run a datanode (Flight server)")
    pd.add_argument("action", choices=["start"])
    pd.add_argument("--node-id", type=int, required=True)
    pd.add_argument("--data-home", required=True)
    pd.add_argument("--host", default="127.0.0.1")
    pd.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (printed as JSON on stdout)")
    pd.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    pd.add_argument("--remote-wal-dir", default=None,
                    help="shared-log broker directory (Kafka-style remote "
                         "WAL; node holds no required local WAL state)")
    pd.add_argument("--managed", action="store_true",
                    help="a metasrv owns region leases (enables lease "
                         "self-fencing; without it leader leases self-renew "
                         "on write)")
    pd.set_defaults(fn=cmd_datanode)

    pf = sub.add_parser("frontend",
                        help="run a stateless frontend (HTTP SQL router)")
    pf.add_argument("action", choices=["start"])
    pf.add_argument("--kvstore", default=None,
                    help="shared metadata store: remote://host:port "
                         "(omit = private in-memory catalog)")
    pf.add_argument("--datanode", action="append", default=[],
                    metavar="ID=HOST:PORT",
                    help="register a datanode (repeatable)")
    pf.add_argument("--http-addr", default="127.0.0.1:0",
                    help="bind address; port 0 = pick free "
                         "(printed as JSON on stdout)")
    pf.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    pf.set_defaults(fn=cmd_frontend)

    pk = sub.add_parser("kvstore",
                        help="run a shared metadata store (etcd analog)")
    pk.add_argument("action", choices=["start"])
    pk.add_argument("--path", required=True,
                    help="sqlite database file backing the key-space")
    pk.add_argument("--host", default="127.0.0.1")
    pk.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (printed as JSON on stdout)")
    pk.set_defaults(fn=cmd_kvstore)

    pm = sub.add_parser("meta", help="metadata snapshot / restore")
    pm.add_argument("action", choices=["snapshot", "restore"])
    pm.add_argument("--data-home", required=True)
    pm.add_argument("--file", required=True)
    pm.set_defaults(fn=cmd_meta)

    pg = sub.add_parser("gc", help="delete orphaned storage objects")
    pg.add_argument("--data-home", required=True)
    pg.add_argument("--grace-seconds", type=float, default=3600.0)
    pg.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    pg.set_defaults(fn=cmd_gc)

    pq_ = sub.add_parser("sql", help="SQL shell / one-shot query")
    pq_.add_argument("--data-home", required=True)
    pq_.add_argument("-e", "--execute", help="run one statement and exit")
    pq_.set_defaults(fn=cmd_sql)

    pe = sub.add_parser("export", help="export all data to parquet")
    pe.add_argument("--data-home", required=True)
    pe.add_argument("--output-dir", required=True)
    pe.set_defaults(fn=cmd_export)

    pi = sub.add_parser("import", help="import a previous export")
    pi.add_argument("--data-home", required=True)
    pi.add_argument("--input-dir", required=True)
    pi.set_defaults(fn=cmd_import)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
