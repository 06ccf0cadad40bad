"""Command-line interface.

Behavioral reference: cmd/cerbos (server / compile subcommands; compile exit
codes: 3 = lint failure, 4 = test failure, main.go:23-25).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def _parse_duration_s(v) -> int:
    """Go-style duration ("10s", "1m30s", "1h") or bare seconds → seconds."""
    if isinstance(v, (int, float)):
        return int(v)
    import re as _re

    total = 0.0
    for num, unit in _re.findall(r"(\d+(?:\.\d+)?)(ms|s|m|h)", str(v)):
        total += float(num) * {"ms": 0.001, "s": 1, "m": 60, "h": 3600}[unit]
    if total == 0 and str(v).strip():
        try:
            total = float(str(v))
        except ValueError:
            pass
    return int(total)


def _build_server(core, config, http_addr=None, grpc_addr=None, reuse_port=False, worker_label=""):
    """One construction site for the full server wiring (admin, authzen,
    playground, TLS, CORS) shared by single-process serve and worker pools."""
    from .server.server import Server, ServerConfig

    server_conf = config.section("server")
    extra = []
    from .server.authzen import AuthZenService

    extra.append(AuthZenService(core.service))
    if server_conf.get("playgroundEnabled", False):
        from .server.playground import PlaygroundService

        extra.append(PlaygroundService())

    tls = server_conf.get("tls", {}) or {}
    cors_conf = server_conf.get("cors") or {}
    return Server(
        core.service,
        ServerConfig(
            http_listen_addr=http_addr or server_conf.get("httpListenAddr", "0.0.0.0:3592"),
            grpc_listen_addr=grpc_addr or server_conf.get("grpcListenAddr", "0.0.0.0:3593"),
            tls_cert=tls.get("cert", ""),
            tls_key=tls.get("key", ""),
            tls_watch_interval_s=float(tls.get("watchInterval", 5.0)),
            cors_disabled=bool(cors_conf.get("disabled", False)),
            cors_allowed_origins=tuple(cors_conf.get("allowedOrigins", []) or []),
            cors_allowed_headers=tuple(cors_conf.get("allowedHeaders", []) or []),
            cors_max_age_s=_parse_duration_s(cors_conf.get("maxAge", 0)),
            max_workers=int(server_conf.get("maxWorkers", 16)),
            grpc_async=bool(server_conf.get("grpcAsync", False)),
            reuse_port=reuse_port,
            # inline dispatch is only safe without the cross-request batcher
            # (which needs concurrent requests in flight to fill batches)
            direct_dispatch=core.batcher is None,
            worker_label=worker_label,
        ),
        admin_service=_admin(core, server_conf),
        extra_services=extra,
    )


def _device_fields() -> str:
    """``key=value`` description of the device THIS process opened at boot
    (bootstrap.initialize -> jitcache.open_device) and its compile cache;
    empty in a process that owns no device (front end, tpu disabled)."""
    from .tpu import jitcache

    st = jitcache.status()
    dev = st["device"]
    if dev is None:
        return ""
    return (
        f"platform={dev['platform']} device_kind={json.dumps(dev['device_kind'])} "
        f"devices={dev['count']} xla_cache={st['dir'] if st['enabled'] else 'off'}"
    )


def _native_field() -> str:
    from . import native

    return f"native={'true' if native.get() is not None else 'false'}"


def cmd_server(args: argparse.Namespace) -> int:
    from . import bootclock

    bootclock.begin()  # boot to ready, by phase: from the process's start, which this call reads
    from .bootstrap import initialize
    from .config import Config

    from .observability import (
        close_exporter,
        close_metrics_exporter,
        init_otlp_from_env,
        init_otlp_metrics_from_env,
        metrics_exporter,
    )

    bootclock.mark(bootclock.IMPORT)  # the command's own imports (gRPC, aiohttp, numpy) are most of it
    config = Config.load(args.config, overrides=args.set or [])
    server_conf = config.section("server")

    def wire_metrics(core) -> None:
        mx = metrics_exporter()
        if mx is not None:
            mx.add_source(core.service.metrics.snapshot)

    def post_init_pool(core) -> None:
        # pool children: the parent announced the ports before forking and
        # holds no device; each device-owning child says what it opened
        wire_metrics(core)
        fields = _device_fields()
        if fields:
            print(f"cerbos-tpu device: pid={os.getpid()} {fields}", flush=True)

    n_frontends = int(getattr(args, "frontends", 0) or server_conf.get("frontends", 0) or 0)
    if n_frontends > 0:
        # multi-process front door: N GIL-light request processes feeding ONE
        # shared batcher/evaluator process over the unix ticket queue — the
        # device topology (one process per chip). --workers multiplies full
        # PDPs instead: a CPU topology, its 2nd worker cannot open a held chip.
        from .server.workers import run_frontdoor_pool

        def announce_fd(http_addr: str, grpc_addr: str) -> None:
            http_port = http_addr.rpartition(":")[2]
            grpc_port = grpc_addr.rpartition(":")[2]
            print(
                f"cerbos-tpu serving: http={http_port} grpc={grpc_port} "
                f"frontends={n_frontends} batcher=1 {_native_field()}",
                flush=True,
            )

        def post_fork_fd() -> None:
            init_otlp_from_env()
            init_otlp_metrics_from_env()

        def pre_exit_fd() -> None:
            close_exporter()
            close_metrics_exporter()

        return run_frontdoor_pool(
            config,
            n_frontends,
            _build_server,
            announce=announce_fd,
            post_fork=post_fork_fd,
            post_init=post_init_pool,
            pre_exit=pre_exit_fd,
        )

    n_workers = int(getattr(args, "workers", 0) or server_conf.get("workers", 1) or 1)
    if n_workers > 1:
        # fork-after-load worker pool (engine.go:74-144 analogue): the pool
        # prints the serving line itself once ports are resolved. The OTLP
        # exporter threads must start POST-fork (each worker exports its own
        # spans/metrics; a pre-fork thread would not exist in the children)
        from .server.workers import run_server_pool

        def announce(http_addr: str, grpc_addr: str) -> None:
            http_port = http_addr.rpartition(":")[2]
            grpc_port = grpc_addr.rpartition(":")[2]
            print(
                f"cerbos-tpu serving: http={http_port} grpc={grpc_port} workers={n_workers} "
                f"{_native_field()}",
                flush=True,
            )

        def post_fork() -> None:
            init_otlp_from_env()
            init_otlp_metrics_from_env()

        def pre_exit() -> None:
            close_exporter()
            close_metrics_exporter()

        return run_server_pool(
            config,
            n_workers,
            _build_server,
            announce=announce,
            post_fork=post_fork,
            post_init=post_init_pool,
            pre_exit=pre_exit,
        )

    # SIGTERM is how supervisors stop a replica: leave through the same drain
    # as Ctrl-C (listeners, batcher, audit log, the device) and exit 0,
    # instead of dying mid-flight with the chip held. As in the pool roles
    # the handler only sets a flag and is installed BEFORE the slow init, so
    # a signal during boot still drains. It ignores every later SIGTERM: a
    # repeated one must not interrupt the drain, nor kill the interpreter
    # while it finalizes (which puts a Python handler back to the default).
    stop = threading.Event()

    def on_term(signum, frame):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        stop.set()

    signal.signal(signal.SIGTERM, on_term)
    init_otlp_from_env()  # OTEL_EXPORTER_OTLP_ENDPOINT et al (ref: otel.go)
    init_otlp_metrics_from_env()
    core = initialize(config)
    wire_metrics(core)
    server = _build_server(core, config)
    try:
        if not stop.is_set():
            server.start()
            bootclock.listening()
            print(
                f"cerbos-tpu serving: http={server.http_port} grpc={server.grpc_port} "
                f"{_device_fields() or 'platform=none'} {_native_field()}",
                flush=True,
            )
        while not stop.wait(0.2):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        core.close()
        close_exporter()  # drain buffered OTLP spans
        close_metrics_exporter()
    return 0


def _admin(core, server_conf):
    admin_conf = server_conf.get("adminAPI", {})
    if not admin_conf.get("enabled", False):
        return None
    from .server.admin import AdminService

    creds = admin_conf.get("adminCredentials", {})
    return AdminService(
        core,
        username=creds.get("username", "cerbos"),
        password_hash=creds.get("passwordHash", ""),
        password=creds.get("password", "cerbosAdmin"),
    )


def cmd_compile(args: argparse.Namespace) -> int:
    from .compile import CompileError, compile_policy_set
    from .storage.disk import BuildError, DiskStore

    try:
        store = DiskStore(args.dir)
        policies = store.get_all()

        def schema_check(ref: str):
            # compile-time schema-ref validation over the same store
            # (ref: cerbos compile behaviour, internal/compile schema checks)
            schema_id = ref[len("cerbos:///"):] if ref.startswith("cerbos:///") else ref
            raw = store.get_schema(schema_id)
            if raw is None:
                return ("missing", f"_schemas/{schema_id}")
            try:
                import jsonschema as _js

                _js.Draft202012Validator.check_schema(json.loads(raw))
            except Exception as e:  # noqa: BLE001
                return ("invalid", f"jsonschema {ref} compilation failed: {e}")
            return None

        compile_policy_set(policies, schema_check=schema_check)
    except (BuildError, CompileError) as e:
        errors = getattr(e, "errors", [str(e)])
        if args.output == "json":
            details = getattr(e, "details", None)
            if details:
                # structured position/path details (the reference's
                # CompileErrors proto shape), not just rendered strings
                print(json.dumps({"errors": [d.to_dict() for d in details]}, indent=2))
            else:
                print(json.dumps({"errors": errors}, indent=2))
        else:
            for err in errors:
                print(f"ERROR: {err}", file=sys.stderr)
        return 3

    print(f"Compiled {len(policies)} policies OK", file=sys.stderr)

    if args.skip_tests:
        return 0

    from .verify.runner import discover_and_run

    results = discover_and_run(args.dir, run_filter=args.run, verbose=getattr(args, "verbose", False))
    if results is None:
        return 0  # no test suites found
    if args.output == "json":
        print(json.dumps(results.to_json(), indent=2))
    elif args.output == "junit":
        print(results.to_junit(verbose=getattr(args, "verbose", False)))
    else:
        print(results.summary())
    return 4 if results.failed else 0


def cmd_compilestore(args: argparse.Namespace) -> int:
    """Build a pre-compiled policy bundle (ref: cerbos compilestore)."""
    from .bundle import BundleError, build_bundle
    from .compile import CompileError, compile_policy_set
    from .storage.disk import BuildError, DiskStore

    try:
        store = DiskStore(args.dir)
        compile_policy_set(store.get_all())  # lint before bundling
        key = None
        if getattr(args, "sign_key", None):
            with open(args.sign_key, "rb") as kf:
                key = kf.read().strip()
        manifest = build_bundle(store, args.output, signing_key=key)
    except (BuildError, CompileError, BundleError) as e:
        for err in getattr(e, "errors", [str(e)]):
            print(f"ERROR: {err}", file=sys.stderr)
        return 3
    print(
        f"wrote {args.output}: {manifest.policy_count} policies, "
        f"{manifest.schema_count} schemas, checksum {manifest.checksum[:16]}…",
        file=sys.stderr,
    )
    # build-time static analysis summary: the same verdicts the PDP exports
    # as cerbos_tpu_policy_analysis_total after swapping this bundle in
    try:
        from .tpu.analyze import analyze_policies

        print(analyze_policies(store.get_all()).summary_line(), file=sys.stderr)
    except Exception as e:  # analysis is advisory; never fail the build
        print(f"policy analysis skipped: {e}", file=sys.stderr)
    return 0


def cmd_healthcheck(args: argparse.Namespace) -> int:
    """Probe a running PDP (ref: cerbos healthcheck, used in containers)."""
    import urllib.request

    url = f"http://{args.host_port}/_cerbos/health"
    try:
        with urllib.request.urlopen(url, timeout=args.timeout) as resp:
            body = json.loads(resp.read())
        if body.get("status") == "SERVING":
            return 0
        print(f"unhealthy: {body}", file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001
        print(f"unreachable: {e}", file=sys.stderr)
        return 1


def cmd_run(args: argparse.Namespace) -> int:
    """Start the PDP, then run a child command with CERBOS_* env injected
    (ref: cerbos run)."""
    import subprocess

    from .bootstrap import initialize
    from .config import Config
    from .server.server import Server, ServerConfig

    config = Config.load(args.config, overrides=(args.set or []) + [
        "server.httpListenAddr=127.0.0.1:0",
        "server.grpcListenAddr=127.0.0.1:0",
    ])
    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print("error: no command given (usage: cerbos-tpu run -- <command> [args...])", file=sys.stderr)
        return 2
    core = initialize(config)
    server = Server(core.service, ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"))
    server.start()
    env = dict(os.environ)
    env["CERBOS_HTTP"] = f"127.0.0.1:{server.http_port}"
    env["CERBOS_GRPC"] = f"127.0.0.1:{server.grpc_port}"
    try:
        return subprocess.call(cmd, env=env)
    finally:
        server.stop()
        core.close()


def cmd_repl(args: argparse.Namespace) -> int:
    from .repl import run_repl

    return run_repl()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="cerbos-tpu", description="TPU-native Cerbos-compatible PDP")
    sub = parser.add_subparsers(dest="command", required=True)

    p_server = sub.add_parser("server", help="start the PDP server")
    p_server.add_argument("--config", help="path to config YAML")
    p_server.add_argument("--set", action="append", help="config overrides (key=value)")
    p_server.add_argument(
        "--workers",
        type=int,
        default=0,
        help="serving worker processes (SO_REUSEPORT pool; default: server.workers config or 1)",
    )
    p_server.add_argument(
        "--frontends",
        type=int,
        default=0,
        help="front-end processes feeding one shared device batcher over a unix "
        "ticket queue (default: server.frontends config or 0 = disabled)",
    )
    p_server.set_defaults(fn=cmd_server)

    p_compile = sub.add_parser("compile", help="compile policies and run policy tests")
    p_compile.add_argument("dir", help="policy directory")
    p_compile.add_argument("--output", choices=("tree", "json", "junit"), default="tree")
    p_compile.add_argument("--run", help="run only tests matching this regex", default="")
    p_compile.add_argument("--verbose", action="store_true", help="include evaluation traces for failed tests")
    p_compile.add_argument("--skip-tests", action="store_true")
    p_compile.set_defaults(fn=cmd_compile)

    p_cs = sub.add_parser("compilestore", help="build a pre-compiled policy bundle")
    p_cs.add_argument("dir", help="policy directory")
    p_cs.add_argument("--output", "-o", default="bundle.crbp")
    p_cs.add_argument("--sign-key", help="HMAC key file recording a detached IR signature (supply-chain authenticity; the IR decode itself is safe for untrusted bundles)")
    p_cs.set_defaults(fn=cmd_compilestore)

    p_hc = sub.add_parser("healthcheck", help="probe a running PDP")
    p_hc.add_argument("--host-port", default="127.0.0.1:3592")
    p_hc.add_argument("--timeout", type=float, default=3.0)
    p_hc.set_defaults(fn=cmd_healthcheck)

    p_run = sub.add_parser("run", help="start a PDP and run a command against it")
    p_run.add_argument("--config", help="path to config YAML")
    p_run.add_argument("--set", action="append", help="config overrides")
    p_run.add_argument("cmd", nargs=argparse.REMAINDER, help="command to run")
    p_run.set_defaults(fn=cmd_run)

    p_repl = sub.add_parser("repl", help="interactive CEL condition REPL")
    p_repl.set_defaults(fn=cmd_repl)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
