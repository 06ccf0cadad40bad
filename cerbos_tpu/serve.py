"""Embedding SDK: run a PDP inside a host application.

Behavioral reference: pkg/cerbos/serve.go (cerbos.Serve with config
file/overrides). ``serve()`` starts the full server and returns a handle;
``embedded()`` returns just the engine-backed service for in-process checks
without any listeners (the ePDP pattern).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from . import bootclock
from .bootstrap import Core, initialize
from .config import Config
from .server.server import Server, ServerConfig
from .util import gctune


@dataclass
class Handle:
    core: Core
    server: Optional[Server] = None

    @property
    def http_addr(self) -> str:
        return f"127.0.0.1:{self.server.http_port}" if self.server else ""

    @property
    def grpc_addr(self) -> str:
        return f"127.0.0.1:{self.server.grpc_port}" if self.server else ""

    def check(self, inputs, params=None):
        return self.core.engine.check(inputs, params=params)

    def close(self) -> None:
        if self.server is not None:
            self.server.stop()
        self.core.close()


def serve(
    config_file: Optional[str] = None,
    overrides: Optional[list[str]] = None,
    use_tpu: Optional[bool] = None,
) -> Handle:
    """Start a full PDP (gRPC + HTTP) and return a handle."""
    # boot to ready from this call on: the host application's own life
    # before it is not this program's boot (bootclock.py)
    bootclock.begin(process_start=False)
    config = Config.load(config_file, overrides=overrides or [])
    core = initialize(config, use_tpu=use_tpu)
    server_conf = config.section("server")
    server = Server(
        core.service,
        ServerConfig(
            http_listen_addr=server_conf.get("httpListenAddr", "127.0.0.1:0"),
            grpc_listen_addr=server_conf.get("grpcListenAddr", "127.0.0.1:0"),
        ),
    )
    # tables are built: pace the collector BEFORE the listeners come up so
    # no in-flight request's transients get frozen (util/gctune)
    gctune.tune_for_serving()
    server.start()
    bootclock.listening()
    return Handle(core=core, server=server)


def embedded(
    policy_dir: Optional[str] = None,
    config_file: Optional[str] = None,
    overrides: Optional[list[str]] = None,
    use_tpu: Optional[bool] = None,
) -> Handle:
    """An in-process PDP with no listeners (embedded/ePDP usage)."""
    ov = list(overrides or [])
    if policy_dir is not None:
        ov.append(f"storage.disk.directory={policy_dir}")
    config = Config.load(config_file, overrides=ov)
    core = initialize(config, use_tpu=use_tpu)
    return Handle(core=core)
