"""Compile-economy observability (docs/OBSERVABILITY.md, "Compile economy").

Covers the PR's acceptance criteria end to end on the CPU jax backend:

- a cold ``check()`` on a fresh evaluator records exactly one compile (with
  nonzero wall time) and one jit-cache miss; a second same-layout batch is
  a pure cache hit with zero new compiles;
- the recompile-storm detector trips once per excursion under a fake clock;
- readiness transitions warming -> ready -> degraded-but-live, and the
  ``/_cerbos/ready`` + gRPC health surfaces gate traffic accordingly;
- the warmup driver pre-compiles one layout per batch size and always
  opens readiness, even on failure;
- ``jitcache.status()`` reports the directory and warm evidence, and
  repeat ``enable()`` calls return the directory instead of None;
- the profiler endpoint is operator-gated, serialized, and bounded.
"""

import json
import os
import urllib.error
import urllib.request

import grpc
import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, Principal, Resource
from cerbos_tpu.engine.flight import recorder as flight_recorder
from cerbos_tpu.engine.readiness import ReadinessState, state as readiness_state
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table
from cerbos_tpu.tpu import TpuEvaluator
from cerbos_tpu.tpu import compilestats, jitcache, profiler
from cerbos_tpu.tpu.compilestats import CompileStats, RecompileStormDetector
from cerbos_tpu.tpu.warmup import WarmupDriver, derive_corpus, synthetic_inputs

POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: album
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: request.resource.attr.owner == request.principal.id || request.resource.attr.public == true
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
"""


def table():
    return build_rule_table(compile_policy_set(list(parse_policies(POLICY))))


def inputs(n: int) -> list:
    return [
        CheckInput(
            principal=Principal(id=f"u{i}", roles=["user"]),
            resource=Resource(kind="album", id=f"a{i}", attr={"owner": f"u{i % 7}"}),
            actions=["view"],
            request_id=f"r{i}",
        )
        for i in range(n)
    ]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# -- acceptance: compile accounting on the real device path -----------------


class TestCompileAccounting:
    def test_cold_check_records_one_compile_then_pure_hits(self):
        """ISSUE acceptance: cold check() = exactly one compile with nonzero
        latency + one miss; second same-layout batch = one hit, no compile.
        The stats are process-global, so every assertion is a delta."""
        ev = TpuEvaluator(table(), use_jax=True, min_device_batch=4)
        before = compilestats.stats().snapshot()

        out = ev.check(inputs(16))
        mid = compilestats.stats().snapshot()
        assert len(out) == 16
        assert mid["compiles"] - before["compiles"] == 1
        assert mid["cache_misses"] - before["cache_misses"] == 1
        assert mid["cache_hits"] - before["cache_hits"] == 0
        assert mid["compile_seconds_total"] > before["compile_seconds_total"]

        out2 = ev.check(inputs(16))
        after = compilestats.stats().snapshot()
        assert len(out2) == 16
        assert after["compiles"] - mid["compiles"] == 0
        assert after["cache_hits"] - mid["cache_hits"] == 1
        assert after["cache_misses"] - mid["cache_misses"] == 0

    def test_distinct_shape_buckets_are_distinct_layouts(self):
        ev = TpuEvaluator(table(), use_jax=True, min_device_batch=4)
        before = compilestats.stats().snapshot()
        ev.check(inputs(16))
        ev.check(inputs(32))
        after = compilestats.stats().snapshot()
        assert after["compiles"] - before["compiles"] == 2
        per = after["per_layout_compiles"]
        assert per.get("B16xBA16", 0) >= 1
        assert per.get("B32xBA32", 0) >= 1

    def test_oracle_path_compiles_nothing(self):
        ev = TpuEvaluator(table(), use_jax=True, min_device_batch=64)
        before = compilestats.stats().snapshot()
        ev.check(inputs(8))  # below min_device_batch: serial oracle
        after = compilestats.stats().snapshot()
        assert after["compiles"] == before["compiles"]
        assert after["cache_misses"] == before["cache_misses"]


# -- recompile-storm detector ------------------------------------------------


class TestStormDetector:
    def test_trips_once_at_threshold(self):
        clk = FakeClock()
        det = RecompileStormDetector(threshold=3, window_s=60.0, clock=clk)
        assert det.observe("L1") is None
        assert det.observe("L2") is None
        assert det.observe("L3") == 3
        assert det.storms == 1

    def test_sustained_storm_is_one_event(self):
        clk = FakeClock()
        det = RecompileStormDetector(threshold=3, window_s=60.0, clock=clk)
        for k in ("L1", "L2", "L3", "L4", "L5", "L6"):
            det.observe(k)
            clk.advance(1.0)
        assert det.storms == 1

    def test_repeat_compiles_of_one_layout_never_storm(self):
        clk = FakeClock()
        det = RecompileStormDetector(threshold=3, window_s=60.0, clock=clk)
        for _ in range(50):
            assert det.observe("L1") is None
            clk.advance(0.5)
        assert det.storms == 0

    def test_rearms_after_window_drains(self):
        clk = FakeClock()
        det = RecompileStormDetector(threshold=3, window_s=60.0, clock=clk)
        for k in ("L1", "L2", "L3"):
            det.observe(k)
        assert det.storms == 1
        clk.advance(120.0)  # old events age out entirely
        assert det.observe("M1") is None  # distinct fell below threshold: re-armed
        assert det.observe("M2") is None
        assert det.observe("M3") == 3
        assert det.storms == 2

    def test_window_prunes_old_events(self):
        clk = FakeClock()
        det = RecompileStormDetector(threshold=3, window_s=10.0, clock=clk)
        det.observe("L1")
        clk.advance(11.0)
        det.observe("L2")
        clk.advance(11.0)
        # never 3 distinct within any 10s window
        assert det.observe("L3") is None
        assert det.storms == 0

    def test_stats_storm_increments_counter_and_flight_event(self):
        clk = FakeClock()
        st = CompileStats(clock=clk, storm_threshold=2, storm_window_s=30.0)

        def storm_events():
            return [
                e for e in flight_recorder().dump()["events"] if e["kind"] == "recompile_storm"
            ]

        n_before = len(storm_events())
        st.record_compile("B16xBA16", 0.1, trace_key=("a",))
        st.record_compile("B32xBA32", 0.1, trace_key=("b",))
        assert st.snapshot()["storms"] == 1
        storms = storm_events()
        assert len(storms) == n_before + 1
        assert storms[-1]["distinct"] == 2
        assert storms[-1]["threshold"] == 2

    def test_configure_rebinds_global_detector_in_place(self):
        det = compilestats.stats().detector
        old_thr, old_win = det.threshold, det.window_s
        try:
            compilestats.configure(storm_threshold=99, storm_window_s=7.0)
            assert compilestats.stats().detector is det
            assert det.threshold == 99
            assert det.window_s == 7.0
        finally:
            compilestats.configure(storm_threshold=old_thr, storm_window_s=old_win)


# -- readiness state machine -------------------------------------------------


class TestReadiness:
    def test_born_ready(self):
        rs = ReadinessState(clock=FakeClock())
        assert rs.status() == "ready"
        assert rs.serving()
        assert rs.snapshot() == {"status": "ready", "compiled_layouts": 0, "expected": 0}

    def test_warming_to_ready(self):
        rs = ReadinessState(clock=FakeClock())
        rs.begin_warmup(expected=2)
        assert rs.status() == "warming"
        assert not rs.serving()
        rs.layout_compiled()
        assert rs.status() == "warming"  # partial warmup still gates
        rs.layout_compiled()
        rs.mark_ready()
        assert rs.status() == "ready"
        assert rs.serving()
        assert rs.snapshot() == {"status": "ready", "compiled_layouts": 2, "expected": 2}

    def test_failed_warmup_still_opens_with_error_recorded(self):
        rs = ReadinessState(clock=FakeClock())
        rs.begin_warmup(expected=3)
        rs.mark_ready(error="size 64: device fell over")
        snap = rs.snapshot()
        assert snap["status"] == "ready"
        assert snap["warmup_error"] == "size 64: device fell over"

    def test_open_breaker_degrades_but_keeps_serving(self):
        rs = ReadinessState(clock=FakeClock())
        rs.bind_health(lambda: "open")
        assert rs.status() == "degraded"
        assert rs.serving()  # degraded-but-live beats a restart loop
        rs.bind_health(lambda: "closed")
        assert rs.status() == "ready"

    def test_breaker_never_masks_warming(self):
        rs = ReadinessState(clock=FakeClock())
        rs.bind_health(lambda: "open")
        rs.begin_warmup(expected=1)
        assert rs.status() == "warming"
        assert not rs.serving()

    def test_broken_health_provider_is_ignored(self):
        rs = ReadinessState(clock=FakeClock())

        def boom():
            raise RuntimeError("no breaker yet")

        rs.bind_health(boom)
        assert rs.status() == "ready"


# -- warmup driver ------------------------------------------------------------


class TestWarmup:
    def test_derive_corpus_from_rule_table(self):
        specs = derive_corpus(table())
        # the admin rule's "*" action is skipped but its role still counts
        assert specs == [{"kind": "album", "actions": ["view"], "roles": ["admin", "user"]}]

    def test_derive_corpus_fallback_when_unreadable(self):
        specs = derive_corpus(object())
        assert specs == [{"kind": "warmup", "actions": ["view"], "roles": ["user"]}]

    def test_synthetic_inputs_shape(self):
        specs = [{"kind": "album", "actions": ["view"], "roles": ["user"]}]
        ins = synthetic_inputs(specs, 5)
        assert len(ins) == 5
        assert {i.resource.kind for i in ins} == {"album"}
        assert ins[0].request_id == "warmup-0"
        assert ins[0].principal.roles == ["user"]

    def test_driver_warms_each_size_and_opens_readiness(self):
        rs = ReadinessState(clock=FakeClock())
        ev = TpuEvaluator(table(), use_jax=False, min_device_batch=4)
        driver = WarmupDriver(ev, batch_sizes=[2, 8], readiness=rs)
        # 2 clamps up to min_device_batch=4: the oracle path compiles nothing
        assert driver.batch_sizes == [4, 8]
        assert driver.expected == 2
        rs.begin_warmup(expected=driver.expected)
        assert not rs.serving()
        summary = driver.run()
        assert summary["layouts"] == 2
        assert summary["inputs"] == 12
        assert summary["errors"] == []
        assert rs.serving()
        assert rs.snapshot() == {"status": "ready", "compiled_layouts": 2, "expected": 2}

    def test_driver_failure_still_marks_ready(self):
        class Exploding:
            min_device_batch = 4
            rule_table = None

            def check(self, inputs):
                raise RuntimeError("device on fire")

        rs = ReadinessState(clock=FakeClock())
        rs.begin_warmup(expected=1)
        driver = WarmupDriver(Exploding(), batch_sizes=[4], corpus=[{"kind": "x"}], readiness=rs)
        summary = driver.run()
        assert summary["layouts"] == 0
        assert len(summary["errors"]) == 1
        snap = rs.snapshot()
        assert snap["status"] == "ready"  # never wedge readiness shut
        assert "device on fire" in snap["warmup_error"]

    def test_background_thread_reports_in(self):
        rs = ReadinessState(clock=FakeClock())
        ev = TpuEvaluator(table(), use_jax=False, min_device_batch=4)
        driver = WarmupDriver(ev, batch_sizes=[4], readiness=rs)
        rs.begin_warmup(expected=driver.expected)
        t = driver.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert rs.snapshot()["status"] == "ready"


# -- jitcache status ----------------------------------------------------------


@pytest.fixture
def jitcache_state():
    saved = (jitcache._enabled, jitcache._external, jitcache._entries_at_enable)
    yield
    jitcache._enabled, jitcache._external, jitcache._entries_at_enable = saved


class TestJitcacheStatus:
    def test_repeat_enable_returns_directory_not_none(self, jitcache_state, tmp_path):
        # the pre-fix behavior returned None on every call after the first,
        # leaving bootstrap logging "cache: None" for a perfectly live cache
        jitcache._enabled = str(tmp_path)
        jitcache._external = False
        assert jitcache.enable() == str(tmp_path)
        assert jitcache.enable() == str(tmp_path)

    def test_entry_count_counts_files(self, jitcache_state, tmp_path):
        jitcache._enabled = str(tmp_path)
        assert jitcache.entry_count() == 0
        for i in range(3):
            (tmp_path / f"entry-{i}").write_bytes(b"x")
        (tmp_path / "subdir").mkdir()  # directories are not cache entries
        assert jitcache.entry_count() == 3

    def test_entry_count_none_when_disabled(self, jitcache_state):
        jitcache._enabled = False
        assert jitcache.entry_count() is None
        assert jitcache.directory() is None

    def test_status_reports_warm_evidence(self, jitcache_state, tmp_path):
        (tmp_path / "warm-entry").write_bytes(b"x")
        jitcache._enabled = str(tmp_path)
        jitcache._external = True
        jitcache._entries_at_enable = 1
        st = jitcache.status()
        assert st["enabled"] is True
        assert st["dir"] == str(tmp_path)
        assert st["external"] is True
        assert st["entries"] == 1
        assert st["warm_at_enable"] is True
        assert isinstance(st["persistent_loads"], int)

    def test_status_when_disabled(self, jitcache_state):
        jitcache._enabled = False
        jitcache._external = False
        jitcache._entries_at_enable = None
        st = jitcache.status()
        assert st["enabled"] is False
        assert st["dir"] is None
        assert st["warm_at_enable"] is False


# jax's cache config is process-global and latched from the environment at
# import, so placement is shown in fresh interpreters
_PLACEMENT_PROBE = """
import json, jax
from cerbos_tpu.tpu import jitcache
d = jitcache.enable()
print(json.dumps({
    "dir": d,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "external": jitcache.status()["external"],
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
    "backend_initialized": bool(jax._src.xla_bridge._backends),
}))
"""


def _placement(env_overrides: dict) -> dict:
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "CERBOS_TPU_XLA_CACHE_DIR")
    }
    env.update(env_overrides, PYTHONPATH=repo)
    p = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE], capture_output=True, text=True, timeout=120, env=env
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


class TestJitcachePlacement:
    def test_env_set_uses_that_directory_and_sets_no_other(self, tmp_path):
        got = _placement({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "from-env")})
        assert got["dir"] == got["jax_dir"] == str(tmp_path / "from-env")
        assert got["external"] is True
        # the persist-everything thresholds apply on this route too:
        # otherwise a sub-second compile is never written, and the next
        # compile's unchanged entry count reads as a persistent load
        assert got["min_secs"] == 0.0 and got["min_bytes"] == 0
        assert got["backend_initialized"] is False

    def test_unset_uses_the_checkout(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        got = _placement({})
        assert got["dir"] == got["jax_dir"] == os.path.join(repo, ".xla_cache")
        assert got["external"] is False
        assert got["min_secs"] == 0.0 and got["min_bytes"] == 0
        assert got["backend_initialized"] is False

    def test_old_private_variable_is_gone(self, tmp_path):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        got = _placement({"CERBOS_TPU_XLA_CACHE_DIR": str(tmp_path / "ignored")})
        assert got["dir"] == os.path.join(repo, ".xla_cache")
        assert not (tmp_path / "ignored").exists()


class TestDeviceOwnership:
    def test_open_device_reports_what_jax_reports(self):
        import jax

        dev = jitcache.open_device()
        assert dev["platform"] == jax.devices()[0].platform == "cpu"
        assert dev["device_kind"] == jax.devices()[0].device_kind
        assert dev["count"] == len(jax.devices())
        assert dev["pid"] == os.getpid()
        assert jitcache.status()["device"] == dev

    def test_a_forked_child_does_not_inherit_ownership(self, monkeypatch):
        jitcache.open_device()
        # what a front end forked from a device-opening process would see
        monkeypatch.setattr(jitcache.os, "getpid", lambda: -1)
        assert jitcache.device() is None
        assert jitcache.status()["device"] is None
        assert jitcache.device_memory() == []

    def test_memory_gauges_only_move_in_the_owner(self, monkeypatch):
        cs = compilestats.stats()
        monkeypatch.setattr(
            jitcache, "device_memory",
            lambda: [
                {"id": 0, "bytes_in_use": 5, "peak_bytes_in_use": 7, "bytes_limit": 100},
                {"id": 1, "bytes_in_use": 1, "peak_bytes_in_use": 2, "bytes_limit": 100},
            ],
        )
        cs.refresh_device_memory()
        assert (cs.m_mem_in_use.value, cs.m_mem_peak.value, cs.m_mem_limit.value) == (6.0, 9.0, 200.0)
        monkeypatch.setattr(jitcache, "device_memory", lambda: [])  # not the owner
        cs.refresh_device_memory()
        assert cs.m_mem_in_use.value == 6.0  # untouched, and no backend was asked

    def test_backend_failure_is_a_boot_failure(self, monkeypatch):
        import jax

        def boom():
            raise RuntimeError("TPU is already in use by process 4242")

        monkeypatch.setattr(jitcache, "_device", None)
        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(jitcache.DeviceInitError, match="already in use by process 4242"):
            jitcache.open_device()


# -- profiler -----------------------------------------------------------------


@pytest.fixture
def profiler_config(tmp_path):
    yield tmp_path
    profiler.configure()  # back to disabled defaults


class TestProfiler:
    def test_disabled_by_default(self, profiler_config):
        profiler.configure()
        assert not profiler.enabled()
        with pytest.raises(profiler.ProfilerDisabled):
            profiler.capture(1)

    def test_bad_duration_rejected(self, profiler_config):
        profiler.configure(enabled=True, dir=str(profiler_config))
        with pytest.raises(ValueError):
            profiler.capture(0)
        with pytest.raises(ValueError):
            profiler.capture(-3)

    def test_capture_clamps_and_writes_artifact_dir(self, profiler_config, monkeypatch):
        profiler.configure(enabled=True, dir=str(profiler_config), max_seconds=0.25)
        captured = {}

        def fake_trace(path, seconds):
            captured["seconds"] = seconds
            os.makedirs(path, exist_ok=True)

        monkeypatch.setattr(profiler, "_run_trace", fake_trace)
        artifact = profiler.capture(999)
        assert captured["seconds"] == 0.25  # clamped to maxSeconds
        assert artifact["seconds"] == 0.25
        assert os.path.isdir(artifact["path"])
        assert os.path.dirname(artifact["path"]) == str(profiler_config)

    def test_artifact_dir_is_bounded(self, profiler_config, monkeypatch):
        profiler.configure(enabled=True, dir=str(profiler_config), max_artifacts=2)
        monkeypatch.setattr(
            profiler, "_run_trace", lambda path, seconds: os.makedirs(path, exist_ok=True)
        )
        paths = [profiler.capture(0.01)["path"] for _ in range(5)]
        remaining = sorted(os.listdir(profiler_config))
        assert len(remaining) == 2
        # the newest captures survive the prune
        assert remaining == sorted(os.path.basename(p) for p in paths[-2:])

    def test_one_capture_at_a_time(self, profiler_config):
        profiler.configure(enabled=True, dir=str(profiler_config))
        with profiler._lock:
            profiler._active = True
        try:
            with pytest.raises(profiler.ProfilerBusy):
                profiler.capture(0.01)
        finally:
            with profiler._lock:
                profiler._active = False


# -- server surfaces: /_cerbos/ready, gRPC health, flight header, profile ----


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    from cerbos_tpu.bootstrap import initialize
    from cerbos_tpu.config import Config
    from cerbos_tpu.server.server import Server, ServerConfig

    policy_dir = tmp_path_factory.mktemp("policies")
    (policy_dir / "album.yaml").write_text(POLICY)
    config = Config.load(
        overrides=[
            f"storage.disk.directory={policy_dir}",
            "server.httpListenAddr=127.0.0.1:0",
            "server.grpcListenAddr=127.0.0.1:0",
            # readiness surfaces don't need a device; the oracle path keeps
            # this module independent of jax backend startup
            "engine.tpu.enabled=false",
        ]
    )
    core = initialize(config, use_tpu=False)
    srv = Server(
        core.service,
        ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0"),
    )
    srv.start()
    yield srv
    srv.stop()
    core.close()


def http_get_status(server, path):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{server.http_port}{path}") as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def grpc_health_check(server):
    with grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}") as ch:
        stub = ch.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        return stub(b"", timeout=10)


@pytest.fixture
def restored_readiness():
    rs = readiness_state()
    yield rs
    rs.mark_ready()
    rs.bind_health(None)


class TestServerReadiness:
    def test_ready_after_bootstrap_without_warmup(self, server):
        status, body, _ = http_get_status(server, "/_cerbos/ready")
        assert status == 200
        assert body["status"] == "ready"

    def test_liveness_stays_green_while_warming(self, server, restored_readiness):
        restored_readiness.begin_warmup(expected=2)
        status, body, _ = http_get_status(server, "/_cerbos/health")
        assert status == 200  # liveness never gates on warmup
        status, body, _ = http_get_status(server, "/_cerbos/ready")
        assert status == 503
        # snapshot may carry extra fields (e.g. policy_epoch from the rollout
        # controller) -- assert the warmup-shaped subset
        assert body["status"] == "warming"
        assert body["compiled_layouts"] == 0
        assert body["expected"] == 2

    def test_ready_flips_when_warmup_completes(self, server, restored_readiness):
        restored_readiness.begin_warmup(expected=2)
        assert http_get_status(server, "/_cerbos/ready")[0] == 503
        assert grpc_health_check(server) == b"\x08\x02"  # NOT_SERVING
        restored_readiness.layout_compiled()
        restored_readiness.layout_compiled()
        restored_readiness.mark_ready()
        status, body, _ = http_get_status(server, "/_cerbos/ready")
        assert status == 200
        assert body["status"] == "ready"
        assert body["compiled_layouts"] == 2
        assert body["expected"] == 2
        assert grpc_health_check(server) == b"\x08\x01"  # SERVING

    def test_degraded_is_still_serving(self, server, restored_readiness):
        restored_readiness.bind_health(lambda: "open")
        status, body, _ = http_get_status(server, "/_cerbos/ready")
        assert status == 200
        assert body["status"] == "degraded"
        assert grpc_health_check(server) == b"\x08\x01"  # SERVING

    def test_readiness_metrics_exported(self, server):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.http_port}/_cerbos/metrics"
        ) as resp:
            text = resp.read().decode()
        assert "cerbos_tpu_readiness_state" in text
        assert "cerbos_tpu_warmup_expected_layouts" in text

    def test_flight_header_carries_jitcache_status(self, server):
        status, _, headers = http_get_status(server, "/_cerbos/debug/flight")
        assert status == 200
        st = json.loads(headers["X-Cerbos-Jitcache"])
        assert set(st) >= {"enabled", "dir", "entries", "warm_at_enable", "persistent_loads"}

    def test_profile_endpoint_is_operator_gated(self, server):
        profiler.configure()  # disabled
        status, body, _ = http_get_status(server, "/_cerbos/debug/profile?seconds=1")
        assert status == 403
        assert "disabled" in body["error"]

    def test_profile_endpoint_captures_when_enabled(self, server, tmp_path, monkeypatch):
        profiler.configure(enabled=True, dir=str(tmp_path), max_seconds=0.05)
        monkeypatch.setattr(
            profiler, "_run_trace", lambda path, seconds: os.makedirs(path, exist_ok=True)
        )
        try:
            status, body, _ = http_get_status(server, "/_cerbos/debug/profile?seconds=9")
            assert status == 200
            assert body["seconds"] == 0.05
            assert os.path.isdir(body["path"])
            status, body, _ = http_get_status(server, "/_cerbos/debug/profile?seconds=bogus")
            assert status == 400
        finally:
            profiler.configure()
