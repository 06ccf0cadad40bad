"""Boot to ready, by phase (``cerbos_tpu/bootclock.py``, PR 38): one cursor from
the start of the process to the first instant it listens and answers SERVING.
The phases are set once and add up to ``ready``; a policy push does not touch
them; the serve command reads the kernel's process start; in a pool the device
owner alone publishes them, the phases up to ``lower`` being the parent's.
"""

import re
import subprocess
import sys
import time

import pytest

from cerbos_tpu import bootclock
from cerbos_tpu import observability as obs

from test_workers import POLICY, REPO, _boot_single, _check, _get, frontdoor  # noqa: F401  (frontdoor: the pool's fixture)

BOOT = "cerbos_tpu_boot_seconds"
SERIES = re.compile(r'^cerbos_tpu_boot_seconds\{(?:worker="([^"]+)",)?phase="([^"]+)"\} (\S+)$', re.M)

POLICY_EXTRA = POLICY.replace("resource: album", "resource: track")


@pytest.fixture()
def fresh():
    """No clock and nothing published, as in a process that has not booted."""
    saved = (bootclock._clock, bootclock.published)
    bootclock._clock, bootclock.published = None, None
    yield
    bootclock._clock, bootclock.published = saved


def gauge() -> dict:
    return {phase: child.value for phase, child in obs.metrics().gauge_vec(BOOT, label="phase")._children.items()}


def test_a_mark_books_the_phase_that_ended_and_the_phases_add_up_to_ready(fresh):
    bootclock.mark(bootclock.LOAD)  # no clock: nothing
    bootclock.listening()
    assert bootclock.published is None
    bootclock.begin(process_start=False)
    time.sleep(0.02)
    bootclock.mark(bootclock.LOAD)
    time.sleep(0.01)
    bootclock.mark(bootclock.OTHER)
    time.sleep(0.03)
    bootclock.mark(bootclock.LOWER)
    bootclock.mark(bootclock.LOAD)  # a phase that comes twice (the store, then the policies) adds up
    assert bootclock.published is None  # not listening yet
    time.sleep(0.01)
    bootclock.listening()
    got = dict(bootclock.published)
    assert set(got) == set(bootclock.PHASES) | {bootclock.READY}
    assert got["import"] == 0.0 and got["compile"] == got["table"] == got["device"] == 0.0
    assert 0.02 <= got["load"] < 0.2 and 0.01 <= got["other"] < 0.2 and 0.03 <= got["lower"] < 0.2
    assert 0.01 <= got["listen"] < 0.2
    assert sum(got[p] for p in bootclock.PHASES) == pytest.approx(got["ready"], abs=1e-9)
    assert gauge() == got
    # set once: nothing that comes later moves a series, and a second serve() in the process restarts nothing
    bootclock.mark(bootclock.TABLE)
    bootclock.begin(process_start=False)
    bootclock.mark(bootclock.COMPILE)
    bootclock.listening()
    bootclock.readiness_changed()
    assert bootclock.published == got and gauge() == got


def test_ready_waits_for_readiness_to_answer_serving(fresh):
    from cerbos_tpu.engine import readiness

    state = readiness.state()
    bootclock.begin(process_start=False)
    state.begin_warmup(expected=1)
    try:
        bootclock.listening()  # bound, but a probe would still read NOT_SERVING
        assert bootclock.published is None
        time.sleep(0.02)
    finally:
        state.mark_ready()  # the warm-up's end is where boot ends
    assert bootclock.published is not None and bootclock.published["listen"] >= 0.02


def test_a_front_end_drops_the_clock_it_inherited(fresh):
    bootclock.begin(process_start=False)
    bootclock.mark(bootclock.LOWER)
    bootclock.abandon()  # what initialize(role="frontend") does first
    bootclock.mark(bootclock.LOAD)
    bootclock.listening()
    assert bootclock.published is None


def test_process_age_is_the_kernels(tmp_path):
    code = (
        "import time\n"
        "from cerbos_tpu import bootclock\n"
        "print(bootclock.process_age_s(), time.time())\n"
    )
    t0 = time.time()
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    age, read_at = map(float, out.stdout.split())
    # the interpreter's own start lies between the fork and the reading; the kernel's clock ticks in 10 ms
    assert 0.0 < age <= read_at - t0 + 0.02


def test_serve_publishes_once_and_a_policy_push_does_not_touch_it(fresh, tmp_path):
    from cerbos_tpu.engine import types as T
    from cerbos_tpu.serve import serve

    (tmp_path / "album.yaml").write_text(POLICY)
    handle = serve(
        overrides=[
            f"storage.disk.directory={tmp_path}",
            "engine.tpu.enabled=false",
            "server.httpListenAddr=127.0.0.1:0",
            "server.grpcListenAddr=127.0.0.1:0",
        ]
    )
    try:
        got = dict(bootclock.published)
        assert got["import"] == 0.0  # an embedding application's own life is not this program's boot
        assert got["load"] > 0 and got["compile"] > 0 and got["table"] > 0 and got["listen"] > 0
        assert got["lower"] == got["device"] == 0.0  # no evaluator was asked for
        assert sum(got[p] for p in bootclock.PHASES) == pytest.approx(got["ready"], abs=1e-9)
        assert gauge() == got

        def plays() -> str:
            inp = T.CheckInput(
                request_id="t", principal=T.Principal(id="bob", roles=["admin"]),
                resource=T.Resource(kind="track", id="t1"), actions=["play"],
            )
            return handle.check([inp])[0].actions["play"].effect

        assert plays() == T.EFFECT_DENY  # no policy for the kind yet
        (tmp_path / "track.yaml").write_text(POLICY_EXTRA)
        handle.core.store.reload()  # the push: a rebuild through the same _build that booked the boot
        deadline = time.time() + 20
        while plays() != T.EFFECT_ALLOW:
            assert time.time() < deadline, "the push never took effect"
            time.sleep(0.05)
        assert bootclock.published == got and gauge() == got
    finally:
        handle.close()


def test_the_serve_command_reads_its_process_start_and_publishes_every_phase(tmp_path):
    t0 = time.time()
    proc = _boot_single(tmp_path)
    try:
        line = proc.stdout.readline()
        assert line.startswith("cerbos-tpu serving:"), line + proc.stderr.read()
        booted_s = time.time() - t0
        port = int(re.search(r"http=(\d+)", line).group(1))
        status, body = _get(port, "/_cerbos/metrics")
        assert status == 200
        got = {phase: float(v) for worker, phase, v in SERIES.findall(body.decode()) if not worker}
        assert set(got) == set(bootclock.PHASES) | {bootclock.READY}
        assert sum(got[p] for p in bootclock.PHASES) == pytest.approx(got["ready"], abs=1e-6)
        # from the kernel's start of the process, not from the command's entry: the imports are in it
        assert got["import"] > 0.05 and got["lower"] > 0 and got["load"] > 0
        assert got["ready"] <= booted_s + 0.02
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_in_a_pool_the_device_owner_alone_publishes_one_value_a_phase(frontdoor):  # noqa: F811
    proc, port = frontdoor
    _check(port)
    found = []
    for _ in range(8):  # whichever front end the kernel hands the scrape to, it holds the whole pool
        status, body = _get(port, "/_cerbos/metrics")
        assert status == 200
        text = body.decode()
        assert 'worker="fe1"' in text and 'worker="fe2"' in text
        found.append(SERIES.findall(text))
    for series in found:
        assert {worker for worker, _, _ in series} == {"batcher"}
        assert sorted(phase for _, phase, _ in series) == sorted(bootclock.PHASES + (bootclock.READY,))
        assert series == found[0]  # set once
    got = {phase: float(v) for _, phase, v in found[0]}
    assert sum(got[p] for p in bootclock.PHASES) == pytest.approx(got["ready"], abs=1e-6)
    # process start is the pool parent's, which built and lowered before the fork
    assert got["import"] > 0.05 and got["lower"] > 0 and got["table"] > 0
