"""chip_smoke.py's verdict logic, on the CPU.

The smoke itself only passes on a TPU. What can be shown here: it fails with
a clear message when the server reports platform ``cpu`` (the full script, in
a subprocess), and its metric parser and each assertion hold or fail as they
should on recorded ``/_cerbos/metrics`` text — one passing pair of scrapes,
and one mutation per failure (no device decisions, a fallback reason, a
breaker trip, a compile in the checked pass, a parity divergence, no parity
check, no compile at all, no device memory, a call id twice or malformed).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# recorded from a CPU-backend debug run of the script at 10 mods (single
# topology, final scrape), trimmed to the families the checks read; the
# memory gauges carry a v5e-sized value where the CPU backend reported 0
RECORDED = """\
# TYPE cerbos_tpu_batcher_batches_total counter
cerbos_tpu_batcher_batches_total 329
# TYPE cerbos_tpu_batcher_oracle_fallbacks_total counter
cerbos_tpu_batcher_oracle_fallbacks_total 0
# TYPE cerbos_tpu_breaker_trips_total counter
cerbos_tpu_breaker_trips_total 0
# TYPE cerbos_tpu_decision_source_total counter
cerbos_tpu_decision_source_total{source="device"} 11201
cerbos_tpu_decision_source_total{source="oracle"} 2131
# TYPE cerbos_tpu_device_memory_bytes_in_use gauge
cerbos_tpu_device_memory_bytes_in_use 1.8432e+06
# TYPE cerbos_tpu_device_memory_peak_bytes_in_use gauge
cerbos_tpu_device_memory_peak_bytes_in_use 2.4576e+06
# TYPE cerbos_tpu_parity_checks_total counter
cerbos_tpu_parity_checks_total{shard="0"} 4
# TYPE cerbos_tpu_parity_divergence_total counter
cerbos_tpu_parity_divergence_total 0
# TYPE cerbos_tpu_xla_compiles_total counter
cerbos_tpu_xla_compiles_total{source="fresh"} 1
cerbos_tpu_xla_compiles_total{source="persistent"} 13
# TYPE cerbos_tpu_xla_compile_seconds histogram
cerbos_tpu_xla_compile_seconds_sum 5.321
cerbos_tpu_xla_compile_seconds_count 14
# TYPE cerbos_tpu_batch_stage_seconds histogram
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="0.0001"} 203
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="0.0005"} 203
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="0.001"} 214
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="0.002"} 272
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="0.005"} 326
cerbos_tpu_batch_stage_seconds_bucket{stage="pack",shard="0",le="+Inf"} 329
cerbos_tpu_batch_stage_seconds_sum{stage="pack",shard="0"} 0.31
cerbos_tpu_batch_stage_seconds_count{stage="pack",shard="0"} 329
"""

# the same families as a --frontends 2 scrape carries them: every series
# relabelled by process, the device path's under worker="batcher"
RECORDED_POOL = """\
cerbos_tpu_batcher_oracle_fallbacks_total{worker="fe1"} 0
cerbos_tpu_xla_compiles_total{worker="fe1"} 0
cerbos_tpu_device_memory_bytes_in_use{worker="fe1"} 0
cerbos_tpu_batcher_oracle_fallbacks_total{worker="batcher"} 0
cerbos_tpu_breaker_trips_total{worker="batcher"} 0
cerbos_tpu_decision_source_total{worker="batcher",source="device"} 10980
cerbos_tpu_decision_source_total{worker="batcher",source="oracle"} 2352
cerbos_tpu_device_memory_bytes_in_use{worker="batcher"} 1.8432e+06
cerbos_tpu_parity_checks_total{worker="batcher",shard="0"} 6
cerbos_tpu_parity_divergence_total{worker="batcher"} 0
cerbos_tpu_xla_compiles_total{worker="batcher",source="persistent"} 13
"""

BATCH_DECISIONS = 5490  # 2 protocols x 2745, as in the recorded run


def scrapes(mutate_after=None, text=RECORDED):
    """(before, after) around a checked pass in which the batch-shaped
    decisions all came from the device. ``before`` is the recorded scrape
    with that pass's traffic taken back out."""
    after = chip_smoke.parse_metrics(text)
    before = dict(after)
    for key in after:
        name, labels = key
        if name == "cerbos_tpu_decision_source_total":
            before[key] -= BATCH_DECISIONS if ("source", "device") in labels else 1176
    if mutate_after:
        mutate_after(after)
    return before, after


def bump(name, n=1.0, **labels):
    key = (name, tuple(sorted(labels.items())))

    def mutate(metrics):
        metrics[key] = metrics.get(key, 0.0) + n

    return mutate


class TestParser:
    def test_parses_plain_labelled_and_histogram_series(self):
        m = chip_smoke.parse_metrics(RECORDED)
        assert m[("cerbos_tpu_batcher_batches_total", ())] == 329
        assert m[("cerbos_tpu_decision_source_total", (("source", "device"),))] == 11201
        assert m[("cerbos_tpu_device_memory_bytes_in_use", ())] == 1843200.0
        assert (
            m[("cerbos_tpu_batch_stage_seconds_bucket", (("le", "+Inf"), ("shard", "0"), ("stage", "pack")))]
            == 329
        )
        assert not any(name.startswith("#") for name, _ in m)

    def test_sums_fold_the_worker_label(self):
        m = chip_smoke.parse_metrics(RECORDED_POOL)
        assert chip_smoke.msum(m, "cerbos_tpu_xla_compiles_total") == 13
        assert chip_smoke.msum(m, "cerbos_tpu_decision_source_total", source="device") == 10980
        assert chip_smoke.by_label(m, "cerbos_tpu_decision_source_total", "source") == {
            "device": 10980,
            "oracle": 2352,
        }

    def test_stage_p50_interpolates_inside_the_owning_bucket(self):
        p50 = chip_smoke.stage_p50s(chip_smoke.parse_metrics(RECORDED), "cerbos_tpu_batch_stage_seconds")
        # half of 329 observations is reached in the first bucket (203 of them)
        assert list(p50) == ["pack"]
        assert 0 < p50["pack"] <= 0.0001


class TestCheckedPass:
    @pytest.mark.parametrize("text", [RECORDED, RECORDED_POOL])
    def test_recorded_pass_holds(self, text):
        before, after = scrapes(text=text)
        assert chip_smoke.check_pass(before, after, BATCH_DECISIONS, BATCH_DECISIONS) == []

    def test_no_device_decisions_fails(self):
        # the shape-(b) pass removed: what is left never reaches the device
        before, after = scrapes()
        failures = chip_smoke.check_pass(before, after, BATCH_DECISIONS, 0)
        assert len(failures) == 1 and 'source="device"' in failures[0] and "share 0.000" in failures[0]

    def test_device_share_just_under_the_bound_fails(self):
        before, after = scrapes()
        assert chip_smoke.check_pass(before, after, 1000, 899)
        assert not chip_smoke.check_pass(before, after, 1000, 900)

    def test_a_fallback_reason_fails(self):
        before, after = scrapes(bump("cerbos_tpu_batcher_oracle_fallbacks_total", 3, reason="timeout"))
        failures = chip_smoke.check_pass(before, after, BATCH_DECISIONS, BATCH_DECISIONS)
        assert failures == ['batcher_oracle_fallbacks_total{reason="timeout"} moved by 3']

    def test_a_front_end_fallback_fails_too(self):
        before, after = scrapes(
            bump("cerbos_tpu_batcher_oracle_fallbacks_total", reason="batcher_down", worker="fe2"),
            text=RECORDED_POOL,
        )
        assert chip_smoke.check_pass(before, after, BATCH_DECISIONS, BATCH_DECISIONS)

    def test_a_breaker_trip_fails(self):
        before, after = scrapes(bump("cerbos_tpu_breaker_trips_total"))
        failures = chip_smoke.check_pass(before, after, BATCH_DECISIONS, BATCH_DECISIONS)
        assert failures == ["breaker_trips_total moved by 1"]

    def test_a_compile_in_the_pass_fails_and_is_marked_retryable(self):
        before, after = scrapes(bump("cerbos_tpu_xla_compiles_total", source="fresh"))
        failures = chip_smoke.check_pass(before, after, BATCH_DECISIONS, BATCH_DECISIONS)
        assert len(failures) == 1 and failures[0].startswith("compile:")


class TestTotals:
    def test_recorded_totals_hold(self):
        assert chip_smoke.check_totals(chip_smoke.parse_metrics(RECORDED)) == []
        assert chip_smoke.check_totals(chip_smoke.parse_metrics(RECORDED_POOL)) == []

    def mutated(self, **series):
        m = chip_smoke.parse_metrics(RECORDED)
        for key in list(m):
            if key[0] in series:
                m[key] = series[key[0]]
        return chip_smoke.check_totals(m)

    def test_a_divergence_fails(self):
        failures = self.mutated(cerbos_tpu_parity_divergence_total=2.0)
        assert failures == ["parity_divergence_total is 2: device and oracle disagree"]

    def test_no_parity_check_fails(self):
        assert "parity_checks_total is 0" in self.mutated(cerbos_tpu_parity_checks_total=0.0)[0]

    def test_no_compile_ever_fails(self):
        assert "xla_compiles_total is 0" in self.mutated(cerbos_tpu_xla_compiles_total=0.0)[0]

    def test_missing_device_memory_fails(self):
        # what a CPU backend's scrape looks like: the gauge exists and is 0
        failures = self.mutated(cerbos_tpu_device_memory_bytes_in_use=0.0)
        assert failures == ["device_memory_bytes_in_use is 0: the backend holds no device memory"]


class TestCallIds:
    """Every reply of a topology names its call, and no two the same: a pool's
    front ends fork after load, and each has to draw from a seed of its own."""

    IDS = [f"{n:032x}" for n in (1, 2, 0xABCDEF)]

    def test_distinct_ids_hold(self):
        assert chip_smoke.check_call_ids(self.IDS) == []

    def test_two_replies_under_one_call_id_fail(self):
        assert chip_smoke.check_call_ids(self.IDS + self.IDS[:1]) == ["1 of 4 replies repeat another reply's call id"]

    @pytest.mark.parametrize("bad", ["", "ABCDEF" + "0" * 26, "0" * 31])
    def test_a_reply_without_a_call_id_of_32_hex_digits_fails(self, bad):
        assert chip_smoke.check_call_ids(self.IDS + [bad]) == ["1 of 4 replies carry no call id of 32 hex digits"]


class TestWireCodec:
    """On the classic traffic every gRPC request is read and every reply
    written by the native codec, in whichever process listened."""

    CODEC = "cerbos_tpu_wire_codec_total"

    def scrape(self, **python):
        m = {}
        for worker in ("fe1", "fe2"):
            for direction in ("request", "reply"):
                bump(self.CODEC, 40, dir=direction, path="native", worker=worker)(m)
        for direction, n in python.items():
            bump(self.CODEC, n, dir=direction, path="python", worker="fe2")(m)
        return m

    def test_all_native_in_both_directions_holds(self):
        assert chip_smoke.check_codec(self.scrape()) == []

    @pytest.mark.parametrize("direction", ["request", "reply"])
    def test_one_on_the_python_path_in_one_front_end_fails(self, direction):
        (failure,) = chip_smoke.check_codec(self.scrape(**{direction: 1}))
        assert failure.startswith(f"wire codec, {direction}: {{'native': 80, 'python': 1}}")

    def test_a_scrape_without_the_counter_fails_in_both_directions(self):
        assert len(chip_smoke.check_codec({})) == 2


class TestPlatform:
    def test_cpu_platform_is_refused(self):
        status = {"device": {"platform": "cpu", "device_kind": "cpu", "count": 1, "pid": 1}}
        with pytest.raises(chip_smoke.SmokeFailure, match="platform='cpu'.*no accelerator"):
            chip_smoke.check_platform(status)
        chip_smoke.check_platform({"device": {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1, "pid": 1}})

    def test_a_server_that_opened_no_device_is_refused(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="no device"):
            chip_smoke.check_platform({"enabled": True, "device": None})

    def test_result_line_has_exactly_the_contract_keys(self):
        """The chip check parses the last stdout line and refuses any other
        key: the observations go on the line before it."""
        line = chip_smoke.result_line({"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    def test_no_option_sets_the_platform_or_the_size(self, tmp_path):
        """A chipless or toy-size run must not be able to end in "ok": true:
        platform, corpus scale and request counts are constants."""
        for flag in (["--platform", "cpu"], ["--mods", "2"], ["--requests", "8"]):
            p = subprocess.run(
                [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flag],
                capture_output=True, text=True, timeout=60, cwd=tmp_path,
            )
            assert p.returncode == 2 and "unrecognized arguments" in p.stderr
            assert p.stdout == ""

    def test_script_exits_nonzero_without_a_result_on_the_cpu(self, tmp_path):
        """The command as the driver runs it, at its full size, against a
        real server process on the CPU backend: it must stop at the platform
        line, before any traffic."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path,
        )
        assert p.returncode == 1, p.stderr[-2000:]
        assert "chip_smoke FAILED: the server reports platform='cpu'" in p.stderr
        assert '"ok"' not in p.stdout
        assert "cold http" not in p.stdout

    def test_script_alone_exits_nonzero(self, tmp_path):
        """In a directory that holds chip_smoke.py and nothing else of the repo."""
        alone = tmp_path / "chip_smoke.py"
        alone.write_bytes(open(os.path.join(REPO, "chip_smoke.py"), "rb").read())
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        p = subprocess.run(
            [sys.executable, str(alone)], capture_output=True, text=True, timeout=60, env=env, cwd=tmp_path
        )
        assert p.returncode == 3
        assert "cerbos_tpu package is not next to this script" in p.stderr
        assert p.stdout == ""
