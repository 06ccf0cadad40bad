"""One server, one generator, and the phases of a run: boot, the window's
requests, the warm replay that rehearses them, the measured window, the stop.
``run.py`` makes one window in a session; the sweep under ``tools`` makes
several, at rising rates, on one server."""

from __future__ import annotations

import json
import math
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from datetime import datetime, timedelta, timezone

from . import corpus, prom, spec, stats, workload
from .server import REPO as ROOT
from .server import HarnessError, ServerProc, write_policies

ROUND_S = 5.0  # the warm replay rehearses the window in slices this long
IDLE_ROUNDS = 2  # slices replayed where no layout has compiled since boot (a mix that bypasses the device)
SETUP_CEILING_S = 220.0  # set-up, compiling and loading layouts apart, may last this long; an unsettled server then fails the run
TRACE_LEAD_S = 1.0  # the capture opens this long before the traced replay starts
TRACE_STRETCH = 2.5  # ... and lasts this many times the replayed slice: under the tracer the last reply comes up to 4.2 s late
STDERR_FAILURES = 40  # failure reasons beyond these go to the failure file only
COLD_DEADLINE_S = 600.0  # warm replay: a cold layout compiles inside its request for up to 40 s


class GenProc:
    """The load generator child (benchmarks/lib/loadgen.py)."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmarks", "lib", "loadgen.py")],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            text=True,
            cwd=ROOT,
        )

    def call(self, cmd: dict) -> dict:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise HarnessError(f"the load generator exited {self.proc.poll()} during {cmd['cmd']}")
        return json.loads(line)

    def run(self, cmd: dict) -> dict:
        self.call({"cmd": "run", **cmd})
        with open(cmd["out"], "rb") as f:
            return pickle.load(f)  # written by the generator this process started

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def docs_of(config: dict) -> list[str]:
    if config["corpus"]["generator"] != "classic":
        raise spec.SpecError(f"no corpus generator {config['corpus']['generator']!r}")
    return corpus.corpus_yaml(int(config["corpus"]["mods"])).split("\n---\n")


def check_platform(status: dict, want: str | None) -> dict:
    dev = status.get("device")
    if not dev:
        raise HarnessError("the server's device owner reports no device: nothing opened a JAX backend")
    if want is not None and dev["platform"] != want:
        raise HarnessError(
            f"the server reports platform={dev['platform']!r} (device_kind={dev['device_kind']!r}), "
            f"not {want!r}: no accelerator behind the served path"
        )
    return dev


def window_command(cell: spec.Cell, seconds: float, seed: int, out: str) -> dict:
    """The generator phase that is the measured window."""
    due = workload.poisson_schedule(cell.pair["rate"], seconds, seed)
    return {"kind": cell.traffic["kind"], "first": 0, "due": due, "deadline_s": float(cell.traffic["deadline_s"]), "out": out}


def warm_slices(window: dict) -> list[dict]:
    """The window cut into slices of ``ROUND_S`` seconds, each a generator
    phase of its own: the window's own requests at their own instants.
    Replayed in order during set-up, they are one rehearsal of the whole
    window."""
    due = window["due"]
    out = []
    for r in range(max(1, math.ceil((due[-1] if due else 0.0) / ROUND_S - 1e-9))):
        ks = [k for k, d in enumerate(due) if r * ROUND_S <= d < (r + 1) * ROUND_S]
        if ks:
            out.append(dict(window, deadline_s=COLD_DEADLINE_S, first=ks[0], due=[due[k] - r * ROUND_S for k in ks]))
    return out


def outcome_rows(res: dict, reqs: dict, now_lo: datetime, now_hi: datetime) -> list[dict]:
    """One row per request the generator sent in the window: its latency from
    due time and why it failed, if it did. Decoding and the comparison with
    the reference happen here, after the window."""
    rows = []
    for k, idx in enumerate(res["index"]):
        status, done, reason = res["status"][k], res["done"][k], None
        if status != "OK":
            reason = f"status {status}: {res['detail'][k]}"
        else:
            diff = workload.compare(reqs[idx], res["reply"][k], now_lo, now_hi)
            if diff:
                reason = "wrong reply: " + diff
        rows.append(
            {
                "index": idx, "due": res["due"][k], "sent": res["sent"][k], "done": done,
                "status": status, "reason": reason, "wrong": bool(reason and status == "OK"),
                "decisions": reqs[idx].decisions(),
            }
        )
    return rows


def generator_stats(cell: spec.Cell, rows: list[dict], seconds: float) -> dict:
    lat = cell.traffic["latency_name"]
    good = [r for r in rows if r["reason"] is None]
    out = {"attempted": len(rows), "failed": len(rows) - len(good), "wrong": sum(r["wrong"] for r in rows)}
    if good:
        ms = [(r["done"] - r["due"]) * 1000.0 for r in good]
        for p in (50, 95, 99):
            out[f"{lat}_p{p}_ms"] = stats.percentile(ms, p)
        ref = cell.traffic.get("ref_p99_ms", {}).get(cell.config_name)
        if ref is not None:
            out["within_ref_p99_share"] = stats.share_within(ms, ref)
    out["decisions_per_s"] = sum(r["decisions"] for r in good) / seconds
    out["completed_per_s"] = len(good) / seconds
    stall, at = longest_stall(rows)
    out["stall_max_ms"], out["stall_at_s"] = stall * 1000.0, at
    if rows:
        out["gen_late_p99_ms"] = stats.percentile([(r["sent"] - r["due"]) * 1000.0 for r in rows], 99)
        out["backlog_at_close"] = sum(1 for r in rows if r["done"] is None or r["done"] > seconds)
    return out


class Watchdog:
    """A thread of the harness (a process that does nothing while the window is
    open) that sleeps 5 ms at a time and notes by how much it overslept: a
    pause of the whole machine shows here as it does in the generator and the
    server, a pause of the server alone does not."""

    def __init__(self):
        self.worst, self.at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.stat0 = _proc_stat()
        self._thread.start()
        return self

    def _run(self) -> None:
        prev = time.perf_counter()
        while not self._stop.wait(0.005):
            now = time.perf_counter()
            if now - prev - 0.005 > self.worst:
                self.worst, self.at = now - prev - 0.005, prev - self.t0
            prev = now

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        stat1 = _proc_stat()
        self.steal_s = (stat1.get("steal", 0) - self.stat0.get("steal", 0)) / os.sysconf("SC_CLK_TCK")


def _proc_stat() -> dict:
    try:
        with open("/proc/stat") as f:
            v = f.readline().split()[1:]
    except OSError:
        return {}
    names = ("user", "nice", "system", "idle", "iowait", "irq", "softirq", "steal")
    return {n: int(x) for n, x in zip(names, v)}


def longest_stall(rows: list[dict]) -> tuple[float, float]:
    """The longest time in which a request was waiting and no reply came back
    (seconds), and when it began (seconds into the window): what one pause of
    the server, or of the generator's host, looks like from outside."""
    done = sorted((r["done"], r["due"]) for r in rows if r["done"] is not None)
    best, at = 0.0, 0.0
    for (prev, _), (cur, due) in zip(done, done[1:]):
        waited = cur - max(prev, due)
        if waited > best:
            best, at = waited, max(prev, due)
    return best, at


def capture_profile(srv: ServerProc, seconds: float, box: dict) -> None:
    try:
        box["capture"] = srv.get_json(f"/_cerbos/debug/profile?seconds={seconds}", timeout=seconds + 240)
    except (OSError, ValueError) as e:
        box["error"] = f"{type(e).__name__}: {e}"


CAPTURE_S = TRACE_LEAD_S + TRACE_STRETCH * ROUND_S  # how long a traced run's capture lasts


def report_failures(rows: list[dict], out_dir: str) -> None:
    """Every failed operation with its reason: to a file, and the first to stderr."""
    failed = [r for r in rows if r["reason"] is not None]
    with open(os.path.join(out_dir, "failures.jsonl"), "w") as f:
        for k, r in enumerate(failed):
            line = json.dumps(
                {"index": r["index"], "due_s": r["due"], "done_s": r["done"], "status": r["status"], "reason": r["reason"]}
            )
            f.write(line + "\n")
            if k < STDERR_FAILURES:
                sys.stderr.write(f"failed operation: {line}\n")
    if len(failed) > STDERR_FAILURES:
        sys.stderr.write(f"... {len(failed) - STDERR_FAILURES} more in {out_dir}/failures.jsonl\n")


class Session:
    def __init__(self, cell: spec.Cell, *, trace: bool = False, policy_transform=None, log=print):
        """Writes the policies and starts the server; returns while it boots.
        ``trace`` enables the server's profiler endpoint for one capture.
        ``policy_transform`` rewrites the documents the SERVER loads (never
        what the reference reads): for the tests and the control runs."""
        self.cell, self.log, self.trace = cell, log, trace
        self.mods = int(cell.config["corpus"]["mods"])
        self.work = tempfile.mkdtemp(prefix="cerbos_bench_")
        self.gen: GenProc | None = None
        self.srv: ServerProc | None = None
        self.t = {"start": time.monotonic()}
        docs = docs_of(cell.config)
        if policy_transform is not None:
            docs = policy_transform(docs)
        self.n_docs = write_policies(os.path.join(self.work, "policies"), docs, self.mods)
        self.t["policies"] = time.monotonic()
        settings = {k: v["value"] for k, v in cell.config.get("assumed", {}).get("server", {}).items()}
        if trace:
            settings["engine.tpu.profiler.enabled"] = True
            settings["engine.tpu.profiler.dir"] = os.path.join(self.work, "profiles")
            settings["engine.tpu.profiler.maxSeconds"] = CAPTURE_S
        self.srv = ServerProc(self.work, os.path.join(self.work, "policies"), settings, log)

    def prepare(self, seed: int, seconds: float) -> dict:
        """The window's requests from the seed, serialized, and the generator
        phases that send them: the window and the slices that rehearse it."""
        tr = self.cell.traffic
        n_window = round(self.cell.pair["rate"] * seconds)
        reqs = workload.build(n_window, self.mods, seed, tr["request"])
        touch = []
        if self.trace and "device_touch" in tr:
            touch = workload.build(int(tr["device_touch"]["count"]), self.mods, seed, tr["device_touch"]["request"])
            for k, r in enumerate(touch):
                r.index = n_window + k
        workload.serialize(reqs + touch)
        wires = os.path.join(self.work, "wires.pickle")
        with open(wires, "wb") as f:
            pickle.dump([r.wire for r in reqs + touch], f)
        window = window_command(self.cell, seconds, seed, os.path.join(self.work, "window.pickle"))
        return {
            "seed": seed, "seconds": seconds, "reqs": {r.index: r for r in reqs}, "wires": wires,
            "window": window, "slices": warm_slices(window),
            "decisions": sum(r.decisions() for r in reqs), "touch": [r.index for r in touch],
        }

    def ready(self, require_platform: str | None) -> dict:
        """Wait for the server, check what it runs on, start the generator."""
        self.srv.wait_serving(timeout=900)
        self.t["ready"] = time.monotonic()
        self.dev = check_platform(self.srv.status(), require_platform)
        if int(self.dev["count"]) < self.cell.chips:
            raise HarnessError(f"the cell asks for {self.cell.chips} chips, the server holds {self.dev['count']}")
        self.gen = GenProc()
        return self.dev

    def load(self, prepared: dict) -> None:
        self.gen.call(
            {"cmd": "load", "path": prepared["wires"], "target": f"127.0.0.1:{self.srv.grpc_port}",
             "connections": int(self.cell.traffic["connections"])}
        )

    def warm(self, prepared: dict) -> int:
        """Replay the window's own traffic, slice after slice, until one whole
        rehearsal of the window (as many slices in a row as it has) has met no
        compile and the brownout ladder is left; where no layout has compiled
        since boot, ``IDLE_ROUNDS`` slices. A server that has not settled when
        set-up, the seconds spent compiling or loading layouts apart, has
        lasted ``SETUP_CEILING_S`` fails the run: no window opens on it."""
        slices = prepared["slices"]
        before, _ = self.srv.scrape()
        compiles0 = prom.total(before, "cerbos_tpu_xla_compiles_total")
        quiet = rounds = 0
        while True:
            res = self.gen.run(dict(slices[rounds % len(slices)], out=os.path.join(self.work, "warm.pickle")))
            rounds += 1
            bad = [s for s in res["status"] if s != "OK"]
            if bad:
                raise HarnessError(f"warm replay round {rounds}: {len(bad)} requests failed, first {bad[0]}")
            cur, _ = self.srv.scrape()
            compiles = prom.total(cur, "cerbos_tpu_xla_compiles_total")
            compile_s = prom.total(cur, "cerbos_tpu_xla_compile_seconds_sum")
            stage = prom.total(cur, "cerbos_tpu_brownout_stage")
            self.log(
                f"warm round {rounds}: {len(res['index'])} requests, compiles +{compiles - compiles0:.0f} "
                f"({prom.total(cur, 'cerbos_tpu_xla_compiles_total', source='fresh'):.0f} not from the cache so far, "
                f"{compile_s:.1f} s compiling and loading), brownout stage {stage:.0f}"
            )
            quiet = quiet + 1 if compiles == compiles0 else 0
            compiles0 = compiles
            need = len(slices) if compiles > 0 else min(len(slices), IDLE_ROUNDS)
            if quiet >= need and stage == 0:
                return rounds
            if time.monotonic() - self.t["start"] - compile_s >= SETUP_CEILING_S:
                raise HarnessError(
                    f"the server has not settled after {rounds} slices of warm replay (quiet {quiet} of {need}, "
                    f"brownout stage {stage:.0f}): set-up has lasted {time.monotonic() - self.t['start']:.0f} s, "
                    f"{compile_s:.0f} s of it compiling or loading layouts"
                )

    def measure(self, prepared: dict) -> dict:
        """The window. Nothing is asked of the server while it is open; the
        scrapes are taken before and after.

        A traced run measures the same untraced window for its host-side
        metrics and its tails, and then replays the window's first slice once
        more inside a profiler capture, between two scrapes of its own: the
        program's profiler turns the Python tracer on, which slows the host
        several times over, so a capture laid over the window would measure a
        server that no ``--trace 0`` run sees. The device numbers are read
        over the span of that traced traffic, first send to last reply, not
        over the capture's nominal length. Device time per operation does not
        depend on the host's speed; the idle share of the span does."""
        seconds = prepared["seconds"]
        scrape_a, _ = self.srv.scrape()
        now_lo = datetime.now(timezone.utc) - timedelta(seconds=2)
        t_open, open_unix_s = time.monotonic(), time.time()
        with Watchdog() as dog:
            res = self.gen.run(prepared["window"])
        now_hi = datetime.now(timezone.utc) + timedelta(seconds=2)
        t_close = time.monotonic()
        scrape_b, text_b = self.srv.scrape()
        out = {"t_open": t_open, "t_close": t_close, "open_unix_s": open_unix_s, "before": scrape_a, "after": scrape_b, "after_text": text_b}
        if self.trace:
            segment = dict(prepared["slices"][0], out=os.path.join(self.work, "traced.pickle"))
            box: dict = {}
            thread = threading.Thread(target=capture_profile, args=(self.srv, CAPTURE_S, box), daemon=True)
            t_capture = time.monotonic()  # the trace counts its time from the start of the capture, which this request starts
            thread.start()
            time.sleep(TRACE_LEAD_S)
            sent = [self.gen.run(segment)] + self.touch_device(prepared)
            thread.join(timeout=CAPTURE_S + 600)
            scrape_c, _ = self.srv.scrape()
            bad = [s for r in sent for s in r["status"] if s != "OK"]
            if bad:
                raise HarnessError(f"traced replay: {len(bad)} requests failed, first {bad[0]}")
            # the traced traffic's span on the trace's clock: first send to last reply
            span = (
                min(r["t0"] + min(r["sent"]) for r in sent) - t_capture,
                max(r["t0"] + max(r["done"]) for r in sent) - t_capture,
            )
            if span[1] > CAPTURE_S:
                self.log(f"traced replay: the last reply came {span[1]:.2f} s after the capture's start, after its end: the span is cut there")
                span = (span[0], CAPTURE_S)
            self.log(
                f"traced replay: {sum(len(r['index']) for r in sent)} requests inside a capture of {CAPTURE_S:g} s, "
                f"first sent {span[0]:.2f} s and last answered {span[1]:.2f} s after its start"
            )
            out.update(profile=box, span=span, trace_before=scrape_b, trace_after=scrape_c)
        out["t_captured"] = time.monotonic()
        # after the window: decode, compare with the reference
        rows = outcome_rows(res, prepared["reqs"], now_lo, now_hi)
        g = generator_stats(self.cell, rows, seconds)
        g.update(host_pause_max_ms=dog.worst * 1000.0, host_pause_at_s=dog.at, host_steal_s=dog.steal_s)
        out.update(rows=rows, gen=g, t_compared=time.monotonic())
        return out

    def touch_device(self, prepared: dict) -> list[dict]:
        """For a mix that bypasses the device (its traffic file says so with
        ``device_touch``): a few page-shaped requests, one at a time, so that a
        traced run's capture holds the device path once, as the benchmark's
        contract asks of every cell. Outside the window; no metric reads them."""
        out = []
        for k, index in enumerate(prepared["touch"]):
            res = self.gen.run(
                {"kind": "open_poisson", "first": index, "due": [0.0], "deadline_s": COLD_DEADLINE_S,
                 "out": os.path.join(self.work, "touch.pickle")}
            )
            if res["status"] != ["OK"]:
                raise HarnessError(f"device touch {k}: {res['status']}")
            out.append(res)
        return out

    def stop(self, out_dir: str) -> dict:
        """Keep what the server remembers of the window beside the failure
        file, then SIGTERM; the device owner's last status. Exit 0 is required."""
        for name, path in (("slow", "/_cerbos/debug/slow"), ("pressure", "/_cerbos/debug/pressure")):
            try:
                with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
                    json.dump(self.srv.get_json(path), f)
            except (OSError, ValueError) as e:
                self.log(f"{path}: {type(e).__name__}: {e}")
        status = self.srv.status()
        self.gen.stop()
        code = self.srv.stop()
        shutil.copyfile(self.srv.stderr_path, os.path.join(out_dir, "server.stderr"))
        if code != 0:
            raise HarnessError(f"server exit code on SIGTERM: {code} (None = had to be killed)")
        return status

    def close(self) -> None:
        """Leave nothing behind, whatever state the run is in."""
        if self.gen is not None:
            self.gen.stop()
        if self.srv is not None and self.srv.proc.poll() is None:
            self.srv.kill()
            sys.stderr.write(f"--- last lines of the server's stderr:\n{self.srv.stderr_tail()}---\n")
        shutil.rmtree(self.work, ignore_errors=True)
