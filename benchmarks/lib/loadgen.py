"""The load generator: a process of its own, off jax, that sends serialized
requests and time-stamps them, and does nothing else while a phase runs.

The harness starts it with ``python benchmarks/lib/loadgen.py``, then writes
one JSON command per line to its stdin and reads one JSON answer per line
from its stdout:

    {"cmd": "load", "path": <pickle of list[bytes]>, "target": "127.0.0.1:<grpc port>",
     "connections": <n>}
    {"cmd": "run", "kind": "open_poisson", "out": <pickle path>, ...}
    {"cmd": "quit"}

Open loop (``open_poisson``): ``first``, ``due`` (seconds from the phase's
start, ascending; request ``first + k`` is due at ``due[k]``), ``deadline_s``.
Every request is sent at its due time whatever became of the earlier ones,
and its latency is taken from the instant it was DUE, so a stall shows in the
requests that queued behind it; how late the generator itself sent each one is
recorded beside it.

Replies are kept as raw bytes; decoding and the comparison with the reference
are the harness's, after the window.
"""

from __future__ import annotations

import json
import pickle
import sys
import threading
import time

METHOD = "/cerbos.svc.v1.CerbosService/CheckResources"
SWITCH_INTERVAL_S = 0.0002  # a thread that wants the interpreter lock (a reply's time stamp) gets it this soon


def _sleep_until(t: float) -> None:
    # sleeping, never spinning: a spinning sender would hold the interpreter
    # lock against the thread that time-stamps the replies
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(left)


class Generator:
    def __init__(self):
        import grpc

        self.grpc = grpc
        self.wires: list[bytes] = []
        self.channels: list = []
        self.stubs: list = []

    def load(self, wires: list[bytes], target: str, connections: int) -> None:
        self.close()
        self.wires = wires
        for _ in range(connections):
            # a channel of its own per connection: by default grpc shares one
            # TCP connection between channels with equal arguments
            ch = self.grpc.insecure_channel(target, options=[("grpc.use_local_subchannel_pool", 1)])
            self.grpc.channel_ready_future(ch).result(timeout=30)
            self.channels.append(ch)
            self.stubs.append(ch.unary_unary(METHOD, request_serializer=None, response_deserializer=None))

    def close(self) -> None:
        for ch in self.channels:
            ch.close()
        self.channels, self.stubs = [], []

    def _outcome(self, call_or_error) -> tuple[str, str]:
        """(status name, detail) of a finished call."""
        code = call_or_error.code()
        if code == self.grpc.StatusCode.OK:
            return "OK", ""
        return code.name, (call_or_error.details() or "")[:200]

    def run_open(self, first: int, due: list[float], deadline_s: float) -> dict:
        n = len(due)
        sent = [0.0] * n
        done = [0.0] * n
        futures = [None] * n
        left = [n]
        all_done = threading.Event()
        lock = threading.Lock()

        def stamp(k: int):
            def cb(_fut) -> None:
                done[k] = time.perf_counter()
                with lock:
                    left[0] -= 1
                    if left[0] == 0:
                        all_done.set()

            return cb

        stubs, wires, nstub = self.stubs, self.wires, len(self.stubs)
        t0, t0_monotonic = time.perf_counter() + 0.02, time.monotonic() + 0.02
        for k in range(n):
            _sleep_until(t0 + due[k])
            sent[k] = time.perf_counter()
            fut = stubs[k % nstub].future(wires[first + k], timeout=deadline_s)
            fut.add_done_callback(stamp(k))
            futures[k] = fut
        if n:
            all_done.wait(timeout=deadline_s + 30)
        status, reply, detail = [], [], []
        for fut in futures:
            if not fut.done():
                fut.cancel()
                status.append("NEVER_DONE"), reply.append(None), detail.append("")
                continue
            s, d = self._outcome(fut)
            status.append(s), detail.append(d)
            reply.append(fut.result() if s == "OK" else None)
        return {
            "t0": t0_monotonic,  # on the clock every process of the machine shares
            "index": [first + k for k in range(n)],
            "due": list(due),
            "sent": [s - t0 for s in sent],
            "done": [d - t0 if d else None for d in done],
            "status": status,
            "detail": detail,
            "reply": reply,
        }

    def run(self, cmd: dict) -> dict:
        if cmd["kind"] == "open_poisson":
            return self.run_open(cmd["first"], cmd["due"], cmd["deadline_s"])
        raise ValueError(f"no generator for traffic kind {cmd['kind']!r}")


def main() -> int:
    sys.setswitchinterval(SWITCH_INTERVAL_S)
    gen = Generator()
    out = sys.stdout
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            break
        if cmd["cmd"] == "load":
            with open(cmd["path"], "rb") as f:
                wires = pickle.load(f)  # written by the harness that started this process
            gen.load(wires, cmd["target"], cmd["connections"])
            answer = {"loaded": len(wires)}
        else:
            res = gen.run(cmd)
            with open(cmd["out"], "wb") as f:
                pickle.dump(res, f)
            answer = {"ran": len(res["index"]), "out": cmd["out"]}
        out.write(json.dumps(answer) + "\n")
        out.flush()
    gen.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
