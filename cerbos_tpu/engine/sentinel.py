"""Parity sentinel: online differential testing of the device path.

The paper's headline guarantee is bit-exact effect parity between the
device evaluator and the reference CPU path — yet nothing in production
would notice if a lowering bug, a packer layout change, or a sick chip
started returning *wrong effects* instead of errors (the breaker only sees
exceptions and timeouts). Cedar ships differential random testing as an
always-on guardrail for exactly this class of engine (PAPERS.md, arxiv
2403.04651); the sentinel is the serving-path analogue:

- a deterministic per-shard sampler picks a configurable fraction of
  COMPLETED device batches (default 1%; the first batch per lane is always
  checked so a bad replica is caught at first traffic, then every
  ``1/rate``-th after that);
- a low-priority background thread replays the sampled batch's raw inputs
  on the COW-shared CPU oracle (the same ``check_input`` walk the breaker
  fallback serves from) and compares effect rows **bit-exactly**;
- each divergence is counted (``cerbos_tpu_parity_divergence_total``),
  recorded into the flight recorder, and captured — raw inputs plus both
  effect sets — into a bounded on-disk corpus replayable offline via
  ``cerbos-tpuctl replay-divergences``;
- a storm policy watches a sliding window per shard: at
  ``stormThreshold`` divergences within ``windowSec`` it trips that lane's
  ``DeviceHealth`` breaker, so traffic routes to the oracle
  (correct-over-fast) and readiness reports ``degraded`` with a ``parity``
  reason.

The sentinel lives in whichever process owns the batcher drain loops, so
it covers all three serving topologies unchanged: single batcher,
``--frontends N`` (the shared-batcher process samples; front ends carry no
device), and the sharded mesh (one sampler state per lane).

Hot-path cost when a batch is NOT sampled is one float add and a compare;
sampled batches enqueue references into a bounded backlog (overflow drops
the sample, never blocks the drain loop).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from ..ruletable import check_input
from ..schema import ROUTE_SHADOW
from . import types as T
from .flight import recorder as flight_recorder

_log = logging.getLogger("cerbos_tpu.engine.sentinel")

DEFAULT_SAMPLE_RATE = 0.01
DEFAULT_WINDOW_SEC = 60.0
DEFAULT_STORM_THRESHOLD = 3
DEFAULT_CORPUS_MAX = 64
DEFAULT_BACKLOG = 64
DEFAULT_RECENT_INPUTS = 512


# -- effect-row comparison ---------------------------------------------------


def effect_rows(outputs: Sequence[T.CheckOutput]) -> list[dict]:
    """The canonical JSON shape of one batch's effect rows: what the paper's
    parity guarantee is *about*. Everything the API caller can observe as a
    decision is in here (effect + matched policy + scope per action);
    ordering is normalized so comparison is layout-independent."""
    rows = []
    for o in outputs:
        rows.append(
            {
                "resourceId": o.resource_id,
                "actions": {
                    a: {"effect": e.effect, "policy": e.policy, "scope": e.scope}
                    for a, e in sorted(o.actions.items())
                },
            }
        )
    return rows


def provenance_rows(outputs: Sequence[T.CheckOutput]) -> list[dict]:
    """Per-row decision provenance (winning rule / row id / evaluator
    source), shaped like :func:`effect_rows`. Deliberately NOT part of the
    parity comparison — attribution is telemetry, not the decision — but
    divergence records carry both sides' winning rules so triage can see
    which rule each path thought won (``replay-divergences --explain``)."""
    rows = []
    for o in outputs:
        rows.append(
            {
                "resourceId": o.resource_id,
                "actions": {
                    a: {
                        "matchedRule": e.matched_rule,
                        "ruleRowId": e.rule_row_id,
                        "source": e.source,
                    }
                    for a, e in sorted(o.actions.items())
                },
            }
        )
    return rows


def compare_rows(device: list[dict], oracle: list[dict]) -> list[int]:
    """Indices of divergent rows — bit-exact dict equality per row. A length
    mismatch marks every trailing index divergent."""
    n = min(len(device), len(oracle))
    diff = [i for i in range(n) if device[i] != oracle[i]]
    diff.extend(range(n, max(len(device), len(oracle))))
    return diff


def input_to_json(i: T.CheckInput) -> dict:
    """Corpus serialization of a raw check input — the audit log's API-JSON
    shape, so corpus records read like decision-log entries and the replay
    path rebuilds inputs without a private format."""
    from ..audit.log import _input_json

    return _input_json(i)


def input_from_json(j: dict) -> T.CheckInput:
    """Rebuild a ``CheckInput`` from a corpus record (inverse of
    :func:`input_to_json`; empty/default fields were dropped on write)."""
    pj = j.get("principal") or {}
    rj = j.get("resource") or {}
    aux = j.get("auxData") or {}
    return T.CheckInput(
        principal=T.Principal(
            id=pj.get("id", ""),
            roles=list(pj.get("roles", [])),
            attr=pj.get("attr", {}) or {},
            policy_version=pj.get("policyVersion", ""),
            scope=pj.get("scope", ""),
        ),
        resource=T.Resource(
            kind=rj.get("kind", ""),
            id=rj.get("id", ""),
            attr=rj.get("attr", {}) or {},
            policy_version=rj.get("policyVersion", ""),
            scope=rj.get("scope", ""),
        ),
        actions=list(j.get("actions", [])),
        request_id=j.get("requestId", ""),
        aux_data=T.AuxData(jwt=aux.get("jwt", {}) or {}) if aux else None,
    )


# -- divergence corpus -------------------------------------------------------


class DivergenceCorpus:
    """Bounded on-disk capture of divergent batches: one JSON file per
    divergence, oldest pruned past ``max_records``. Raw inputs ride along so
    ``cerbos-tpuctl replay-divergences`` reproduces the comparison offline
    with no access to live traffic."""

    PREFIX = "divergence-"

    def __init__(self, dir: str, max_records: int = DEFAULT_CORPUS_MAX):
        self.dir = dir
        self.max_records = max(1, int(max_records))
        self._seq = 0
        self._lock = threading.Lock()
        if self.dir:
            os.makedirs(self.dir, exist_ok=True)

    def append(self, record: dict) -> Optional[str]:
        if not self.dir:
            return None
        with self._lock:
            self._seq += 1
            name = f"{self.PREFIX}{int(time.time() * 1000):013d}-{self._seq:06d}.json"
            path = os.path.join(self.dir, name)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(record, f, indent=2, default=str)
                f.write("\n")
            os.replace(tmp, path)
            self._prune_locked()
        return path

    def _prune_locked(self) -> None:
        entries = self._list()
        excess = len(entries) - self.max_records
        if excess <= 0:
            return
        for path in entries[:excess]:
            try:
                os.unlink(path)
            except OSError:
                pass

    def _list(self) -> list[str]:
        try:
            names = sorted(
                n
                for n in os.listdir(self.dir)
                if n.startswith(self.PREFIX) and n.endswith(".json")
            )
        except OSError:
            return []
        return [os.path.join(self.dir, n) for n in names]

    def size(self) -> int:
        return len(self._list()) if self.dir else 0

    @staticmethod
    def load(dir: str) -> list[tuple[str, dict]]:
        """All corpus records in a directory, oldest first (the replay CLI's
        input). Unreadable files are skipped with a warning, not fatal."""
        out: list[tuple[str, dict]] = []
        corpus = DivergenceCorpus(dir="", max_records=1)
        corpus.dir = dir  # avoid mkdir on a read-only path
        for path in corpus._list():
            try:
                with open(path, encoding="utf-8") as f:
                    out.append((path, json.load(f)))
            except (OSError, ValueError) as e:
                _log.warning("skipping unreadable corpus record %s: %s", path, e)
        return out


# -- the sentinel ------------------------------------------------------------


@dataclass
class _Sample:
    """One sampled batch awaiting oracle replay (references, not copies —
    outputs are settled and immutable by the time the batch completes)."""

    shard: int
    inputs: list[T.CheckInput]
    outputs: list[T.CheckOutput]
    params: Optional[T.EvalParams]
    rule_table: Any
    schema_mgr: Any
    batch_id: int
    trace_ids: list[str]
    done_at: float  # sentinel clock at batch completion
    health: Any = None


@dataclass
class _PlanSample:
    """One sampled PLAN batch awaiting sequential replay. The plan-mode
    parity guarantee is stronger than the check-mode one: not just effects
    but the full serialized filter AST must match byte-for-byte."""

    shard: int
    inputs: list[Any]  # PlanInput
    outputs: list[Any]  # PlanOutput
    params: Optional[T.EvalParams]
    rule_table: Any
    schema_mgr: Any
    batch_id: int
    done_at: float


@dataclass
class _LaneState:
    """Per-shard sampler + storm-window state. The accumulator starts at 1.0
    so the FIRST completed batch on every lane is always checked — a replica
    shipping wrong effects is caught at first traffic, not after 1/rate
    batches."""

    acc: float = 1.0
    seen: int = 0
    sampled: int = 0
    divergences: deque = field(default_factory=deque)  # timestamps
    storm_until: float = 0.0


class ParitySentinel:
    """Samples completed device batches, replays them on the CPU oracle in
    the background, and enforces the correct-over-fast storm policy."""

    def __init__(
        self,
        sample_rate: float = DEFAULT_SAMPLE_RATE,
        window_sec: float = DEFAULT_WINDOW_SEC,
        storm_threshold: int = DEFAULT_STORM_THRESHOLD,
        corpus_dir: str = "",
        corpus_max: int = DEFAULT_CORPUS_MAX,
        max_backlog: int = DEFAULT_BACKLOG,
        clock: Callable[[], float] = time.monotonic,
        enabled: bool = True,
    ):
        self.enabled = enabled and sample_rate > 0
        self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
        self.window_sec = float(window_sec)
        self.storm_threshold = max(1, int(storm_threshold))
        self.max_backlog = max(1, int(max_backlog))
        self.corpus = DivergenceCorpus(corpus_dir, corpus_max)
        self._clock = clock
        self._lanes: dict[int, _LaneState] = {}
        # plan-mode parity keeps its own sampler lanes: plan batches are
        # rarer than check batches, so sharing an accumulator would let a
        # busy check lane starve plan sampling (and vice versa)
        self._plan_lanes: dict[int, _LaneState] = {}
        self._lock = threading.Lock()
        self._backlog: deque[_Sample] = deque()
        self._inflight = 0  # popped but not yet verified (drain must wait)
        self._wakeup = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # brownout shed flag (engine/brownout.py shed_parity): sampling
        # pauses while set, the worker and backlog stay intact
        self._shed = False
        # rollout-canary boost (engine/rollout.py): for a bounded window
        # after a cutover the sentinel samples at an elevated rate so a bad
        # epoch is caught inside canarySec, not at the steady-state rate
        self._boost_rate = 0.0
        self._boost_until = 0.0
        # bounded ring of recently sampled live inputs — the rollout gate's
        # differential-replay corpus alongside the on-disk divergence corpus
        self.recent: deque[T.CheckInput] = deque(maxlen=DEFAULT_RECENT_INPUTS)
        self.stats = {
            "seen": 0,
            "sampled": 0,
            "checks": 0,
            "divergences": 0,
            "dropped": 0,
            "storms": 0,
            "replay_errors": 0,
            "replay_seconds": 0.0,
            "plan_checks": 0,
            "plan_divergences": 0,
        }
        self._init_metrics()

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_checks = reg.counter_vec(
            "cerbos_tpu_parity_checks_total",
            "device batches replayed on the CPU oracle by the parity sentinel, by shard",
            label="shard",
        )
        self.m_divergence = reg.counter_vec(
            "cerbos_tpu_parity_divergence_total",
            "sampled batches whose device effects diverged bit-exactly from the CPU oracle, by shard",
            label="shard",
        )
        self.m_lag = reg.histogram(
            "cerbos_tpu_parity_lag_seconds",
            "delay from device-batch completion to the sentinel's parity verdict",
            buckets=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0],
        )
        self.m_rate = reg.gauge(
            "cerbos_tpu_parity_sample_rate",
            "configured fraction of completed device batches the sentinel replays",
        )
        self.m_dropped = reg.counter(
            "cerbos_tpu_parity_dropped_total",
            "sampled batches dropped because the sentinel backlog was full",
        )
        self.m_replay_seconds = reg.counter(
            "cerbos_tpu_parity_replay_seconds_total",
            "cumulative wall time the sentinel spent replaying batches on the CPU oracle",
        )
        self.m_storms = reg.counter_vec(
            "cerbos_tpu_parity_storms_total",
            "parity storms: divergence bursts that tripped a lane's breaker to the oracle, by shard",
            label="shard",
        )
        self.m_corpus = reg.gauge(
            "cerbos_tpu_parity_corpus_records",
            "divergence records currently captured in the on-disk corpus",
        )
        self.m_plan_checks = reg.counter(
            "cerbos_tpu_plan_parity_checks_total",
            "batched PlanResources flights replayed through the sequential planner by the parity sentinel",
        )
        self.m_plan_divergence = reg.counter(
            "cerbos_tpu_plan_parity_divergence_total",
            "sampled plan batches whose serialized filter AST differed byte-for-byte from the sequential planner",
        )
        self.m_rate.set(self.sample_rate if self.enabled else 0.0)

    # -- wiring --------------------------------------------------------------

    def attach(self, batcher: Any) -> "ParitySentinel":
        """Point every batcher lane at this sentinel. Accepts a single
        ``BatchingEvaluator`` or a ``ShardedBatchingEvaluator`` pool; the
        lanes call :meth:`observe_batch` from their drain threads."""
        lanes = getattr(batcher, "shards", None) or [batcher]
        for lane in lanes:
            lane.sentinel = self
        if self.enabled:
            self._ensure_worker()
        return self

    def _ensure_worker(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = False
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="parity-sentinel"
            )
            self._thread.start()

    def close(self) -> None:
        with self._wakeup:
            self._stop = True
            self._wakeup.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=5)

    def set_shed(self, flag: bool) -> None:
        """Brownout applier (stage ``shed_parity``): pause shadow sampling
        while engaged — the CPU oracle's cycles go to degraded-path traffic
        instead of replays. Fully reversible: the exported sample-rate gauge
        reads 0 while shed and restores the configured rate on release."""
        self._shed = bool(flag)
        self.m_rate.set(
            0.0 if self._shed or not self.enabled else self.sample_rate
        )

    def set_boost(self, rate: float, duration_s: float) -> None:
        """Rollout-canary hook: sample at ``max(rate, sample_rate)`` for the
        next ``duration_s`` seconds, then fall back to the configured rate
        automatically (no timer thread — expiry is checked on the sampling
        path). The exported rate gauge tracks the boost so the elevated
        window is visible on dashboards."""
        rate = min(1.0, max(0.0, float(rate)))
        with self._lock:
            self._boost_rate = rate
            self._boost_until = self._clock() + max(0.0, float(duration_s))
        if self.enabled and not self._shed:
            self.m_rate.set(max(rate, self.sample_rate))

    def _effective_rate(self) -> float:
        """Current sampling rate honoring an active canary boost (caller
        holds ``self._lock``)."""
        if self._boost_until > 0.0:
            if self._clock() < self._boost_until:
                return max(self.sample_rate, self._boost_rate)
            # boost expired: restore the steady-state gauge once
            self._boost_until = 0.0
            self._boost_rate = 0.0
            self.m_rate.set(
                0.0 if self._shed or not self.enabled else self.sample_rate
            )
        return self.sample_rate

    def recent_inputs(self) -> list:
        """A bounded snapshot of recently sampled live inputs (newest last)
        — the rollout gate replays these old-vs-new before a cutover."""
        with self._lock:
            return list(self.recent)

    # -- hot path (batcher drain thread) ------------------------------------

    def should_sample(self, shard: int) -> bool:
        """Deterministic fractional sampler, one accumulator per shard:
        ``acc += rate`` per completed batch, sample when it crosses 1.0. No
        RNG — the sampled sequence is a pure function of the batch count, so
        tests and incident replays see identical pick patterns."""
        if not self.enabled or self._shed:
            return False
        with self._lock:
            st = self._lanes.setdefault(shard, _LaneState())
            st.seen += 1
            self.stats["seen"] += 1
            st.acc += self._effective_rate()
            if st.acc < 1.0:
                return False
            st.acc -= 1.0
            st.sampled += 1
            self.stats["sampled"] += 1
            return True

    def observe_batch(self, batcher: Any, flight: Any, outputs: list[T.CheckOutput]) -> None:
        """Called by a batcher lane after a device batch settled OK. Cheap
        when the batch is not sampled; otherwise snapshots references and
        hands off to the replay thread. Never raises, never blocks."""
        try:
            shard = batcher.shard_id or 0
            if not self.should_sample(shard):
                return
            group = flight.group
            inputs: list[T.CheckInput] = []
            for p in group:
                inputs.extend(p.inputs)
            with self._lock:
                self.recent.extend(inputs)
            ev = batcher.evaluator
            sample = _Sample(
                shard=shard,
                inputs=inputs,
                outputs=list(outputs),
                params=group[0].params if group else None,
                # capture the table the device batch actually ran against so
                # a concurrent policy swap can't manufacture a divergence
                rule_table=getattr(ev, "rule_table", None),
                schema_mgr=getattr(ev, "schema_mgr", None),
                batch_id=flight.batch_id,
                trace_ids=sorted(
                    {p.ctx.trace_id for p in group if getattr(p, "ctx", None) is not None}
                ),
                done_at=self._clock(),
                health=getattr(batcher, "health", None),
            )
            with self._wakeup:
                if len(self._backlog) >= self.max_backlog:
                    self.stats["dropped"] += 1
                    self.m_dropped.inc()
                    return
                self._backlog.append(sample)
                self._wakeup.notify()
            self._ensure_worker()
        except Exception:  # noqa: BLE001  (diagnostics must never hurt serving)
            _log.exception("parity sentinel observe_batch failed")

    def observe_inline(self, shard: int, inputs: Sequence[T.CheckInput]) -> None:
        """Called on a request's own thread after the batcher answered it from
        the CPU oracle with no flight (``BatchingEvaluator._serve_inline``).
        Replaying an oracle answer against the oracle proves nothing, so there
        is no sample and no replay; but ``recent`` is what the rollout gate
        replays before a cutover, and a host that serves one-resource checks
        alone would leave it empty: the inputs are offered to the ring at the
        sampler's own rate. Never raises, never blocks."""
        try:
            if self.should_sample(shard):
                self.remember(inputs)
        except Exception:  # noqa: BLE001  (diagnostics must never hurt serving)
            _log.exception("parity sentinel observe_inline failed")

    def remember(self, inputs: Sequence[T.CheckInput]) -> None:
        """Put inputs into the ring of recently served ones, unsampled: the
        caller sampled them (``observe_inline``; a pool's front end, which
        samples what it answers itself at ``sample_rate`` and sends the owner
        the sample, engine/ipc.py)."""
        if self.enabled and not self._shed:
            with self._lock:
                self.recent.extend(inputs)

    def should_sample_plan(self, shard: int) -> bool:
        """Plan-lane twin of :meth:`should_sample` — same deterministic
        fractional accumulator, separate per-shard state, same first-batch
        guarantee (acc starts at 1.0)."""
        if not self.enabled or self._shed:
            return False
        with self._lock:
            st = self._plan_lanes.setdefault(shard, _LaneState())
            st.seen += 1
            st.acc += self._effective_rate()
            if st.acc < 1.0:
                return False
            st.acc -= 1.0
            st.sampled += 1
            return True

    def observe_plan_batch(
        self,
        batcher: Any,
        inputs: list[Any],
        params: Optional[T.EvalParams],
        outputs: list[Any],
    ) -> None:
        """Called after a batched-planner flight settled OK. Snapshots the
        PlanInputs/PlanOutputs and the table the batch ran against, then
        hands off to the replay thread, which re-plans every query through
        an independent sequential :class:`~cerbos_tpu.plan.Planner` and
        compares serialized filter ASTs byte-for-byte. Never raises."""
        try:
            shard = getattr(batcher, "shard_id", 0) or 0
            if not self.should_sample_plan(shard):
                return
            planner = getattr(batcher, "plan_planner", None) or batcher
            sample = _PlanSample(
                shard=shard,
                inputs=list(inputs),
                outputs=list(outputs),
                params=params,
                rule_table=getattr(planner, "rt", None),
                schema_mgr=getattr(planner, "schema_mgr", None),
                batch_id=getattr(batcher, "_batch_seq", 0),
                done_at=self._clock(),
            )
            with self._wakeup:
                if len(self._backlog) >= self.max_backlog:
                    self.stats["dropped"] += 1
                    self.m_dropped.inc()
                    return
                self._backlog.append(sample)
                self._wakeup.notify()
            self._ensure_worker()
        except Exception:  # noqa: BLE001  (diagnostics must never hurt serving)
            _log.exception("parity sentinel observe_plan_batch failed")

    # -- background replay ---------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._wakeup:
                while not self._backlog and not self._stop:
                    self._wakeup.wait(timeout=1.0)
                if self._stop and not self._backlog:
                    return
                sample = self._backlog.popleft()
                self._inflight += 1
            try:
                if isinstance(sample, _PlanSample):
                    self._verify_plan(sample)
                else:
                    self._verify(sample)
            except Exception:  # noqa: BLE001
                _log.exception("parity sentinel verification failed")
            finally:
                with self._lock:
                    self._inflight -= 1

    def _verify(self, s: _Sample) -> None:
        t0 = time.perf_counter()
        device = effect_rows(s.outputs)
        params = s.params or T.EvalParams()
        oracle: list[dict]
        oracle_prov: list[dict] = []
        replay_error = ""
        try:
            oracle_outputs = [
                check_input(s.rule_table, i, params, s.schema_mgr, ROUTE_SHADOW) for i in s.inputs
            ]
            oracle = effect_rows(oracle_outputs)
            oracle_prov = provenance_rows(oracle_outputs)
        except Exception as e:  # noqa: BLE001  (an oracle crash IS a divergence signal)
            replay_error = f"{type(e).__name__}: {e}"
            oracle = []
        replay_s = time.perf_counter() - t0
        lag = max(0.0, self._clock() - s.done_at)
        shard_label = str(s.shard)
        self.stats["checks"] += 1
        self.stats["replay_seconds"] += replay_s
        self.m_checks.inc(shard_label)
        self.m_replay_seconds.inc(replay_s)
        self.m_lag.observe(lag)
        if replay_error:
            self.stats["replay_errors"] += 1
        diff = compare_rows(device, oracle) if not replay_error else list(range(len(device)))
        if not diff:
            return
        self._divergence(s, device, oracle, diff, replay_error, lag, oracle_prov)

    def _verify_plan(self, s: _PlanSample) -> None:
        """Byte-exact filter-AST parity: serialize both planners' outputs
        with sorted keys and compare the strings. No storm trip — a plan
        divergence is a planner bug, not a sick chip, so it is counted and
        captured but never routes check traffic to the oracle."""
        from ..plan import Planner

        t0 = time.perf_counter()
        replay_error = ""
        diff: list[int] = []
        device = [json.dumps(o.to_json(), sort_keys=True) for o in s.outputs]
        sequential: list[str] = []
        try:
            planner = Planner(s.rule_table, s.schema_mgr)
            for i in s.inputs:
                sequential.append(
                    json.dumps(planner.plan(i, s.params).to_json(), sort_keys=True)
                )
        except Exception as e:  # noqa: BLE001  (a replay crash IS a divergence signal)
            replay_error = f"{type(e).__name__}: {e}"
        if replay_error:
            diff = list(range(len(device)))
        else:
            n = min(len(device), len(sequential))
            diff = [i for i in range(n) if device[i] != sequential[i]]
            diff.extend(range(n, max(len(device), len(sequential))))
        replay_s = time.perf_counter() - t0
        lag = max(0.0, self._clock() - s.done_at)
        self.stats["plan_checks"] += 1
        self.stats["checks"] += 1
        self.stats["replay_seconds"] += replay_s
        if replay_error:
            self.stats["replay_errors"] += 1
        self.m_plan_checks.inc()
        self.m_replay_seconds.inc(replay_s)
        self.m_lag.observe(lag)
        if not diff:
            return
        self.stats["plan_divergences"] += 1
        self.stats["divergences"] += 1
        self.m_plan_divergence.inc()
        record = {
            "ts": time.time(),
            "kind": "plan",
            "shard": s.shard,
            "batch_id": s.batch_id,
            "lag_seconds": round(lag, 6),
            "divergent_indices": diff,
            "replay_error": replay_error,
            "device_filters": device,
            "sequential_filters": sequential,
        }
        path = None
        try:
            path = self.corpus.append(record)
        except Exception:  # noqa: BLE001
            _log.exception("failed to persist plan divergence record")
        self.m_corpus.set(float(self.corpus.size()))
        flight_recorder().record_event(
            "plan_parity_divergence",
            shard=s.shard,
            batch_id=s.batch_id,
            inputs=len(s.inputs),
            divergent=len(diff),
            corpus_path=path,
            replay_error=replay_error or None,
        )
        _log.error(
            "PLAN PARITY DIVERGENCE: batched filter AST differs from the sequential planner",
            extra={
                "fields": {
                    "shard": s.shard,
                    "inputs": len(s.inputs),
                    "divergent": len(diff),
                    "corpus": path,
                }
            },
        )

    def _divergence(
        self,
        s: _Sample,
        device: list[dict],
        oracle: list[dict],
        diff: list[int],
        replay_error: str,
        lag: float,
        oracle_prov: Optional[list[dict]] = None,
    ) -> None:
        self.stats["divergences"] += 1
        self.m_divergence.inc(str(s.shard))
        record = {
            "ts": time.time(),
            "shard": s.shard,
            "batch_id": s.batch_id,
            "trace_ids": s.trace_ids,
            "lag_seconds": round(lag, 6),
            "divergent_indices": diff,
            "replay_error": replay_error,
            "inputs": [input_to_json(i) for i in s.inputs],
            "device_effects": device,
            "oracle_effects": oracle,
            # both sides' winning rules: not compared for parity, but triage
            # wants to know which rule each path claims won
            "device_provenance": provenance_rows(s.outputs),
            "oracle_provenance": oracle_prov or [],
        }
        path = None
        try:
            path = self.corpus.append(record)
        except Exception:  # noqa: BLE001  (a full disk must not kill the sentinel)
            _log.exception("failed to persist divergence record")
        self.m_corpus.set(float(self.corpus.size()))
        flight_recorder().record_event(
            "parity_divergence",
            shard=s.shard,
            batch_id=s.batch_id,
            inputs=len(s.inputs),
            divergent=len(diff),
            trace_ids=s.trace_ids,
            corpus_path=path,
            replay_error=replay_error or None,
        )
        _log.error(
            "PARITY DIVERGENCE: device effects differ from the CPU oracle",
            extra={
                "fields": {
                    "shard": s.shard,
                    "inputs": len(s.inputs),
                    "divergent": len(diff),
                    "corpus": path,
                }
            },
        )
        self._storm_check(s)

    def _storm_check(self, s: _Sample) -> None:
        now = self._clock()
        trip = False
        with self._lock:
            st = self._lanes.setdefault(s.shard, _LaneState())
            st.divergences.append(now)
            horizon = now - self.window_sec
            while st.divergences and st.divergences[0] < horizon:
                st.divergences.popleft()
            if len(st.divergences) >= self.storm_threshold and now >= st.storm_until:
                # re-arm: a continuing storm re-trips after the window, not
                # on every divergence (the breaker's probe machinery needs
                # room to attempt recovery)
                st.storm_until = now + self.window_sec
                trip = True
        if not trip:
            return
        self.stats["storms"] += 1
        self.m_storms.inc(str(s.shard))
        flight_recorder().record_event(
            "parity_storm",
            shard=s.shard,
            divergences=self.storm_threshold,
            window_sec=self.window_sec,
        )
        _log.error(
            "parity storm: tripping shard %d to the CPU oracle (correct-over-fast)",
            s.shard,
        )
        health = s.health
        if health is not None:
            try:
                health.trip("parity_storm")
            except Exception:  # noqa: BLE001
                _log.exception("failed to trip breaker for parity storm")

    # -- readiness / reporting ----------------------------------------------

    def storm_shards(self) -> list[int]:
        """Shards currently inside a parity storm window — the readiness
        ``parity`` degradation reason. A storm clears once the sliding
        window slides past its divergences."""
        now = self._clock()
        out = []
        with self._lock:
            for shard, st in sorted(self._lanes.items()):
                horizon = now - self.window_sec
                while st.divergences and st.divergences[0] < horizon:
                    st.divergences.popleft()
                if now < st.storm_until or len(st.divergences) >= self.storm_threshold:
                    out.append(shard)
        return out

    def backlog(self) -> int:
        with self._lock:
            return len(self._backlog)

    def drain(self, timeout: float = 5.0) -> bool:
        """Block until the backlog is fully replayed (tests, bench teardown).
        True when drained; False on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                # a popped-but-unverified sample (self._inflight) must hold
                # drain open: stats for it land only after verification
                if not self._backlog and not self._inflight:
                    return True
            time.sleep(0.005)
        return False

    def snapshot(self) -> dict:
        """Counts since start (checks, divergences, storms, lag, replay
        seconds), per lane and in all; ``tests/test_parity_sentinel.py`` is
        its reader (no endpoint serves it yet)."""
        with self._lock:
            lanes = {
                shard: {"seen": st.seen, "sampled": st.sampled}
                for shard, st in sorted(self._lanes.items())
            }
            stats = dict(self.stats)
        return {
            "enabled": self.enabled,
            "sample_rate": self.sample_rate,
            "shed": self._shed,
            "window_sec": self.window_sec,
            "storm_threshold": self.storm_threshold,
            "checks": stats["checks"],
            "divergences": stats["divergences"],
            "dropped": stats["dropped"],
            "storms": stats["storms"],
            "replay_errors": stats["replay_errors"],
            "plan_checks": stats["plan_checks"],
            "plan_divergences": stats["plan_divergences"],
            "replay_seconds": round(stats["replay_seconds"], 6),
            "lag_p99_s": round(self.m_lag.percentile(0.99), 6),
            "corpus_records": self.corpus.size(),
            "lanes": lanes,
        }


def from_config(conf: dict, clock: Callable[[], float] = time.monotonic) -> ParitySentinel:
    """Build a sentinel from the ``engine.tpu.paritySentinel`` config map."""
    conf = conf or {}
    return ParitySentinel(
        sample_rate=float(conf.get("sampleRate", DEFAULT_SAMPLE_RATE)),
        window_sec=float(conf.get("windowSec", DEFAULT_WINDOW_SEC)),
        storm_threshold=int(conf.get("stormThreshold", DEFAULT_STORM_THRESHOLD)),
        corpus_dir=str(conf.get("corpusDir", "") or ""),
        corpus_max=int(conf.get("corpusMax", DEFAULT_CORPUS_MAX)),
        max_backlog=int(conf.get("maxBacklog", DEFAULT_BACKLOG)),
        enabled=bool(conf.get("enabled", True)),
        clock=clock,
    )
