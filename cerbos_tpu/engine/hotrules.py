"""Bounded hot-rule telemetry: which rules live traffic actually lands on.

Decision provenance (ISSUE 20) stamps every ActionEffect with the winning
rule-table row id. This module aggregates those ids into a fixed-size hit
array indexed by ``rule_row_id`` — one int64 per lowered rule row, ZERO
label-cardinality risk — and exposes:

* a top-K snapshot for ``/_cerbos/debug/hotrules`` (rule FQN, analyzer
  class, hit count, traffic share), the ranking input for the oracle-
  extinction burn-down (ROADMAP item 5);
* a ``cerbos_tpu_rule_hits_total{class}`` rollup keyed by the PR-14 static
  analyzer class (device / tagged-fallback / oracle-only / unknown) plus
  the per-source split (device vs oracle) and the unattributed remainder —
  operators see what fraction of live decisions lands on device-eligible
  rules without per-rule metric series.

The recorder is process-global (mirrors engine/flight.py): every batcher
lane feeds the same array, the IPC control plane snapshots it from the
batcher process, and the counts survive batcher restarts within the
process. Aggregation happens after request settle (alongside the parity
sentinel's observe hook), so it never adds to request latency.
"""

from __future__ import annotations

import threading
from typing import Any, Optional, Sequence

import numpy as np

from .. import observability as obs
from . import types as T

# hard cap on the hit array: a rule table bigger than this only tracks the
# first _MAX_ROWS rows (counts beyond fold into "unattributed")
_MAX_ROWS = 1 << 20

# observe() buffers raw counts in plain dicts and defers the numpy fold +
# metric increments until this many decisions accumulate: at small batch
# sizes (the served path coalesces 1-4 requests per flight) the per-batch
# fold cost would not amortize, and the drain thread shares the core with
# serving on 1-core hosts
_FLUSH_EVERY = 256

_CLASS_UNKNOWN = "unknown"


class HotRuleRecorder:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hits = np.zeros(0, dtype=np.int64)
        self._decisions = 0
        self._unattributed = 0
        self._by_source: dict[str, int] = {}
        # pending micro-buffer (raw counts, folded on flush): rid -> count
        # (rid outside [0, _MAX_ROWS) folds into "unattributed"), src -> count
        self._pend_rows: dict[int, int] = {}
        self._pend_src: dict[str, int] = {}
        self._pend_n = 0
        # analyzer-class cache: rebuilt lazily whenever tpu.analyze publishes
        # a new report (identity-compared — publish() swaps the object)
        self._cls_report: Any = None
        self._cls_by_row: dict[int, str] = {}
        reg = obs.metrics()
        self.m_rule_hits = reg.counter_vec(
            "cerbos_tpu_rule_hits_total",
            "decisions attributed to a winning rule, by static-analyzer class "
            "(device/tagged-fallback/oracle-only; 'unknown' when no analysis "
            "report is published, 'unattributed' when no rule fired)",
            label="class",
        )
        self.m_decision_source = reg.counter_vec(
            "cerbos_tpu_decision_source_total",
            "decisions by evaluator provenance (device vs CPU-oracle)",
            label="source",
        )

    # -- ingest --------------------------------------------------------------

    def observe(self, outputs: Sequence[T.CheckOutput]) -> None:
        """Fold one settled batch's decisions into the hit array. Never
        raises; called after futures settle so it adds no request latency."""
        try:
            self._observe(outputs)
        except Exception:  # noqa: BLE001 - telemetry must never break serving
            pass

    def _observe(self, outputs: Sequence[T.CheckOutput]) -> None:
        # hot path: dict increments only — the numpy fold, analyzer-class
        # resolution, and metric increments happen at flush (every
        # _FLUSH_EVERY decisions or on snapshot), so per-batch cost stays
        # a few microseconds even at 1-2 decisions per flight
        flush = None
        with self._lock:
            pr, ps = self._pend_rows, self._pend_src
            n = 0
            for o in outputs:
                for ae in o.actions.values():
                    rid = getattr(ae, "rule_row_id", -1)
                    src = getattr(ae, "source", "") or "unknown"
                    pr[rid] = pr.get(rid, 0) + 1
                    ps[src] = ps.get(src, 0) + 1
                    n += 1
            self._pend_n += n
            if self._pend_n >= _FLUSH_EVERY:
                flush = self._flush_locked()
        if flush:
            self._publish(flush)

    def _flush_locked(self) -> Optional[tuple[dict[str, int], int, dict[str, int]]]:
        """Fold the pending micro-buffer into the hit array and the aggregate
        counters. Caller holds the lock; returns the (class, unattributed,
        source) rollup for _publish(), or None when nothing was pending."""
        if not self._pend_n:
            return None
        rows: dict[int, int] = {}
        unattributed = 0
        for rid, n in self._pend_rows.items():
            if 0 <= rid < _MAX_ROWS:
                rows[rid] = n
            else:
                unattributed += n
        src_counts = self._pend_src
        self._pend_rows, self._pend_src, self._pend_n = {}, {}, 0
        self._decisions += sum(rows.values()) + unattributed
        self._unattributed += unattributed
        for s, n in src_counts.items():
            self._by_source[s] = self._by_source.get(s, 0) + n
        cls_counts: dict[str, int] = {}
        if rows:
            top = max(rows)
            if top >= self._hits.size:
                grown = np.zeros(max(top + 1, self._hits.size * 2, 256), dtype=np.int64)
                grown[: self._hits.size] = self._hits
                self._hits = grown
            cls_map = self._class_map()
            for rid, n in rows.items():
                self._hits[rid] += n
                cls = cls_map.get(rid, _CLASS_UNKNOWN) if cls_map else _CLASS_UNKNOWN
                cls_counts[cls] = cls_counts.get(cls, 0) + n
        return (cls_counts, unattributed, src_counts)

    def _publish(self, flush: tuple[dict[str, int], int, dict[str, int]]) -> None:
        """Metric rollups for one flush: one inc per class/source, not per
        decision. Outside the lock — the registry has its own."""
        cls_counts, unattributed, src_counts = flush
        for cls, n in cls_counts.items():
            self.m_rule_hits.inc(cls, n)
        if unattributed:
            self.m_rule_hits.inc("unattributed", unattributed)
        for s, n in src_counts.items():
            self.m_decision_source.inc(s, n)

    # -- class + rule resolution ---------------------------------------------

    def _class_map(self) -> dict[int, str]:
        """row_id → analyzer eligibility class, from the latest published
        static-analysis report (tpu/analyze.py). Rebuilt when the report
        object changes (bootstrap publish / policy-swap republish)."""
        try:
            from ..tpu import analyze as analyze_mod

            report = analyze_mod.latest()
        except Exception:  # noqa: BLE001
            report = None
        if report is self._cls_report:
            return self._cls_by_row
        mapping: dict[int, str] = {}
        if report is not None:
            for rep in getattr(report, "rules", ()):
                rid = getattr(rep, "row_id", -1)
                if rid >= 0:
                    mapping[rid] = rep.eligibility
        self._cls_by_row = mapping
        self._cls_report = report
        return mapping

    @staticmethod
    def _rule_label(rule_table: Any, rid: int) -> dict[str, Any]:
        """Resolve a row id to its rule FQN against the CURRENT table. After
        an epoch swap old-row hits may resolve to a different (or no) rule —
        acceptable for a debug heatmap, called out in the endpoint payload."""
        row = None
        if rule_table is not None:
            try:
                rows = rule_table.idx.rows  # list indexed by row id
                row = rows[rid] if 0 <= rid < len(rows) else None
            except Exception:  # noqa: BLE001
                row = None
        if row is None:
            return {"rule_row_id": rid, "rule": None, "policy": None}
        from ..ruletable.check import _rule_src

        try:
            meta = rule_table.get_meta(row.origin_fqn)
            src = _rule_src(meta, row)
        except Exception:  # noqa: BLE001
            src = f"{row.origin_fqn}#{getattr(row, 'name', '')}"
        policy, _, rule = src.partition("#")
        return {"rule_row_id": rid, "rule": src, "policy": policy, "rule_name": rule}

    # -- snapshot ------------------------------------------------------------

    def snapshot(self, k: int = 20, rule_table: Any = None) -> dict[str, Any]:
        """Top-K hit rows plus the aggregate split — the
        ``/_cerbos/debug/hotrules`` payload and the ``analyze --hot`` input."""
        with self._lock:
            flush = self._flush_locked()
            hits = self._hits.copy()
            decisions = self._decisions
            unattributed = self._unattributed
            by_source = dict(self._by_source)
        if flush:
            self._publish(flush)
        k = max(1, min(int(k), 1000))
        nz = np.nonzero(hits)[0]
        order = nz[np.argsort(hits[nz])[::-1][:k]]
        cls_map = self._class_map()
        attributed = int(hits.sum())
        top = []
        for rid in order.tolist():
            entry = self._rule_label(rule_table, rid)
            entry["hits"] = int(hits[rid])
            entry["share"] = round(entry["hits"] / attributed, 6) if attributed else 0.0
            entry["class"] = cls_map.get(rid, _CLASS_UNKNOWN) if cls_map else _CLASS_UNKNOWN
            top.append(entry)
        by_class: dict[str, int] = {}
        for rid in nz.tolist():
            cls = cls_map.get(rid, _CLASS_UNKNOWN) if cls_map else _CLASS_UNKNOWN
            by_class[cls] = by_class.get(cls, 0) + int(hits[rid])
        return {
            "decisions": decisions,
            "attributed": attributed,
            "unattributed": unattributed,
            "attribution_rate": round(attributed / decisions, 6) if decisions else 0.0,
            "by_source": by_source,
            "by_class": by_class,
            "tracked_rows": int(hits.size),
            "top": top,
            # labels resolve against the current table: counts recorded
            # under an older policy epoch may rename after a swap
            "note": "row labels resolved against the current policy epoch",
        }

    def reset(self) -> None:
        with self._lock:
            self._hits = np.zeros(0, dtype=np.int64)
            self._decisions = 0
            self._unattributed = 0
            self._by_source = {}
            self._pend_rows, self._pend_src, self._pend_n = {}, {}, 0


_recorder: Optional[HotRuleRecorder] = None
_recorder_lock = threading.Lock()


def recorder() -> HotRuleRecorder:
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = HotRuleRecorder()
    return _recorder
