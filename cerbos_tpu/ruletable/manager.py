"""Rule-table manager: storage events → recompile → re-lower device tables.

Behavioral reference: internal/ruletable/manager.go — RELOAD rebuilds the
whole table; ADD/DELETE recompile the affected policy and its dependents
atomically under a write lock; failures keep the last valid state
(manager.go:74-84,108-111). The TPU twist (SURVEY.md §3.4): after a
successful swap, the lowered device tables are refreshed.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Optional

from .. import bootclock
from ..compile import CompileError, compile_policy_set
from ..storage.store import Event, Store
from .table import RuleTable, build_rule_table

log = logging.getLogger("cerbos_tpu.ruletable")


class RuleTableManager:
    def __init__(
        self,
        store: Store,
        on_swap: Optional[Callable[[RuleTable], None]] = None,
        prebuilt_table: Optional[RuleTable] = None,
    ):
        self.store = store
        self.on_swap = on_swap
        # when a RolloutController is attached (bootstrap), storage events
        # are delegated to its staged build→gate→cutover path and the
        # on_swap chain is never consulted; a gate-rejected bundle leaves
        # self.rule_table untouched
        self.rollout: Optional[Any] = None
        self._lock = threading.RLock()
        # a prebuilt table (bootstrap.prebuild, COW-shared across forked
        # workers) skips the parse+compile+build pipeline; storage events
        # still rebuild from this process's own store
        self.rule_table = prebuilt_table if prebuilt_table is not None else self._build()
        store.subscribe(self.on_storage_event)

    def _build(self) -> RuleTable:
        from ..util import gctune

        with gctune.build_phase():
            # a BinaryStore-style bundle can carry the compiled IR, skipping
            # the parse+compile pipeline (the RuleTableStore fast path)
            # the marks book a process's FIRST build to its boot clock and
            # do nothing once it is ready (a rebuild after a push)
            get_compiled = getattr(self.store, "get_compiled", None)
            compiled = get_compiled() if get_compiled is not None else None
            if compiled is None:
                policies = self.store.get_all()
                bootclock.mark(bootclock.LOAD)
                compiled = compile_policy_set(policies)
                bootclock.mark(bootclock.COMPILE)
            else:
                bootclock.mark(bootclock.LOAD)
            table = build_rule_table(compiled)
            bootclock.mark(bootclock.TABLE)
            return table

    def build_table(self) -> RuleTable:
        """Build a fresh table off the serving path (the rollout
        controller's shadow-build stage). ``self.rule_table`` is untouched."""
        with self._lock:
            return self._build()

    def commit_table(self, new_table: RuleTable) -> None:
        """Atomically publish a gated table (the rollout controller's
        cutover stage — called inside the lane drain barrier)."""
        with self._lock:
            self.rule_table = new_table

    def on_storage_event(self, events: list[Event]) -> None:
        """Rebuild into a fresh table and swap the pointer atomically, so
        in-flight checks keep reading a consistent table and failures keep
        the last valid state (ref: manager.go:74-84,108-111). Incremental
        delete/ingest on the live table stays available to the Admin API via
        RuleTable directly; the event path always swaps whole tables, which
        doubles as the device-table double-buffering (SURVEY.md §7.8).

        With a rollout controller attached, the whole sequence — shadow
        build, analyzer gate, differential replay, epoch-versioned barrier
        cutover, canary — replaces the bare build-and-swap below."""
        if self.rollout is not None:
            self.rollout.on_storage_event(events)
            return
        with self._lock:
            try:
                new_table = self._build()
            except CompileError as e:
                log.error("policy reload failed; keeping last valid state: %s", e)
                return
            except Exception:  # noqa: BLE001
                log.exception("policy reload failed; keeping last valid state")
                return
            self.rule_table = new_table
        if self.on_swap is not None:
            self.on_swap(self.rule_table)

    def evaluator_refresh_hook(self, evaluator: Any) -> None:
        """Wire a TpuEvaluator so reloads re-lower the device tables."""
        original = self.on_swap

        def hook(rt: RuleTable) -> None:
            evaluator.rule_table = rt
            evaluator.lowered.table = rt
            evaluator.refresh()
            if original is not None:
                original(rt)

        self.on_swap = hook
