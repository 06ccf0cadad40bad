"""Engine facade: batch dispatch over the rule table.

Behavioral reference: internal/engine/engine.go (Check entry, audit hook).
The reference fans small batches onto a goroutine pool; here the batch path
is the TPU evaluator (cerbos_tpu.tpu) and the CPU oracle serves small
batches serially, mirroring the reference's parallelismThreshold=5 split.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Callable, Optional, Sequence

from . import types as T
from .hotrules import recorder as hotrule_recorder

if TYPE_CHECKING:  # avoid circular imports (ruletable.check imports engine.types)
    from ..compile.compiler import CompiledPolicy
    from ..ruletable import RuleTable


class Engine:
    def __init__(
        self,
        rule_table: "RuleTable",
        schema_mgr: Any = None,
        eval_params: Optional[T.EvalParams] = None,
        tpu_evaluator: Any = None,
        tpu_batch_threshold: int = 5,
        on_decision: Optional[Callable[[list[T.CheckInput], list[T.CheckOutput]], None]] = None,
    ):
        self.rule_table = rule_table
        self.schema_mgr = schema_mgr
        self.eval_params = eval_params or T.EvalParams()
        self.tpu_evaluator = tpu_evaluator
        self.tpu_batch_threshold = tpu_batch_threshold
        self.on_decision = on_decision

    @classmethod
    def from_policies(cls, policies: "list[CompiledPolicy]", **kwargs) -> "Engine":
        from ..ruletable import build_rule_table

        return cls(build_rule_table(policies), **kwargs)

    def check(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        from ..observability import start_span

        params = params or self.eval_params
        with start_span("engine.Check", batch_size=len(inputs)) as span:
            kwargs = self._route(span, len(inputs), wf, pclass, awaited=False)
            if kwargs is None:
                outputs = self._serial(inputs, params, wf)
            else:
                # the one difference between the two doors: a blocking
                # evaluator is given the request's deadline (from the gRPC
                # context; the batcher drops expired work at drain time) only
                # if it says it takes one, and only when there is one
                if deadline is not None and getattr(self.tpu_evaluator, "supports_deadline", False):
                    kwargs["deadline"] = deadline
                outputs = self.tpu_evaluator.check(list(inputs), params, **kwargs)
                self._evaluated(wf, kwargs)
        return self._decided(inputs, outputs)

    @property
    def supports_async(self) -> bool:
        """True when the dispatch evaluator can settle checks on an asyncio
        loop (the RemoteBatcherClient in front-end mode). The HTTP server
        uses this to skip the per-request thread-pool hop entirely."""
        return self.tpu_evaluator is not None and hasattr(self.tpu_evaluator, "check_await")

    async def check_await(
        self,
        inputs: Sequence[T.CheckInput],
        params: Optional[T.EvalParams] = None,
        deadline: Optional[float] = None,
        wf: Optional[Any] = None,
        pclass: Optional[str] = None,
    ) -> list[T.CheckOutput]:
        """Event-loop-native check: awaits the evaluator's reply future with
        no executor hop. Small batches below the device threshold still take
        the serial oracle inline: at threshold sizes that is cheaper than a
        loop hand-off."""
        from ..observability import start_span

        params = params or self.eval_params
        with start_span("engine.Check", batch_size=len(inputs)) as span:
            kwargs = self._route(span, len(inputs), wf, pclass, awaited=True)
            if kwargs is None:
                outputs = self._serial(inputs, params, wf)
            else:
                # ...and an evaluator with ``check_await`` always takes the
                # deadline, None included: the name is part of that signature
                outputs = await self.tpu_evaluator.check_await(list(inputs), params, deadline=deadline, **kwargs)
                self._evaluated(wf, kwargs)
        return self._decided(inputs, outputs)

    def _route(self, span: Any, n: int, wf: Any, pclass: Optional[str], awaited: bool) -> Optional[dict]:
        """Device or serial, on the span; for the device route, the keyword
        arguments the evaluator says it supports, and None for the serial
        walk (no evaluator, a batch under the threshold, or a caller that
        awaits an evaluator that cannot be awaited)."""
        ev = self.tpu_evaluator
        if ev is None or n < self.tpu_batch_threshold or (awaited and not hasattr(ev, "check_await")):
            span.set_attribute("path", "serial")
            return None
        span.set_attribute("path", "device")
        kwargs = {}
        if wf is not None and getattr(ev, "supports_waterfall", False):
            kwargs["wf"] = wf
        if pclass is not None and getattr(ev, "supports_pclass", False):
            # admission class rides down to the batcher's priority
            # lanes (queue budget + weighted scheduling)
            kwargs["pclass"] = pclass
        return kwargs

    @staticmethod
    def _evaluated(wf: Any, kwargs: dict) -> None:
        if wf is not None and "wf" not in kwargs:
            # evaluator without stage bookkeeping: the whole device
            # call books as one evaluate stage
            wf.mark("evaluate")

    def _serial(self, inputs: Sequence[T.CheckInput], params: T.EvalParams, wf: Any) -> list[T.CheckOutput]:
        from ..ruletable import check_input

        # read the table once: a rollout cutover between inputs must
        # not split one request across two tables, and the epoch
        # stamp must describe the table actually used
        rt = self.rule_table
        T.set_current_epoch(getattr(rt, "policy_epoch", None))
        outputs = [check_input(rt, i, params, self.schema_mgr) for i in inputs]
        # serial decisions bypass the batcher: fold them into the
        # hot-rule heatmap here so attribution telemetry stays
        # complete on low-traffic hosts (ISSUE 20)
        hotrule_recorder().observe(outputs)
        if wf is not None:
            wf.mark("evaluate")
        return outputs

    def _decided(self, inputs: Sequence[T.CheckInput], outputs: list[T.CheckOutput]) -> list[T.CheckOutput]:
        if self.on_decision is not None:
            self.on_decision(list(inputs), outputs)
        return outputs
