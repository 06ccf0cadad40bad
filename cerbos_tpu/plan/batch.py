"""BatchPlanner: vectorized partial evaluation for PlanResources.

Evaluates a batch of plan queries in one device dispatch. Every condition
kernel in the lowered rule table is evaluated over the whole batch exactly
as the check path does — resource attributes the query supplies in
``known_attrs`` are encoded into the SoA columns, everything else encodes
as missing — and each (query, condition) pair then resolves to a ternary
verdict:

* **TRUE / FALSE** — the kernel is statically residualizable
  (``CondKernel.plan_reason is None``) and every resource-rooted dependency
  is known for this query, so the device sat bit equals what concrete host
  evaluation would produce (missing-principal-attr errors collapse to FALSE
  on both paths).
* **RESIDUAL** — anything else: the walk falls back to the sequential
  planner's symbolic :class:`~cerbos_tpu.plan.partial.PartialEvaluator`,
  which produces the identical filter-AST fragment the sequential planner
  would, byte for byte.

The role/scope walk itself is inherited unchanged from :class:`Planner`;
only the two condition-evaluation seams (``_binding_node`` /
``_derived_role_node``) are overridden, so the combination machinery
(``_or``/``_and``/``_not``, gate-by-child-override, RPC pending allows …)
is shared code, not a reimplementation. Routing is decided statically at
compile time (``condcompile.plan_verdict``) — the runtime never guesses.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ..engine import types as T
from .planner import FALSE, TRUE, Planner
from .types import PlanInput, PlanOutput

_RESIDUAL_BUCKETS = [0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]


@dataclass
class _QueryCtx:
    """Per-query routing context, live only while its walk runs."""

    sat_row: Optional[np.ndarray]
    known: frozenset
    oracle: bool  # fallback tag fired while encoding this query's columns
    device_rules: int = 0
    symbolic_rules: int = 0


@dataclass
class BatchStats:
    """Cumulative routing counters (also exported as metrics)."""

    batches: int = 0
    queries: int = 0
    device_queries: int = 0  # resolved without any symbolic fallback
    symbolic_queries: int = 0
    memo_queries: int = 0  # exact duplicates of an earlier query in the batch
    device_rules: int = 0
    symbolic_rules: int = 0

    def as_dict(self) -> dict[str, int]:
        return {
            "batches": self.batches,
            "queries": self.queries,
            "device_queries": self.device_queries,
            "symbolic_queries": self.symbolic_queries,
            "memo_queries": self.memo_queries,
            "device_rules": self.device_rules,
            "symbolic_rules": self.symbolic_rules,
        }


class BatchPlanner(Planner):
    """Plan many (principal, action) queries against one device dispatch.

    Owns its own :class:`LoweredTable` by default (separate compiler and
    string interner, so concurrent check batches never race the plan path;
    pass ``lowered=`` to share one). ``globals_`` must match the globals the
    serving params carry — a mismatched batch routes every query symbolic
    rather than risk a divergent constant fold.
    """

    def __init__(
        self,
        rule_table,
        schema_mgr: Any = None,
        globals_: Optional[dict[str, Any]] = None,
        lowered: Any = None,
        use_jax: bool = False,
    ):
        super().__init__(rule_table, schema_mgr=schema_mgr)
        self._globals = dict(globals_ or {})
        self._lowered = lowered
        self._packer = None
        self._use_jax = use_jax
        self._need_attrs_cache: dict[int, frozenset] = {}
        self._lock = threading.Lock()  # serializes batch encodes
        self._tls = threading.local()  # per-thread query context
        self.stats = BatchStats()
        self._init_metrics()

    #: max per-bucket candidates compared during batch dedup
    DEDUP_SCAN = 8

    def _init_metrics(self) -> None:
        from ..observability import metrics

        reg = metrics()
        self.m_batch = reg.histogram_vec(
            "cerbos_tpu_plan_batch_seconds",
            "Wall time of one batched PlanResources dispatch, by evaluation mode",
            label="mode",
        )
        self.m_queries = reg.counter_vec(
            "cerbos_tpu_plan_queries_total",
            "Plan queries by resolution path: device = every condition resolved "
            "on the ternary device path, symbolic = at least one sequential "
            "PartialEvaluator fallback",
            label="path",
        )
        self.m_residual = reg.histogram(
            "cerbos_tpu_plan_residual_rules",
            "Per plan query: rules that fell back to symbolic partial evaluation",
            buckets=_RESIDUAL_BUCKETS,
        )

    # -- lowering ----------------------------------------------------------

    def _lt(self):
        lt = self._lowered
        if lt is None:
            from ..tpu.lowering import lower_table

            lt = self._lowered = lower_table(self.rt, self._globals)
        return lt

    def _get_packer(self):
        p = self._packer
        if p is None:
            from ..tpu.packer import Packer

            p = self._packer = Packer(self._lt())
        return p

    def refresh(self, rule_table=None) -> None:
        """Drop lowered state after a policy swap; relowers lazily."""
        with self._lock:
            if rule_table is not None:
                self.rt = rule_table
            self._lowered = None
            self._packer = None
            self._need_attrs_cache.clear()

    # -- batch entry -------------------------------------------------------

    def plan_batch(
        self, inputs: list[PlanInput], params: Optional[T.EvalParams] = None
    ) -> list[PlanOutput]:
        """Evaluate a batch of plan queries; order-preserving.

        Queries that are field-identical except for ``request_id`` provably
        produce the same output (the walk never reads the id), so the batch
        is deduplicated first: only unique queries are encoded, dispatched
        and walked; duplicates clone the representative's output under their
        own request id and are booked as ``path="memo"``. Serving sweeps —
        the same (principal, action, kind) planned once per list request —
        collapse almost entirely.
        """
        params = params or T.EvalParams()
        with self._lock:
            t0 = time.perf_counter()
            uniques: list[PlanInput] = []
            order: list[int] = []
            buckets: dict[tuple, list[int]] = {}
            for q in inputs:
                p = q.principal
                key = (
                    q.resource_kind,
                    tuple(q.actions),
                    p.id,
                    q.resource_policy_version,
                    q.resource_scope,
                    len(q.resource_attr),
                    len(p.attr),
                )
                cands = buckets.setdefault(key, [])
                u = -1
                # bounded scan: best-effort dedup stays O(batch) even when an
                # adversarial batch funnels distinct queries into one bucket
                for c in cands[: self.DEDUP_SCAN]:
                    if self._same_query(uniques[c], q):
                        u = c
                        break
                if u < 0:
                    u = len(uniques)
                    uniques.append(q)
                    cands.append(u)
                order.append(u)
            plans, sat = self._device_sat(uniques, params)
            uout: list[PlanOutput] = []
            st = self.stats
            st.batches += 1
            for i, q in enumerate(uniques):
                ctx = _QueryCtx(
                    sat_row=None if sat is None else sat[i],
                    known=frozenset(str(k) for k in q.resource_attr),
                    oracle=plans[i].oracle if plans is not None else True,
                )
                self._tls.ctx = ctx
                try:
                    uout.append(self._plan(q, params))
                finally:
                    self._tls.ctx = None
                st.queries += 1
                st.device_rules += ctx.device_rules
                st.symbolic_rules += ctx.symbolic_rules
                if ctx.symbolic_rules:
                    st.symbolic_queries += 1
                    self.m_queries.inc("symbolic")
                else:
                    st.device_queries += 1
                    self.m_queries.inc("device")
                self.m_residual.observe(float(ctx.symbolic_rules))
            outputs: list[PlanOutput] = []
            memo = 0
            for q, u in zip(inputs, order):
                if uniques[u] is q:
                    outputs.append(uout[u])
                else:
                    outputs.append(self._clone_output(uout[u], q))
                    memo += 1
            if memo:
                st.queries += memo
                st.memo_queries += memo
                self.m_queries.inc("memo", memo)
            self.m_batch.observe(self._mode(), time.perf_counter() - t0)
            return outputs

    @staticmethod
    def _same_query(a: PlanInput, b: PlanInput) -> bool:
        """Field-identity modulo ``request_id`` — everything ``_plan`` reads.
        Deep dict equality runs in C; the bucket key already matched kind,
        actions, principal id, version, scope and both attr-dict sizes."""
        pa, pb = a.principal, b.principal
        try:
            return (
                a.include_meta == b.include_meta
                and pa.roles == pb.roles
                and pa.scope == pb.scope
                and pa.policy_version == pb.policy_version
                and a.resource_attr == b.resource_attr
                and pa.attr == pb.attr
                and (a.aux_data.jwt if a.aux_data is not None else None)
                == (b.aux_data.jwt if b.aux_data is not None else None)
            )
        except (TypeError, ValueError):
            return False  # uncomparable values: evaluate both standalone

    def _clone_output(self, out: PlanOutput, q: PlanInput) -> PlanOutput:
        """Duplicate a representative's output under another request id.
        The condition AST is shared (treated as immutable after the walk);
        container fields are shallow-copied so callers may mutate."""
        return PlanOutput(
            request_id=q.request_id,
            actions=list(out.actions),
            kind=out.kind,
            resource_kind=out.resource_kind,
            policy_version=out.policy_version,
            scope=out.scope,
            condition=out.condition,
            matched_scopes=dict(out.matched_scopes),
            validation_errors=list(out.validation_errors),
            include_meta=out.include_meta,
            policy_match=out.policy_match,
            effective_policies=dict(out.effective_policies),
        )

    def _mode(self) -> str:
        return "jax" if self._use_jax else "numpy"

    def _device_sat(self, inputs: list[PlanInput], params: T.EvalParams):
        """Encode the batch and evaluate every kernel group once.

        Returns (plans, sat[B, C]) — or (None, None) when the device path
        can't be trusted for the whole batch (mismatched globals) and every
        query must go symbolic.
        """
        if dict(params.globals or {}) != self._globals:
            # kernels folded different global constants than this request
            # carries; the static verdict no longer applies
            return None, None
        lt = self._lt()
        packer = self._get_packer()
        from ..tpu.condcompile import Refs
        from ..tpu.evaluator import _sat_groups
        from ..tpu.packer import InputPlan

        plans = []
        for q in inputs:
            check_in = T.CheckInput(
                principal=q.principal,
                resource=T.Resource(
                    kind=q.resource_kind,
                    id="",
                    attr=dict(q.resource_attr),
                    scope=q.resource_scope,
                    policy_version=q.resource_policy_version,
                ),
                actions=list(q.actions),
                aux_data=q.aux_data,
            )
            plans.append(
                InputPlan(
                    input=check_in,
                    principal_scopes=[],
                    resource_scopes=[],
                    principal_policy_key="",
                    resource_policy_key="",
                    resource_policy_fqn="",
                    scoped_principal_exists=False,
                    scoped_resource_exists=False,
                    roles=list(q.principal.roles),
                )
            )
        compiler = lt.compiler
        if not compiler.kernels:
            return plans, None
        cb = packer._encode_columns(plans, params)
        xp: Any = np
        if self._use_jax:
            import jax.numpy as jnp

            xp = jnp
        refs = Refs(
            xp,
            cb.tags,
            cb.his,
            cb.los,
            cb.sids,
            cb.nans,
            cb.pred_vals,
            cb.pred_errs,
            list_sids=cb.list_sids,
            list_states=cb.list_states,
            ts_his=cb.ts_his,
            ts_los=cb.ts_los,
            ts_states=cb.ts_states,
            now_hi=cb.now_hi,
            now_lo=cb.now_lo,
        )
        sat = np.asarray(_sat_groups(xp, compiler, len(plans), refs))
        return plans, sat

    # -- ternary routing (the overridden Planner seams) --------------------

    def _ctx(self) -> Optional[_QueryCtx]:
        return getattr(self._tls, "ctx", None)

    def _need_attrs(self, cid: int) -> frozenset:
        """Resource attr leaves kernel ``cid``'s verdict depends on."""
        need = self._need_attrs_cache.get(cid)
        if need is None:
            k = self._lt().compiler.kernels[cid]
            need = frozenset(
                p[2]
                for p in k.resource_dep_paths()
                if len(p) == 3 and p[1] == "attr"
            )
            self._need_attrs_cache[cid] = need
        return need

    def _device_value(self, ctx: _QueryCtx, cid: int) -> tuple[bool, bool]:
        """(usable, value) of the device ternary for one kernel/query."""
        k = self._lt().compiler.kernels[cid]
        if k.emit is None or k.plan_reason is not None:
            return False, False
        if not self._need_attrs(cid) <= ctx.known:
            return False, False  # RESIDUAL: this query doesn't know enough
        return True, bool(ctx.sat_row[cid])

    def _binding_cond_ids(self, b) -> Optional[tuple[int, ...]]:
        """Kernel ids for a rule binding as returned by ``Index.query``.

        Regular indexed rows carry their own (cond, derived-role cond) pair;
        role-policy conditional allows surface as synthetic DENY bindings
        whose condition is ``none(original)`` — lowered once as
        ``negated_cond_id``. Anything unrecognized returns None and goes
        symbolic (never guess).
        """
        if b.id < 0:
            return None
        lr = self._lt().rows.get(b.id)
        if lr is None:
            return None
        if lr.row is b:
            return (lr.cond_id, lr.drcond_id)
        if (
            b.from_role_policy
            and b.effect == "EFFECT_DENY"
            and b.derived_role_condition is None
            and b.condition is not None
            and b.condition.kind == "none"
            and len(b.condition.children) == 1
            and b.condition.children[0] is lr.row.condition
            and lr.negated_cond_id >= 0
        ):
            return (lr.negated_cond_id,)
        return None

    def _binding_node(self, pe_factory, known, drl, b):
        if b.condition is None and b.derived_role_condition is None:
            return TRUE  # unconditional binding on either path
        ctx = self._ctx()
        if ctx is not None and ctx.sat_row is not None and not ctx.oracle:
            cids = self._binding_cond_ids(b)
            if cids is not None:
                val = True
                usable = True
                for cid in cids:
                    if cid < 0:
                        continue
                    ok, v = self._device_value(ctx, cid)
                    if not ok:
                        usable = False
                        break
                    val = val and v
                if usable:
                    ctx.device_rules += 1
                    return TRUE if val else FALSE
        if ctx is not None:
            ctx.symbolic_rules += 1
        return super()._binding_node(pe_factory, known, drl, b)

    def _derived_role_node(self, pe_factory, known, dr):
        if dr.condition is None:
            return TRUE
        ctx = self._ctx()
        if ctx is not None and ctx.sat_row is not None and not ctx.oracle:
            cid = self._lt().dr_cond_ids.get(id(dr), -1)
            if cid >= 0:
                ok, v = self._device_value(ctx, cid)
                if ok:
                    ctx.device_rules += 1
                    return TRUE if v else FALSE
        if ctx is not None:
            ctx.symbolic_rules += 1
        return super()._derived_role_node(pe_factory, known, dr)

    def _partial_evaluator(self, input: PlanInput, params: T.EvalParams):
        """Lazy PE factory: request messages and the activation are only
        built the first time a binding actually goes symbolic — a query
        fully resolved on the device path never constructs any of it."""
        real: list[Any] = [None]

        def make(known_attrs, var_defs, constants, drl=None):
            if real[0] is None:
                real[0] = Planner._partial_evaluator(self, input, params)
            return real[0](known_attrs, var_defs, constants, drl)

        return make
