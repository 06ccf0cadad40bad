"""The check algorithm: CPU oracle evaluator.

Behavioral reference: internal/ruletable/check.go:95-441. Per action:
policy types in (PRINCIPAL, RESOURCE) order; per principal role (principal
policies consume only the first iteration); scopes walked most-specific-first;
bindings queried per (version, resource, scope, action, parent-roles, kind,
principal); derived-role conditions evaluated before rule conditions; DENY
breaks the scope walk; accumulated ALLOWs resolve via the scope's
scope-permissions (OVERRIDE_PARENT → ALLOW, REQUIRE_PARENTAL_CONSENT → defer
to parent); first role ALLOW wins; default DENY.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from .. import namer
from ..cel.errors import CelError
from ..cel.interp import Activation, LazyVal, Message, evaluate
from ..cel.values import Timestamp
from ..compile import CompiledCondition, CompiledExpr, PolicyParams
from ..engine import types as T
from .rows import KIND_PRINCIPAL, KIND_RESOURCE, RuleRow
from .table import RuleTable
from ..policy.model import (
    SCOPE_PERMISSIONS_OVERRIDE_PARENT,
    SCOPE_PERMISSIONS_REQUIRE_PARENTAL_CONSENT,
)

import datetime as _dt


@dataclass
class EffectInfo:
    effect: str
    policy: str
    scope: str = ""
    # decision provenance (ISSUE 20): the winning rule (`<policy>#<rule>`)
    # and its rule-table row id. Empty for default DENY / NO_MATCH and for
    # scope-permissions NO_MATCH placeholders — no rule fired.
    rule: str = ""
    rule_row_id: int = -1


@dataclass
class PolicyEvalResult:
    effects: dict[str, EffectInfo] = field(default_factory=dict)
    effective_derived_roles: set[str] = field(default_factory=set)
    to_resolve: set[str] = field(default_factory=set)
    validation_errors: list[T.ValidationError] = field(default_factory=list)
    outputs: list[T.OutputEntry] = field(default_factory=list)
    effective_policies: dict[str, dict[str, Any]] = field(default_factory=dict)

    def set_effect(self, action: str, effect: EffectInfo) -> None:
        """DENY always takes precedence (check.go:489-507)."""
        self.to_resolve.discard(action)
        if effect.effect == T.EFFECT_DENY:
            self.effects[action] = effect
            return
        current = self.effects.get(action)
        if current is None or current.effect != T.EFFECT_DENY:
            self.effects[action] = effect


def _default_now() -> Timestamp:
    return Timestamp.from_datetime(_dt.datetime.now(_dt.timezone.utc))


class EvalContext:
    """Ref: check.go:533-786 (EvalContext)."""

    def __init__(self, params: T.EvalParams, request: Message, principal: Message, resource: Message):
        self.params = params
        self.request = request
        self.principal = principal
        self.resource = resource
        self.effective_derived_roles: set[str] = set()
        self._now_fn = params.now_fn or _default_now
        self._now_cache: Optional[Timestamp] = None

    def with_effective_derived_roles(self, edr: set[str]) -> "EvalContext":
        ec = EvalContext(self.params, self.request, self.principal, self.resource)
        ec.effective_derived_roles = edr
        ec._now_fn = self._now_fn
        ec._now_cache = self._now_cache
        return ec

    def _now(self) -> Timestamp:
        if self._now_cache is None:
            v = self._now_fn()
            if not isinstance(v, Timestamp):
                v = Timestamp.from_datetime(v)
            self._now_cache = v
        return self._now_cache

    def _runtime(self) -> Message:
        return Message({"effectiveDerivedRoles": sorted(self.effective_derived_roles)})

    def activation(self, constants: dict[str, Any], variables: dict[str, Any]) -> Activation:
        consts = dict(constants or {})
        variables = variables or {}
        return Activation(
            {
                "request": self.request,
                "R": self.resource,
                "P": self.principal,
                "runtime": LazyVal(self._runtime),
                "constants": consts,
                "C": consts,
                "variables": variables,
                "V": variables,
                "globals": self.params.globals,
                "G": self.params.globals,
            },
            now_fn=self._now,
        )

    def evaluate_variables(self, constants: dict[str, Any], ordered_variables) -> dict[str, Any]:
        """A variable whose expression yields a CEL error *value* (missing
        key, no-such-overload, ...) becomes null — check.go:776-786
        evaluateCELExprToRaw returns (nil, nil) for IsError results and the
        name is still assigned (check.go:582). Non-CEL failures (interpreter
        bugs) propagate, mirroring the reference's genuine-error path."""
        evald: dict[str, Any] = {}
        for var in ordered_variables:
            act = self.activation(constants, evald)
            try:
                evald[var.name] = evaluate(var.expr.node, act)
            except CelError:
                evald[var.name] = None
        return evald

    def satisfies_condition(self, cond: Optional[CompiledCondition], constants, variables) -> bool:
        if cond is None:
            return True
        if cond.kind == "expr":
            try:
                v = evaluate(cond.expr.node, self.activation(constants, variables))
            except CelError:
                return False
            return v is True
        if cond.kind == "all":
            return all(self.satisfies_condition(c, constants, variables) for c in cond.children)
        if cond.kind == "any":
            return any(self.satisfies_condition(c, constants, variables) for c in cond.children)
        if cond.kind == "none":
            return not any(self.satisfies_condition(c, constants, variables) for c in cond.children)
        raise ValueError(f"unknown condition kind {cond.kind}")

    def evaluate_output(self, name: str, src: str, action: str, expr: CompiledExpr, constants, variables) -> T.OutputEntry:
        entry = T.OutputEntry(src=src, action=action)
        try:
            entry.val = _to_json(evaluate(expr.node, self.activation(constants, variables)))
        except CelError as e:
            entry.error = str(e)
        return entry


def _to_json(v: Any) -> Any:
    """CEL value → JSON (structpb.Value) for output entries."""
    from ..cel.stdlib import _to_string
    from ..cel.values import Duration, UInt

    if v is None or isinstance(v, (bool, str)):
        return v
    if isinstance(v, (Timestamp, Duration)):
        # same formatting as CEL string() conversions (stdlib._to_string)
        return _to_string(v)
    if isinstance(v, UInt):
        return float(int(v))
    if isinstance(v, int):
        return float(v)
    if isinstance(v, float):
        return v
    if isinstance(v, bytes):
        import base64

        return base64.b64encode(v).decode("ascii")
    if isinstance(v, (list, tuple)):
        return [_to_json(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _to_json(x) for k, x in v.items()}
    return str(v)


def build_request_messages(input: T.CheckInput) -> tuple[Message, Message, Message]:
    principal = Message(
        {
            "id": input.principal.id,
            "roles": list(input.principal.roles),
            "attr": input.principal.attr,
            "policyVersion": input.principal.policy_version,
            "scope": namer.scope_value(input.principal.scope),
        }
    )
    resource = Message(
        {
            "kind": input.resource.kind,
            "id": input.resource.id,
            "attr": input.resource.attr,
            "policyVersion": input.resource.policy_version,
            "scope": namer.scope_value(input.resource.scope),
        }
    )
    aux = input.aux_data or T.AuxData()
    aux_msg = Message({"jwt": aux.jwt})
    # cel-go resolves proto fields by their proto (snake_case) names, so the
    # reference's conditions write `request.aux_data.jwt`; accept both.
    request = Message({"principal": principal, "resource": resource, "auxData": aux_msg, "aux_data": aux_msg})
    return request, principal, resource


def check_input(
    rt: RuleTable,
    input: T.CheckInput,
    params: Optional[T.EvalParams] = None,
    schema_mgr: Any = None,
    route: str = "oracle",
) -> T.CheckOutput:
    """``route``: who answers the input, for the schema manager's counters
    alone (``oracle``, or ``inline`` from a request's own thread)."""
    params = params or T.EvalParams()
    result = _check(rt, input, params, schema_mgr, route)

    output = T.CheckOutput(request_id=input.request_id, resource_id=input.resource.id)
    for action in input.actions:
        # everything produced here ran on the CPU walk, so the provenance
        # label is "oracle" — the device assembly path stamps its own
        ae = T.ActionEffect(effect=T.EFFECT_DENY, policy=T.NO_POLICY_MATCH, source="oracle")
        einfo = result.effects.get(action)
        if einfo is not None:
            ae.effect = einfo.effect
            ae.policy = einfo.policy
            ae.scope = einfo.scope
            ae.matched_rule = einfo.rule
            ae.rule_row_id = einfo.rule_row_id
        output.actions[action] = ae
    output.effective_derived_roles = sorted(result.effective_derived_roles)
    output.validation_errors = result.validation_errors
    output.outputs = result.outputs
    output.effective_policies = {
        namer.policy_key_from_fqn(fqn): attrs for fqn, attrs in result.effective_policies.items()
    }
    return output


def _check(
    rt: RuleTable, input: T.CheckInput, params: T.EvalParams, schema_mgr: Any, route: str = "oracle"
) -> PolicyEvalResult:
    principal_scope = T.effective_scope(input.principal.scope, params)
    principal_version = T.effective_version(input.principal.policy_version, params)
    resource_scope = T.effective_scope(input.resource.scope, params)
    resource_version = T.effective_version(input.resource.policy_version, params)

    result = PolicyEvalResult(to_resolve=set(input.actions))

    principal_scopes, principal_policy_key, _principal_fqn = rt.get_all_scopes(
        KIND_PRINCIPAL, principal_scope, input.principal.id, principal_version, params.lenient_scope_search
    )
    resource_scopes, resource_policy_key, resource_policy_fqn = rt.get_all_scopes(
        KIND_RESOURCE, resource_scope, input.resource.kind, resource_version, params.lenient_scope_search
    )

    if not principal_scopes and not resource_scopes:
        return result

    # schema validation (check.go:129-151)
    if schema_mgr is not None:
        vr_errors, reject = schema_mgr.validate_check_input(rt.get_schema(resource_policy_fqn), input, route=route)
        if vr_errors:
            result.validation_errors = vr_errors
            if reject:
                for action in input.actions:
                    result.set_effect(action, EffectInfo(effect=T.EFFECT_DENY, policy=resource_policy_key))
                return result

    request, principal, resource = build_request_messages(input)
    eval_ctx = EvalContext(params, request, principal, resource)

    actions_to_resolve = sorted(result.to_resolve, key=input.actions.index)
    if not actions_to_resolve:
        return result

    sanitized_resource = namer.sanitize(input.resource.kind)
    scoped_principal_exists = rt.idx.scoped_principal_exists(principal_version, principal_scopes)
    scoped_resource_exists = rt.idx.scoped_resource_exists(resource_version, sanitized_resource, resource_scopes)
    if not scoped_principal_exists and not scoped_resource_exists:
        return result

    all_roles = rt.idx.add_parent_roles([resource_scope], input.principal.roles)
    including_parent_roles = set(all_roles)

    var_cache: dict[int, dict[str, Any]] = {}
    condition_cache: dict[str, bool] = {}
    processed_scoped_derived_roles: set[str] = set()

    def cached_variables(params_obj: Optional[PolicyParams]) -> tuple[dict[str, Any], dict[str, Any]]:
        if params_obj is None:
            return {}, {}
        key = params_obj.cache_key()
        if key in var_cache:
            return params_obj.constants, var_cache[key]
        # evaluate against the *current* context so variables referencing
        # runtime.effectiveDerivedRoles see the roles activated for this
        # scope (check.go:242-251 uses the post-withEffectiveDerivedRoles ctx)
        variables = nonlocal_ctx["eval_ctx"].evaluate_variables(
            params_obj.constants, params_obj.ordered_variables
        )
        var_cache[key] = variables
        return params_obj.constants, variables

    nonlocal_ctx = {"eval_ctx": eval_ctx}

    for action in actions_to_resolve:
        action_effect = EffectInfo(effect=T.EFFECT_NO_MATCH, policy=T.NO_POLICY_MATCH)

        for pt in (KIND_PRINCIPAL, KIND_RESOURCE):
            if pt == KIND_PRINCIPAL:
                main_policy_key = principal_policy_key
                scopes = principal_scopes
            else:
                main_policy_key = resource_policy_key
                scopes = resource_scopes

            action_effect = EffectInfo(effect=T.EFFECT_NO_MATCH, policy=T.NO_POLICY_MATCH)

            for role_idx, role in enumerate(input.principal.roles):
                # principal rules are role-agnostic: single iteration suffices
                if role_idx > 0 and pt == KIND_PRINCIPAL:
                    break

                has_allow = False
                allow_rule = ""  # first satisfied ALLOW binding (provenance)
                allow_row = -1
                role_effect = EffectInfo(effect=T.EFFECT_NO_MATCH, policy=T.NO_POLICY_MATCH)
                if (pt == KIND_RESOURCE and scoped_resource_exists) or (
                    pt == KIND_PRINCIPAL and scoped_principal_exists
                ):
                    role_effect.policy = main_policy_key

                parent_roles = rt.idx.add_parent_roles([resource_scope], [role])

                broke_out = False
                for scope in scopes:
                    # effectiveDerivedRoles bookkeeping per resource scope
                    # (check.go:228-271)
                    if pt == KIND_RESOURCE and scope not in processed_scoped_derived_roles:
                        edr: set[str] = set()
                        drs = rt.get_derived_roles(
                            namer.resource_policy_fqn(input.resource.kind, resource_version, scope)
                        )
                        if drs:
                            for name, dr in drs.items():
                                # the literal "*" parent role matches any
                                # principal role (internal/utils.go:56-68)
                                if "*" not in dr.parent_roles and not (
                                    dr.parent_roles & including_parent_roles
                                ):
                                    continue
                                constants, variables = cached_variables(dr.params)
                                try:
                                    ok = nonlocal_ctx["eval_ctx"].satisfies_condition(dr.condition, constants, variables)
                                except Exception:
                                    continue
                                if ok:
                                    edr.add(name)
                                    result.effective_derived_roles.add(name)
                        nonlocal_ctx["eval_ctx"] = nonlocal_ctx["eval_ctx"].with_effective_derived_roles(edr)
                        processed_scoped_derived_roles.add(scope)
                    ec = nonlocal_ctx["eval_ctx"]

                    if role_effect.effect != T.EFFECT_NO_MATCH:
                        break

                    pid = input.principal.id if pt == KIND_PRINCIPAL else ""
                    bindings = rt.idx.query(
                        resource_version, sanitized_resource, scope, action, parent_roles, pt, pid
                    )
                    for b in bindings:
                        for f, attrs in rt.get_chain_source_attributes(b.origin_fqn).items():
                            result.effective_policies[f] = dict(attrs)

                        constants, variables = cached_variables(b.params)

                        cache_key = b.evaluation_key if b.id >= 0 else ""
                        if cache_key and cache_key in condition_cache:
                            satisfied = condition_cache[cache_key]
                        else:
                            # derived-role condition first (check.go:316-351)
                            if b.derived_role_condition is not None:
                                dr_constants, dr_variables = cached_variables(b.derived_role_params)
                                if not ec.satisfies_condition(b.derived_role_condition, dr_constants, dr_variables):
                                    if cache_key:
                                        condition_cache[cache_key] = False
                                    continue
                            satisfied = ec.satisfies_condition(b.condition, constants, variables)
                            if cache_key:
                                condition_cache[cache_key] = satisfied

                        meta_obj = rt.get_meta(b.origin_fqn)
                        rule_src = _rule_src(meta_obj, b)

                        if satisfied:
                            if b.emit_output is not None and b.emit_output.rule_activated is not None:
                                result.outputs.append(
                                    ec.evaluate_output(b.name, rule_src, action, b.emit_output.rule_activated, constants, variables)
                                )
                            if b.effect == T.EFFECT_ALLOW:
                                if not has_allow:
                                    allow_rule, allow_row = rule_src, b.id
                                has_allow = True
                            if b.effect == T.EFFECT_DENY:
                                role_effect.effect = T.EFFECT_DENY
                                role_effect.scope = scope
                                role_effect.rule = rule_src
                                role_effect.rule_row_id = b.id
                                if b.from_role_policy:
                                    role_effect.policy = namer.policy_key_from_fqn(b.origin_fqn)
                                broke_out = True
                                break
                            elif b.no_match_for_scope_permissions:
                                role_effect.policy = T.NO_MATCH_SCOPE_PERMISSIONS
                                role_effect.scope = scope
                        else:
                            if b.emit_output is not None and b.emit_output.condition_not_met is not None:
                                result.outputs.append(
                                    ec.evaluate_output(b.name, rule_src, action, b.emit_output.condition_not_met, constants, variables)
                                )

                    if broke_out:
                        break

                    if has_allow:
                        sp = rt.get_scope_scope_permissions(scope)
                        if sp == SCOPE_PERMISSIONS_REQUIRE_PARENTAL_CONSENT:
                            has_allow = False
                            allow_rule, allow_row = "", -1
                        elif sp == SCOPE_PERMISSIONS_OVERRIDE_PARENT:
                            role_effect.effect = T.EFFECT_ALLOW
                            role_effect.scope = scope
                            role_effect.rule = allow_rule
                            role_effect.rule_row_id = allow_row
                            break

                # first role result wins while NO_MATCH (check.go:409-423)
                if action_effect.effect == T.EFFECT_NO_MATCH:
                    action_effect = role_effect
                if role_effect.effect == T.EFFECT_ALLOW:
                    action_effect = role_effect
                    break
                if (
                    role_effect.effect == T.EFFECT_DENY
                    and action_effect.policy == T.NO_MATCH_SCOPE_PERMISSIONS
                    and role_effect.policy != T.NO_MATCH_SCOPE_PERMISSIONS
                ):
                    action_effect = role_effect

            if action_effect.effect in (T.EFFECT_ALLOW, T.EFFECT_DENY):
                break

        if action_effect.effect == T.EFFECT_NO_MATCH:
            action_effect = EffectInfo(effect=T.EFFECT_DENY, policy=action_effect.policy, scope=action_effect.scope)

        result.set_effect(action, action_effect)

    return result


def _rule_src(meta, b: RuleRow) -> str:
    """`<policy key>#<rule name>` used in output entries (namer.RuleFQN)."""
    if meta is None:
        return f"{namer.policy_key_from_fqn(b.origin_fqn)}#{b.name}"
    if meta.kind == "PRINCIPAL":
        fqn = namer.principal_policy_fqn(meta.name, meta.version, b.scope)
    elif meta.kind == "RESOURCE":
        fqn = namer.resource_policy_fqn(meta.name, meta.version, b.scope)
    else:
        fqn = namer.role_policy_fqn(meta.name, meta.version, b.scope)
    return f"{namer.policy_key_from_fqn(fqn)}#{b.name}"
