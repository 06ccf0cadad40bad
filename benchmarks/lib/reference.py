"""The plain reference: what a Cerbos PDP must answer for the classic template.

Written from the policy documents of ``corpus.py`` read as upstream Cerbos
reads them, not from the program: it imports nothing of ``cerbos_tpu`` and
evaluates no rule table. Each policy of the template is written out as the
decision it makes, so the file is short and can be checked against the YAML
by eye:

- a resource policy is chosen by (kind, policyVersion or "default", scope);
  scoped policies are walked from the resource's scope to the root and the
  first scope with a matching rule decides the action (upstream's default
  ``SCOPE_PERMISSIONS_OVERRIDE_PARENT``); no matching rule anywhere, or no
  policy for the kind and version at all, is EFFECT_DENY;
- a rule matches when its action glob matches, one of its roles or derived
  roles is held, and its condition is true; a condition whose evaluation
  errors (a missing attribute) is false;
- the only principal policies are ``donald_duck_<i>``'s, and no request is
  sent as that principal; the ``defer`` rules are never asked for.

``now`` is passed in: one condition family compares a timestamp with
``now()``, so a caller that checks a reply brackets the time the server saw.
"""

from __future__ import annotations

import ipaddress
import re
from datetime import datetime, timezone

ALLOW = "EFFECT_ALLOW"
DENY = "EFFECT_DENY"

DIVERSE_KINDS = 25
_KIND = re.compile(r"^(leave_request|employee_record|diverse_record|salary_record)_(\d+)$")
_UK_NET = ipaddress.ip_network("10.20.0.0/16")
_MISSING = object()


class _Error(Exception):
    """A CEL evaluation error: the condition that raised it is false."""


def _get(attr: dict, key: str):
    v = attr.get(key, _MISSING)
    if v is _MISSING:
        raise _Error(key)
    return v


def _ts(text) -> datetime:
    if not isinstance(text, str):
        raise _Error("timestamp")
    try:
        return datetime.fromisoformat(text.replace("Z", "+00:00"))
    except ValueError:
        raise _Error("timestamp") from None


def _num(v):
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise _Error("number")
    return v


def _cond(fn) -> bool:
    try:
        return bool(fn())
    except _Error:
        return False


def _diverse_condition(form: int, i: int, P: dict, R: dict, now: datetime) -> bool:
    """The sixteen condition families of ``corpus._diverse_conditions``."""
    if form == 0:
        return _cond(lambda: _get(R, "status") == f"S{i % 7}")
    if form == 1:
        return _cond(lambda: _num(_get(R, "level")) > i % 10)
    if form == 2:
        return _cond(lambda: _num(_get(R, "score")) <= i * 10 + 0.5)
    if form == 3:
        return _cond(lambda: _get(P, "region") == _get(R, "region"))
    if form == 4:
        return _cond(lambda: _get(R, "priority") in (i % 5, i % 5 + 1, 9))
    if form == 5:
        return _cond(lambda: _get(R, "category") in (f"cat_a{i % 4}", f"cat_b{i % 4}"))
    if form == 6:
        return _cond(lambda: f"tag{i % 6}" in _get(R, "tags"))
    if form == 7:
        limit = datetime(2026, i % 9 + 1, 1, tzinfo=timezone.utc)
        return _cond(lambda: _ts(_get(R, "created")) < limit)
    if form == 8:
        return _cond(lambda: _ts(_get(R, "created")) <= now)
    if form == 9:
        return _cond(lambda: _get(R, "flag") is (i % 2 == 0))
    if form == 10:
        return _cond(lambda: _get(R, "deleted_at") is None)
    if form == 11:
        return _cond(lambda: _num(_get(P, "clearance")) >= _num(_get(R, "sensitivity")))
    if form == 12:  # all
        return _cond(lambda: _num(_get(R, "level")) >= i % 4) and _cond(
            lambda: _get(R, "status") != f"CLOSED{i % 3}"
        )
    if form == 13:  # any
        return _cond(lambda: _num(_get(R, "score")) > 50 + i) or _cond(lambda: _get(P, "region") == "HQ")
    if form == 14:  # none
        return not (
            _cond(lambda: _get(R, "flag") is True) or _cond(lambda: _num(_get(R, "level")) < i % 3)
        )
    if form == 15:
        return _cond(lambda: _str(_get(R, "name")).startswith(f"n{i % 5}"))
    raise AssertionError(form)


def _str(v) -> str:
    if not isinstance(v, str):
        raise _Error("string")
    return v


def _diverse(i: int, roles: set, P: dict, R: dict, action: str, now: datetime) -> str:
    if i >= DIVERSE_KINDS:
        return DENY  # no such policy
    if "admin" in roles:
        return ALLOW
    m = re.fullmatch(r"op([0-3])", action)
    if m and roles & {"user", "employee"}:
        j = int(m.group(1))
        if _diverse_condition((i * 4 + j) % 16, i, P, R, now):
            return ALLOW
    return DENY


def _principal_location(P: dict) -> str:
    ip = _get(P, "ip_address")
    try:
        return "GB" if ipaddress.ip_address(ip) in _UK_NET else ""
    except ValueError:
        raise _Error("ip") from None


def _leave_request(version: str, scope: str, roles: set, pid: str, P: dict, R: dict, action: str) -> str:
    owner = "employee" in roles and _cond(lambda: _get(R, "owner") == pid)
    any_employee = "employee" in roles
    direct_manager = "manager" in roles and _cond(
        lambda: _get(R, "geography") == _get(P, "geography")
    ) and _cond(lambda: _get(R, "geography") == _get(P, "managed_geographies"))
    pending = lambda: _cond(lambda: _get(R, "status") == "PENDING_APPROVAL")  # noqa: E731
    located = lambda: _cond(lambda: _get(R, "geography") == _principal_location(P))  # noqa: E731
    view = action.startswith("view:")

    if version == "20210210":
        if scope:
            return DENY  # the 20210210 policy has no scoped children
        if (
            "admin" in roles
            or (action == "create" and owner)
            or (view and (owner or direct_manager))
            or (action == "view:public" and any_employee)
            or (action == "approve" and direct_manager and pending())
            or (action == "delete" and direct_manager and located())
        ):
            return ALLOW
        return DENY
    if version != "default":
        return DENY

    chain = {"": [""], "acme": ["acme", ""], "acme.hr": ["acme.hr", "acme", ""],
             "acme.hr.uk": ["acme.hr.uk", "acme.hr", "acme", ""]}.get(scope)
    if chain is None:
        return DENY  # no policy at that scope
    for s in chain:
        if s == "acme.hr.uk":
            if action == "delete" and (direct_manager or owner) and located():
                return ALLOW
        elif s == "acme.hr":
            if (
                (view and (owner or direct_manager))
                or (action == "delete" and direct_manager and located())
                or (action == "approve" and direct_manager and pending())
            ):
                return ALLOW
        elif s == "acme":
            if (action == "create" and owner) or (action == "view:public" and any_employee):
                return ALLOW
        elif "admin" in roles:
            return ALLOW
    return DENY


def effects(principal: dict, resource: dict, actions: list, now: datetime) -> dict:
    """action -> effect for one resource of a CheckResources request. The
    ``defer`` action and the ``donald_duck_<i>`` principals are outside what
    this reference covers and raise."""
    if principal["id"].startswith("donald_duck"):
        raise ValueError("principal policies are outside this reference")
    roles = set(principal["roles"])
    P, R = principal["attr"], resource["attr"]
    m = _KIND.match(resource["kind"])
    version = resource["policyVersion"] or "default"
    scope = resource["scope"]
    out = {}
    for action in actions:
        if action == "defer":
            raise ValueError("the defer rules are outside this reference")
        if m is None or m.group(1) == "salary_record":
            out[action] = DENY
        elif m.group(1) == "diverse_record":
            ok = version == "default" and not scope
            out[action] = _diverse(int(m.group(2)), roles, P, R, action, now) if ok else DENY
        elif m.group(1) == "employee_record":
            ok = version == "default" and not scope and "admin" in roles
            out[action] = ALLOW if ok else DENY
        else:
            out[action] = _leave_request(version, scope, roles, principal["id"], P, R, action)
    return out


def uses_now(resource: dict, actions: list) -> bool:
    """Whether the answer for this resource can depend on the clock."""
    m = _KIND.match(resource["kind"])
    if m is None or m.group(1) != "diverse_record":
        return False
    i = int(m.group(2))
    return any((i * 4 + j) % 16 == 8 for j in range(4) if f"op{j}" in actions)
