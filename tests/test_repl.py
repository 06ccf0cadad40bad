"""Scripted REPL session.

Drives cerbos_tpu.repl.Repl the way cmd/cerbos/repl's own tests drive its
directive handler: a sequence of lines in, assertions over the printed
output — covering expression eval with ``_``, :let (plain and special
JSON), :vars, :load of a policy dir, :rules, :exec with concrete results,
:exec producing a RESIDUAL for missing attributes, and :reset.
"""


import pytest

from cerbos_tpu.repl import Repl

POLICY = """
apiVersion: api.cerbos.dev/v1
variables:
  is_owner: R.attr.owner == P.id
resourcePolicy:
  resource: leave_request
  version: default
  importDerivedRoles: [common_roles]
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [employee]
      name: view-own
      condition:
        match:
          expr: V.is_owner
    - actions: ["approve"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager]
      name: approve
      condition:
        match:
          expr: R.attr.status == "PENDING_APPROVAL"
    - actions: ["*"]
      effect: EFFECT_ALLOW
      roles: [admin]
      name: admin-all
"""

DERIVED = """
apiVersion: api.cerbos.dev/v1
derivedRoles:
  name: common_roles
  definitions:
    - name: direct_manager
      parentRoles: [manager]
      condition:
        match:
          expr: R.attr.managerId == P.id
"""


@pytest.fixture()
def policy_dir(tmp_path):
    (tmp_path / "leave_request.yaml").write_text(POLICY)
    (tmp_path / "derived.yaml").write_text(DERIVED)
    return str(tmp_path)


class Session:
    def __init__(self):
        self.lines: list[str] = []
        self.repl = Repl(out=self.lines.append)

    def run(self, *inputs: str) -> str:
        self.lines.clear()
        for line in inputs:
            assert self.repl.handle(line) is True
        return "\n".join(self.lines)


def test_expressions_and_underscore():
    s = Session()
    assert s.run("1 + 1") == "2"
    assert s.run("_ + 5") == "7"
    assert s.run('"test".charAt(1)') == '"e"'


def test_let_plain_and_special():
    s = Session()
    assert "x = 12" in s.run(":let x = 12")
    assert "y = 6" in s.run(":let y = 1 + 5")
    assert s.run("x + y") == "18"
    out = s.run(':let P = {"id":"john","roles":["employee"]}')
    assert "P set" in out
    assert s.run("P.id") == '"john"'
    out = s.run(":vars")
    assert '"john"' in out and '"x": 12' in out


def test_let_errors():
    s = Session()
    assert "usage" in s.run(":let x")
    assert "takes JSON" in s.run(":let P = not-json")
    assert "error:" in s.run("1 +")


def test_load_rules_exec(policy_dir):
    s = Session()
    out = s.run(f":load {policy_dir}")
    assert "loaded" in out and "rules" in out
    out = s.run(":rules")
    assert "resource.leave_request.vdefault#view-own" in out
    assert "derived:direct_manager" in out
    assert 'R.attr.status == "PENDING_APPROVAL"' in out

    # concrete true: owner matches
    s.run(':let P = {"id":"john","roles":["employee"]}')
    s.run(':let R = {"kind":"leave_request","attr":{"owner":"john","status":"OPEN"}}')
    rules_out = s.run(":rules")
    idx = next(
        i for i, line in enumerate(rules_out.splitlines())
        if "#view-own" in line
    )
    rule_no = rules_out.splitlines()[idx].split()[0]  # "#N"
    out = s.run(f":exec {rule_no}")
    assert "result: true" in out

    # concrete false: different owner
    s.run(':let R = {"kind":"leave_request","attr":{"owner":"sally","status":"OPEN"}}')
    out = s.run(f":exec {rule_no}")
    assert "result: false" in out


def test_exec_residual_for_missing_attr(policy_dir):
    s = Session()
    s.run(f":load {policy_dir}")
    s.run(':let P = {"id":"john","roles":["employee"]}')
    # resource carries NO attrs: the view-own condition over R.attr.owner
    # cannot be decided concretely -> residual referencing the attribute
    s.run(':let R = {"kind":"leave_request","attr":{}}')
    rules_out = s.run(":rules")
    idx = next(i for i, line in enumerate(rules_out.splitlines()) if "#view-own" in line)
    rule_no = rules_out.splitlines()[idx].split()[0]
    out = s.run(f":exec {rule_no}")
    assert "residual:" in out
    assert "owner" in out


def test_exec_unconditional_and_bad_refs(policy_dir):
    s = Session()
    s.run(f":load {policy_dir}")
    rules_out = s.run(":rules")
    idx = next(i for i, line in enumerate(rules_out.splitlines()) if "#admin-all" in line)
    rule_no = rules_out.splitlines()[idx].split()[0]
    out = s.run(f":exec {rule_no}")
    assert "unconditional" in out
    assert "usage" in s.run(":exec 3")
    assert "no rule" in s.run(":exec #999")


def test_reset_and_help():
    s = Session()
    s.run(":let x = 1")
    out = s.run(":reset")
    assert "cleared" in out
    assert "error:" in s.run("x")  # x is gone
    assert ":load" in s.run(":help")


def test_load_missing_path():
    s = Session()
    out = s.run(":load /nonexistent/path.yaml")
    assert "error" in out.lower()
