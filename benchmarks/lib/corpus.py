"""The benchmark's own copy of ``cerbos_tpu/util/bench_corpus.py`` (policy
documents and the seeded request mix), kept here so that no later PR can
change what the cells send. It imports nothing of the program: requests are
plain dicts. The draws are in the original's order, so a seed gives the same
requests as the original did at the commit this was copied from.

Synthetic benchmark corpus mirroring the reference load-test workload.

Behavioral reference: hack/loadtest/templates/classic — per name-mod: two
derived-role exports (alpha/beta), the 20210210 leave_request policy (with
the inIPAddrRange location variable, the JWT defer rule and schema refs —
resource_leave_request_20210210.yaml.tpl:1-66), the default-version scope
chain (noscope/acme/acme.hr/acme.hr.uk), an employee_record policy and a
donald_duck principal policy: 9 policy documents per mod (7 runnable + 2
derived-role exports), matching the reference's 9 classic template files,
so 100 mods = 900 documents — at least the configuration the reference's
loadtest reports label "800 policies". Requests mirror cr_req01.json.tpl
(5 × [view:public, approve]) and cr_req02.json.tpl (scoped principal with
ip_address, delete/create/edit action mixes, one salary_record no-match).
Generated from scratch: structure parity, not copied text.
"""

from __future__ import annotations

import json
import random



def Principal(id, roles, attr=None, policy_version="", scope=""):
    return {"id": id, "roles": roles, "attr": attr or {}, "policyVersion": policy_version, "scope": scope}


def Resource(kind, id="", attr=None, policy_version="", scope=""):
    return {"kind": kind, "id": id, "attr": attr or {}, "policyVersion": policy_version, "scope": scope}


def CheckInput(request_id, principal, resource, actions, jwt=None):
    """One resource with its actions, as the generator draws them; ``jwt`` is
    the claim set of the request's auxData token, or None."""
    return {"requestId": request_id, "principal": principal, "resource": resource, "actions": actions, "jwt": jwt}


_DERIVED_ROLES_ALPHA = """
apiVersion: api.cerbos.dev/v1
derivedRoles:
  name: alpha_{i}
  definitions:
    - name: admin
      parentRoles: [admin]
    - name: tester
      parentRoles: [dev, qa]
    - name: employee_that_owns_the_record
      parentRoles: [employee]
      condition:
        match:
          expr: R.attr.owner == P.id
"""

_DERIVED_ROLES_BETA = """
apiVersion: api.cerbos.dev/v1
variables:
  same_geography: request.resource.attr.geography == request.principal.attr.geography
derivedRoles:
  name: beta_{i}
  definitions:
    - name: any_employee
      parentRoles: [employee]
    - name: direct_manager
      parentRoles: [manager]
      condition:
        match:
          all:
            of:
              - expr: V.same_geography
              - expr: request.resource.attr.geography == request.principal.attr.managed_geographies
"""

_RESOURCE_POLICY_V20210210 = """
apiVersion: api.cerbos.dev/v1
variables:
  pending_approval: ("PENDING_APPROVAL")
  principal_location: |-
    (P.attr.ip_address.inIPAddrRange("10.20.0.0/16") ? "GB" : "")
resourcePolicy:
  resource: leave_request_{i}
  version: "20210210"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  schemas:
    principalSchema:
      ref: "cerbos:///principal_{i}.json"
    resourceSchema:
      ref: "cerbos:///leave_request_{i}.json"
  rules:
    - actions: ['*']
      effect: EFFECT_ALLOW
      roles: [admin]
      name: wildcard
    - actions: ["create"]
      effect: EFFECT_ALLOW
      derivedRoles: [employee_that_owns_the_record]
    - actions: ["view:*"]
      effect: EFFECT_ALLOW
      derivedRoles: [employee_that_owns_the_record, direct_manager]
    - actions: ["view:public"]
      effect: EFFECT_ALLOW
      derivedRoles: [any_employee]
      name: public-view
    - actions: ["approve"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager]
      condition:
        match:
          expr: request.resource.attr.status == V.pending_approval
    - actions: ["delete"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager]
      condition:
        match:
          expr: request.resource.attr.geography == variables.principal_location
    - actions: ["defer"]
      effect: EFFECT_ALLOW
      roles: [employee]
      condition:
        match:
          all:
            of:
              - expr: '"cerbos-jwt-tests" in request.aux_data.jwt.aud'
              - expr: '"A" in request.aux_data.jwt.customArray'
"""

_RESOURCE_POLICY_DEFAULT = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: leave_request_{i}
  version: "default"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  schemas:
    principalSchema:
      ref: "cerbos:///principal_{i}.json"
    resourceSchema:
      ref: "cerbos:///leave_request_{i}.json"
  rules:
    - actions: ['*']
      effect: EFFECT_ALLOW
      roles: [admin]
      name: wildcard
"""

_RESOURCE_POLICY_ACME = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: leave_request_{i}
  version: "default"
  scope: "acme"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  schemas:
    principalSchema:
      ref: "cerbos:///principal_{i}.json"
    resourceSchema:
      ref: "cerbos:///leave_request_{i}.json"
  rules:
    - actions: ["create"]
      effect: EFFECT_ALLOW
      derivedRoles: [employee_that_owns_the_record]
    - actions: ["view:public"]
      effect: EFFECT_ALLOW
      derivedRoles: [any_employee]
      name: public-view
"""

_RESOURCE_POLICY_ACME_HR = """
apiVersion: api.cerbos.dev/v1
variables:
  pending_approval: ("PENDING_APPROVAL")
  principal_location: |-
    (P.attr.ip_address.inIPAddrRange("10.20.0.0/16") ? "GB" : "")
resourcePolicy:
  resource: leave_request_{i}
  version: "default"
  scope: "acme.hr"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  rules:
    - actions: ["view:*"]
      effect: EFFECT_ALLOW
      derivedRoles: [employee_that_owns_the_record, direct_manager]
    - actions: ["delete"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager]
      condition:
        match:
          expr: request.resource.attr.geography == variables.principal_location
    - actions: ["approve"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager]
      condition:
        match:
          expr: request.resource.attr.status == V.pending_approval
    - actions: ["defer"]
      effect: EFFECT_ALLOW
      roles: [employee]
      condition:
        match:
          all:
            of:
              - expr: '"cerbos-jwt-tests" in request.aux_data.jwt.aud'
              - expr: '"A" in request.aux_data.jwt.customArray'
"""

_RESOURCE_POLICY_ACME_HR_UK = """
apiVersion: api.cerbos.dev/v1
variables:
  pending_approval: ("PENDING_APPROVAL")
  principal_location: |-
    (P.attr.ip_address.inIPAddrRange("10.20.0.0/16") ? "GB" : "")
resourcePolicy:
  resource: leave_request_{i}
  version: "default"
  scope: "acme.hr.uk"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  rules:
    - actions: ["delete"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager, employee_that_owns_the_record]
      condition:
        match:
          expr: request.resource.attr.geography == variables.principal_location
    - actions: ["defer"]
      effect: EFFECT_ALLOW
      derivedRoles: [direct_manager, employee_that_owns_the_record]
"""

_EMPLOYEE_RECORD_POLICY = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: employee_record_{i}
  version: "default"
  importDerivedRoles: [alpha_{i}, beta_{i}]
  schemas:
    principalSchema:
      ref: "cerbos:///principal_{i}.json"
    resourceSchema:
      ref: "cerbos:///employee_record_{i}.json"
  rules:
    - actions: ['*']
      effect: EFFECT_ALLOW
      roles: [admin]
      name: wildcard
"""

# the unmodded `resource: leave_request` / `salary_record` targets are
# faithful to the reference template (principal_donald_duck.yaml.tpl has no
# NameMod on them), so — exactly as in the reference loadtest — these rules
# never match the modded resource kinds
_PRINCIPAL_POLICY = """
apiVersion: api.cerbos.dev/v1
variables:
  is_dev_record: request.resource.attr.dev_record == true
principalPolicy:
  principal: donald_duck_{i}
  version: "20210210"
  rules:
    - resource: leave_request
      actions:
        - action: "*"
          effect: EFFECT_ALLOW
          name: dev_admin
          condition:
            match:
              expr: variables.is_dev_record
    - resource: salary_record
      actions:
        - action: "*"
          effect: EFFECT_DENY
"""

_MOD_TEMPLATES = [
    _DERIVED_ROLES_ALPHA,
    _DERIVED_ROLES_BETA,
    _RESOURCE_POLICY_V20210210,
    _RESOURCE_POLICY_DEFAULT,
    _RESOURCE_POLICY_ACME,
    _RESOURCE_POLICY_ACME_HR,
    _RESOURCE_POLICY_ACME_HR_UK,
    _EMPLOYEE_RECORD_POLICY,
    _PRINCIPAL_POLICY,
]

# -- condition-diversity extension ------------------------------------------
#
# The classic corpus lowers to a handful of condition kernels; a throughput
# claim about "vectorized CEL" needs structural breadth. DIVERSE_KINDS extra
# resource policies carry 4 rules each whose conditions cycle through ~16
# structural families — string/number/bool/null equality, numeric ordering
# vs constants and attribute-vs-attribute, membership over constant lists
# and over attribute string lists, timestamp comparisons (constant and
# now()), all/any/none combinators, ternaries, and a couple of host-predicate
# forms (startsWith / string ordering) — every one parameterized per kind so
# the lowered table holds 100+ DISTINCT conditions.

DIVERSE_KINDS = 25
_DIVERSE_ACTIONS = ["op0", "op1", "op2", "op3"]


def _diverse_conditions(i: int) -> list[str]:
    """Four condition expressions for diverse_record_{i}; the family mix
    rotates with i so every structural form appears across the corpus."""
    forms = [
        # equality / identity families
        lambda: f'R.attr.status == "S{i % 7}"',
        lambda: f"R.attr.level > {i % 10}",
        lambda: f"R.attr.score <= {i * 10}.5",
        lambda: "P.attr.region == R.attr.region",
        lambda: f"R.attr.priority in [{i % 5}, {i % 5 + 1}, 9]",
        lambda: f'R.attr.category in ["cat_a{i % 4}", "cat_b{i % 4}"]',
        lambda: f'\'"tag{i % 6}" in R.attr.tags\'',
        lambda: f'timestamp(R.attr.created) < timestamp("2026-0{i % 9 + 1}-01T00:00:00Z")',
        lambda: "timestamp(R.attr.created) <= now()",
        lambda: f"R.attr.flag == {'true' if i % 2 == 0 else 'false'}",
        lambda: "R.attr.deleted_at == null",
        lambda: "P.attr.clearance >= R.attr.sensitivity",
        # combinators
        lambda: (
            "all:\n            of:\n"
            f'              - expr: R.attr.level >= {i % 4}\n'
            f'              - expr: R.attr.status != "CLOSED{i % 3}"'
        ),
        lambda: (
            "any:\n            of:\n"
            f'              - expr: R.attr.score > {50 + i}\n'
            '              - expr: P.attr.region == "HQ"'
        ),
        lambda: (
            "none:\n            of:\n"
            f'              - expr: R.attr.flag == true\n'
            f'              - expr: R.attr.level < {i % 3}'
        ),
        # host-predicate forms (string ops stay host-evaluated predicate
        # columns; the inputs remain device-served)
        lambda: f'R.attr.name.startsWith("n{i % 5}")',
    ]
    picks = [forms[(i * 4 + j) % len(forms)] for j in range(4)]
    return [p() for p in picks]


def _diverse_policy(i: int) -> str:
    conds = _diverse_conditions(i)
    rules = []
    for j, action in enumerate(_DIVERSE_ACTIONS):
        body = conds[j]
        if body.startswith(("all:", "any:", "none:")):
            cond_yaml = f"        match:\n          {body}"
        else:
            cond_yaml = f"        match:\n          expr: {body}"
        rules.append(
            f"    - actions: [\"{action}\"]\n"
            f"      effect: EFFECT_ALLOW\n"
            f"      roles: [user, employee]\n"
            f"      condition:\n{cond_yaml}"
        )
    rules.append(
        '    - actions: ["*"]\n'
        "      effect: EFFECT_ALLOW\n"
        "      roles: [admin]"
    )
    return (
        "apiVersion: api.cerbos.dev/v1\n"
        "resourcePolicy:\n"
        f"  resource: diverse_record_{i}\n"
        '  version: "default"\n'
        "  rules:\n" + "\n".join(rules)
    )


def corpus_yaml(n_mods: int) -> str:
    """n_mods × 9 classic policy documents (7 runnable + 2 derived-role
    exports, matching the reference's 9 classic template files per
    name-mod) plus DIVERSE_KINDS condition-diversity policies. At
    n_mods=100 that is 925 documents — MORE than the "800 policies" the
    reference's loadtest reports label that configuration, so throughput
    comparisons against the 800-policy baseline are conservative."""
    docs = []
    for i in range(n_mods):
        for tpl in _MOD_TEMPLATES:
            docs.append(tpl.format(i=i))
    for i in range(DIVERSE_KINDS):
        docs.append(_diverse_policy(i))
    return "\n---\n".join(docs)


def _principal_schema() -> dict:
    return {
        "$schema": "https://json-schema.org/draft/2020-12/schema",
        "type": "object",
        "properties": {
            "department": {"type": "string", "enum": ["marketing", "engineering", "finance"]},
            "geography": {"type": "string"},
            "team": {"type": "string"},
            "managed_geographies": {"type": "string"},
            "ip_address": {"type": "string"},
        },
        "required": ["department", "geography", "team"],
    }


def _leave_request_schema() -> dict:
    return {
        "$schema": "https://json-schema.org/draft/2020-12/schema",
        "type": "object",
        "properties": {
            "department": {"type": "string", "enum": ["marketing", "engineering", "finance"]},
            "geography": {"type": "string"},
            "team": {"type": "string"},
            "id": {"type": "string"},
            "owner": {"type": "string"},
            "status": {"type": "string"},
            "dev_record": {"type": "boolean"},
        },
        "required": ["department", "geography", "team", "id"],
    }


def schemas(n_mods: int) -> dict[str, bytes]:
    """Schema id → JSON bytes, shaped like templates/classic/schemas/*."""
    out: dict[str, bytes] = {}
    for i in range(n_mods):
        out[f"principal_{i}.json"] = json.dumps(_principal_schema()).encode()
        out[f"leave_request_{i}.json"] = json.dumps(_leave_request_schema()).encode()
        out[f"employee_record_{i}.json"] = json.dumps(_leave_request_schema()).encode()
    return out


_DEPTS = ["marketing", "engineering", "finance"]
_TEAMS = ["design", "backend", "accounting", "sre"]
_OWNERS = ["john", "jenny", "dani", "robert", "anya"]


def _diverse_request(rng: random.Random, i: int) -> dict:
    """One request against a diverse_record kind, attrs shaped so every
    condition family is exercised (and flips) across the batch."""
    kind_i = rng.randrange(DIVERSE_KINDS)
    principal = Principal(
        id=f"user{rng.randrange(50)}",
        roles=rng.choice([["user"], ["employee"], ["user", "employee"], ["admin"]]),
        attr={
            "region": rng.choice(["EU", "US", "APAC", "HQ"]),
            "clearance": float(rng.randrange(0, 8)),
        },
    )
    attr: dict = {
        "status": rng.choice(["S0", "S1", "S2", "S3", "CLOSED0", "CLOSED1"]),
        "level": float(rng.randrange(0, 12)),
        "score": float(rng.randrange(0, 400)) + 0.5,
        "region": rng.choice(["EU", "US", "APAC"]),
        "priority": float(rng.randrange(0, 10)),
        "category": rng.choice(["cat_a0", "cat_a1", "cat_b2", "cat_c3"]),
        "tags": rng.sample(["tag0", "tag1", "tag2", "tag3", "tag4", "tag5"], k=rng.randrange(0, 4)),
        "created": f"202{rng.randrange(4, 7)}-{rng.randrange(1, 13):02d}-{rng.randrange(1, 28):02d}T10:00:00Z",
        "flag": rng.random() < 0.5,
        "sensitivity": float(rng.randrange(0, 8)),
        "name": rng.choice(["n0_doc", "n1_doc", "n2_doc", "other"]),
    }
    if rng.random() < 0.5:
        attr["deleted_at"] = None
    resource = Resource(
        kind=f"diverse_record_{kind_i}",
        id=f"DV{i}",
        attr=attr,
    )
    n_act = rng.choice([2, 3])
    actions = rng.sample(["op0", "op1", "op2", "op3"], k=n_act)
    return CheckInput(
        request_id=f"req-{i}",
        principal=principal,
        resource=resource,
        actions=actions,
    )


def requests(n: int, n_mods: int, seed: int = 7) -> list[dict]:
    """Mirror the cr_req01/cr_req02 request mix, one resource per CheckInput
    (the batcher recombines them): mostly 20210210 [view:public, approve]
    pairs, with a scoped slice carrying ip_address and delete/create, and a
    ~30% slice against the condition-diversity kinds."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        if rng.random() < 0.30:
            out.append(_diverse_request(rng, i))
            continue
        mod = rng.randrange(n_mods)
        dept = rng.choice(_DEPTS)
        geo = rng.choice(["GB", "US"])
        owner = rng.choice(_OWNERS)
        scoped = rng.random() < 0.25  # cr_req02's share of the mix
        if scoped:
            principal = Principal(
                id="john",
                scope="acme.hr",
                roles=["employee"],
                attr={
                    "department": dept,
                    "geography": geo,
                    "team": rng.choice(_TEAMS),
                    "ip_address": rng.choice(["10.20.5.5", "192.168.1.1"]),
                },
            )
            if rng.random() < 0.25:
                # cr_req02's salary_record entry: no matching resource
                # policy, exercising the full default-deny path
                resource = Resource(
                    kind=f"salary_record_{mod}",
                    policy_version="20210210",
                    id=f"YY{i}",
                    attr={"department": dept, "geography": geo, "id": f"YY{i}", "owner": owner},
                )
                actions = ["view:public", "delete", "edit"]
            else:
                resource = Resource(
                    kind=f"leave_request_{mod}",
                    scope=rng.choice(["acme.hr.uk", "acme.hr"]),
                    id=f"XX{i}",
                    attr={
                        "department": dept,
                        "geography": geo,
                        "id": f"XX{i}",
                        "owner": owner,
                        "team": rng.choice(_TEAMS),
                    },
                )
                actions = ["view:public", "delete", "create"]
        else:
            principal = Principal(
                id=rng.choice(["john", "jenny"]),
                policy_version="20210210",
                roles=rng.choice([["employee"], ["manager"], ["employee", "manager"]]),
                attr={"department": dept, "geography": geo, "team": rng.choice(_TEAMS)},
            )
            resource = Resource(
                kind=f"leave_request_{mod}",
                policy_version="20210210",
                id=f"XX{i}",
                attr={
                    "department": rng.choice(_DEPTS),
                    "geography": rng.choice(["GB", "US"]),
                    "id": f"XX{i}",
                    "owner": owner,
                    "status": rng.choice(["PENDING_APPROVAL", "DRAFT"]),
                },
            )
            actions = ["view:public", "approve"]
        out.append(
            CheckInput(
                request_id=f"req-{i}",
                principal=principal,
                resource=resource,
                actions=actions,
                jwt={"aud": ["cerbos-jwt-tests"], "customArray": ["A", "B"]}
                if rng.random() < 0.2
                else None,
            )
        )
    return out
