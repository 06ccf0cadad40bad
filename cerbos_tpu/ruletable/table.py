"""The rule table: indexed rows + scope maps + per-policy metadata.

Behavioral reference: internal/ruletable/ruletable.go:466-933 (RuleTable
struct, scope maps, scope permissions map, policy derived roles, GetAllScopes,
CombineScopes).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from .. import namer
from ..compile import (
    CompiledDerivedRole,
    CompiledPolicy,
    CompiledPrincipalPolicy,
    CompiledResourcePolicy,
    CompiledRolePolicy,
)
from ..policy import model
from .index import Index
from .rows import KIND_PRINCIPAL, KIND_RESOURCE, RuleRow, rows_from_policy


@dataclass
class PolicyMeta:
    fqn: str
    name: str
    version: str
    kind: str  # RESOURCE | PRINCIPAL | ROLE
    source_attributes: dict[str, Any] = field(default_factory=dict)
    annotations: dict[str, str] = field(default_factory=dict)


class RuleTable:
    def __init__(self, index_backend: Optional[str] = None) -> None:
        # index_backend: "bitmap" (default) or "legacy" — see Index; None
        # defers to the CERBOS_TPU_RULE_INDEX env override
        self.idx = Index(backend=index_backend)
        self.principal_scope_map: dict[str, bool] = {}
        self.resource_scope_map: dict[str, bool] = {}
        self.scope_scope_permissions: dict[str, str] = {}
        # module_id -> derived role name -> CompiledDerivedRole
        self.policy_derived_roles: dict[int, dict[str, CompiledDerivedRole]] = {}
        self.schemas: dict[int, model.Schemas] = {}
        self.meta: dict[int, PolicyMeta] = {}
        self.scope_parent_roles: dict[str, dict[str, list[str]]] = {}
        # fqn -> chain source attributes (static per table build; hot on the
        # evaluator's cold-assembly path)
        self._chain_attr_memo: dict[str, dict[str, dict]] = {}
        # the table's content identity (engine/rollout.bundle_hash_of), kept
        # from its first computation until the table is edited
        self.bundle_hash_memo: Optional[str] = None

    # -- build ------------------------------------------------------------

    def ingest_policy(self, p: CompiledPolicy) -> None:
        self._chain_attr_memo.clear()
        self.bundle_hash_memo = None
        mod_id = namer.module_id(p.fqn)
        if isinstance(p, CompiledResourcePolicy):
            self.meta[mod_id] = PolicyMeta(
                fqn=p.fqn, name=p.resource, version=p.version, kind="RESOURCE",
                source_attributes=p.source_attributes, annotations=p.annotations,
            )
            if p.schemas is not None:
                self.schemas[mod_id] = p.schemas
            if p.derived_roles:
                self.policy_derived_roles[mod_id] = dict(p.derived_roles)
        elif isinstance(p, CompiledPrincipalPolicy):
            self.meta[mod_id] = PolicyMeta(
                fqn=p.fqn, name=p.principal, version=p.version, kind="PRINCIPAL",
                source_attributes=p.source_attributes, annotations=p.annotations,
            )
        elif isinstance(p, CompiledRolePolicy):
            self.meta[mod_id] = PolicyMeta(
                fqn=p.fqn, name=p.role, version=p.version, kind="ROLE",
                source_attributes=p.source_attributes, annotations=p.annotations,
            )
            self.scope_parent_roles.setdefault(p.scope, {})[p.role] = list(p.parent_roles)

        rows = rows_from_policy(p)
        self._index_rows(rows)
        self.idx.index_parent_roles(self.scope_parent_roles)

    def _index_rows(self, rows: list[RuleRow]) -> None:
        for row in rows:
            if row.scope_permissions != model.SCOPE_PERMISSIONS_UNSPECIFIED:
                self.scope_scope_permissions[row.scope] = row.scope_permissions
            if row.policy_kind == KIND_PRINCIPAL:
                self.principal_scope_map[row.scope] = True
            elif row.policy_kind == KIND_RESOURCE:
                self.resource_scope_map[row.scope] = True
        self.idx.index_rules(rows)

    def delete_policy(self, fqn: str) -> None:
        self._chain_attr_memo.clear()
        self.bundle_hash_memo = None
        self.idx.delete_policy(fqn)
        mod_id = namer.module_id(fqn)
        meta = self.meta.pop(mod_id, None)
        self.schemas.pop(mod_id, None)
        self.policy_derived_roles.pop(mod_id, None)
        # a deleted role policy must stop granting its parent-role inheritance
        if meta is not None and meta.kind == "ROLE":
            scope = namer.scope_from_fqn(fqn)
            role_parents = self.scope_parent_roles.get(scope)
            if role_parents is not None:
                role_parents.pop(meta.name, None)
                if not role_parents:
                    del self.scope_parent_roles[scope]
            self.idx.index_parent_roles(self.scope_parent_roles)
        # scope maps/permissions are rebuilt from surviving rows
        self._rebuild_scope_maps()

    def _rebuild_scope_maps(self) -> None:
        self.principal_scope_map.clear()
        self.resource_scope_map.clear()
        self.scope_scope_permissions.clear()
        for row in self.idx.get_all_rows():
            if row.scope_permissions != model.SCOPE_PERMISSIONS_UNSPECIFIED:
                self.scope_scope_permissions[row.scope] = row.scope_permissions
            if row.policy_kind == KIND_PRINCIPAL:
                self.principal_scope_map[row.scope] = True
            elif row.policy_kind == KIND_RESOURCE:
                self.resource_scope_map[row.scope] = True

    # -- lookups ----------------------------------------------------------

    def get_derived_roles(self, fqn: str) -> Optional[dict[str, CompiledDerivedRole]]:
        return self.policy_derived_roles.get(namer.module_id(fqn))

    def get_schema(self, fqn: str) -> Optional[model.Schemas]:
        """Only the schema defined by the root (scopeless) policy of the scope
        chain is in effect (compile/compile.go:182-183)."""
        root = fqn.partition("/")[0]
        return self.schemas.get(namer.module_id(root))

    def get_chain_source_attributes(self, fqn: str) -> dict[str, dict]:
        """Source attributes for a policy AND its scope ancestors — compiled
        policy sets carry the whole ancestor chain's SourceAttributes
        (compile.go:153-165), so one binding attributes every policy in its
        chain."""
        hit = self._chain_attr_memo.get(fqn)
        if hit is not None:
            return hit
        out: dict[str, dict] = {}
        root, sep, scope = fqn.partition("/")
        chain = [fqn]
        if sep:
            segs = scope.split(".")
            for i in range(len(segs) - 1, 0, -1):
                chain.append(f"{root}/{'.'.join(segs[:i])}")
            chain.append(root)
        for f in chain:
            meta = self.meta.get(namer.module_id(f))
            if meta is not None and meta.source_attributes:
                out[f] = meta.source_attributes
        self._chain_attr_memo[fqn] = out
        return out

    def get_meta(self, fqn: str) -> Optional[PolicyMeta]:
        return self.meta.get(namer.module_id(fqn))

    def get_scope_scope_permissions(self, scope: str) -> str:
        return self.scope_scope_permissions.get(scope, model.SCOPE_PERMISSIONS_UNSPECIFIED)

    def get_all_scopes(
        self, kind: str, scope: str, name: str, version: str, lenient: bool
    ) -> tuple[list[str], str, str]:
        """Ref: ruletable.go:814-848. Returns (scopes most-specific-first,
        first policy key, first FQN)."""
        if kind == KIND_PRINCIPAL:
            fqn_fn = namer.principal_policy_fqn
            scope_map = self.principal_scope_map
        else:
            fqn_fn = namer.resource_policy_fqn
            scope_map = self.resource_scope_map

        first_key = ""
        first_fqn = ""
        scopes: list[str] = []
        if scope in scope_map:
            first_fqn = fqn_fn(name, version, scope)
            first_key = namer.policy_key_from_fqn(first_fqn)
            scopes.append(scope)
        elif not lenient:
            return [], "", ""

        for s in namer.scope_parents(scope):
            if s in scope_map:
                scopes.append(s)
                if not first_key:
                    first_fqn = fqn_fn(name, version, s)
                    first_key = namer.policy_key_from_fqn(first_fqn)

        return scopes, first_key, first_fqn

    def combine_scopes(self, principal_scopes: list[str], resource_scopes: list[str]) -> list[str]:
        """Children-first DFS over the union scope tree (ruletable.go:855-906)."""
        unique = set(principal_scopes) | set(resource_scopes)
        children: dict[str, dict] = {}

        for scope in unique:
            if scope == "":
                continue
            cur = children
            parts = scope.split(".")
            for part in parts:
                cur = cur.setdefault(part, {})

        result: list[str] = []

        def dfs(node: dict, prefix: str) -> None:
            for part, sub in node.items():
                full = f"{prefix}.{part}" if prefix else part
                dfs(sub, full)
                if full in unique:
                    result.append(full)

        dfs(children, "")
        if "" in unique:
            result.append("")
        return result


def build_rule_table(
    policies: list[CompiledPolicy], index_backend: Optional[str] = None
) -> RuleTable:
    rt = RuleTable(index_backend=index_backend)
    for p in policies:
        rt.ingest_policy(p)
    return rt
