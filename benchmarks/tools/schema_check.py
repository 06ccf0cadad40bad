#!/usr/bin/env python3
"""Schema validation's plain reading: which validation errors a reply must carry.

The classic template's policies name a ``principalSchema`` and a
``resourceSchema`` (``lib/corpus.py``'s documents), and with
``schema.enforcement: warn`` or ``reject`` the server validates every input
whose resource policy names them and returns what it found in the result's
``validation_errors``. This file says what that must be, from the documents,
the schemas (``corpus.schemas``) and a request as ``workload.build`` makes it:

- which policy's schemas hold: those of the ROOT (scopeless) policy of the
  resource's kind and version, for every scope of its chain, where a policy
  for the input's own scope exists (upstream ``compile.go:182-183``);
- the errors, as ``(source, path, keyword)``: the template's schemas use four
  keywords (``type``, ``properties``, ``enum``, ``required``; a schema with any
  other is refused), checked the way upstream's validator
  (santhosh-tekuri/jsonschema) reports them: a value of the wrong ``type`` is
  checked no further, and ONE ``required`` error names all of an object's
  missing properties, at the object's own path;
- the totals of a window (seed, rate, seconds): validations, errors by source,
  inputs with errors, to hold ``cerbos_tpu_schema_errors_total`` against.

Imports nothing of ``cerbos_tpu`` and no validator library.

    python benchmarks/tools/schema_check.py --workload classic-800-schema.pages --seed 0 --seconds 40
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import corpus, spec, workload  # noqa: E402

PRINCIPAL, RESOURCE = "SOURCE_PRINCIPAL", "SOURCE_RESOURCE"
KEYWORDS = {"$schema", "type", "properties", "enum", "required"}
# how a served message names its keyword: upstream's wording, which is part of the wire format
MESSAGE_KEYWORDS = (
    ("missing properties: ", "required"),
    ("value must be one of ", "enum"),
    ("expected ", "type"),
    ("failed to load schema ", "load"),
)
_URL_PREFIX = "cerbos:///"


class SchemaError(Exception):
    """A schema this plain reading cannot check."""


def json_type(v) -> str:
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "boolean"
    if isinstance(v, (int, float)):
        return "number"
    if isinstance(v, str):
        return "string"
    return "array" if isinstance(v, list) else "object"


def check_schema(schema: dict, where: str = "") -> None:
    unknown = sorted(set(schema) - KEYWORDS)
    if unknown:
        raise SchemaError(f"schema{where}: keywords {unknown} are not among {sorted(KEYWORDS)}")
    for name, sub in schema.get("properties", {}).items():
        check_schema(sub, f"{where}/properties/{name}")


def errors(schema: dict, value, path: str = "") -> list[tuple[str, str]]:
    """``(path, keyword)`` of every error of ``value`` against ``schema``; the root's path is ``/``."""
    here = path or "/"
    want = schema.get("type")
    if want is not None:
        got = json_type(value)
        wanted = [want] if isinstance(want, str) else want
        if got not in wanted and not (got == "number" and "integer" in wanted and float(value).is_integer()):
            return [(here, "type")]
    out = []
    if "enum" in schema and value not in schema["enum"]:
        out.append((here, "enum"))
    if isinstance(value, dict):
        if any(name not in value for name in schema.get("required", ())):
            out.append((here, "required"))
        for name, sub in schema.get("properties", {}).items():
            if name in value:
                out.extend(errors(sub, value[name], f"{path}/{name}"))
    return out


class Table:
    """Which schemas hold for a resource, read from the corpus's documents."""

    def __init__(self, docs: list[str], schemas: dict[str, bytes]):
        import yaml

        self.policies: set[tuple[str, str, str]] = set()  # (kind, version, scope) of every resource policy
        self.refs: dict[tuple[str, str], tuple[str | None, str | None]] = {}  # root policies: (kind, version) -> refs
        loader = getattr(yaml, "CSafeLoader", yaml.SafeLoader)
        for doc in docs:
            if "resourcePolicy:" not in doc:
                continue
            rp = yaml.load(doc, Loader=loader)["resourcePolicy"]
            kind, version, scope = rp["resource"], str(rp.get("version", "default")), rp.get("scope", "")
            self.policies.add((kind, version, scope))
            named = rp.get("schemas")
            if named and not scope:
                for side in ("principalSchema", "resourceSchema"):
                    if named.get(side, {}).get("ignoreWhen"):
                        raise SchemaError(f"{kind}/{version}: {side}.ignoreWhen is not part of this plain reading")
                self.refs[(kind, version)] = tuple(
                    (named.get(side) or {}).get("ref") or None for side in ("principalSchema", "resourceSchema")
                )
        self.schemas: dict[str, dict] = {}
        for refs in self.refs.values():
            for ref in refs:
                if ref is not None and ref not in self.schemas:
                    name = ref[len(_URL_PREFIX):] if ref.startswith(_URL_PREFIX) else ref
                    if name not in schemas:
                        raise SchemaError(f"the policies name {ref}, which the corpus does not ship")
                    self.schemas[ref] = json.loads(schemas[name])
                    check_schema(self.schemas[ref])

    @classmethod
    def of_corpus(cls, mods: int) -> "Table":
        return cls(corpus.corpus_yaml(mods).split("\n---\n"), corpus.schemas(mods))

    def kinds_with_schemas(self) -> set[str]:
        return {kind for kind, _ in self.refs}

    def refs_for(self, resource: dict) -> tuple[str | None, str | None]:
        """(principal ref, resource ref) that hold for ``resource``; (None,
        None) where no policy covers its kind, version and scope, or its root
        policy names none."""
        version = resource.get("policyVersion") or "default"
        if (resource["kind"], version, resource.get("scope", "")) not in self.policies:
            return None, None
        return self.refs.get((resource["kind"], version), (None, None))

    def expected(self, req: workload.Request) -> list[list[tuple[str, str, str]]]:
        """For each resource of ``req``, in order: its errors as sorted
        ``(source, path, keyword)``; empty for a kind without schemas."""
        out = []
        for resource, _actions in req.entries:
            p_ref, r_ref = self.refs_for(resource)
            found = []
            if p_ref is not None:
                found += [(PRINCIPAL, p, k) for p, k in errors(self.schemas[p_ref], req.principal["attr"])]
            if r_ref is not None:
                found += [(RESOURCE, p, k) for p, k in errors(self.schemas[r_ref], resource["attr"])]
            out.append(sorted(found))
        return out


def keyword_of(message: str) -> str:
    for prefix, keyword in MESSAGE_KEYWORDS:
        if message.startswith(prefix):
            return keyword
    return "?"


def diff(expected: list[list[tuple[str, str, str]]], served: list[list[tuple[str, str, str]]]) -> str | None:
    """None when every result carries exactly the expected errors. ``served``:
    for each result of a reply, in order, its ``validation_errors`` as
    ``(source name, path, message)``; a message has to be there, and to name
    the keyword in upstream's wording."""
    if len(served) != len(expected):
        return f"{len(served)} results for {len(expected)} resources"
    for k, (want, got) in enumerate(zip(expected, served)):
        if any(not message for _, _, message in got):
            return f"result {k}: an error with no message: {got}"
        named = sorted((source, path, keyword_of(message)) for source, path, message in got)
        if named != want:
            return f"result {k}: got {named} (messages {[m for _, _, m in got]}) want {want}"
    return None


def totals(table: Table, reqs: list[workload.Request]) -> dict:
    out = {
        "requests": len(reqs), "inputs": 0, "validated_inputs": 0, "validations": 0, "errors": 0,
        "errors_principal": 0, "errors_resource": 0, "inputs_with_errors": 0,
        "inputs_failing_principal": 0, "inputs_failing_resource": 0,
    }
    for req in reqs:
        for (resource, _), found in zip(req.entries, table.expected(req)):
            held = sum(ref is not None for ref in table.refs_for(resource))  # validator runs: one per source whose schema holds
            out["inputs"] += 1
            out["validations"] += held
            out["validated_inputs"] += bool(held)
            principal = sum(source == PRINCIPAL for source, _, _ in found)
            out["errors"] += len(found)
            out["errors_principal"] += principal
            out["errors_resource"] += len(found) - principal
            out["inputs_with_errors"] += bool(found)
            out["inputs_failing_principal"] += bool(principal)
            out["inputs_failing_resource"] += len(found) > principal
    if reqs:
        for key in ("inputs", "validations", "errors", "inputs_with_errors", "inputs_failing_principal", "inputs_failing_resource"):
            out[f"{key}_per_request"] = out[key] / len(reqs)
    return out


def window_totals(root: str, workload_name: str, seed: int, seconds: float) -> dict:
    """The totals of the window ``benchmarks/run.py --workload ... --seed ...
    --seconds ...`` sends (``Session.prepare`` builds the same requests)."""
    cell = spec.Cell(root, workload_name)
    mods = int(cell.config["corpus"]["mods"])
    reqs = workload.build(round(cell.pair["rate"] * seconds), mods, seed, cell.traffic["request"])
    return {"workload": workload_name, "seed": seed, "seconds": seconds, **totals(Table.of_corpus(mods), reqs)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    print(json.dumps(window_totals(ROOT, args.workload, args.seed, args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
