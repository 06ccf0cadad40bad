"""JSON-schema validation of principals/resources.

Behavioral reference: internal/schema/schema.go — enforcement levels
none/warn/reject (schema.go:31-35), schemas referenced from resource
policies as ``cerbos:///<id>``, ignoreWhen action globs, validation errors
attributed to SOURCE_PRINCIPAL / SOURCE_RESOURCE, cache invalidated on store
events (schema.go:129-151).

Validators are built AHEAD of traffic: :meth:`SchemaManager.load` resolves
every ref a rule table names, at boot and at each cutover, so a schema that is
missing or does not parse shows as ``cerbos_tpu_schema_validators{state=
"failed"}`` and a log line before a request meets it (the request still reads
upstream's ``failed to load schema <ref>``). A ref that no table named is
built at its first use, as before. A store event empties the cache; a
validator built from what the store held before the event is never filed
after it (``_generation``).

What validation costs and finds is on ``cerbos_tpu_schema_*`` (the series are
listed in docs/OBSERVABILITY.md), one count and one observation per
validation, under the route that answered the input: ``device`` (the
evaluator's assembly, on the drain thread), ``oracle`` (the CPU walk of a
flight or a fallback) or ``inline`` (the CPU walk on the request's own
thread); a replay that answers no request (``shadow``: the parity sentinel,
the rollout gate) is validated and not counted. With enforcement ``none``
nothing is loaded, counted or timed.

A loaded schema is read by one of two interpreters, chosen by what the
document holds and by nothing else. Where every keyword, at every depth, is
``type`` (a name or a list of names), ``properties``, ``required``, ``enum``,
``additionalProperties: true | false`` or an annotation (``$schema``,
``title``, ``description``, ``$comment``, ``default``, ``examples``), the
document is COMPILED once, at ``load`` or at the ref's first use, into closures
of plain Python (:func:`compile_schema`), and a validation costs a few
microseconds; refs whose documents are the same bytes share one compiled
validator (the template's ``principal_<i>.json`` are one document under a
hundred names). Any other keyword anywhere (``$ref``, ``items``, ``pattern``,
``oneOf``, ``minimum``, a boolean subschema, ...) and the whole document stays
with python-jsonschema (``Draft202012Validator.iter_errors``, filtered and
re-worded to upstream's: :func:`_generic_check`), some ten times dearer. The
generic reading is the definition: the compiled one yields the same ``(path,
message, source)``, in the same order, and tests/test_schema_compiled.py holds
it to that. ``cerbos_tpu_schema_validators_compiled`` says how many of the
loaded validators are compiled, ``cerbos_tpu_schema_validator_runs_total
{engine}`` which interpreter made the runs.
"""

from __future__ import annotations

import json
import logging
import numbers
import threading
import time
from typing import Any, Callable, Iterable, NamedTuple, Optional

import jsonschema

from . import globs
from .engine import types as T
from .observability import metrics
from .policy import model
from .storage.store import Event, Store

_log = logging.getLogger("cerbos_tpu.schema")

ENFORCEMENT_NONE = "none"
ENFORCEMENT_WARN = "warn"
ENFORCEMENT_REJECT = "reject"

ROUTE_DEVICE = "device"
ROUTE_ORACLE = "oracle"
ROUTE_INLINE = "inline"
ROUTES = (ROUTE_DEVICE, ROUTE_ORACLE, ROUTE_INLINE)
# a replay that answers no request (the parity sentinel's, the rollout gate's): validated, since under reject
# validation decides effects, and neither counted nor timed, so the series read what the replies carry
ROUTE_SHADOW = "shadow"

SOURCE_PRINCIPAL = "SOURCE_PRINCIPAL"
SOURCE_RESOURCE = "SOURCE_RESOURCE"
# the metric label of each source
SOURCE_LABELS = {SOURCE_PRINCIPAL: "principal", SOURCE_RESOURCE: "resource"}
OUTCOMES = ("valid", "invalid", "ignored", "no_schema")

_URL_PREFIX = "cerbos:///"


def _quoted(names: Iterable[str]) -> str:
    return ", ".join(f"'{n}'" for n in names)


def _error_message(err: "jsonschema.ValidationError") -> str:
    """Validation message in the reference's wording where it differs.

    The reference validates with santhosh-tekuri/jsonschema (v5); its messages
    are part of the wire response (server corpus pins ``enum``). Translate the
    shapes that appear in practice; anything else keeps python-jsonschema's
    phrasing."""
    kind = err.validator
    if kind == "enum":
        allowed = ", ".join(json.dumps(v) for v in err.validator_value)
        return f"value must be one of {allowed}"
    if kind == "required":
        # one error names every missing property (see _upstream_errors)
        return f"missing properties: {_quoted(p for p in err.validator_value if p not in err.instance)}"
    if kind == "type":
        want = err.validator_value
        return f"expected {want if isinstance(want, str) else ' or '.join(want)}, but got {_json_type(err.instance)}"
    if kind == "additionalProperties" and err.validator_value is False:
        known = set(err.schema.get("properties", ()))
        patterns = err.schema.get("patternProperties")
        if not patterns:
            return f"additionalProperties {_quoted(p for p in err.instance if p not in known)} not allowed"
    return err.message


def _json_type(v: Any) -> str:
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "boolean"
    if isinstance(v, (int, float)):
        return "number"
    if isinstance(v, str):
        return "string"
    return "array" if isinstance(v, (list, tuple)) else "object"


def _upstream_errors(errs: list) -> list:
    """python-jsonschema's errors as the reference's validator would have
    raised them: ONE ``required`` error per object (it lists the missing
    properties; python-jsonschema raises one per property), and where a value
    fails ``type`` no other keyword of that schema is checked against it.
    Errors of one value against one subschema share both objects, so their
    identities are the key: no path is built."""
    if len(errs) < 2:
        return errs
    typed = {(id(e.instance), id(e.schema)) for e in errs if e.validator == "type"}
    required_seen: set = set()
    out = []
    for e in errs:
        kind = e.validator
        if kind == "required":
            where = (id(e.instance), id(e.schema))
            if where in required_seen:
                continue
            required_seen.add(where)
        elif kind != "type" and typed and (id(e.instance), id(e.schema)) in typed:
            continue
        out.append(e)
    return out


ENGINE_COMPILED = "compiled"
ENGINE_GENERIC = "generic"
ENGINES = (ENGINE_COMPILED, ENGINE_GENERIC)


class Validator(NamedTuple):
    """A loaded schema: ``check(instance, out)`` appends the instance's
    findings to ``out`` as ``(keyword, path, message)``, in python-jsonschema's
    order and upstream's wording, whichever interpreter ``engine`` names. The
    keyword travels with the error because a plan query drops ``required``."""

    check: Callable[[Any, list], None]
    engine: str


def _generic_check(validator: "jsonschema.Draft202012Validator") -> Callable[[Any, list], None]:
    """python-jsonschema's own reading of a document, filtered and re-worded:
    the definition that the compiled reading is held to
    (tests/test_schema_compiled.py)."""

    def check(instance: Any, out: list) -> None:
        for err in _upstream_errors(list(validator.iter_errors(instance))):
            out.append((err.validator, "/" + "/".join(str(p) for p in err.absolute_path), _error_message(err)))

    return check


# keywords that assert nothing
_ANNOTATIONS = frozenset(("$schema", "title", "description", "$comment", "default", "examples"))
# python-jsonschema's Draft 2020-12 type checker, not Python's: a bool is no number, 1.0 is an integer, a tuple is
# no array (and `_json_type` still calls it one, as on the generic path)
_IS_TYPE: dict[str, Callable[[Any], bool]] = {
    "array": lambda v: isinstance(v, list),
    "boolean": lambda v: isinstance(v, bool),
    "integer": lambda v: (isinstance(v, int) and not isinstance(v, bool)) or (isinstance(v, float) and v.is_integer()),
    "null": lambda v: v is None,
    "number": lambda v: isinstance(v, numbers.Number) and not isinstance(v, bool),
    "object": lambda v: isinstance(v, dict),
    "string": lambda v: isinstance(v, str),
}


def _equal(one: Any, two: Any) -> bool:
    """``enum``'s equality, python-jsonschema's: True is not 1 and False is
    not 0, at any depth of a list or an object; 1 is 1.0."""
    if one is two:
        return True
    if isinstance(one, str) or isinstance(two, str):
        return one == two
    if isinstance(one, (list, tuple)) and isinstance(two, (list, tuple)):
        return len(one) == len(two) and all(_equal(a, b) for a, b in zip(one, two))
    if isinstance(one, dict) and isinstance(two, dict):
        return len(one) == len(two) and all(k in two and _equal(v, two[k]) for k, v in one.items())
    if isinstance(one, bool) or isinstance(two, bool):
        return False  # not both the same singleton
    return one == two


def compile_schema(schema: Any, path: str = "") -> Optional[Callable[[Any, list], None]]:
    """A schema document as a plain-Python ``check(instance, out)`` that
    appends the findings the generic path reports, element for element; None
    where any keyword at any depth is outside what this reads (``type``,
    ``properties``, ``required``, ``enum``, ``additionalProperties: true |
    false``, annotations), and the whole document then stays with
    python-jsonschema. No ``items`` and no ``$ref``, so every subschema sits
    at ONE ``path``, known here: a run builds no path."""
    if not isinstance(schema, dict):
        return None  # true | false, or not a schema at all
    here = path or "/"
    is_type: Optional[Callable[[Any], bool]] = None
    # the subschema's keywords in the document's own order, as python-jsonschema walks them; `typed` are the
    # steps of a value that passed `type` (or met none), `untyped` those of one that failed it: the `type` error
    # in its place, and neither `enum` nor `additionalProperties` (_upstream_errors)
    typed: list[Callable[[Any, list], None]] = []
    untyped: list[Callable[[Any, list], None]] = []
    for keyword, value in schema.items():
        if keyword in _ANNOTATIONS:
            continue
        if keyword == "type":
            names = [value] if isinstance(value, str) else value
            if not isinstance(names, list) or not all(isinstance(n, str) and n in _IS_TYPE for n in names):
                return None
            is_type = _type_test(names)
            untyped.append(_type_step(here, f"expected {' or '.join(names)}, but got "))
        elif keyword == "enum":
            if not isinstance(value, list):
                return None
            typed.append(_enum_step(here, value))
        elif keyword == "additionalProperties":
            if not isinstance(value, bool):
                return None
            if not value:
                known = schema.get("properties")
                typed.append(_no_additional_step(here, frozenset(known) if isinstance(known, dict) else frozenset()))
        elif keyword == "required":
            if not isinstance(value, list) or not all(isinstance(n, str) for n in value):
                return None
            step = _required_step(here, tuple(value))
            typed.append(step)
            untyped.append(step)
        elif keyword == "properties":
            if not isinstance(value, dict):
                return None
            children = [(name, compile_schema(sub, f"{path}/{name}")) for name, sub in value.items()]
            if any(child is None for _, child in children):
                return None
            step = _properties_step(tuple(children))
            typed.append(step)
            untyped.append(step)
        else:
            return None
    return _subschema(is_type, tuple(typed), tuple(untyped))


def _type_test(names: list) -> Callable[[Any], bool]:
    if len(names) == 1:
        return _IS_TYPE[names[0]]
    tests = tuple(_IS_TYPE[n] for n in names)
    return lambda v: any(test(v) for test in tests)


def _type_step(here: str, expected: str) -> Callable[[Any, list], None]:
    return lambda value, out: out.append(("type", here, expected + _json_type(value)))


def _enum_step(here: str, allowed: list) -> Callable[[Any, list], None]:
    finding = ("enum", here, "value must be one of " + ", ".join(json.dumps(v) for v in allowed))
    if all(isinstance(v, str) for v in allowed):
        strings = frozenset(allowed)

        def check(value: Any, out: list) -> None:
            if not (isinstance(value, str) and value in strings):
                out.append(finding)
    else:

        def check(value: Any, out: list) -> None:
            for each in allowed:
                if _equal(each, value):
                    return
            out.append(finding)

    return check


def _required_step(here: str, required: tuple) -> Callable[[Any, list], None]:
    def check(value: Any, out: list) -> None:
        if isinstance(value, dict):
            for name in required:
                if name not in value:  # ONE error names every missing property
                    out.append(("required", here, f"missing properties: {_quoted(p for p in required if p not in value)}"))
                    return

    return check


def _properties_step(children: tuple) -> Callable[[Any, list], None]:
    def check(value: Any, out: list) -> None:
        if isinstance(value, dict):
            for name, child in children:
                if name in value:
                    child(value[name], out)

    return check


def _no_additional_step(here: str, known: frozenset) -> Callable[[Any, list], None]:
    def check(value: Any, out: list) -> None:
        if isinstance(value, dict):
            extras = [p for p in value if p not in known]
            if extras:
                out.append(("additionalProperties", here, f"additionalProperties {_quoted(extras)} not allowed"))

    return check


def _subschema(is_type: Optional[Callable[[Any], bool]], typed: tuple, untyped: tuple) -> Callable[[Any, list], None]:
    def check(value: Any, out: list) -> None:
        for step in typed if is_type is None or is_type(value) else untyped:
            step(value, out)

    return check


class Tally:
    """What some validations counted, found and took, gathered to be booked
    into the instruments at once (``SchemaManager.book``): a flight's 43
    validator runs then take each instrument's lock once, not once each, on
    the drain thread. Every run is still one observation of
    ``schema_validate_seconds`` and one count."""

    __slots__ = ("outcomes", "errors", "seconds", "engines")

    def __init__(self) -> None:
        self.outcomes: dict[tuple, int] = {}  # (source label, outcome, route) -> inputs
        self.errors: dict[str, int] = {}  # source label -> errors
        self.seconds: dict[str, list[float]] = {}  # source -> one entry per validator run
        self.engines: dict[str, int] = {}  # engine -> runs of a loaded validator

    def count(self, key: tuple) -> None:
        self.outcomes[key] = self.outcomes.get(key, 0) + 1


class SchemaManager:
    def __init__(self, store: Store, enforcement: str = ENFORCEMENT_NONE):
        self.store = store
        self.enforcement = enforcement
        self._cache: dict[str, Optional[Validator]] = {}  # ref -> validator, None where it could not be loaded
        # raw document -> its validator: refs whose documents are the same bytes (the template's principal_<i>.json
        # are one document under 100 names) share ONE, so a page's 43 runs walk two or three closure trees that stay
        # in the CPU's caches and not forty that do not; dropped with the cache, to stay as small
        self._by_document: dict[bytes, Validator] = {}
        self._generation = 0  # store events seen: a validator is filed only under the generation it was built in
        self._lock = threading.Lock()
        reg = metrics()
        self._m_validations = reg.counter_vec(
            "cerbos_tpu_schema_validations_total",
            "schema validations asked for, one per check input and source whose resource policy is in the table, by "
            "outcome (valid | invalid: at least one error, a schema that failed to load among them | ignored: "
            "every action matches ignoreWhen | no_schema: the policy names none for this source) and by the route "
            "that answered the input (device | oracle | inline); nothing with schema.enforcement none",
            label=("source", "outcome", "route"),
        )
        self._m_errors = reg.counter_vec(
            "cerbos_tpu_schema_errors_total",
            "validation errors found (errors, not inputs: what the replies' validation_errors carry), by source",
            label="source",
        )
        seconds = reg.histogram_vec(
            "cerbos_tpu_schema_validate_seconds",
            "seconds of one validation (one source of one input against its schema), on the thread that ran it, "
            "by source",
            label="source",
            buckets=[0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.01, 0.1],
        )
        self._m_seconds = {src: seconds.labels(label) for src, label in SOURCE_LABELS.items()}
        validators = reg.gauge_vec(
            "cerbos_tpu_schema_validators",
            "schema refs in the validator cache, by state: loaded, or failed (missing from the store, not JSON, "
            "or not a schema: an input under it reads `failed to load schema`); 0 after a store event until the "
            "new table's refs are loaded; always 0 with schema.enforcement none",
            label="state",
        )
        self._m_loaded, self._m_failed = validators.labels("loaded"), validators.labels("failed")
        self._m_compiled = reg.gauge(
            "cerbos_tpu_schema_validators_compiled",
            "of the loaded validators, those compiled into plain Python: every keyword of the document, at every "
            "depth, is type, properties, required, enum, additionalProperties true | false or an annotation; the "
            "others are read by python-jsonschema, to the same errors",
        )
        self._m_runs = reg.counter_vec(
            "cerbos_tpu_schema_validator_runs_total",
            "runs of a loaded validator (cerbos_tpu_schema_validate_seconds' count, less the validations whose "
            "schema failed to load), by the interpreter that made them: compiled | generic (python-jsonschema)",
            label="engine",
        )
        self._m_resets = reg.counter(
            "cerbos_tpu_schema_cache_resets_total", "store events that emptied the validator cache"
        )
        # every series at 0 from boot
        for label in SOURCE_LABELS.values():
            self._m_errors.inc(label, 0.0)
            for outcome in OUTCOMES:
                for route in ROUTES:
                    self._m_validations.inc((label, outcome, route), 0.0)
        for engine in ENGINES:
            self._m_runs.inc(engine, 0.0)
        self._m_resets.inc(0.0)
        self._book_cache()
        store.subscribe(self._on_event)

    @property
    def enabled(self) -> bool:
        return self.enforcement != ENFORCEMENT_NONE

    def _on_event(self, events: list[Event]) -> None:
        with self._lock:
            self._generation += 1
            self._cache = {}
            self._by_document = {}
        self._m_resets.inc()
        self._book_cache()

    def _book_cache(self) -> None:
        with self._lock:  # a request's thread may be filing a validator meanwhile
            total = len(self._cache)
            failed = sum(1 for v in self._cache.values() if v is None)
            compiled = sum(1 for v in self._cache.values() if v is not None and v.engine == ENGINE_COMPILED)
        self._m_loaded.set(total - failed)
        self._m_failed.set(failed)
        self._m_compiled.set(compiled)

    def load(self, rule_table: Any) -> tuple[int, int]:
        """Build the validator of every schema ref ``rule_table`` names, ahead
        of the requests that need them. -> (loaded, failed) of the cache;
        nothing with enforcement ``none``."""
        if not self.enabled:
            return 0, 0
        refs = set()
        for schemas in rule_table.schemas.values():
            for schema_ref in (schemas.principal_schema, schemas.resource_schema):
                if schema_ref is not None and schema_ref.ref:
                    refs.add(schema_ref.ref)
        failed = sorted(ref for ref in refs if self._validator(ref, book=False) is None)
        self._book_cache()
        if failed:
            _log.warning(
                "%d of %d schemas named by the policies failed to load (inputs under them read "
                "`failed to load schema`): %s",
                len(failed), len(refs), ", ".join(failed[:8]) + (", ..." if len(failed) > 8 else ""),
            )
        else:
            _log.info("%d schemas loaded, enforcement %s", len(refs), self.enforcement)
        return len(refs) - len(failed), len(failed)

    def _validator(self, ref: str, book: bool = True) -> Optional[Validator]:
        cache = self._cache
        if ref in cache:
            return cache[ref]
        generation = self._generation
        schema_id = ref[len(_URL_PREFIX):] if ref.startswith(_URL_PREFIX) else ref
        raw = self.store.get_schema(schema_id)
        validator = None if raw is None else self._by_document.get(raw)
        if raw is not None and validator is None:
            try:
                document = json.loads(raw)
                # what the compiler reads is an object python-jsonschema's constructor accepts, so a document
                # that is not a schema fails to load where it did
                compiled = compile_schema(document)
                if compiled is not None:
                    validator = Validator(compiled, ENGINE_COMPILED)
                else:
                    validator = Validator(_generic_check(jsonschema.Draft202012Validator(document)), ENGINE_GENERIC)
            except Exception:  # noqa: BLE001 — invalid schema acts as missing
                validator = None
        with self._lock:
            if generation == self._generation:
                self._cache[ref] = validator
                if validator is not None:
                    self._by_document[raw] = validator
        if book:  # a ref met first inside a request; load() books once for all of its refs
            self._book_cache()
        return validator

    def _validate(
        self,
        schema_ref: Optional[model.SchemaRef],
        attrs: dict[str, Any],
        actions: list[str],
        source: str,
        route: str,
        errors: list[T.ValidationError],
        tally: "Tally",
        ignore_required: bool = False,
    ) -> None:
        label = SOURCE_LABELS[source]
        if schema_ref is None or not schema_ref.ref:
            tally.count((label, "no_schema", route))
            return
        if self._ignored(schema_ref, actions):
            tally.count((label, "ignored", route))
            return
        t0 = time.perf_counter()
        had = len(errors)
        validator = self._validator(schema_ref.ref)
        if validator is None:
            errors.append(T.ValidationError(path="", message=f"failed to load schema {schema_ref.ref}", source=source))
        else:
            findings: list = []
            validator.check(attrs, findings)
            for keyword, path, message in findings:
                if ignore_required and keyword == "required":
                    continue
                errors.append(T.ValidationError(path=path, message=message, source=source))
            tally.engines[validator.engine] = tally.engines.get(validator.engine, 0) + 1
        found = len(errors) - had
        tally.seconds.setdefault(source, []).append(time.perf_counter() - t0)
        tally.count((label, "invalid" if found else "valid", route))
        if found:
            tally.errors[label] = tally.errors.get(label, 0) + found

    def book(self, tally: "Tally") -> None:
        """Into the instruments, each one's lock taken once."""
        for key, n in tally.outcomes.items():
            self._m_validations.inc(key, n)
        for label, n in tally.errors.items():
            self._m_errors.inc(label, n)
        for source, seconds in tally.seconds.items():
            self._m_seconds[source].observe_many(seconds)
        for engine, n in tally.engines.items():
            self._m_runs.inc(engine, n)

    def validate_check_input(
        self,
        schemas: Optional[model.Schemas],
        input: T.CheckInput,
        principal_only: bool = False,
        resource_ignore_required: bool = False,
        route: str = ROUTE_ORACLE,
        tally: Optional["Tally"] = None,
    ) -> tuple[list[T.ValidationError], bool]:
        """→ (errors, reject). Ref: schema.go ValidateCheckInput;
        ``resource_ignore_required`` mirrors ValidatePlanResourcesInput
        (schema_common.go:157-162): resource attributes are optional when
        planning, so required-property errors are filtered. ``route``: who
        answers the input, for the counters alone (``shadow``: no one, and
        nothing is booked). ``tally``: where a caller with many inputs in hand
        (a flight) gathers what is counted, to :meth:`book` it once; without
        one this input's own is booked before returning."""
        if not self.enabled:
            return [], False
        if schemas is None:
            schemas = _NO_SCHEMAS
        own = tally is None
        if own:
            tally = Tally()
        errors: list[T.ValidationError] = []
        self._validate(
            schemas.principal_schema, input.principal.attr, input.actions, SOURCE_PRINCIPAL, route, errors, tally
        )
        if not principal_only:
            self._validate(
                schemas.resource_schema, input.resource.attr, input.actions, SOURCE_RESOURCE, route, errors, tally,
                ignore_required=resource_ignore_required,
            )
        if own and route != ROUTE_SHADOW:
            self.book(tally)
        reject = bool(errors) and self.enforcement == ENFORCEMENT_REJECT
        return errors, reject

    def _ignored(self, schema_ref: model.SchemaRef, actions: list[str]) -> bool:
        """ignoreWhen: skip validation when every action matches a glob."""
        if not schema_ref.ignore_when_actions:
            return False
        return all(
            any(globs.matches_glob(pat, a) for pat in schema_ref.ignore_when_actions) for a in actions
        )


_NO_SCHEMAS = model.Schemas()
