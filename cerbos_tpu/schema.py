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
"""

from __future__ import annotations

import json
import logging
import threading
import time
from typing import Any, Iterable, Optional

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


class Tally:
    """What some validations counted, found and took, gathered to be booked
    into the instruments at once (``SchemaManager.book``): a flight's 43
    validator runs then take each instrument's lock once, not once each, on
    the drain thread. Every run is still one observation of
    ``schema_validate_seconds`` and one count."""

    __slots__ = ("outcomes", "errors", "seconds")

    def __init__(self) -> None:
        self.outcomes: dict[tuple, int] = {}  # (source label, outcome, route) -> inputs
        self.errors: dict[str, int] = {}  # source label -> errors
        self.seconds: dict[str, list[float]] = {}  # source -> one entry per validator run

    def count(self, key: tuple) -> None:
        self.outcomes[key] = self.outcomes.get(key, 0) + 1


class SchemaManager:
    def __init__(self, store: Store, enforcement: str = ENFORCEMENT_NONE):
        self.store = store
        self.enforcement = enforcement
        self._cache: dict[str, Any] = {}  # ref -> validator, None where it could not be loaded
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
        self._m_resets = reg.counter(
            "cerbos_tpu_schema_cache_resets_total", "store events that emptied the validator cache"
        )
        # every series at 0 from boot
        for label in SOURCE_LABELS.values():
            self._m_errors.inc(label, 0.0)
            for outcome in OUTCOMES:
                for route in ROUTES:
                    self._m_validations.inc((label, outcome, route), 0.0)
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
        self._m_resets.inc()
        self._book_cache()

    def _book_cache(self) -> None:
        with self._lock:  # a request's thread may be filing a validator meanwhile
            total = len(self._cache)
            failed = sum(1 for v in self._cache.values() if v is None)
        self._m_loaded.set(total - failed)
        self._m_failed.set(failed)

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

    def _validator(self, ref: str, book: bool = True) -> Optional[Any]:
        cache = self._cache
        if ref in cache:
            return cache[ref]
        generation = self._generation
        schema_id = ref[len(_URL_PREFIX):] if ref.startswith(_URL_PREFIX) else ref
        raw = self.store.get_schema(schema_id)
        validator = None
        if raw is not None:
            try:
                validator = jsonschema.Draft202012Validator(json.loads(raw))
            except Exception:  # noqa: BLE001 — invalid schema acts as missing
                validator = None
        with self._lock:
            if generation == self._generation:
                self._cache[ref] = validator
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
            for err in _upstream_errors(list(validator.iter_errors(attrs))):
                if ignore_required and err.validator == "required":
                    continue
                path = "/" + "/".join(str(p) for p in err.absolute_path)
                errors.append(T.ValidationError(path=path, message=_error_message(err), source=source))
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
