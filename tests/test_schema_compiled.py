"""The compiled validator against the generic one (cerbos_tpu/schema.py).

A schema whose every keyword is ``type``, ``properties``, ``required``,
``enum``, ``additionalProperties: true | false`` or an annotation is compiled
once into plain Python; any other keyword anywhere and the whole document
stays with python-jsonschema. The generic reading (``_generic_check``:
``Draft202012Validator.iter_errors`` filtered by ``_upstream_errors`` and
worded by ``_error_message``) is the definition. Held here: the two yield the
same errors, in the same order, with the same words, on drawn schemas and
instances (hypothesis), on hand-picked corners of python-jsonschema's typing,
and on the template's own schemas under the benchmark's own requests, which
are also held to the plain reading (``benchmarks/tools/schema_check.py``: no
validator library, nothing of the program); then what selects the interpreter,
what fails to load, what a plan query drops, the cache across a store event,
and the two series of the mechanism.
"""

import json
import os
import sys
import threading

import jsonschema
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import corpus  # noqa: E402
from benchmarks.tools import schema_check  # noqa: E402

from cerbos_tpu import observability as obs  # noqa: E402
from cerbos_tpu import schema as schema_mod  # noqa: E402
from cerbos_tpu.engine import types as T  # noqa: E402
from cerbos_tpu.policy import model  # noqa: E402
from cerbos_tpu.schema import ENGINE_COMPILED, ENGINE_GENERIC, SchemaManager  # noqa: E402
from cerbos_tpu.storage.store import Event  # noqa: E402

# the tiny classic corpus, its requests and the in-process helpers of the served tests
from test_schema_served import MODS, PAGES, SINGLES, TABLE, disk, inputs_of, rule_table_of  # noqa: E402, F401  (disk: a fixture)


class Documents:
    """A store of schema documents alone: id -> raw bytes (None: not there)."""

    def __init__(self, raw: dict):
        self.raw = dict(raw)
        self.listeners = []

    def get_schema(self, schema_id):
        return self.raw.get(schema_id)

    def subscribe(self, fn):
        self.listeners.append(fn)

    def fire(self):
        for fn in self.listeners:
            fn([Event(kind="reload")])


def documents(**docs) -> Documents:
    return Documents({f"{name}.json": json.dumps(doc).encode() for name, doc in docs.items()})


def ref(name: str) -> model.SchemaRef:
    return model.SchemaRef(f"cerbos:///{name}.json")


def check_input(principal_attr, resource_attr=None, actions=("a",)) -> T.CheckInput:
    return T.CheckInput(
        request_id="x", principal=T.Principal(id="p", roles=[], attr=principal_attr), actions=list(actions),
        resource=T.Resource(kind="k", id="r", attr={} if resource_attr is None else resource_attr),
    )


def findings(check, instance) -> list:
    out: list = []
    check(instance, out)
    return out


def both(doc):
    """(compiled check, generic check) of ``doc``, which has to compile."""
    compiled = schema_mod.compile_schema(doc)
    assert compiled is not None, doc
    return compiled, schema_mod._generic_check(jsonschema.Draft202012Validator(doc))


def runs(engine: str) -> float:
    return obs.metrics().counter_vec("cerbos_tpu_schema_validator_runs_total", label="engine").get(engine)


def compiled_gauge() -> float:
    return obs.metrics().gauge("cerbos_tpu_schema_validators_compiled").value


# -- (a) the differential: drawn schemas x drawn instances -----------------------------

NAMES = ["a", "b", "c", "d/e"]
TYPES = ["array", "boolean", "integer", "null", "number", "object", "string"]
SCALARS = st.sampled_from([None, True, False, 0, 1, 2, 0.0, 1.0, 1.5, -3, "", "x", "y", "1", "marketing"])
JSON_VALUES = st.recursive(
    SCALARS,
    lambda kids: st.lists(kids, max_size=3) | st.dictionaries(st.sampled_from(NAMES), kids, max_size=3),
    max_leaves=6,
)
# what reaches a validator: JSON-like, and a tuple where a caller built the attributes by hand
INSTANCES = st.recursive(
    SCALARS,
    lambda kids: st.lists(kids, max_size=3)
    | st.lists(kids, max_size=3).map(tuple)
    | st.dictionaries(st.sampled_from(NAMES + ["z"]), kids, max_size=5),
    max_leaves=14,
)
OBJECTS = st.dictionaries(st.sampled_from(NAMES + ["z"]), INSTANCES, max_size=5)


def subschemas(depth: int):
    keywords = {
        "type": st.sampled_from(TYPES) | st.lists(st.sampled_from(TYPES), max_size=3),
        "enum": st.lists(JSON_VALUES, max_size=4),
        "required": st.lists(st.sampled_from(NAMES), max_size=4),  # a name twice is a schema too
        "additionalProperties": st.booleans(),
        "$schema": st.just("https://json-schema.org/draft/2020-12/schema"),
        "title": st.just("a title"),
        "description": st.just("words"),
        "$comment": st.just("more words"),
        "default": JSON_VALUES,
        "examples": st.lists(JSON_VALUES, max_size=2),
    }
    if depth:
        keywords["properties"] = st.dictionaries(st.sampled_from(NAMES), subschemas(depth - 1), max_size=4)
    # keywords in shuffled order: python-jsonschema reports in the document's own
    return st.fixed_dictionaries({}, optional=keywords).flatmap(
        lambda doc: st.permutations(list(doc.items())).map(dict)
    )


PROFILES = {
    "one subschema, any instance": (subschemas(0), INSTANCES),
    "one level of properties, objects": (subschemas(1), OBJECTS),
    "one level of properties, any instance": (subschemas(1), INSTANCES),
    "three levels of properties, objects": (subschemas(3), OBJECTS),
    "three levels of properties, any instance": (subschemas(3), INSTANCES),
}


@pytest.mark.parametrize("profile", list(PROFILES))
def test_the_compiled_reading_equals_the_generic_one_on_drawn_schemas_and_instances(profile):
    schemas, instances = PROFILES[profile]

    @settings(max_examples=400, deadline=None, database=None, derandomize=True)
    @given(doc=schemas, many=st.lists(instances, min_size=1, max_size=6))
    def differential(doc, many):
        compiled, generic = both(json.loads(json.dumps(doc)))  # as the store hands it over
        for instance in many:
            assert findings(compiled, instance) == findings(generic, instance), (doc, instance)

    differential()


ROOT_OBJECT = {"type": "object"}
CORNERS = {
    "a bool is not a number": ({"properties": {"n": {"type": "number"}}}, [{"n": True}, {"n": False}, {"n": 1}, {"n": 1.5}]),
    "a bool is not an integer, 1.0 is one, 1.5 is not": (
        {"properties": {"n": {"type": "integer"}}}, [{"n": True}, {"n": 1.0}, {"n": 1.5}, {"n": 7}, {"n": float("nan")}]
    ),
    "enum tells True from 1 and False from 0, and not 1 from 1.0": (
        {"enum": [1, False]}, [1, 1.0, True, 0, 0.0, False, "1", None]
    ),
    "enum tells them apart inside lists and objects too": (
        {"enum": [[True, {"k": 0}], {"k": [1]}]},
        [[True, {"k": 0}], [1, {"k": 0}], [True, {"k": False}], (True, {"k": 0}), {"k": [1.0]}, {"k": [True]}, {"k": [1], "l": 2}],
    ),
    "enum of strings meets what is not a string": ({"enum": ["x", "y"]}, ["x", "z", 1, None, ["x"], {"x": 1}, True]),
    "an empty enum allows nothing": ({"enum": []}, [None, "x"]),
    "a list is an array and a tuple is not, though it is called one": (
        {"properties": {"l": {"type": "array"}, "m": {"type": ["array", "null"]}}}, [{"l": [1], "m": (1,)}, {"l": (1, 2), "m": None}, {"l": {}}]
    ),
    "an empty list of types allows nothing": ({"type": []}, [None, {}]),
    "required, properties and additionalProperties bind objects only": (
        {"required": ["a"], "properties": {"a": {"type": "string"}}, "additionalProperties": False}, [[], "s", 3, None, ("a",), {}]
    ),
    "one required error names every missing property, one twice if the schema does": (
        {"required": ["a", "b", "a", "c"]}, [{}, {"b": 1}, {"a": 1, "b": 2, "c": 3}]
    ),
    "a value that fails type meets neither enum nor additionalProperties": (
        {"enum": [{}], "additionalProperties": False, "type": "string", "required": ["a"], "properties": {"b": {"type": "null"}}},
        [{"b": 1, "x": 2}, {"a": 1}, "s", 5],
    ),
    "properties under a failed type are still walked, in the document's order": (
        {"properties": {"a": {"type": "integer"}}, "type": "string", "required": ["z"]}, [{"a": "x"}, {"a": 1, "z": 0}]
    ),
    "errors come in the schema's property order, not the instance's": (
        {"properties": {"b": {"type": "string"}, "a": {"type": "string"}, "c": {"enum": [1]}}}, [{"a": 1, "c": 2, "b": 3}]
    ),
    "keywords before type": ({"required": ["a"], "enum": [{"a": 1}], "type": "object"}, [{}, {"a": 1}, {"a": True}, []]),
    "additionalProperties with no properties beside it": ({"additionalProperties": False}, [{}, {"x": 1, "y": 2}]),
    "additionalProperties true allows all": (
        {"properties": {"a": {}}, "additionalProperties": True, "required": ["b"]}, [{"a": 1, "x": 2}]
    ),
    "a path is the names joined, unescaped": (
        {"properties": {"d/e": {"properties": {"": {"type": "null"}, "~": {"type": "null"}}}}}, [{"d/e": {"": 1, "~": 2}}]
    ),
    "nested objects, depth first": (
        {**ROOT_OBJECT, "required": ["o", "p"], "properties": {
            "o": {**ROOT_OBJECT, "required": ["k", "l"], "properties": {"k": {"type": ["string", "null"], "enum": ["x", None]}}},
            "q": {"type": "boolean"},
        }},
        [{"o": {"k": "y"}, "q": 0}, {"o": {"k": None, "l": 1}, "p": 1, "q": False}, {"o": [], "q": None}],
    ),
    "annotations assert nothing": (
        {"$schema": "https://json-schema.org/draft/2020-12/schema", "title": "t", "description": "d", "$comment": "c",
         "default": {"type": 3}, "examples": [{"enum": 1}], "type": "object"},
        [{}, []],
    ),
    "the template's principal schema": (corpus._principal_schema(), [{}, {"department": "x", "geography": 1.0, "team": None}]),
    "the template's resource schema": (
        corpus._leave_request_schema(), [{"department": "finance", "geography": "GB", "team": "t", "id": "i", "dev_record": 1.0}]
    ),
}


@pytest.mark.parametrize("corner", list(CORNERS))
def test_a_corner_reads_the_same_through_either_interpreter(corner):
    """Values as a caller may build them by hand: ints, tuples, no object at the root."""
    doc, instances = CORNERS[corner]
    compiled, generic = both(doc)
    for instance in instances:
        assert findings(compiled, instance) == findings(generic, instance), instance
    assert any(findings(compiled, instance) for instance in instances), "a corner that finds no error shows nothing"


@pytest.mark.parametrize("corner", list(CORNERS))
@pytest.mark.parametrize("source", ["principal", "resource"])
def test_a_corners_reply_is_the_same_through_either_interpreter(corner, source, monkeypatch):
    """Through the manager, as attributes arrive (``normalize_attr``: an object
    at the root, numbers as floats, no tuple): ``(path, message, source)`` in
    order, what the reply carries. The corner sits under a property."""
    doc, instances = CORNERS[corner]
    doc = {"properties": {"v": doc}}
    compiled_mgr = SchemaManager(documents(s=doc), "warn")
    with monkeypatch.context() as m:
        m.setattr(schema_mod, "compile_schema", lambda document: None)  # no document compiles: the parent's path
        generic_mgr = SchemaManager(documents(s=doc), "warn")
        assert generic_mgr._validator("cerbos:///s.json").engine == ENGINE_GENERIC
    assert compiled_mgr._validator("cerbos:///s.json").engine == ENGINE_COMPILED
    schemas = model.Schemas(**{f"{source}_schema": ref("s")})
    found = 0
    for instance in instances:
        inp = check_input({"v": instance}, {}) if source == "principal" else check_input({}, {"v": instance})
        got, want = compiled_mgr.validate_check_input(schemas, inp), generic_mgr.validate_check_input(schemas, inp)
        assert got == want, instance
        assert all(e.source == f"SOURCE_{source.upper()}" and e.path.startswith("/v") for e in got[0])
        found += len(got[0])
    assert found


# -- (b) the template's schemas under the benchmark's own requests --------------------


@pytest.mark.parametrize("traffic", ["pages", "singles"])
@pytest.mark.parametrize("source", ["principal", "resource"])
def test_the_templates_schemas_read_the_benchmarks_requests_as_the_plain_reading_does(traffic, source):
    checks = {r: both(doc) for r, doc in TABLE.schemas.items()}
    validated = errors = 0
    for req in PAGES if traffic == "pages" else SINGLES:
        for (resource, _actions), want in zip(req.entries, TABLE.expected(req)):
            schema_ref = TABLE.refs_for(resource)[0 if source == "principal" else 1]
            if schema_ref is None:
                continue
            attrs = req.principal["attr"] if source == "principal" else resource["attr"]
            compiled, generic = checks[schema_ref]
            got = findings(compiled, attrs)
            assert got == findings(generic, attrs), attrs
            assert sorted((path, keyword) for keyword, path, _ in got) == [
                (path, keyword) for s, path, keyword in want if s == f"SOURCE_{source.upper()}"
            ]
            assert all(schema_check.keyword_of(message) == keyword for keyword, _, message in got)
            validated += 1
            errors += len(got)
    want = schema_check.totals(TABLE, PAGES if traffic == "pages" else SINGLES)
    assert validated and errors == want[f"errors_{source}"]  # the singles' principals are all complete: 0 there
    assert want["errors_principal"] > 0 or traffic == "singles"


def test_every_schema_of_the_template_compiles():
    mgr = SchemaManager(Documents(corpus.schemas(MODS)), "warn")
    for name in corpus.schemas(MODS):
        assert mgr._validator(f"cerbos:///{name}").engine == ENGINE_COMPILED
    assert compiled_gauge() == 3 * MODS
    # ... and ONCE a document: the template's 3 x MODS refs are two documents (a leave request and an employee
    # record read the same), so a page's runs walk two closure trees, which stay in the CPU's caches
    assert len({id(v.check) for v in mgr._cache.values()}) == len(set(corpus.schemas(MODS).values())) == 2


# -- (c) any keyword outside the subset, anywhere: the whole document stays generic -----

OUTSIDE = {
    "$ref": {"$ref": "#/$defs/x"},
    "$id": {"$id": "https://example.com/s.json"},
    "$defs": {"$defs": {"x": {"type": "string"}}},
    "$anchor": {"$anchor": "here"},
    "items": {"items": {"type": "string"}},
    "prefixItems": {"prefixItems": [{"type": "string"}]},
    "contains": {"contains": {"type": "string"}},
    "pattern": {"pattern": "^x"},
    "format": {"format": "date"},
    "const": {"const": 1},
    "oneOf": {"oneOf": [{"type": "string"}]},
    "anyOf": {"anyOf": [{"type": "string"}]},
    "allOf": {"allOf": [{"type": "string"}]},
    "not": {"not": {"type": "string"}},
    "if": {"if": {"type": "string"}, "then": {"enum": ["x"]}},
    "minimum": {"minimum": 1},
    "maximum": {"maximum": 1},
    "exclusiveMinimum": {"exclusiveMinimum": 1},
    "multipleOf": {"multipleOf": 2},
    "minLength": {"minLength": 1},
    "maxLength": {"maxLength": 1},
    "minItems": {"minItems": 1},
    "uniqueItems": {"uniqueItems": True},
    "minProperties": {"minProperties": 1},
    "maxProperties": {"maxProperties": 1},
    "propertyNames": {"propertyNames": {"enum": ["a"]}},
    "patternProperties": {"patternProperties": {"^x": {"type": "string"}}},
    "patternProperties beside additionalProperties": {"additionalProperties": False, "patternProperties": {"^x": {}}},
    "additionalProperties as a schema": {"additionalProperties": {"type": "string"}},
    "dependentRequired": {"dependentRequired": {"a": ["b"]}},
    "dependentSchemas": {"dependentSchemas": {"a": {"required": ["b"]}}},
    "unevaluatedProperties": {"unevaluatedProperties": False},
    "a keyword no draft knows": {"x-internal": 1},
    "a boolean subschema": True,
    "a type that is no name": {"type": 3},
    "required that is no list of names": {"required": ["a", 1]},
    "enum that is no list": {"enum": "xy"},
    "properties that is no object": {"properties": None},
}


@pytest.mark.parametrize("keyword", list(OUTSIDE))
def test_one_keyword_outside_the_subset_keeps_the_whole_document_generic(keyword):
    inside = {"type": "object", "required": ["a"], "properties": {"a": {"type": "string", "enum": ["x"]}}}
    assert schema_mod.compile_schema(inside) is not None
    deep = json.loads(json.dumps(inside))
    deep["properties"]["o"] = {"type": "object", "properties": {"leaf": OUTSIDE[keyword]}}  # two levels down
    assert schema_mod.compile_schema(deep) is None
    mgr = SchemaManager(documents(s=deep), "warn")
    assert mgr._validator("cerbos:///s.json").engine == ENGINE_GENERIC and compiled_gauge() == 0
    before = {e: runs(e) for e in schema_mod.ENGINES}
    errors, _ = mgr.validate_check_input(model.Schemas(principal_schema=ref("s")), check_input({"a": "y", "o": 3}))
    # the part of the document that WOULD compile is read by python-jsonschema too, to the same words
    assert [(e.path, e.message) for e in errors] == [("/a", 'value must be one of "x"'), ("/o", "expected object, but got number")]
    assert {e: runs(e) - before[e] for e in schema_mod.ENGINES} == {ENGINE_COMPILED: 0, ENGINE_GENERIC: 1}


def test_a_document_that_does_not_compile_still_finds_what_its_keywords_say():
    mgr = SchemaManager(documents(s={"properties": {"n": {"type": "string", "minLength": 3}}, "required": ["n", "m"]}), "warn")
    errors, _ = mgr.validate_check_input(model.Schemas(principal_schema=ref("s")), check_input({"n": "ab"}))
    assert [(e.path, e.message) for e in errors] == [("/n", "'ab' is too short"), ("/", "missing properties: 'm'")]


# -- (d) what is not a schema still fails to load, where it did -------------------------

NOT_A_SCHEMA = {"a list": b"[]", "a string": b'"x"', "a number": b"3", "null": b"null", "not JSON": b"{not json", "missing": None}


@pytest.mark.parametrize("what", list(NOT_A_SCHEMA))
def test_what_is_not_a_schema_still_reads_failed_to_load_schema(what):
    mgr = SchemaManager(Documents({"s.json": NOT_A_SCHEMA[what]}), "warn")
    before = {e: runs(e) for e in schema_mod.ENGINES}
    errors, _ = mgr.validate_check_input(model.Schemas(resource_schema=ref("s")), check_input({}))
    assert [(e.path, e.message, e.source) for e in errors] == [("", "failed to load schema cerbos:///s.json", "SOURCE_RESOURCE")]
    gauges = obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state")
    assert (gauges.get("loaded"), gauges.get("failed"), compiled_gauge()) == (0, 1, 0)
    assert {e: runs(e) - before[e] for e in schema_mod.ENGINES} == {ENGINE_COMPILED: 0, ENGINE_GENERIC: 0}  # neither ran


@pytest.mark.parametrize("document,errors", [(True, 0), (False, 1)])
def test_a_boolean_document_is_a_schema_and_python_jsonschemas_to_read(document, errors):
    mgr = SchemaManager(documents(s=document), "warn")
    assert mgr._validator("cerbos:///s.json").engine == ENGINE_GENERIC
    found, _ = mgr.validate_check_input(model.Schemas(principal_schema=ref("s")), check_input({"a": 1}))
    assert len(found) == errors and all(e.path == "/" for e in found)


# -- (e) a plan query drops the required errors, and no other ---------------------------


@pytest.mark.parametrize("engine", [ENGINE_COMPILED, ENGINE_GENERIC])
def test_resource_ignore_required_drops_exactly_the_required_errors(engine):
    doc = {
        "type": "object", "required": ["a", "b"], "additionalProperties": False,
        "properties": {"o": {"type": "object", "required": ["k"]}, "e": {"enum": ["x"]}, "t": {"type": "string"}},
    }
    if engine == ENGINE_GENERIC:
        doc["minProperties"] = 0  # asserts nothing, and keeps the document with python-jsonschema
    mgr = SchemaManager(documents(s=doc), "warn")
    assert mgr._validator("cerbos:///s.json").engine == engine
    schemas = model.Schemas(principal_schema=ref("s"), resource_schema=ref("s"))
    attrs = {"o": {}, "e": "y", "t": 1, "x": 0}
    full, _ = mgr.validate_check_input(schemas, check_input(attrs, attrs))
    planned, _ = mgr.validate_check_input(schemas, check_input(attrs, attrs), resource_ignore_required=True)
    kept = [("/", "additionalProperties 'x' not allowed"), ("/e", 'value must be one of "x"'), ("/t", "expected string, but got number")]
    required = [("/", "missing properties: 'a', 'b'"), ("/o", "missing properties: 'k'")]
    by_source = lambda errors, source: sorted((e.path, e.message) for e in errors if e.source == source)  # noqa: E731
    assert by_source(full, "SOURCE_RESOURCE") == by_source(full, "SOURCE_PRINCIPAL") == sorted(kept + required)
    assert by_source(planned, "SOURCE_PRINCIPAL") == sorted(kept + required)  # the principal's stay
    assert by_source(planned, "SOURCE_RESOURCE") == sorted(kept)
    assert [e for e in planned if e.source == "SOURCE_RESOURCE"] == [
        e for e in full if e.source == "SOURCE_RESOURCE" and not e.message.startswith("missing properties")
    ]  # ... in the order they had


# -- (f) a store event drops the compiled validators with the cache ---------------------


def test_a_store_event_drops_the_compiled_validators_and_the_next_use_compiles_the_new_document():
    store = documents(s={"type": "object", "required": ["a"]}, g={"required": ["a"], "minProperties": 0})
    mgr = SchemaManager(store, "warn")
    schemas = model.Schemas(principal_schema=ref("s"), resource_schema=ref("g"))
    assert [e.message for e in mgr.validate_check_input(schemas, check_input({}))[0]] == ["missing properties: 'a'"] * 2
    loaded = obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state").labels("loaded")
    assert (loaded.value, compiled_gauge()) == (2, 1)
    store.raw["s.json"] = json.dumps({"type": "object", "required": ["a", "b"]}).encode()
    assert [e.message for e in mgr.validate_check_input(schemas, check_input({}))[0]][0] == "missing properties: 'a'"  # no event yet
    store.fire()
    assert mgr._cache == {} and (loaded.value, compiled_gauge()) == (0, 0)
    assert [e.message for e in mgr.validate_check_input(schemas, check_input({}))[0]][0] == "missing properties: 'a', 'b'"
    assert (loaded.value, compiled_gauge()) == (2, 1)
    store.raw["s.json"] = json.dumps({"type": "object", "pattern": "x"}).encode()  # the same ref, now outside the subset
    store.fire()
    assert mgr._validator("cerbos:///s.json").engine == ENGINE_GENERIC and compiled_gauge() == 0


def test_refs_with_the_same_document_share_one_validator_until_an_event_or_an_edit_parts_them():
    same = {"type": "object", "required": ["a"]}
    store = documents(s=same, t=same, g={"required": ["a"], "pattern": "x"}, h={"required": ["a"], "pattern": "x"})
    mgr = SchemaManager(store, "warn")
    s, t, g, h = (mgr._validator(f"cerbos:///{name}.json") for name in "stgh")
    assert s is t and g is h and s is not g and (s.engine, g.engine) == (ENGINE_COMPILED, ENGINE_GENERIC)
    loaded = obs.metrics().gauge_vec("cerbos_tpu_schema_validators", label="state").labels("loaded")
    assert (loaded.value, compiled_gauge()) == (4, 2)  # the gauges count refs, as before
    store.raw["t.json"] = json.dumps({"type": "object", "required": ["a", "b"]}).encode()
    store.fire()
    assert mgr._by_document == {}  # dropped with the cache
    s2, t2 = mgr._validator("cerbos:///s.json"), mgr._validator("cerbos:///t.json")
    assert s2 is not t2 and s2 is not s
    schemas = model.Schemas(principal_schema=ref("s"), resource_schema=ref("t"))
    assert [e.message for e in mgr.validate_check_input(schemas, check_input({}))[0]] == [
        "missing properties: 'a'", "missing properties: 'a', 'b'"
    ]


def test_a_document_compiled_from_what_the_store_held_before_an_event_is_never_filed_after_it():
    store = documents(s={"type": "object", "required": ["a"]})
    mgr = SchemaManager(store, "warn")
    get_schema, entered, go = store.get_schema, threading.Event(), threading.Event()

    def slow_get_schema(schema_id):
        raw = get_schema(schema_id)  # what the store holds NOW
        entered.set()
        assert go.wait(10)
        return raw

    store.get_schema = slow_get_schema
    built = []
    t = threading.Thread(target=lambda: built.append(mgr._validator("cerbos:///s.json")))
    t.start()
    assert entered.wait(10)
    store.fire()  # the event lands while the old bytes are being compiled
    go.set()
    t.join(10)
    assert not t.is_alive() and built[0].engine == ENGINE_COMPILED  # the request in hand is answered
    assert "cerbos:///s.json" not in mgr._cache and mgr._generation == 1 and compiled_gauge() == 0
    assert mgr._by_document == {}


# -- (g) the two series of the mechanism, after a served page ---------------------------


def test_a_served_page_is_read_by_compiled_validators_alone_and_the_series_say_so(disk):
    """A page through the batcher, the evaluator's assembly (the device route,
    the drain thread) and its one ``Tally`` a flight."""
    from cerbos_tpu.engine.batcher import BatchingEvaluator
    from cerbos_tpu.tpu import TpuEvaluator

    mgr = SchemaManager(disk, "warn")
    rt = rule_table_of(disk)
    assert mgr.load(rt) == (3 * MODS, 0) and compiled_gauge() == 3 * MODS  # compiled at load, ahead of traffic
    batcher = BatchingEvaluator(TpuEvaluator(rt, schema_mgr=mgr, use_jax=False), max_wait_ms=1.0)
    page = PAGES[1]
    seconds = obs.metrics().histogram_vec("cerbos_tpu_schema_validate_seconds", label="source")
    observed = lambda: sum(seconds.labels(s).count for s in ("principal", "resource"))  # noqa: E731
    before, observed_before = {e: runs(e) for e in schema_mod.ENGINES}, observed()
    try:
        outputs = batcher.check(inputs_of(page))
    finally:
        batcher.close()
    got = [[(e.source, e.path, e.message) for e in o.validation_errors] for o in outputs]
    assert schema_check.diff(TABLE.expected(page), got) is None
    want = schema_check.totals(TABLE, [page])
    assert want["validations"] > len(page.entries) and want["errors"] > 0
    assert {e: runs(e) - before[e] for e in schema_mod.ENGINES} == {ENGINE_COMPILED: want["validations"], ENGINE_GENERIC: 0}
    assert observed() - observed_before == want["validations"]  # one observation a run, as before
    rendered = obs.metrics().render()
    assert f"cerbos_tpu_schema_validators_compiled {3 * MODS}" in rendered
    assert 'cerbos_tpu_schema_validator_runs_total{engine="generic"}' in rendered  # there from boot, at 0 or not


def test_with_none_nothing_compiles_and_both_series_stay_at_0(disk):
    mgr = SchemaManager(disk, "none")
    before = {e: runs(e) for e in schema_mod.ENGINES}
    assert mgr.load(rule_table_of(disk)) == (0, 0) and mgr._cache == {} and compiled_gauge() == 0
    assert mgr.validate_check_input(None, inputs_of(PAGES[0])[0]) == ([], False)
    assert {e: runs(e) - before[e] for e in schema_mod.ENGINES} == {ENGINE_COMPILED: 0, ENGINE_GENERIC: 0}
