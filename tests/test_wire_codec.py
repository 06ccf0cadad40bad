"""The gRPC listener's native codec (PR 49) against its definition.

``cerbos_native.check_request_decode`` reads a ``CheckResourcesRequest`` from
its wire bytes into validated ``CheckInput``s and ``check_reply_encode`` writes
the ``CheckResourcesResponse`` from the ``CheckOutput``s. protobuf's own parse
with ``server/convert.py`` and ``server/wire_validate.py`` is the definition:
what the codec takes it reads and writes exactly as they do, field for field,
type for type and word for word; whatever it is not sure of it DECLINES (it
returns None and raises nothing, whatever the bytes), and ``_WireCodec`` then
answers from the Python path, which also raises what is to be raised. This file
runs under the ASAN build too (``make test-native-asan``).
"""

import os
import random
import struct
import sys

import grpc
import pytest
from google.protobuf.message import DecodeError

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib import workload  # noqa: E402
from cerbos_tpu import native  # noqa: E402
from cerbos_tpu.api.cerbos.request.v1 import request_pb2  # noqa: E402
from cerbos_tpu.api.cerbos.response.v1 import response_pb2  # noqa: E402
from cerbos_tpu.engine import budget as budget_mod  # noqa: E402
from cerbos_tpu.engine import types as T  # noqa: E402
from cerbos_tpu.engine.batcher import BatchingEvaluator  # noqa: E402
from cerbos_tpu.engine.budget import FRONT_PARTS, STAGE_ADMISSION  # noqa: E402
from cerbos_tpu.engine.engine import Engine  # noqa: E402
from cerbos_tpu.observability import metrics  # noqa: E402
from cerbos_tpu.server import convert, wire_validate  # noqa: E402
from cerbos_tpu.server import server as server_mod  # noqa: E402
from cerbos_tpu.server.server import Server, ServerConfig, _WireCodec  # noqa: E402
from cerbos_tpu.server.service import CerbosService  # noqa: E402

from test_ipc import OracleEvaluator, table, wait_for  # noqa: E402

N = native.get()
pytestmark = pytest.mark.skipif(
    N is None or not hasattr(N, "check_request_decode"), reason="the native module is not built"
)

MODS = 20
CALL_ID = "0123456789abcdef0123456789abcdef"
EMPTY = object()  # a Value with no member of its oneof set
FromString = request_pb2.CheckResourcesRequest.FromString


def decode(data):
    return N.check_request_decode(data, T.Principal, T.Resource, T.CheckInput)


# -- a wire writer of the test's own: also what protobuf's encoder never emits ---


def varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def ld(field: int, payload: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def st(field: int, text: str) -> bytes:
    return ld(field, text.encode())


def vi(field: int, n: int) -> bytes:
    return varint(field << 3) + varint(n)


def value(v) -> bytes:
    if v is EMPTY:
        return b""
    if v is None:
        return b"\x08\x00"
    if isinstance(v, bool):
        return b"\x20" + bytes([v])
    if isinstance(v, (int, float)):
        return b"\x11" + struct.pack("<d", v)
    if isinstance(v, str):
        return st(3, v)
    if isinstance(v, dict):
        v = tuple(v.items())
    if isinstance(v, tuple):  # a struct as (key, value) pairs: keys may repeat
        return ld(5, b"".join(ld(1, entry(k, x)) for k, x in v))
    return ld(6, b"".join(ld(1, value(x)) for x in v))


def entry(k: str, v, flip: bool = False) -> bytes:
    parts = [st(1, k), ld(2, value(v))]
    return b"".join(reversed(parts) if flip else parts)


def entity(first="", version="", third="", scope="", attr=(), extra=b"") -> bytes:
    """engine.v1.Principal (id, policy_version, roles...) or Resource (kind,
    policy_version, id): the same field numbers, 1 2 3 4 5."""
    chunks = [st(1, first)] if first else []
    if version:
        chunks.append(st(2, version))
    if isinstance(third, (list, tuple)):
        chunks += [st(3, r) for r in third]
    elif third:
        chunks.append(st(3, third))
    chunks += [ld(4, entry(k, v)) for k, v in (attr.items() if isinstance(attr, dict) else attr)]
    if scope:
        chunks.append(st(5, scope))
    return b"".join(chunks) + extra


def resource_entry(actions=("view",), resource=entity("album", third="a1"), extra=b"") -> bytes:
    body = b"".join(st(1, a) for a in actions)
    if resource is not None:
        body += ld(2, resource)
    return body + extra


PRINCIPAL = entity("u1", third=["user"])


def request(principal=PRINCIPAL, entries=(resource_entry(),), request_id="", include_meta=None, aux=None, extra=b"") -> bytes:
    out = st(1, request_id) if request_id else b""
    if include_meta is not None:
        out += vi(2, include_meta)
    if principal is not None:
        out += ld(3, principal)
    out += b"".join(ld(4, e) for e in entries)
    if aux is not None:
        out += ld(5, aux)
    return out + extra


# -- what "agrees" means ---------------------------------------------------------


def same(a, b) -> bool:
    """Equal AND of the same types all the way down; floats by their bits
    (NaN is itself, -0.0 is not 0.0)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return struct.pack("<d", a) == struct.pack("<d", b)
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(type(k) is str and same(a[k], b[k]) for k in a)
    if isinstance(a, list):
        return len(a) == len(b) and all(map(same, a, b))
    return a == b


ENTITY_FIELDS = {T.Principal: ("id", "roles", "attr", "policy_version", "scope"),
                 T.Resource: ("kind", "id", "attr", "policy_version", "scope")}


def same_entity(a, b) -> bool:
    return type(a) is type(b) and all(same(getattr(a, f), getattr(b, f)) for f in ENTITY_FIELDS[type(a)])


def agrees(data: bytes) -> str:
    """``native`` when the codec read ``data`` exactly as FromString, convert
    and wire_validate do; ``declined`` when it returned None (always, where
    protobuf refuses the bytes); anything else fails the test."""
    got = decode(data)
    try:
        msg = FromString(data)
    except DecodeError:
        assert got is None, "the codec read bytes that protobuf refuses"
        return "declined"
    if got is None:
        return "declined"
    inputs, request_id, include_meta, token, key_set_id, violation, raw = got
    assert raw is data
    assert same(request_id, msg.request_id) and include_meta is msg.include_meta
    assert same(token, msg.aux_data.jwt.token) and same(key_set_id, msg.aux_data.jwt.key_set_id)
    assert same(violation, wire_validate.check_resources_proto(msg))
    if violation is not None:
        assert inputs == []
        return "native"
    want = convert.check_resources_request_to_inputs(msg, None)
    assert inputs == want or b"\xf8\x7f" in data  # as dataclasses (a NaN is not itself)
    assert len(inputs) == len(want)
    for g, w in zip(inputs, want):
        assert type(g) is T.CheckInput and g.aux_data is None
        assert g.principal is inputs[0].principal and same_entity(g.principal, w.principal)
        assert same_entity(g.resource, w.resource)
        assert same(g.actions, w.actions) and same(g.request_id, w.request_id)
    return "native"


# -- request parity: the benchmark's own traffic ---------------------------------


def bench_wires(seed: int, lo: int, hi: int, n: int):
    reqs = workload.build(n, MODS, seed, {"resources": [lo, hi]})
    workload.serialize(reqs)
    return reqs


@pytest.mark.parametrize("seed", [0, 7, 2147483653, 2147530001])
@pytest.mark.parametrize("shape", [(16, 50), (1, 1)], ids=["pages", "singles"])
def test_the_benchmarks_requests_are_all_read_natively(seed, shape):
    reqs = bench_wires(seed, *shape, n=40)
    with_token = sum(1 for r in reqs if decode(r.wire)[3])
    assert 0 < with_token < len(reqs)  # the mix carries both
    assert [agrees(r.wire) for r in reqs] == ["native"] * len(reqs)


def test_a_token_reaches_every_input_through_the_handlers_attach():
    (req,) = [r for r in bench_wires(7, 16, 50, n=10) if decode(r.wire)[3]][:1]
    msg = FromString(req.wire)
    aux = T.AuxData(jwt={"iss": "cerbos-test", "n": 1})
    inputs = decode(req.wire)[0]
    for i in inputs:
        i.aux_data = aux
    assert inputs == convert.check_resources_request_to_inputs(msg, aux)


# -- request parity: what a Value can hold ----------------------------------------

KINDS = {
    "null": None,
    "true": True,
    "false": False,
    "number": 1.0,
    "integral_number": 7,
    "negative_zero": -0.0,
    "nan": float("nan"),
    "infinity": float("-inf"),
    "denormal": 5e-324,
    "string": "owner",
    "empty_string": "",
    "non_ascii": "département ключ 部門 🙂",
    "nul_in_string": "a\x00b",
    "unset_oneof": EMPTY,
    "empty_list": [],
    "empty_struct": {},
    "list_of_every_kind": [None, True, 1.5, "x", [], {}, EMPTY],
    "struct_of_every_kind": {"n": None, "b": False, "d": 2.0, "s": "y", "l": [1.0], "m": {"k": "v"}, "": EMPTY},
    "three_deep_struct": {"a": {"b": {"c": [1.0, {"d": None}]}}},
    "three_deep_list": [[[True, [2.0, "z"]]]],
    "non_ascii_keys": {"ключ": {"部門": "🙂"}},
    "duplicate_keys": (("dup", 1.0), ("other", "x"), ("dup", "the last one stands")),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_kind_of_value_in_both_attribute_maps(kind):
    v = KINDS[kind]
    data = request(
        principal=entity("u1", third=["user"], attr={"v": v, "w": [v, {"x": v}]}),
        entries=[resource_entry(resource=entity("album", third="a1", attr={"v": v, "deep": {"l": [v]}}))],
    )
    assert agrees(data) == "native"
    got = decode(data)[0][0]
    if kind == "number":
        assert type(got.resource.attr["v"]) is float and got.principal.attr["v"] == 1.0
    if kind == "integral_number":
        assert type(got.resource.attr["v"]) is float
    if kind == "true":
        assert got.resource.attr["v"] is True
    if kind in ("null", "unset_oneof"):
        assert got.resource.attr["v"] is None


SHAPES = {
    "empty_attr": request(),
    "fifty_resources": request(entries=[resource_entry(("view", "edit"), entity("album", third=f"a{i}", attr={"i": float(i)})) for i in range(50)]),
    "include_meta": request(include_meta=1, request_id="r-1"),
    "include_meta_false": request(include_meta=0),
    "include_meta_a_long_varint": request(extra=b"\x10\xff\xff\xff\xff\xff\xff\xff\xff\xff\x01"),
    "request_id_twice_the_last_stands": st(1, "first") + request(request_id="second"),
    "fields_in_reverse_order": ld(5, ld(1, st(2, "ks") + st(1, "t.o.k"))) + ld(4, resource_entry()) + ld(3, PRINCIPAL) + vi(2, 1) + st(1, "r"),
    "resources_around_the_principal": ld(4, resource_entry()) + ld(3, PRINCIPAL) + ld(4, resource_entry(resource=entity("album", third="a2"))),
    "resource_before_its_actions": request(entries=[ld(2, entity("album", third="a1")) + st(1, "view")]),
    "entry_value_before_key": request(principal=entity("u1", third=["user"]) + ld(4, entry("k", 1.0, flip=True))),
    "entry_without_a_key": request(principal=PRINCIPAL + ld(4, ld(2, value("v")))),
    "entry_without_a_value": request(principal=PRINCIPAL + ld(4, st(1, "k"))),
    "entry_key_twice": request(principal=PRINCIPAL + ld(4, st(1, "a") + st(1, "b") + ld(2, value(1.0)))),
    "attribute_twice_the_last_stands": request(principal=entity("u1", third=["user"], attr=(("k", 1.0), ("k", "two")))),
    "scalar_fields_twice": request(principal=st(1, "u0") + st(5, "a") + PRINCIPAL + st(5, "acme.hr") + st(2, "v1") + st(2, "v2")),
    "non_ascii_everywhere_but_the_patterns": request(
        principal=entity("ключ", third=["部門", "🙂"], attr={"é": "è"}),
        entries=[resource_entry(("vüe",), entity("albüm", third="ä1"))], request_id="ré"),
    "token_and_key_set": request(aux=ld(1, st(1, "t.o.k") + st(2, "ks"))),
    "key_set_without_a_token": request(aux=ld(1, st(2, "ks"))),
    "empty_aux_data": request(aux=b""),
    "empty_jwt": request(aux=ld(1, b"")),
    "unknown_fields_at_the_top": request(extra=vi(9, 300) + ld(77, b"abc") + varint(15 << 3 | 1) + b"\x00" * 8 + varint(2000 << 3 | 5) + b"\x00" * 4),
    "unknown_fields_in_the_principal": request(principal=PRINCIPAL + vi(6, 1) + ld(19, b"\xff\xfe")),
    "unknown_fields_in_an_entry_and_its_resource": request(entries=[resource_entry(resource=entity("album", third="a1", extra=vi(8, 2)), extra=ld(3, b"x"))]),
    "unknown_fields_in_a_value": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, vi(7, 1) + value("s")))),
    "unknown_fields_in_a_struct_and_a_list": request(
        principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, ld(5, vi(2, 1) + ld(1, entry("a", 1.0))))) + ld(4, st(1, "l") + ld(2, ld(6, ld(1, value(1.0)) + ld(2, b"zz"))))),
    "unknown_fields_in_aux_data": request(aux=vi(3, 1) + ld(1, st(1, "tok") + ld(9, b"q"))),
    "a_null_that_is_not_zero": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, b"\x08\x05"))),
    "a_bool_that_is_not_one": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, b"\x20\x7f"))),
    "an_empty_principal_is_there": request(principal=b""),
    "an_empty_entry": request(entries=[b""]),
    "nothing_at_all": b"",
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_request_shapes_protobuf_reads_are_read_the_same(shape):
    assert agrees(SHAPES[shape]) == "native"


def test_include_meta_and_the_ids_are_what_the_message_says():
    inputs, request_id, include_meta, token, key_set_id, violation, _ = decode(SHAPES["fields_in_reverse_order"])
    assert (request_id, include_meta, token, key_set_id, violation) == ("r", True, "t.o.k", "ks", None)
    assert [i.request_id for i in inputs] == ["r"]


# -- every violation, word for word -----------------------------------------------

RES = entity("album", third="a1")
VIOLATIONS = {
    "no_principal": (request(principal=None), "principal: value is required"),
    "principal_id": (request(principal=entity("", third=["user"])), "principal.id: value length must be at least 1"),
    "empty_principal": (request(principal=b""), "principal.id: value length must be at least 1"),
    "no_roles": (request(principal=entity("u1")), "principal.roles: value is required and must contain at least one item"),
    "empty_role": (request(principal=entity("u1", third=["user", ""])), "principal.roles: items must be non-empty strings"),
    "role_twice": (request(principal=entity("u1", third=["a", "b", "a"])), "principal.roles: items must be unique"),
    "empty_role_before_a_duplicate": (request(principal=entity("u1", third=["a", "", "a"])), "principal.roles: items must be non-empty strings"),
    "duplicate_before_an_empty_role": (request(principal=entity("u1", third=["a", "a", ""])), "principal.roles: items must be unique"),
    "principal_version": (request(principal=entity("u1", "v-1", ["user"])), "principal.policyVersion: must match ^[\\w]*$"),
    "principal_version_inner_newline": (request(principal=entity("u1", "v\n1", ["user"])), "principal.policyVersion: must match ^[\\w]*$"),
    "principal_version_two_newlines": (request(principal=entity("u1", "v1\n\n", ["user"])), "principal.policyVersion: must match ^[\\w]*$"),
    "principal_scope": (request(principal=entity("u1", "", ["user"], "a..b")), "principal.scope: invalid scope"),
    "version_before_scope": (request(principal=entity("u1", "v 1", ["user"], "..")), "principal.policyVersion: must match ^[\\w]*$"),
    "no_resources": (request(entries=()), "resources: value is required and must contain at least one item"),
    "principal_before_resources": (request(principal=entity("u1"), entries=()), "principal.roles: value is required and must contain at least one item"),
    "no_actions": (request(entries=[resource_entry(())]), "resources[0].actions: value is required and must contain at least one item"),
    "empty_action": (request(entries=[resource_entry(("view", ""))]), "resources[0].actions: items must be non-empty strings"),
    "action_twice": (request(entries=[resource_entry(("view", "edit", "view"))]), "resources[0].actions: items must be unique"),
    "no_resource": (request(entries=[resource_entry(resource=None)]), "resources[0].resource: value is required"),
    "actions_before_resource": (request(entries=[resource_entry((), resource=None)]), "resources[0].actions: value is required and must contain at least one item"),
    "resource_kind": (request(entries=[resource_entry(resource=entity("", third="a1"))]), "resources[0].resource.kind: value length must be at least 1"),
    "empty_resource": (request(entries=[resource_entry(resource=b"")]), "resources[0].resource.kind: value length must be at least 1"),
    "resource_id": (request(entries=[resource_entry(resource=entity("album"))]), "resources[0].resource.id: value length must be at least 1"),
    "resource_version": (request(entries=[resource_entry(resource=entity("album", "1.0", "a1"))]), "resources[0].resource.policyVersion: must match ^[\\w]*$"),
    "resource_scope": (request(entries=[resource_entry(resource=entity("album", "", "a1", "_acme"))]), "resources[0].resource.scope: invalid scope"),
    "third_entry": (request(entries=[resource_entry(), resource_entry(resource=entity("album", third="a2")), resource_entry(resource=entity("album", "", "a3", "a."))]), "resources[2].resource.scope: invalid scope"),
    "twelfth_entry": (request(entries=[resource_entry()] * 11 + [resource_entry(("",))]), "resources[11].actions: items must be non-empty strings"),
    "first_entry_before_the_second": (request(entries=[resource_entry(resource=entity("album")), resource_entry(())]), "resources[0].resource.id: value length must be at least 1"),
}


@pytest.mark.parametrize("case", sorted(VIOLATIONS))
def test_a_violation_is_the_string_wire_validate_returns(case):
    data, words = VIOLATIONS[case]
    assert wire_validate.check_resources_proto(FromString(data)) == words
    got = decode(data)
    assert got is not None and got[5] == words and got[0] == []


SCOPES = ["", ".", "a", "acme.hr", "acme.hr.uk", "a-b_c.d-e", "a.-b", "a-", "a._b", "0", "A.B", "a\n", ".\n", "\n", "acme.hr\n",
          "..", "a..b", "_a", "a.", "-a", ".a", "a b", "a.b.", "a\n\n", "\na", "a\nb", " ", "a.b c"]
VERSIONS = ["", "default", "20210210", "v_1", "V1\n", "\n", "v-1", "v.1", "v 1", "v1\n\n", "\nv1", "1\n2"]


@pytest.mark.parametrize("where", ["principal", "resource"])
@pytest.mark.parametrize("scope", SCOPES)
def test_scopes_are_judged_as_the_pattern_judges_them(where, scope):
    data = request(principal=entity("u1", "", ["user"], scope)) if where == "principal" else request(
        entries=[resource_entry(resource=entity("album", "", "a1", scope))])
    assert agrees(data) == "native"


@pytest.mark.parametrize("where", ["principal", "resource"])
@pytest.mark.parametrize("version", VERSIONS)
def test_versions_are_judged_as_the_pattern_judges_them(where, version):
    data = request(principal=entity("u1", version, ["user"])) if where == "principal" else request(
        entries=[resource_entry(resource=entity("album", version, "a1"))])
    assert agrees(data) == "native"


# -- what the codec declines, and who answers then ---------------------------------

DEEP = 1.0
for _ in range(20):
    DEEP = [DEEP]
DEEPER = 1.0
for _ in range(25):
    DEEPER = {"k": DEEPER}
TOO_DEEP = 1.0
for _ in range(40):
    TOO_DEEP = {"k": TOO_DEEP}

DECLINED = {
    "principal_twice_would_merge": ld(3, entity("u0", third=["a"])) + request(),
    "aux_data_twice_would_merge": request(aux=ld(1, st(1, "tok"))) + ld(5, ld(1, st(2, "ks"))),
    "jwt_twice_would_merge": request(aux=ld(1, st(1, "tok")) + ld(1, st(2, "ks"))),
    "resource_twice_in_an_entry": request(entries=[resource_entry() + ld(2, entity("album", third="a2"))]),
    "entry_value_twice": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, value({"a": 1.0})) + ld(2, value({"b": 2.0})))),
    "two_members_of_a_oneof": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, value(1.0) + value("s")))),
    "struct_twice_in_a_value": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, value({"a": 1.0}) + value({"b": 2.0})))),
    "an_unknown_field_in_a_map_entry": request(principal=PRINCIPAL + ld(4, entry("k", 1.0) + vi(3, 1))),
    "a_group_at_the_top": request(extra=varint(9 << 3 | 3) + varint(9 << 3 | 4)),
    "a_group_in_a_resource": request(entries=[resource_entry(resource=entity("album", third="a1", extra=varint(9 << 3 | 3) + varint(9 << 3 | 4)))]),
    "lists_twenty_deep": request(principal=entity("u1", third=["user"], attr={"deep": DEEP})),
    "structs_twenty_five_deep": request(entries=[resource_entry(resource=entity("album", third="a1", attr={"deep": DEEPER}))]),
    "a_known_field_of_another_wire_type": request(extra=vi(1, 5)),
    "a_number_as_a_varint": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, vi(2, 1)))),
    "a_padded_tag": request(extra=b"\x8a\x00\x01x"),
    "a_padded_length": request(extra=b"\x0a\x81\x00x"),
    "a_non_ascii_scope_is_the_patterns": request(principal=entity("u1", "", ["user"], "département")),
    "a_non_ascii_version_is_the_patterns": request(entries=[resource_entry(resource=entity("album", "ключ", "a1"))]),
    "a_hundred_actions": request(entries=[resource_entry([f"a{i}" for i in range(100)])]),
    "a_hundred_roles": request(principal=entity("u1", third=[f"r{i}" for i in range(100)])),
}


@pytest.mark.parametrize("case", sorted(DECLINED))
def test_what_the_codec_is_not_sure_of_it_declines_and_the_python_path_answers(case):
    data = DECLINED[case]
    assert decode(data) is None
    codec = _WireCodec()
    before = wire_counts()
    req = codec.decode(data)  # protobuf reads every one of these
    assert isinstance(req, request_pb2.CheckResourcesRequest) and req == FromString(data)
    assert grew(before) == {("request", "python"): 1}


MALFORMED = {
    "a_truncated_length": request()[:-3],
    "a_length_past_the_end": b"\x0a\x05abc",
    "a_tag_and_nothing": b"\x0a",
    "an_endless_varint": b"\x10" + b"\xff" * 11,
    "field_number_zero": b"\x02\x00",
    "wire_type_six": b"\x0e",
    "wire_type_seven": request(extra=b"\x4f"),
    "a_request_id_that_is_no_utf8": ld(1, b"\xff\xfe") + request(),
    "a_surrogate_in_an_action": request(entries=[ld(1, b"\xed\xa0\x80") + resource_entry()]),
    "an_overlong_nul_in_a_key": request(principal=PRINCIPAL + ld(4, ld(1, b"\xc0\x80") + ld(2, value(1.0)))),
    "a_string_value_that_is_no_utf8": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, ld(3, b"\xe2\x28\xa1")))),
    "a_truncated_number": request(principal=PRINCIPAL + ld(4, st(1, "k") + ld(2, b"\x11\x00\x00"))),
    "a_group_that_never_ends": request(extra=varint(9 << 3 | 3)),
    "structs_forty_deep_are_past_protobufs_own_limit": request(principal=entity("u1", third=["user"], attr={"deep": TOO_DEEP})),
    "a_truncated_unknown_fixed32": request(extra=varint(9 << 3 | 5) + b"\x00\x00"),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_bytes_raise_what_fromstring_raises(case):
    data = MALFORMED[case]
    with pytest.raises(DecodeError) as want:
        FromString(data)
    assert decode(data) is None
    before = wire_counts()
    with pytest.raises(DecodeError) as got:
        _WireCodec().decode(data)
    assert str(got.value) == str(want.value)
    assert grew(before) == {}  # no request was read


@pytest.mark.parametrize("seed", [0, 7])
def test_every_truncation_of_a_page_is_declined_or_agrees(seed):
    (req,) = bench_wires(seed, 16, 50, n=1)
    verdicts = [agrees(req.wire[:n]) for n in range(len(req.wire))]
    assert verdicts.count("declined") > len(verdicts) // 2  # most prefixes cut a field in two


@pytest.mark.parametrize("chunk", range(8))
def test_every_corruption_of_one_byte_is_declined_or_agrees(chunk):
    """Never a crash, never a reading of its own (the ASAN build's case)."""
    data = request(
        principal=entity("u1", "v1", ["user", "admin"], "acme.hr", attr={"a": {"b": [1.0, None, "x", True]}}),
        entries=[resource_entry(("view", "edit"), entity("album", "default", "a1", "acme", attr={"public": False, "tags": ["x", "y"]}))],
        request_id="r-1", include_meta=1, aux=ld(1, st(1, "t.o.k") + st(2, "ks")),
    )
    rng = random.Random(chunk)
    for at in range(chunk, len(data), 8):
        for byte in {0x00, 0x01, 0x7F, 0x80, 0xFF, data[at] ^ 0x80, data[at] ^ 0x01, rng.randrange(256)}:
            agrees(data[:at] + bytes([byte]) + data[at + 1:])


# -- the counter -------------------------------------------------------------------


def wire_counts() -> dict:
    return dict(metrics().counter_vec("cerbos_tpu_wire_codec_total", label=("dir", "path"))._children)


def grew(before: dict) -> dict:
    return {k: int(v - before.get(k, 0)) for k, v in wire_counts().items() if v != before.get(k, 0)}


def test_a_request_and_a_reply_are_counted_once_each_under_native():
    codec = _WireCodec()
    before = wire_counts()
    req = codec.decode(request(request_id="r"))
    assert type(req) is tuple
    inputs = req[0]
    resp = codec.encode(req, req[1], CALL_ID, inputs, [T.CheckOutput("r", "a1", {"view": T.ActionEffect("EFFECT_ALLOW", "p")})], req[2])
    assert type(resp) is bytes
    assert grew(before) == {("request", "native"): 1, ("reply", "native"): 1}


def test_with_the_native_module_absent_both_directions_are_python_and_counted_so(monkeypatch):
    monkeypatch.setattr(native, "get", lambda: None)
    codec = _WireCodec()
    before = wire_counts()
    data = request(request_id="r")
    req = codec.decode(data)
    assert req == FromString(data)
    inputs = convert.check_resources_request_to_inputs(req, None)
    outputs = [T.CheckOutput("r", "a1", {"view": T.ActionEffect("EFFECT_DENY", "p")})]
    resp = codec.encode(req, "r", CALL_ID, inputs, outputs, False)
    assert resp == convert.outputs_to_check_resources_response(req, outputs, CALL_ID)
    assert grew(before) == {("request", "python"): 1, ("reply", "python"): 1}


# -- reply parity ---------------------------------------------------------------------


def inputs_of(n: int, **resource):
    p = T.Principal("u1", ["user"])
    return [T.CheckInput(p, T.Resource(**{"kind": "album", "id": f"a{i}", **resource}), ["view"], "r-1") for i in range(n)]


def both_replies(inputs, outputs, include_meta=False, request_id="r-1", call_id=CALL_ID):
    """(the message the native bytes parse to, the message convert builds)."""
    req = request_pb2.CheckResourcesRequest(request_id=request_id, include_meta=include_meta)
    for i in inputs:
        e = req.resources.add()
        e.resource.kind, e.resource.id = i.resource.kind, i.resource.id
        e.resource.policy_version, e.resource.scope = i.resource.policy_version, i.resource.scope
    data = N.check_reply_encode(request_id, call_id, inputs, outputs, include_meta)
    want = convert.outputs_to_check_resources_response(req, outputs, call_id)
    return (None if data is None else response_pb2.CheckResourcesResponse.FromString(data)), want


def reply_agrees(inputs, outputs, **kw):
    got, want = both_replies(inputs, outputs, **kw)
    assert got is not None, "declined"
    assert got == want
    # and presence for presence: a second serialisation of both is the same bytes
    assert got.SerializeToString(deterministic=True) == want.SerializeToString(deterministic=True)


AE = T.ActionEffect
EFFECTS = {
    "allow": {"view": AE("EFFECT_ALLOW", "resource.album.vdefault", "")},
    "deny": {"view": AE("EFFECT_DENY", "resource.album.vdefault", "acme")},
    "no_match": {"view": AE("EFFECT_NO_MATCH", "NO_MATCH", "")},
    "an_unknown_effect_is_deny": {"view": AE("EFFECT_MAYBE", "p", "")},
    "an_empty_effect_is_deny": {"view": AE("", "", "")},
    "several_actions": {"view": AE("EFFECT_ALLOW", "p", "s"), "edit": AE("EFFECT_DENY", "q", ""), "": AE("EFFECT_NO_MATCH", "", "")},
    "no_actions": {},
    "non_ascii_action": {"vüe:部門": AE("EFFECT_ALLOW", "ключ", "é")},
}


@pytest.mark.parametrize("include_meta", [False, True], ids=["plain", "meta"])
@pytest.mark.parametrize("case", sorted(EFFECTS))
def test_reply_effects_and_meta(case, include_meta):
    outputs = [T.CheckOutput("r-1", "a0", EFFECTS[case], ["employee", ""] if case == "allow" else [])]
    reply_agrees(inputs_of(1), outputs, include_meta=include_meta)


VE = T.ValidationError
VERRS = {
    "principal": [VE("/department", "missing properties: 'a'", "SOURCE_PRINCIPAL")],
    "resource": [VE("/", "additionalProperties 'x' not allowed", "SOURCE_RESOURCE")],
    "an_unknown_source_is_unspecified": [VE("/p", "m", "SOURCE_OTHER")],
    "empty_strings": [VE("", "", "")],
    "twenty_four_a_page": [VE(f"/attr{i}", f"message {i} é", "SOURCE_RESOURCE" if i % 2 else "SOURCE_PRINCIPAL") for i in range(24)],
}


@pytest.mark.parametrize("case", sorted(VERRS))
def test_reply_validation_errors(case):
    reply_agrees(inputs_of(1), [T.CheckOutput("r-1", "a0", EFFECTS["deny"], [], VERRS[case])], include_meta=True)


OUTPUT_VALUES = {
    "none": None,
    "true": True,
    "false": False,
    "int": 3,
    "big_int": 2**62,
    "negative_int": -7,
    "float": 1.5,
    "negative_zero": -0.0,
    "infinity": float("inf"),
    "string": "x",
    "empty_string": "",
    "non_ascii": "département 🙂",
    "empty_list": [],
    "empty_dict": {},
    "tuple": (1, "a", None),
    "list_of_every_kind": [None, True, 2, 2.5, "s", [], {}, [[]], {"k": {}}],
    "dict_of_every_kind": {"n": None, "b": False, "i": 1, "d": 0.5, "s": "", "l": [1, [2]], "m": {"x": {"y": []}}, "": "empty key"},
    "three_deep": {"a": [{"b": [{"c": (True, None)}]}]},
}


@pytest.mark.parametrize("case", sorted(OUTPUT_VALUES))
def test_reply_outputs_of_every_kind(case):
    oe = T.OutputEntry("resource.album.vdefault#rule", "view", OUTPUT_VALUES[case])
    reply_agrees(inputs_of(1), [T.CheckOutput("r-1", "a0", EFFECTS["allow"], [], [], [oe, T.OutputEntry("", "", None, "")])])


def test_reply_output_with_an_error_carries_no_value():
    oes = [T.OutputEntry("src", "view", {"ignored": 1}, "failed to evaluate"), T.OutputEntry("src2", "", "kept")]
    reply_agrees(inputs_of(1), [T.CheckOutput("r-1", "a0", {}, [], [], oes)])
    got, _ = both_replies(inputs_of(1), [T.CheckOutput("r-1", "a0", {}, [], [], oes)])
    assert not got.results[0].outputs[0].HasField("val") and got.results[0].outputs[1].HasField("val")


REPLY_SHAPES = {
    "no_results": (0, 0),
    "fifty_results": (50, 50),
    "more_inputs_than_outputs": (3, 2),
    "more_outputs_than_inputs": (2, 3),
}


@pytest.mark.parametrize("case", sorted(REPLY_SHAPES))
def test_reply_shapes(case):
    n_in, n_out = REPLY_SHAPES[case]
    outputs = [T.CheckOutput("r-1", f"a{i}", EFFECTS["several_actions"], ["role"]) for i in range(n_out)]
    reply_agrees(inputs_of(n_in, policy_version="20210210", scope="acme.hr"), outputs, include_meta=True)


def test_reply_without_ids_and_with_an_empty_resource():
    reply_agrees([T.CheckInput(T.Principal("u", ["r"]), T.Resource(""), ["a"])], [T.CheckOutput("", "")], request_id="", call_id="")


def test_a_long_result_moves_its_content_for_the_longer_length():
    """Lengths of 127, 128 and 16,384 bytes and over: one, two and three bytes."""
    for n in (90, 91, 92, 93, 200, 16300, 16400, 70000):
        reply_agrees(inputs_of(1), [T.CheckOutput("r-1", "a0", {"view": AE("EFFECT_ALLOW", "p" * n, "")})], include_meta=True)


class Stringified:
    def __str__(self):
        return "stringified"


REPLY_DECLINED = {
    "a_value_py_to_value_would_stringify": [T.OutputEntry("s", "a", Stringified())],
    "such_a_value_deep_in_a_list": [T.OutputEntry("s", "a", [1, {"k": [Stringified()]}])],
    "bytes_are_stringified_too": [T.OutputEntry("s", "a", b"raw")],
    "a_key_that_is_no_str": [T.OutputEntry("s", "a", {1: "x"})],
    "values_twenty_deep": [T.OutputEntry("s", "a", DEEP)],
}


@pytest.mark.parametrize("case", sorted(REPLY_DECLINED))
def test_a_reply_the_codec_declines_is_written_by_the_python_path(case):
    inputs = inputs_of(1)
    outputs = [T.CheckOutput("r-1", "a0", EFFECTS["allow"], [], [], REPLY_DECLINED[case])]
    assert N.check_reply_encode("r-1", CALL_ID, inputs, outputs, False) is None
    codec = _WireCodec()
    data = request(entries=[resource_entry(resource=entity("album", third="a0"))], request_id="r-1")
    req = codec.decode(data)
    assert type(req) is tuple  # read natively: the Python path parses the bytes it kept
    before = wire_counts()
    resp = codec.encode(req, "r-1", CALL_ID, inputs, outputs, False)
    assert resp == convert.outputs_to_check_resources_response(FromString(data), outputs, CALL_ID)
    assert grew(before) == {("reply", "python"): 1}


@pytest.mark.parametrize("case", ["an_int_no_double_holds", "a_lone_surrogate", "an_effect_that_is_no_str", "outputs_that_are_no_list"])
def test_where_the_python_path_raises_the_codec_declines_and_it_raises(case):
    out = T.CheckOutput("r-1", "a0", EFFECTS["allow"])
    if case == "an_int_no_double_holds":
        out.outputs = [T.OutputEntry("s", "a", 10**400)]
    elif case == "a_lone_surrogate":
        out.outputs = [T.OutputEntry("s", "a", "\ud800")]
    elif case == "an_effect_that_is_no_str":
        out.actions = {"view": AE(["EFFECT_ALLOW"], "p")}
    else:
        out.outputs = 7
    assert N.check_reply_encode("r-1", CALL_ID, inputs_of(1), [out], False) is None
    with pytest.raises(Exception):  # noqa: B017, PT011  (each its own: OverflowError, UnicodeEncodeError, TypeError)
        both_replies(inputs_of(1), [out])


# -- 2,000 seeded random requests and replies ------------------------------------------

WORDS = ["", "a", "owner", "département", "ключ", "部門", "x" * 130, "with space", "\x00nul", "🙂"]
NUMBERS = [0.0, -0.0, 1.0, -1.5, 1e300, 5e-324, float("inf"), float("-inf"), 2**53, 7]


def tree(rng, depth: int):
    """A drawn attribute value: what a Value can hold, every kind at every depth."""
    kind = rng.randrange(9 if depth < 4 else 6)
    if kind == 0:
        return None
    if kind == 1:
        return rng.random() < 0.5
    if kind == 2:
        return rng.choice(NUMBERS)
    if kind == 3:
        return rng.choice(WORDS)
    if kind == 4:
        return EMPTY
    if kind == 5:
        return rng.random() * 1e6
    if kind == 6:
        return [tree(rng, depth + 1) for _ in range(rng.randrange(4))]
    keys = [rng.choice(WORDS) for _ in range(rng.randrange(4))]  # may repeat: the last one stands
    return tuple((k, tree(rng, depth + 1)) for k in keys)


def drawn_entity(rng, first, third, shuffle: bool) -> bytes:
    chunks = [st(1, first)] if first else []
    version = rng.choice(["", "", "default", "default", "v1", "bad version", "é"])
    scope = rng.choice(["", "", "acme.hr", "acme", "a.b-c", "..bad", "ü"])
    chunks += [st(2, version)] if version else []
    chunks += [st(3, r) for r in third] if isinstance(third, list) else ([st(3, third)] if third else [])
    for _ in range(rng.randrange(5)):
        chunks.append(ld(4, entry(rng.choice(WORDS + ["dup", "dup"]), tree(rng, 0), flip=shuffle and rng.random() < 0.3)))
    chunks += [st(5, scope)] if scope else []
    if rng.random() < 0.1:
        chunks.append(vi(rng.randrange(6, 40), rng.randrange(1 << 40)))  # an unknown field
    if shuffle:
        rng.shuffle(chunks)
    return b"".join(chunks)


def drawn_request(seed: int) -> bytes:
    rng = random.Random(seed)
    shuffle = rng.random() < 0.5
    chunks = []
    if rng.random() < 0.8:
        chunks.append(st(1, rng.choice(WORDS)))
    if rng.random() < 0.5:
        chunks.append(vi(2, rng.choice([0, 1, 1, 2, 300])))
    if rng.random() < 0.95:
        roles = [rng.choice(WORDS[1:]) for _ in range(rng.randrange(4))]
        chunks.append(ld(3, drawn_entity(rng, rng.choice(WORDS), roles, shuffle)))
    for _ in range(rng.randrange(0, 6)):
        parts = [st(1, rng.choice(["view", "edit", "", "delete", "view:public"])) for _ in range(rng.randrange(4))]
        if rng.random() < 0.95:
            parts.append(ld(2, drawn_entity(rng, rng.choice(WORDS), rng.choice(WORDS), shuffle)))
        if shuffle:
            rng.shuffle(parts)
        chunks.append(ld(4, b"".join(parts)))
    if rng.random() < 0.5:
        jwt = [st(1, rng.choice(["", "tok.en.sig"])), st(2, rng.choice(["", "keys"]))]
        chunks.append(ld(5, ld(1, b"".join(rng.sample(jwt, rng.randrange(3))))))
    if rng.random() < 0.1:
        chunks.append(ld(rng.randrange(6, 3000), bytes(rng.randrange(256) for _ in range(rng.randrange(9)))))
    if shuffle:
        rng.shuffle(chunks)
    return b"".join(chunks)


@pytest.mark.parametrize("chunk", range(20))
def test_a_hundred_drawn_requests_round_trip(chunk):
    verdicts = [agrees(drawn_request(chunk * 100 + k)) for k in range(100)]
    assert verdicts.count("native") >= 60  # declined: a non-ASCII subject of a pattern, reached


def drawn_output_value(rng, depth: int):
    kind = rng.randrange(9 if depth < 4 else 6)
    if kind == 0:
        return None
    if kind == 1:
        return rng.random() < 0.5
    if kind == 2:
        return rng.choice(NUMBERS)
    if kind == 3:
        return rng.choice(WORDS)
    if kind == 4:
        return rng.randrange(-5, 1 << 40)
    if kind == 5:
        return rng.random()
    if kind == 6:
        seq = [drawn_output_value(rng, depth + 1) for _ in range(rng.randrange(4))]
        return tuple(seq) if rng.random() < 0.3 else seq
    return {rng.choice(WORDS): drawn_output_value(rng, depth + 1) for _ in range(rng.randrange(4))}


def drawn_reply(seed: int):
    rng = random.Random(seed)
    n = rng.randrange(0, 8)
    inputs = [
        T.CheckInput(T.Principal("u", ["r"]), T.Resource(rng.choice(WORDS), rng.choice(WORDS), {}, rng.choice(["", "default"]), rng.choice(["", "acme.hr"])), ["a"], "r")
        for _ in range(n)
    ]
    outputs = []
    for i in range(n):
        actions = {rng.choice(WORDS + ["view", "edit"]): AE(rng.choice(["EFFECT_ALLOW", "EFFECT_DENY", "EFFECT_NO_MATCH", "?"]), rng.choice(WORDS), rng.choice(["", "acme"]))
                   for _ in range(rng.randrange(4))}
        verrs = [VE(rng.choice(WORDS), rng.choice(WORDS), rng.choice(["SOURCE_PRINCIPAL", "SOURCE_RESOURCE", ""])) for _ in range(rng.randrange(3))]
        outs = [T.OutputEntry(rng.choice(WORDS), rng.choice(WORDS), drawn_output_value(rng, 0), rng.choice(["", "", "boom"])) for _ in range(rng.randrange(3))]
        outputs.append(T.CheckOutput("r", f"a{i}", actions, [rng.choice(WORDS) for _ in range(rng.randrange(3))], verrs, outs))
    return inputs, outputs, rng.random() < 0.5, rng.choice(WORDS), rng.choice(["", CALL_ID])


@pytest.mark.parametrize("chunk", range(20))
def test_a_hundred_drawn_replies_round_trip(chunk):
    for k in range(100):
        inputs, outputs, include_meta, request_id, call_id = drawn_reply(chunk * 100 + k)
        reply_agrees(inputs, outputs, include_meta=include_meta, request_id=request_id, call_id=call_id)


# -- served: a real sync gRPC server, with the module and with it hidden -----------------

METHOD = "/cerbos.svc.v1.CerbosService/CheckResources"
PAGE = request(
    principal=entity("u1", third=["user"], attr={"team": "design", "level": 3.0}),
    entries=[resource_entry(("view", "edit"), entity("album", third=f"a{i}", attr={"owner": "u1" if i % 3 else "u2", "public": i % 2 == 0, "tags": ["x", {"k": None}]}))
             for i in range(24)],
    request_id="page-1", include_meta=1,
)
SINGLE = request(entries=[resource_entry(("view",), entity("album", third="a1", attr={"owner": "u1", "public": False}))], request_id="single-1")
INVALID = request(entries=[resource_entry(), resource_entry(("view", "view"))], request_id="bad-1")


@pytest.fixture()
def tracker():
    trk = budget_mod.tracker()
    prev = (trk.enabled, trk.slow_threshold_s, trk._ring.maxlen)
    trk.configure(enabled=True)
    trk.reset()
    yield trk
    trk.configure(enabled=prev[0], slow_threshold_ms=prev[1] * 1000, slow_capacity=prev[2])
    trk.reset()


def served_replies(wires, tracker=None, grpc_async=False):
    """Each wire's reply (a message with its call id taken out, or the RpcError)
    from one gRPC server (the sync one unless ``grpc_async``) over a real
    batcher, and the counter's growth."""
    rt = table()
    batcher = BatchingEvaluator(OracleEvaluator(rt), max_wait_ms=1.0)
    srv = Server(CerbosService(Engine(rt, tpu_evaluator=batcher, tpu_batch_threshold=1)),
                 ServerConfig(http_listen_addr="127.0.0.1:0", grpc_listen_addr="127.0.0.1:0", grpc_async=grpc_async))
    srv.start()
    before = wire_counts()
    handled = tracker.m_handler.count if tracker is not None else 0
    replies = []
    try:
        with grpc.insecure_channel(f"127.0.0.1:{srv.grpc_port}") as ch:
            call = ch.unary_unary(METHOD, request_serializer=None, response_deserializer=None)
            for wire in wires:
                try:
                    resp = response_pb2.CheckResourcesResponse.FromString(call(wire, timeout=10))
                    assert len(resp.cerbos_call_id) == 32
                    resp.cerbos_call_id = ""
                    replies.append(resp)
                except grpc.RpcError as e:
                    replies.append(e)
        if tracker is not None:
            answered = sum(1 for r in replies if not isinstance(r, grpc.RpcError))
            assert wait_for(lambda: tracker.m_handler.count == handled + answered)
    finally:
        srv.stop()
        batcher.close()
    return replies, grew(before)


def test_a_served_page_and_a_single_check_are_answered_as_with_the_module_hidden(tracker, monkeypatch):
    seen = []
    real = tracker.finish

    def finish(wf, *args, **kwargs):
        out = real(wf, *args, **kwargs)
        if wf is not None:
            seen.append(wf)
        return out

    monkeypatch.setattr(tracker, "finish", finish)
    wires = [PAGE, SINGLE, INVALID]
    native_replies, native_counts = served_replies(wires, tracker=tracker)
    assert native_counts == {("request", "native"): 3, ("reply", "native"): 2}
    # the waterfall's six front parts still add up to admission, each once a request
    assert len(seen) == 2
    for wf in seen:
        names = [p for p, _ in wf.parts]
        assert names[:6] == list(FRONT_PARTS)
        assert sum(dict(wf.parts)[p] for p in FRONT_PARTS) == pytest.approx(dict(wf.stages)[STAGE_ADMISSION], abs=2e-6)
        assert dict(wf.stages)["ingress_parse"] > 0.0

    monkeypatch.setattr(native, "get", lambda: None)
    python_replies, python_counts = served_replies(wires)
    assert python_counts == {("request", "python"): 3, ("reply", "python"): 2}

    for got, want in zip(native_replies[:2], python_replies[:2]):
        assert got == want
    page, single = native_replies[:2]
    assert len(page.results) == 24 and page.request_id == "page-1" and page.results[0].HasField("meta")
    assert [r.resource.id for r in page.results] == [f"a{i}" for i in range(24)]
    assert len(single.results) == 1 and not single.results[0].HasField("meta")
    for got, want in zip(native_replies[2:], python_replies[2:]):
        assert got.code() == want.code() == grpc.StatusCode.INVALID_ARGUMENT
        assert got.details() == want.details() == "resources[1].actions: items must be unique"


def test_the_aio_server_wraps_the_same_codec():
    (page, single, err), counts = served_replies([PAGE, SINGLE, INVALID], grpc_async=True)
    (want_page, want_single, _), _ = served_replies([PAGE, SINGLE, INVALID])
    assert page == want_page and single == want_single
    assert err.code() == grpc.StatusCode.INVALID_ARGUMENT and err.details() == "resources[1].actions: items must be unique"
    assert counts == {("request", "native"): 3, ("reply", "native"): 2}


def test_a_served_request_the_codec_declines_is_answered_by_the_python_path():
    """Declined for its non-ASCII scope, which the pattern then refuses for
    its space; a principal met twice merges into one the rules accept."""
    refused = request(principal=entity("u1", "", ["user"], "dé partement"))
    merged = ld(3, st(1, "u1")) + request(principal=st(3, "user"), request_id="merged-1")
    (err, resp), counts = served_replies([refused, merged])
    assert err.code() == grpc.StatusCode.INVALID_ARGUMENT and err.details() == "principal.scope: invalid scope"
    assert resp.request_id == "merged-1" and len(resp.results) == 1
    assert counts == {("request", "python"): 2, ("reply", "native"): 1}


def test_served_malformed_bytes_are_refused_as_protobuf_refuses_them():
    (err,), counts = served_replies([b"\x0a\x05abc"])
    assert err.code() == grpc.StatusCode.INTERNAL  # gRPC's own answer to a deserializer that raises
    assert counts == {}


def test_the_deserializer_is_the_codec_and_stamps_what_it_returns():
    seen = {}

    class Stamps:
        def put(self, key, t_raw, t_decoded):
            seen[key] = (t_raw, t_decoded)

    real = server_mod._GRPC_STAMPS
    server_mod._GRPC_STAMPS = Stamps()
    try:
        req = server_mod._stamping_deserializer(_WireCodec().decode)(SINGLE)
    finally:
        server_mod._GRPC_STAMPS = real
    assert type(req) is tuple and id(req) in seen and seen[id(req)][1] >= seen[id(req)][0]
