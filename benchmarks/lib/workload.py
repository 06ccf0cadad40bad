"""Requests of a cell, from the seed: what goes on the wire and what must come back.

One general builder reads a traffic file's ``request`` block. A request is a
gRPC ``CheckResourcesRequest`` serialized before the window opens; the
generator sends the bytes as they are. The only thing taken from the program
is the public API's generated protobuf classes (the wire format the system
under test speaks).
"""

from __future__ import annotations

import base64
import hashlib
import hmac
import json
import random
from datetime import datetime

from . import corpus, reference

JWT_SECRET = b"cerbos-tpu-benchmark-secret"
EFFECT_NAMES = {1: reference.ALLOW, 2: reference.DENY}


def jwt_token(claims: dict) -> str:
    def b64(b: bytes) -> bytes:
        return base64.urlsafe_b64encode(b).rstrip(b"=")

    head = b64(json.dumps({"alg": "HS256", "typ": "JWT"}).encode())
    payload = b64(json.dumps(claims).encode())
    sig = b64(hmac.new(JWT_SECRET, head + b"." + payload, hashlib.sha256).digest())
    return (head + b"." + payload + b"." + sig).decode()


def page_sizes(n: int, lo: int, hi: int, rng: random.Random) -> list[int]:
    """The same multiset of sizes for every seed (lo..hi cycled), in an order
    drawn from the seed: no seed gets more work than another."""
    sizes = [lo + k % (hi - lo + 1) for k in range(n)]
    rng.shuffle(sizes)
    return sizes


class Request:
    """One request: the principal, its resources with their actions, the
    claim set of its token if it has one, and the serialized bytes."""

    __slots__ = ("index", "principal", "entries", "jwt", "wire")

    def __init__(self, index: int, principal: dict, entries: list, jwt):
        self.index = index
        self.principal = principal
        self.entries = entries  # [(resource dict, actions list)]
        self.jwt = jwt
        self.wire = b""

    @property
    def request_id(self) -> str:
        return f"r{self.index}"

    def decisions(self) -> int:
        return sum(len(a) for _, a in self.entries)

    def expected(self, now: datetime) -> list[dict]:
        return [reference.effects(self.principal, r, a, now) for r, a in self.entries]

    def uses_now(self) -> bool:
        return any(reference.uses_now(r, a) for r, a in self.entries)


def build(n: int, mods: int, seed: int, shape: dict) -> list[Request]:
    """``n`` requests of ``shape`` = {"resources": [lo, hi]}. One principal
    (the first drawn input's) over the resources of a run of consecutive
    corpus inputs, as a list endpoint sends a page; [1, 1] is upstream's own
    sidecar shape, one resource with its actions."""
    lo, hi = shape["resources"]
    rng = random.Random(seed ^ 0x5EED)
    sizes = page_sizes(n, lo, hi, rng)
    pool = corpus.requests(sum(sizes), mods, seed=seed)
    out, at = [], 0
    for k, size in enumerate(sizes):
        chunk = pool[at : at + size]
        at += size
        first = chunk[0]
        out.append(Request(k, first["principal"], [(c["resource"], c["actions"]) for c in chunk], first["jwt"]))
    return out


def _fill_value(value, v) -> None:
    if v is None:
        value.null_value = 0
    elif isinstance(v, bool):
        value.bool_value = v
    elif isinstance(v, (int, float)):
        value.number_value = v
    elif isinstance(v, str):
        value.string_value = v
    elif isinstance(v, list):
        value.list_value.SetInParent()
        for item in v:
            _fill_value(value.list_value.values.add(), item)
    else:
        raise TypeError(f"attribute value {v!r}")


def serialize(reqs: list[Request]) -> None:
    """Fill ``wire`` on every request."""
    from cerbos_tpu.api.cerbos.request.v1 import request_pb2

    tokens: dict[str, str] = {}
    for req in reqs:
        msg = request_pb2.CheckResourcesRequest()
        msg.request_id = req.request_id
        p = req.principal
        msg.principal.id = p["id"]
        msg.principal.roles.extend(p["roles"])
        msg.principal.policy_version = p["policyVersion"]
        msg.principal.scope = p["scope"]
        for k, v in p["attr"].items():
            _fill_value(msg.principal.attr[k], v)
        for res, actions in req.entries:
            e = msg.resources.add()
            e.actions.extend(actions)
            e.resource.kind = res["kind"]
            e.resource.id = res["id"]
            e.resource.policy_version = res["policyVersion"]
            e.resource.scope = res["scope"]
            for k, v in res["attr"].items():
                _fill_value(e.resource.attr[k], v)
        if req.jwt is not None:
            key = json.dumps(req.jwt, sort_keys=True)
            if key not in tokens:
                tokens[key] = jwt_token(req.jwt)
            msg.aux_data.jwt.token = tokens[key]
        req.wire = msg.SerializeToString()


def decode_reply(raw: bytes) -> tuple[str, list[tuple[str, dict]]]:
    """(request id, [(resource id, {action: effect name})]) of a reply."""
    from cerbos_tpu.api.cerbos.response.v1 import response_pb2

    resp = response_pb2.CheckResourcesResponse.FromString(raw)
    return resp.request_id, [
        (r.resource.id, {a: EFFECT_NAMES.get(e, str(e)) for a, e in r.actions.items()}) for r in resp.results
    ]


def compare(req: Request, raw: bytes, now_lo: datetime, now_hi: datetime) -> str | None:
    """None when the reply is complete and every effect is the reference's;
    otherwise what differs. The server read its clock between ``now_lo`` and
    ``now_hi``: an effect that depends on it may be either bound's."""
    try:
        request_id, results = decode_reply(raw)
    except Exception as e:  # noqa: BLE001 - an undecodable reply is a wrong reply, with its cause
        return f"undecodable reply: {type(e).__name__}: {e}"
    if request_id != req.request_id:
        return f"reply for {request_id!r}"
    if len(results) != len(req.entries):
        return f"incomplete reply: {len(results)} results for {len(req.entries)} resources"
    want = req.expected(now_lo)
    alt = req.expected(now_hi) if req.uses_now() else want
    for k, ((rid, got), (res, _)) in enumerate(zip(results, req.entries)):
        if rid != res["id"]:
            return f"result {k} is for resource {rid!r}, not {res['id']!r}"
        if got != want[k] and got != alt[k]:
            return f"resource {res['kind']}/{rid}: got {got} want {want[k]}"
    return None


def digest(reqs: list[Request], now: datetime) -> str:
    """SHA-256 over the reference's effects for ``reqs``, in order."""
    h = hashlib.sha256()
    for req in reqs:
        for (res, actions), eff in zip(req.entries, req.expected(now)):
            h.update(f"{req.request_id}|{res['kind']}|{res['id']}|".encode())
            h.update(",".join(f"{a}={eff[a]}" for a in actions).encode())
            h.update(b"\n")
    return h.hexdigest()


def poisson_schedule(rate: float, seconds: float, seed: int) -> list[float]:
    """Due times of an open loop, ascending, in seconds from the window's
    start: a Poisson process of ``rate`` conditioned on its expected count,
    i.e. ``round(rate * seconds)`` arrivals placed independently and uniformly
    in the window. Every seed gives the same number of requests, at other
    instants."""
    rng = random.Random(seed ^ 0xA221_7A1)
    return sorted(rng.random() * seconds for _ in range(round(rate * seconds)))
