"""Vectorized host predicates (the memo-cold pack cost).

Predicate columns are boolean subexpressions the device kernels can't
evaluate (string *content* ops like ``startsWith``, IP range membership).
The generic path evaluates them through the full CEL interpreter with a
per-input ``EvalContext`` — ~30µs per distinct value combination, which a
memo-cold batch pays for every input (packer._encode_preds).

This module compiles the overwhelmingly common predicate shapes into
closed-form batch evaluators: one Python-level loop per AST op over the
gathered attribute columns, no activation/context objects, no interpreter
dispatch. Everything else returns None and rides the generic path.

Supported grammar (mirrors cel.interp semantics EXACTLY — see the unit
equivalence test in tests/test_fastpred.py):

  e := Lit
     | path                                (request/R/P select chains with
                                            the packer's fast accessor
                                            shapes)
     | e == e | e != e | cond ? e : e | !e
     | str_path.startsWith/endsWith/contains(Lit str)
     | path.inIPAddrRange(Lit str)

Error semantics reproduced: missing attribute -> no_such_key error;
non-string method target/arg -> no-such-overload error; IP/CIDR parse
failure -> error; IP version mismatch -> False (not an error);
non-bool ternary condition -> error. Errors at any subexpression poison
the whole predicate (evaluate() raises), which `evaluate_pred_host`
reports as (False, True).
"""

from __future__ import annotations

import ipaddress
from typing import Callable, Optional

from ..cel import ast as A
from ..cel.values import values_equal
from .condcompile import _ROOT_ALIASES, _split_chain

# evaluation contract: an op is eval(ctx) -> (vals, errs) where
#   vals: list[Any] of length n (entries meaningless where errs[i])
#   errs: list[bool]
# and ctx maps path -> gathered value list (packer supplies, including
# _MISSING/_ERR sentinels from the accessors)

_MISSING = None  # bound by configure() to the packer's sentinels
_ERR = None


def configure(missing_sentinel, err_sentinel) -> None:
    global _MISSING, _ERR
    _MISSING = missing_sentinel
    _ERR = err_sentinel


class _Bail(Exception):
    pass


def _fast_path(node: A.Node) -> tuple[str, ...]:
    """Select chain → canonical path, restricted to the accessor shapes
    whose missing/present semantics match the oracle's Select walk
    (packer._path_accessor fast shapes)."""
    split = _split_chain(node)
    if split is None or split[0] not in _ROOT_ALIASES:
        raise _Bail
    path = _ROOT_ALIASES[split[0]] + split[1]
    if len(path) == 3 and path[0] in ("aux_data", "auxData") and path[1] == "jwt":
        return path
    if len(path) == 3 and path[0] in ("principal", "resource") and path[1] == "attr":
        return path
    if (
        len(path) == 2
        and path[0] in ("principal", "resource")
        and path[1] in ("id", "kind", "roles", "attr", "policyVersion", "scope")
    ):
        return path
    raise _Bail


def _compile(node: A.Node, paths: set) -> Callable:
    if isinstance(node, A.Lit):
        v = node.value

        def op_lit(ctx, n, v=v):
            return [v] * n, [False] * n

        return op_lit

    if isinstance(node, (A.Select, A.Index)):
        path = _fast_path(node)
        paths.add(path)

        def op_path(ctx, n, path=path):
            vals = ctx[path]
            errs = [v is _MISSING or v is _ERR for v in vals]
            return vals, errs

        return op_path

    if isinstance(node, A.Call):
        fn = node.fn
        if node.target is None:
            if fn in ("_==_", "_!=_") and len(node.args) == 2:
                a = _compile(node.args[0], paths)
                b = _compile(node.args[1], paths)
                neg = fn == "_!=_"

                def op_eq(ctx, n, a=a, b=b, neg=neg):
                    av, ae = a(ctx, n)
                    bv, be = b(ctx, n)
                    vals = [False] * n
                    errs = [False] * n
                    for i in range(n):
                        if ae[i] or be[i]:
                            errs[i] = True
                        else:
                            r = values_equal(av[i], bv[i])
                            vals[i] = (not r) if neg else r
                    return vals, errs

                return op_eq

            if fn == "_?_:_" and len(node.args) == 3:
                c = _compile(node.args[0], paths)
                t = _compile(node.args[1], paths)
                f = _compile(node.args[2], paths)

                def op_ternary(ctx, n, c=c, t=t, f=f):
                    cv, ce = c(ctx, n)
                    tv, te = t(ctx, n)
                    fv, fe = f(ctx, n)
                    vals = [None] * n
                    errs = [False] * n
                    for i in range(n):
                        if ce[i] or type(cv[i]) is not bool:
                            errs[i] = True
                        elif cv[i]:
                            vals[i], errs[i] = tv[i], te[i]
                        else:
                            vals[i], errs[i] = fv[i], fe[i]
                    return vals, errs

                return op_ternary

            if fn == "!_" and len(node.args) == 1:
                a = _compile(node.args[0], paths)

                def op_not(ctx, n, a=a):
                    av, ae = a(ctx, n)
                    vals = [False] * n
                    errs = [False] * n
                    for i in range(n):
                        if ae[i] or type(av[i]) is not bool:
                            errs[i] = True
                        else:
                            vals[i] = not av[i]
                    return vals, errs

                return op_not

            raise _Bail

        # target methods
        if fn in ("startsWith", "endsWith", "contains") and len(node.args) == 1:
            arg = node.args[0]
            if not (isinstance(arg, A.Lit) and isinstance(arg.value, str)):
                raise _Bail
            lit = arg.value
            t = _compile(node.target, paths)
            mode = fn

            def op_str(ctx, n, t=t, lit=lit, mode=mode):
                tv, te = t(ctx, n)
                vals = [False] * n
                errs = [False] * n
                for i in range(n):
                    v = tv[i]
                    if te[i] or not isinstance(v, str):
                        errs[i] = True
                    elif mode == "startsWith":
                        vals[i] = v.startswith(lit)
                    elif mode == "endsWith":
                        vals[i] = v.endswith(lit)
                    else:
                        vals[i] = lit in v
                return vals, errs

            return op_str

        if fn == "inIPAddrRange" and len(node.args) == 1:
            arg = node.args[0]
            if not (isinstance(arg, A.Lit) and isinstance(arg.value, str)):
                raise _Bail
            t = _compile(node.target, paths)
            try:
                net = ipaddress.ip_network(arg.value, strict=False)
            except ValueError:
                # the oracle raises CelError on every evaluation
                def op_ip_bad(ctx, n, t=t):
                    tv, te = t(ctx, n)
                    return [False] * n, [True] * n

                return op_ip_bad
            v4 = net.version == 4
            net_int = int(net.network_address)
            mask = int(net.netmask)
            memo: dict[str, tuple[bool, bool]] = {}

            def op_ip(ctx, n, t=t, v4=v4, net_int=net_int, mask=mask, memo=memo):
                tv, te = t(ctx, n)
                vals = [False] * n
                errs = [False] * n
                for i in range(n):
                    v = tv[i]
                    if te[i] or not isinstance(v, str):
                        errs[i] = True
                        continue
                    hit = memo.get(v)
                    if hit is None:
                        hit = _ip_check(v, v4, net_int, mask)
                        if len(memo) > 65536:
                            memo.clear()
                        memo[v] = hit
                    vals[i], errs[i] = hit
                return vals, errs

            return op_ip

    raise _Bail


def _parse_ipv4(s: str) -> Optional[int]:
    """Strict dotted-quad parse mirroring ipaddress.IPv4Address: exactly 4
    decimal octets, 0-255, no leading zeros (ambiguous octal), no signs or
    whitespace. Returns the 32-bit int or None."""
    parts = s.split(".")
    if len(parts) != 4:
        return None
    out = 0
    for p in parts:
        lp = len(p)
        if lp == 0 or lp > 3 or not p.isascii() or not p.isdigit():
            return None
        if lp > 1 and p[0] == "0":
            return None
        v = int(p)
        if v > 255:
            return None
        out = (out << 8) | v
    return out


def _ip_check(s: str, v4: bool, net_int: int, mask: int) -> tuple[bool, bool]:
    """(value, error) of inIPAddrRange for one address string, against a
    pre-parsed network. Fast path for clean IPv4; ipaddress otherwise."""
    a4 = _parse_ipv4(s)
    if a4 is not None:
        if not v4:
            return False, False  # version mismatch -> False, no error
        return (a4 & mask) == net_int, False
    try:
        addr = ipaddress.ip_address(s)
    except ValueError:
        return False, True  # oracle: CelError
    if (addr.version == 4) != v4:
        return False, False
    return (int(addr) & mask) == net_int, False


class FastPred:
    __slots__ = ("eval", "paths")

    def __init__(self, ev: Callable, paths: set):
        self.eval = ev
        self.paths = paths


def compile_fast_pred(spec) -> Optional[FastPred]:
    """PredSpec → FastPred, or None when any fragment is outside the fast
    grammar (the caller keeps the generic interpreter path)."""
    if spec.time_dependent:
        return None
    paths: set = set()
    try:
        op = _compile(spec.node, paths)
    except _Bail:
        return None

    def run(ctx, n, op=op):
        vals, errs = op(ctx, n)
        # evaluate_pred_host contract: value = (result is True) and errors
        # report as (False, True)
        return [(not e) and (v is True) for v, e in zip(vals, errs)], errs

    return FastPred(run, paths)
