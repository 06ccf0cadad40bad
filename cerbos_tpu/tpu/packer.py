"""Request batch → device tensors.

The packer is the host half of the TPU evaluator: it resolves scope chains,
expands parent roles, gathers candidate rule rows per (input, action, role)
— by calling the same Index.query the CPU oracle uses, memoized per
dimension tuple — and encodes attribute columns. Inputs the device cannot
evaluate faithfully (candidate overflow, unsupported value shapes at
device-compared paths, runtime-referencing conditions) are flagged for CPU
oracle fallback, so device coverage is a performance property, never a
correctness property.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from .. import namer
from .. import native as native_mod
from ..engine import drainclock
from ..engine import types as T
from ..ruletable.rows import KIND_PRINCIPAL, KIND_RESOURCE, RuleRow
from ..ruletable.check import EvalContext, build_request_messages
from .columns import (
    ColumnBatch,
    TAG_NUM,
    TAG_OTHER,
    encode_value,
)
from . import compilestats
from .condcompile import TAG_ERR, evaluate_pred_host
from .lowering import (
    EFFECT_DENY_CODE,
    EFFECT_NONE,
    LoweredTable,
    sp_code,
)

PT_PRINCIPAL = 0
PT_RESOURCE = 1


@dataclass(slots=True)
class CandEntry:
    """One candidate binding for an (input, action, role) cell."""

    cond_id: int
    drcond_id: int
    effect: int
    pt: int
    depth: int
    from_role_policy: bool
    origin_fqn: str
    row: Optional[RuleRow]  # original row (for outputs); None for pure synthetics
    needs_oracle: bool
    has_output: bool


@dataclass(slots=True)
class InputPlan:
    input: T.CheckInput
    principal_scopes: list[str]
    resource_scopes: list[str]
    principal_policy_key: str
    resource_policy_key: str
    resource_policy_fqn: str
    scoped_principal_exists: bool
    scoped_resource_exists: bool
    roles: list[str]
    oracle: bool = False  # fall back to the CPU oracle for this input
    trivial: bool = False  # no scopes/rows at all: every action default-DENY
    ba_range: tuple[int, int] = (0, 0)  # [start, end) in the flattened axis
    # small integer identifying the request SHAPE (one per distinct shape-memo
    # entry); the evaluator's assembly memo keys on it instead of re-hashing
    # every shape field per input
    sig: int = -1


@dataclass
class PackedBatch:
    plans: list[InputPlan]
    columns: ColumnBatch
    # flattened (input, action) axis
    ba_input: np.ndarray  # [BA] int32 → input index
    ba_action: list[str]
    # candidates [BA, K, J]
    cand_cond: np.ndarray
    cand_drcond: np.ndarray
    cand_effect: np.ndarray
    cand_pt: np.ndarray
    cand_depth: np.ndarray
    cand_valid: np.ndarray
    # scope permissions per input [B, 2, D]
    scope_sp: np.ndarray
    # host-side candidate entries for attribution/output reconstruction
    cand_entries: list[list[list[Optional[CandEntry]]]]  # [BA][K][J]
    # the table's layout class when the batch was packed (LayoutClass), not the batch's own extents
    K: int
    J: int
    D: int


class LayoutClass:
    """The ``(K, J, D)`` every batch of one table is packed and dispatched at.

    Three extents (role slots, candidates a slot, scope depth), each a power
    of two and at most its cap: the running maximum of what the table's
    request shapes have needed, never a batch's own maxima, so one shape
    bucket is one device program whatever the page holds. ``cover`` raises
    an extent and nothing lowers one within a table's life; ``restart``
    begins the next table's. Lane evaluators of one table share one object
    (``TpuEvaluator.shard_clone``), as they share the lowered table. The
    class starts at ``(1, 1, 1)`` or at what the layout manifest holds for
    the table (``restore``, once a life, ahead of the first device-route
    pack: see ``evaluator._LayoutPreloader.restore``)."""

    __slots__ = ("caps", "kjd", "restored", "_lock")

    def __init__(self, max_roles: int, max_candidates: int, max_depth: int):
        self.caps = (max_roles, max_candidates, max_depth)
        self.kjd = (1, 1, 1)
        self.restored = False
        self._lock = threading.Lock()

    def _raised(self, need) -> tuple[int, int, int]:
        return tuple(max(have, min(_pow2(n), cap)) for have, n, cap in zip(self.kjd, need, self.caps))

    def cover(self, K: int, J: int, D: int) -> tuple[int, int, int]:
        """The class, raised first where a request shape needs more."""
        kjd = self.kjd
        if K <= kjd[0] and J <= kjd[1] and D <= kjd[2]:
            return kjd
        with self._lock:
            old, self.kjd = self.kjd, self._raised((K, J, D))
            grown = [dim for dim, a, b in zip(compilestats.CLASS_DIMS, old, self.kjd) if b > a]
        compilestats.stats().record_layout_class(self.kjd, grown)
        return self.kjd

    def restore(self, filed) -> None:
        """Once a table's life: start at ``filed()``'s class (None: stay).
        Under the lock, so a sibling lane's first pack waits for the read
        instead of packing below the filed class. Not a growth."""
        with self._lock:
            if self.restored:
                return
            self.restored = True
            kjd = filed()
            if kjd is not None:
                self.kjd = self._raised(kjd)
        compilestats.stats().record_layout_class(self.kjd)

    def restart(self) -> None:
        """Another table is in place: its class is its own, from ``(1, 1, 1)``
        or from what the manifest holds for ITS identity (``restore``)."""
        with self._lock:
            self.kjd = (1, 1, 1)
            self.restored = False



class _ScalarPlan:
    """What the scalar store needs of the table and not of the batch: the
    paths in the order of the matrices' rows, which of them the fused C pass
    encodes, and the fallback-tag test as one lookup table. Built on the
    packer's first batch, dropped by ``invalidate()`` (``lt.paths`` and
    ``lt.fallback_tags`` are rebuilt with the table)."""

    __slots__ = ("paths", "fused_ix", "specs", "rest", "trig_rows", "trig_lut", "trig_ix")

    def __init__(self, lt: LoweredTable, fused_mode, native):
        self.paths = tuple(sorted(lt.paths))
        modes = [fused_mode(p) for p in self.paths]
        if not hasattr(native, "encode_attr_columns_multi"):
            modes = [None] * len(modes)
        # rows the fused C pass writes, with their (mode, root, leaf); the rest go through encode_column
        self.fused_ix = np.asarray([i for i, m in enumerate(modes) if m is not None], dtype=np.int64)
        self.specs = [m for m in modes if m is not None]
        self.rest = [i for i, m in enumerate(modes) if m is None]
        # one 256-entry lookup row per path that has trigger tags; a path
        # without any is not tested at all
        rows = [i for i, p in enumerate(self.paths) if lt.fallback_tags.get(p)]
        self.trig_lut = np.zeros((len(rows), 256), dtype=bool)
        for r, i in enumerate(rows):
            self.trig_lut[r, np.fromiter(lt.fallback_tags[self.paths[i]], dtype=np.uint8)] = True
        self.trig_ix = np.arange(len(rows))[:, None]
        # every row tested: the whole matrix is the lookup's index, no row gather
        self.trig_rows = slice(None) if len(rows) == len(self.paths) else np.asarray(rows, dtype=np.int64)


def _memo_put(memo: dict, key, val):
    """Bounded memo insert: wholesale clear past the cap (simple, O(1)
    amortized; the caches re-warm in one batch)."""
    if len(memo) > 65536:
        memo.clear()
    memo[key] = val
    return val


class Packer:
    def __init__(
        self,
        lowered: LoweredTable,
        max_roles: int = 8,
        max_candidates: int = 32,
        max_depth: int = 8,
        layout_class: Optional[LayoutClass] = None,
    ):
        self.lt = lowered
        # the caps; the extents a batch is built at are the layout class's
        self.K = max_roles
        self.J = max_candidates
        self.D = max_depth
        self.layout_class = layout_class if layout_class is not None else LayoutClass(max_roles, max_candidates, max_depth)
        self._cand_cache: dict[tuple, Optional[list[list[CandEntry]]]] = {}
        self._pred_cache: dict[tuple, tuple[bool, bool]] = {}
        self._scope_cache: dict[tuple, tuple] = {}
        self._exists_cache: dict[tuple, bool] = {}
        self._cell_cache: dict[tuple, Optional[tuple]] = {}
        self._accessors: dict[tuple, Any] = {}
        self._pred_accessors: dict[int, list] = {}
        self._encode_cache: dict[Any, tuple] = {}
        self._ts_memo: dict[Any, Any] = {}
        self._list_memo: dict[Any, list[int]] = {}
        self._shape_memo: dict[tuple, tuple] = {}
        # monotone shape-signature sequence; NOT reset by invalidate() so a
        # sig never aliases across reloads (downstream memos key on it)
        self._sig_seq = 0
        # block registry: every distinct candidate cell block gets a stable
        # uid at shape-build time; pack() assembles cand_* tensors with one
        # gather over a cached [n_blocks, K, J] stack instead of per-cell
        # Python work. Same scheme for scope-permission rows.
        self._block_uid: dict[int, int] = {}
        self._block_store: list[tuple] = []
        self._block_stacked: Optional[tuple[int, int, int, list[np.ndarray]]] = None  # (K, J, n, arrays)
        self._sp_uid: dict[bytes, int] = {}
        self._sp_store: list[np.ndarray] = []
        self._sp_stacked: Optional[tuple[int, np.ndarray]] = None
        # scratch interner for predicate group keys (kept separate from the
        # device interner so grouping never grows the device string space)
        self._pred_scratch: dict[str, int] = {}
        # pred_id -> fastpred program (None = outside the fast grammar)
        self._fast_preds: dict[int, Any] = {}
        self._scalar_plan: Optional[_ScalarPlan] = None

    def invalidate(self) -> None:
        self._cand_cache.clear()
        self._pred_cache.clear()
        self._scope_cache.clear()
        self._exists_cache.clear()
        self._cell_cache.clear()
        self._accessors.clear()
        self._pred_accessors.clear()
        self._encode_cache.clear()
        self._ts_memo.clear()
        self._list_memo.clear()
        self._shape_memo.clear()
        self._pred_scratch.clear()
        self._block_uid.clear()
        self._block_store.clear()
        self._block_stacked = None
        self._sp_uid.clear()
        self._sp_store.clear()
        self._sp_stacked = None
        self._fast_preds.clear()
        self._scalar_plan = None

    def _get_all_scopes(self, kind: str, scope: str, name: str, version: str, lenient: bool):
        key = (kind, scope, name, version, lenient)
        hit = self._scope_cache.get(key)
        if hit is None:
            hit = self.lt.table.get_all_scopes(kind, scope, name, version, lenient)
            self._scope_cache[key] = hit
        return hit

    def _exists(self, kind: str, version: str, name: str, scopes: list[str]) -> bool:
        key = (kind, version, name, tuple(scopes))
        hit = self._exists_cache.get(key)
        if hit is None:
            idx = self.lt.table.idx
            if kind == KIND_PRINCIPAL:
                hit = idx.scoped_principal_exists(version, scopes)
            else:
                hit = idx.scoped_resource_exists(version, name, scopes)
            self._exists_cache[key] = hit
        return hit

    # -- candidate generation ---------------------------------------------

    def _candidates(
        self,
        pt: int,
        version: str,
        resource: str,
        chain: tuple[str, ...],
        action: str,
        role: str,
        pid: str,
        resource_scope: str,
    ) -> Optional[list[list[CandEntry]]]:
        """Candidates per depth for one (pt, action, role); None → oracle."""
        key = (pt, version, resource, chain, action, role, pid, resource_scope)
        hit = self._cand_cache.get(key, False)
        if hit is not False:
            return hit
        rt = self.lt.table
        kind = KIND_PRINCIPAL if pt == PT_PRINCIPAL else KIND_RESOURCE
        # parent roles expand against the input's resource scope, matching
        # check.go:221 (AddParentRoles([resourceScope], [role]))
        parent_roles = rt.idx.add_parent_roles([resource_scope], [role])
        out: list[list[CandEntry]] = []
        ok = True
        for depth, scope in enumerate(chain):
            if depth >= self.D:
                ok = False
                break
            rows = rt.idx.query(version, resource, scope, action, parent_roles, kind, pid)
            entries: list[CandEntry] = []
            for r in rows:
                e = self._lower_candidate(r, pt, depth)
                if e is None or e.needs_oracle:
                    ok = False
                entries.append(e)  # keep shape; caller bails on not ok
            out.append(entries)
        result = out if ok else None
        self._cand_cache[key] = result
        return result

    def _lower_candidate(self, r: RuleRow, pt: int, depth: int) -> Optional[CandEntry]:
        lt = self.lt
        lr = lt.rows.get(r.id) if r.id >= 0 else None
        if lr is not None and lr.row is r:
            # regular indexed row
            return CandEntry(
                cond_id=lr.cond_id,
                drcond_id=lr.drcond_id,
                effect=lr.effect_code,
                pt=pt,
                depth=depth,
                from_role_policy=r.from_role_policy,
                origin_fqn=r.origin_fqn,
                row=r,
                needs_oracle=lr.needs_oracle,
                has_output=r.emit_output is not None,
            )
        # synthetic bindings produced by Index.query
        if r.no_match_for_scope_permissions:
            return CandEntry(
                cond_id=-1, drcond_id=-1, effect=EFFECT_DENY_CODE, pt=pt, depth=depth,
                from_role_policy=True, origin_fqn=r.origin_fqn, row=r,
                needs_oracle=False, has_output=False,
            )
        if r.from_role_policy and r.id >= 0:
            lr = lt.rows.get(r.id)
            if lr is None:
                return None
            if r.effect == "EFFECT_DENY":
                # negated-condition synthetic deny
                return CandEntry(
                    cond_id=lr.negated_cond_id, drcond_id=-1, effect=EFFECT_DENY_CODE,
                    pt=pt, depth=depth, from_role_policy=True, origin_fqn=r.origin_fqn,
                    row=r, needs_oracle=lr.negated_cond_id >= 0 and lt.compiler.kernels[lr.negated_cond_id].emit is None,
                    has_output=r.emit_output is not None,
                )
            # no-effect output carrier
            return CandEntry(
                cond_id=-1, drcond_id=-1, effect=EFFECT_NONE, pt=pt, depth=depth,
                from_role_policy=True, origin_fqn=r.origin_fqn, row=r,
                needs_oracle=False, has_output=r.emit_output is not None,
            )
        return None

    # -- packing -----------------------------------------------------------

    def pack(self, inputs: list[T.CheckInput], params: T.EvalParams) -> PackedBatch:
        """On the batcher's drain thread a flight's ``pack`` state is entered
        with its first part, ``pack_plan`` (``TpuEvaluator.submit``), and each
        ``drainclock.part`` below moves the state's second cursor on: the six
        parts tile ``pack``. On any other thread, and inside a state entered
        without a part, the stamps do nothing."""
        plans: list[InputPlan] = []
        # everything except the input reference depends only on the REQUEST
        # SHAPE — (principal id/scope/version, resource kind/scope/version,
        # roles, actions) — a handful of distinct shapes per corpus. The
        # shape memo carries the full per-input packing product (plan fields,
        # resolved candidate blocks, scope-permission row, K/J/D extents) so
        # the per-input loop is one tuple build + dict hit. This is a
        # shape-level memo, not a value-level one: it stays hot under
        # per-request-unique attribute values (the memo-cold benchmark).
        shape_memo = self._shape_memo
        if len(shape_memo) > 65536:
            # the shape memo anchors the block/sp registries (uids live in
            # its values) and the cell cache (block identity) — evict them
            # together, and ONLY between batches: a mid-batch clear would
            # invalidate uids already collected for earlier inputs of the
            # same pack() call. One batch may overshoot the cap by its own
            # input count; that's bounded and re-warms immediately.
            self._clear_shape_caches()
        lenient = params.lenient_scope_search
        ba_count = 0
        ba_counts: list[int] = []
        ba_action: list[str] = []
        uid_chunks: list[np.ndarray] = []
        cand_entries: list[list[list[Optional[CandEntry]]]] = []
        K_max, J_max, chain_max = 1, 1, 1
        sp_uids: list[int] = []
        plans_append = plans.append
        idx_principal = self.lt.table.idx.principal
        for inp in inputs:
            principal = inp.principal
            resource = inp.resource
            # principals with no principal policy anywhere canonicalize to
            # one shape: the id cannot influence any decision (the index has
            # no rows for it), so per-request-unique ids share the shape
            # memo, the assembly memo AND the jit variant instead of
            # rebuilding everything per request
            pid = principal.id if principal.id in idx_principal else ""
            sk = (
                pid, principal.scope, principal.policy_version,
                resource.kind, resource.scope, resource.policy_version,
                tuple(principal.roles), tuple(inp.actions), lenient,
                params.default_scope, params.default_policy_version,
            )
            hit = shape_memo.get(sk)
            if hit is None:
                hit = self._build_shape(inp, params, lenient, pid)
                shape_memo[sk] = hit
            (p_scopes, r_scopes, p_key, r_key, r_fqn, sp_exists, sr_exists,
             roles, trivial, oracle, blk_uids, blk_entries, uniq_actions,
             K_blk, J_blk, sp_uid, chain_len, sig) = hit
            bi = len(plans)
            n = 0
            if blk_uids is not None:
                n = len(uniq_actions)
                ba_action.extend(uniq_actions)
                uid_chunks.append(blk_uids)
                cand_entries.extend(blk_entries)
                if K_blk > K_max:
                    K_max = K_blk
                if J_blk > J_max:
                    J_max = J_blk
                if chain_len > chain_max:
                    chain_max = chain_len
            plans_append(InputPlan(
                input=inp,
                principal_scopes=p_scopes,
                resource_scopes=r_scopes,
                principal_policy_key=p_key,
                resource_policy_key=r_key,
                resource_policy_fqn=r_fqn,
                scoped_principal_exists=sp_exists,
                scoped_resource_exists=sr_exists,
                roles=roles,
                trivial=trivial,
                oracle=oracle,
                ba_range=(ba_count, ba_count + n),
                sig=sig,
            ))
            ba_counts.append(n)
            sp_uids.append(sp_uid)
            ba_count += n

        drainclock.part(drainclock.PACK_GATHER)
        BA = ba_count
        # the table's class, not this batch's own maxima: what a page holds
        # (a second role, a second candidate, a scoped resource) does not
        # choose among device programs. Slots past a shape's own extents are
        # what they are when one batch mixes shapes: invalid candidates,
        # conditions and depths -1, scope rows 0
        K, J, D = self.layout_class.cover(K_max, J_max, chain_max)
        if BA:
            ba_input = np.repeat(
                np.arange(len(plans), dtype=np.int32),
                np.asarray(ba_counts, dtype=np.int64),
            )
            all_uids = np.concatenate(uid_chunks)
            stacked = self._stacked_blocks(K, J)
            cand_cond = stacked[0][all_uids]
            cand_drcond = stacked[1][all_uids]
            cand_effect = stacked[2][all_uids]
            cand_pt = stacked[3][all_uids]
            cand_depth = stacked[4][all_uids]
            cand_valid = stacked[5][all_uids]
        else:
            ba_input = np.zeros(0, dtype=np.int32)
            cand_cond = np.full((0, K, J), -1, dtype=np.int32)
            cand_drcond = np.full((0, K, J), -1, dtype=np.int32)
            cand_effect = np.zeros((0, K, J), dtype=np.int8)
            cand_pt = np.zeros((0, K, J), dtype=np.int8)
            cand_depth = np.full((0, K, J), -1, dtype=np.int8)
            cand_valid = np.zeros((0, K, J), dtype=bool)

        # scope permissions per input [B, 2, D]: rows precomputed per shape,
        # assembled with one gather over the registered-row stack
        if plans:
            scope_sp = self._stacked_sp()[np.asarray(sp_uids, dtype=np.int64)][:, :, :D]
        else:
            scope_sp = np.zeros((0, 2, D), dtype=np.int8)

        drainclock.part(drainclock.PACK_SCALARS)
        columns = self._encode_columns(plans, params)
        return PackedBatch(
            plans=plans,
            columns=columns,
            ba_input=np.asarray(ba_input, dtype=np.int32),
            ba_action=ba_action,
            cand_cond=cand_cond,
            cand_drcond=cand_drcond,
            cand_effect=cand_effect,
            cand_pt=cand_pt,
            cand_depth=cand_depth,
            cand_valid=cand_valid,
            scope_sp=scope_sp,
            cand_entries=cand_entries,
            K=int(K),
            J=int(J),
            D=D,
        )

    def _clear_shape_caches(self) -> None:
        """Evict the shape memo and everything whose identity it anchors."""
        self._shape_memo.clear()
        self._cell_cache.clear()
        self._block_uid.clear()
        self._block_store.clear()
        self._block_stacked = None
        self._sp_uid.clear()
        self._sp_store.clear()
        self._sp_stacked = None

    def _register_block(self, blk: tuple) -> int:
        uid = self._block_uid.get(id(blk))
        if uid is None:
            uid = len(self._block_store)
            self._block_uid[id(blk)] = uid
            self._block_store.append(blk)
        return uid

    def _register_sp(self, sp_row: np.ndarray) -> int:
        # content-keyed: distinct scope-permission patterns are few, so the
        # store stays tiny no matter how many shapes register
        key = sp_row.tobytes()
        uid = self._sp_uid.get(key)
        if uid is None:
            uid = len(self._sp_store)
            self._sp_uid[key] = uid
            self._sp_store.append(sp_row)
        return uid

    def _stacked_blocks(self, K: int, J: int) -> list[np.ndarray]:
        """[n_blocks, K, J] stacks of every registered block, padded to the
        layout class: ONE stack, grown incrementally (new registrations
        append into capacity-doubled arrays: amortized O(new blocks), not
        O(all blocks) per batch) and restacked when the class grows."""
        n = len(self._block_store)
        hit = self._block_stacked
        if hit is not None and hit[:2] != (K, J):
            hit = None  # the class grew: restack
        if hit is not None and hit[2] == n:
            return [a[:n] for a in hit[3]]
        if hit is not None and hit[3][0].shape[0] >= n:
            start, arrays = hit[2], hit[3]
        else:
            cap = max(16, 1 << (n - 1).bit_length()) if n else 16
            arrays = [
                np.full((cap, K, J), -1, dtype=np.int32),
                np.full((cap, K, J), -1, dtype=np.int32),
                np.zeros((cap, K, J), dtype=np.int8),
                np.zeros((cap, K, J), dtype=np.int8),
                np.full((cap, K, J), -1, dtype=np.int8),
                np.zeros((cap, K, J), dtype=bool),
            ]
            start = 0
            if hit is not None:
                start = hit[2]
                for a, old in zip(arrays, hit[3]):
                    a[:start] = old[:start]
        for i in range(start, n):
            blk = self._block_store[i]
            kk, jj = blk[0].shape
            # a block this batch gathers fits (its shape raised the class
            # before the gather). One larger than the class is a lane's, of
            # the table before a ``restart``, until its own ``invalidate``:
            # never gathered at this class, so cutting it here is safe
            kk, jj = min(kk, K), min(jj, J)
            for a, src in zip(arrays, blk[:6]):
                a[i, :kk, :jj] = src[:kk, :jj]
        self._block_stacked = (K, J, n, arrays)
        return [a[:n] for a in arrays]

    def _stacked_sp(self) -> np.ndarray:
        n = len(self._sp_store)
        hit = self._sp_stacked
        if hit is not None and hit[0] == n:
            return hit[1]
        stacked = np.stack(self._sp_store) if n else np.zeros((0, 2, self.D), dtype=np.int8)
        self._sp_stacked = (n, stacked)
        return stacked

    def _build_shape(self, inp: T.CheckInput, params: T.EvalParams, lenient: bool, pid: str) -> tuple:
        """Resolve the full packing product for one request shape: plan
        fields, candidate blocks per unique action, scope-permission row and
        K/J/D extents. Runs once per distinct shape; every input with the
        same shape reuses the result verbatim. ``pid`` is the CANONICAL
        principal id ("" when the id has no principal policy rows — see
        pack(); such ids cannot influence decisions)."""
        rt = self.lt.table
        principal_scope = T.effective_scope(inp.principal.scope, params)
        principal_version = T.effective_version(inp.principal.policy_version, params)
        resource_scope = T.effective_scope(inp.resource.scope, params)
        resource_version = T.effective_version(inp.resource.policy_version, params)
        p_scopes, p_key, _p_fqn = self._get_all_scopes(
            KIND_PRINCIPAL, principal_scope, pid, principal_version, lenient
        )
        r_scopes, r_key, r_fqn = self._get_all_scopes(
            KIND_RESOURCE, resource_scope, inp.resource.kind, resource_version, lenient
        )
        sp_exists = self._exists(KIND_PRINCIPAL, principal_version, "", p_scopes)
        sr_exists = self._exists(
            KIND_RESOURCE, resource_version, namer.sanitize(inp.resource.kind), r_scopes
        )
        roles = list(inp.principal.roles)
        trivial = (not p_scopes and not r_scopes) or (not sp_exists and not sr_exists)
        oracle = len(roles) > self.K or len(p_scopes) > self.D or len(r_scopes) > self.D

        # scope-permission row at the full configured depth; pack() slices
        # to the batch's bucketed D
        sp_row = np.zeros((2, self.D), dtype=np.int8)
        for pi, chain in ((PT_PRINCIPAL, p_scopes), (PT_RESOURCE, r_scopes)):
            for d, scope in enumerate(chain[: self.D]):
                sp_row[pi, d] = sp_code(rt.get_scope_scope_permissions(scope))

        shape_blocks: Optional[list[tuple]] = None
        uniq_actions: list[str] = []
        K_blk, J_blk = 1, 1
        chain_len = max(len(p_scopes), len(r_scopes), 1)
        if not trivial and not oracle:
            shape_blocks = []
            seen: set[str] = set()
            for a in inp.actions:
                if a in seen:
                    continue
                seen.add(a)
                blk = self._cell_block(
                    inp, pid, p_scopes, r_scopes, roles, a, resource_version, resource_scope
                )
                if blk is None:
                    oracle = True
                    shape_blocks = None
                    uniq_actions = []
                    break
                uniq_actions.append(a)
                shape_blocks.append(blk)
                K_blk = max(K_blk, blk[0].shape[0])
                J_blk = max(J_blk, blk[0].shape[1])
        if shape_blocks is not None:
            blk_uids = np.fromiter(
                (self._register_block(blk) for blk in shape_blocks),
                dtype=np.int64, count=len(shape_blocks),
            )
            blk_entries = [blk[6] for blk in shape_blocks]
        else:
            blk_uids = None
            blk_entries = None
        self._sig_seq += 1
        return (
            p_scopes, r_scopes, p_key, r_key, r_fqn, sp_exists, sr_exists,
            roles, trivial, oracle, blk_uids, blk_entries, uniq_actions,
            K_blk, J_blk, self._register_sp(sp_row),
            min(chain_len, self.D), self._sig_seq,
        )

    def _cell_block(
        self,
        inp: T.CheckInput,
        pid: str,
        p_scopes: list[str],
        r_scopes: list[str],
        roles: list[str],
        action: str,
        resource_version: str,
        resource_scope: str,
    ) -> Optional[tuple]:
        """Candidate cell for one (shape, action); memoized across shapes
        that share the dimension tuple. None → oracle fallback. ``pid`` is
        already canonical (see pack())."""
        cell_blocks = self._cell_cache
        pid_key = pid
        key = (
            resource_version, inp.resource.kind, tuple(p_scopes),
            tuple(r_scopes), tuple(roles), action, pid_key, resource_scope,
        )
        hit = cell_blocks.get(key, False)
        if hit is not False:
            return hit
        sanitized = namer.sanitize(inp.resource.kind)
        per_k_entries: list[list[CandEntry]] = []
        ok = True
        for k, role in enumerate(roles):
            entries: list[CandEntry] = []
            for pt, chain, qpid in (
                (PT_PRINCIPAL, tuple(p_scopes), pid),
                (PT_RESOURCE, tuple(r_scopes), ""),
            ):
                if pt == PT_PRINCIPAL and k > 0:
                    continue  # principal pass uses only the first role
                if pt == PT_PRINCIPAL and not qpid:
                    # canonical "" = this principal id has no rows anywhere
                    # (see pack()); an empty id would mean match-all to
                    # Index.query, so don't query at all
                    continue
                cands = self._candidates(
                    pt, resource_version, sanitized, chain, action, role, qpid, resource_scope
                )
                if cands is None:
                    ok = False
                    break
                for depth_entries in cands:
                    entries.extend(depth_entries)
            if not ok or len(entries) > self.J or any(e is None for e in entries):
                ok = False
                break
            per_k_entries.append(entries)
        if not ok:
            cell_blocks[key] = None
            return None
        K_used = len(per_k_entries)
        J_used = max((len(es) for es in per_k_entries), default=0)
        block = (
            np.full((K_used, J_used), -1, dtype=np.int32),  # cond
            np.full((K_used, J_used), -1, dtype=np.int32),  # drcond
            np.zeros((K_used, J_used), dtype=np.int8),  # effect
            np.zeros((K_used, J_used), dtype=np.int8),  # pt
            np.full((K_used, J_used), -1, dtype=np.int8),  # depth
            np.zeros((K_used, J_used), dtype=bool),  # valid
            per_k_entries,
        )
        for k, es in enumerate(per_k_entries):
            for j, e in enumerate(es):
                block[0][k, j] = e.cond_id
                block[1][k, j] = e.drcond_id
                block[2][k, j] = e.effect
                block[3][k, j] = e.pt
                block[4][k, j] = e.depth
                block[5][k, j] = True
        cell_blocks[key] = block
        return block

    # -- columns -----------------------------------------------------------

    def _input_view(self, inp: T.CheckInput) -> dict:
        aux = inp.aux_data or T.AuxData()
        jwt = {"jwt": aux.jwt}
        return {
            "aux_data": jwt,
            "principal": {
                "id": inp.principal.id,
                "roles": list(inp.principal.roles),
                "attr": inp.principal.attr,
                "policyVersion": inp.principal.policy_version,
                "scope": namer.scope_value(inp.principal.scope),
            },
            "resource": {
                "kind": inp.resource.kind,
                "id": inp.resource.id,
                "attr": inp.resource.attr,
                "policyVersion": inp.resource.policy_version,
                "scope": namer.scope_value(inp.resource.scope),
            },
            "auxData": jwt,
        }

    def _path_accessor(self, path: tuple[str, ...]):
        """Compile a fast value resolver for a column path. The overwhelmingly
        common shapes (principal/resource attr leaves and top-level fields)
        skip the generic dict walk."""
        fn = self._accessors.get(path)
        if fn is not None:
            return fn
        _MISSING = _MISSING_SENTINEL
        if len(path) == 3 and path[0] in ("aux_data", "auxData") and path[1] == "jwt":
            leaf = path[2]

            def fn(inp, leaf=leaf):  # type: ignore[misc]
                aux = inp.aux_data
                if aux is None:
                    return _MISSING
                return aux.jwt.get(leaf, _MISSING)

        elif len(path) == 3 and path[0] in ("principal", "resource") and path[1] == "attr":
            root, leaf = path[0], path[2]

            def fn(inp, root=root, leaf=leaf):  # type: ignore[misc]
                return getattr(inp, root).attr.get(leaf, _MISSING)

        elif (
            len(path) == 2
            and path[0] in ("principal", "resource")
            # only the wire-format field names; anything else (e.g. a
            # snake_case dataclass attribute) must behave as missing, like
            # the generic view walk does
            and path[1] in ("id", "kind", "roles", "attr", "policyVersion", "scope")
        ):
            root, leaf = path[0], path[1]
            if leaf == "scope":
                scope_value = namer.scope_value

                def fn(inp, root=root, scope_value=scope_value):  # type: ignore[misc]
                    return scope_value(getattr(inp, root).scope)

            else:
                attr_name = {"policyVersion": "policy_version"}.get(leaf, leaf)

                def fn(inp, root=root, attr_name=attr_name):  # type: ignore[misc]
                    return getattr(getattr(inp, root), attr_name, _MISSING)

        else:

            def fn(inp):  # type: ignore[misc]
                view = self._input_view(inp)
                return _walk_view(view, path)

        self._accessors[path] = fn
        return fn

    def _encode_columns(self, plans: list[InputPlan], params: T.EvalParams) -> ColumnBatch:
        B = len(plans)
        cb = ColumnBatch(size=B)
        native = native_mod.get()
        # filter once, not once per path
        active = [(bi, plan) for bi, plan in enumerate(plans) if not (plan.trivial or plan.oracle)]
        if native is not None and hasattr(native, "encode_column"):
            self._encode_columns_native(cb, plans, active, native)
            self._encode_rest(cb, plans, active, params)
            return cb
        interner = self.lt.interner
        encode_cache = self._encode_cache
        paths = sorted(self.lt.paths)
        for p in paths:
            t = np.zeros(B, dtype=np.int8)
            h = np.zeros(B, dtype=np.int32)
            l = np.zeros(B, dtype=np.int32)
            s = np.zeros(B, dtype=np.int32)
            nn = np.zeros(B, dtype=bool)
            accessor = self._path_accessor(p)
            trig = self.lt.fallback_tags.get(p)
            # float values batch through the native key encoder
            num_idx: list[int] = []
            num_vals: list[float] = []
            for bi, plan in active:
                v = accessor(plan.input)
                if v is _MISSING_SENTINEL:
                    continue  # TAG_MISSING zeros already in place
                if v is _ERR_SENTINEL:
                    t[bi] = TAG_ERR
                    continue
                if native is not None and type(v) is float:
                    t[bi] = TAG_NUM
                    num_idx.append(bi)
                    num_vals.append(v)
                    continue
                # cache encodings per concrete value; key includes the type so
                # True / 1.0 / 1 don't collide as dict keys
                try:
                    ck = (type(v), v)
                    enc = encode_cache.get(ck)
                except TypeError:
                    tag, hi, lo, sid, is_nan = encode_value(v, True, interner)
                else:
                    if enc is None:
                        tag, hi, lo, sid, is_nan = encode_value(v, True, interner)
                        if len(encode_cache) > 65536:
                            encode_cache.clear()
                        encode_cache[ck] = (tag, hi, lo, sid, is_nan)
                    else:
                        tag, hi, lo, sid, is_nan = enc
                t[bi], h[bi], l[bi], s[bi], nn[bi] = tag, hi, lo, sid, is_nan
                if trig and tag in trig:
                    plan.oracle = True
            if num_idx:
                arr = np.asarray(num_vals, dtype=np.float64)
                hi_b, lo_b, nan_b = native.encode_double_keys(arr.tobytes())
                idx = np.asarray(num_idx, dtype=np.int64)
                h[idx] = np.frombuffer(hi_b, dtype=np.int32)
                l[idx] = np.frombuffer(lo_b, dtype=np.int32)
                nn[idx] = np.frombuffer(nan_b, dtype=np.uint8).astype(bool)
            cb.tags[p], cb.his[p], cb.los[p], cb.sids[p], cb.nans[p] = t, h, l, s, nn

        self._encode_rest(cb, plans, active, params)
        return cb

    def _encode_rest(self, cb: ColumnBatch, plans, active, params) -> None:
        """What follows the scalar columns, with or without the native
        encoder: one part of the drain clock each (``pack_preds`` runs on to
        the end of ``pack``)."""
        drainclock.part(drainclock.PACK_LISTS)
        self._encode_list_columns(cb, plans, active)
        drainclock.part(drainclock.PACK_TS)
        self._encode_ts_columns(cb, plans, active, params)
        drainclock.part(drainclock.PACK_PREDS)
        self._encode_preds(cb, plans, active, params)

    def _encode_ts_columns(self, cb: ColumnBatch, plans, active, params) -> None:
        """Parsed-timestamp key columns for paths used inside timestamp(...)
        comparisons, plus the batch-constant now() key. Conversion is the CEL
        runtime's own timestamp() overload set (columns.timestamp_key), so
        device semantics match the oracle bit-exactly; unconvertible values
        carry state 2 (a CEL error on device)."""
        from .columns import timestamp_key

        ts_paths = self.lt.ts_paths
        if not ts_paths and not self.lt.uses_now:
            return
        B = cb.size
        memo = self._ts_memo
        for p in sorted(ts_paths):
            accessor = self._path_accessor(p)
            hi = np.zeros(B, dtype=np.int32)
            lo = np.zeros(B, dtype=np.int32)
            state = np.zeros(B, dtype=np.int8)
            for bi, plan in active:
                if plan.oracle:
                    continue
                v = accessor(plan.input)
                if v is _MISSING_SENTINEL:
                    continue  # state 0: the attribute access itself errors
                try:
                    mk = (type(v), v)
                    enc = memo.get(mk)
                except TypeError:
                    mk, enc = None, None
                if enc is None:
                    try:
                        enc = timestamp_key(v)
                    except Exception:  # noqa: BLE001 — CEL would error on this value
                        enc = "err"
                    if mk is not None:
                        _memo_put(memo, mk, enc)
                if enc == "err":
                    state[bi] = 2
                else:
                    hi[bi], lo[bi] = enc
                    state[bi] = 1
            cb.ts_his[p], cb.ts_los[p], cb.ts_states[p] = hi, lo, state
        now_fn = getattr(params, "now_fn", None)
        if now_fn is not None:
            now_val = now_fn()
        else:
            import datetime as _dt

            now_val = _dt.datetime.now(_dt.timezone.utc).isoformat()
        nh, nl = timestamp_key(now_val)
        cb.now_hi = np.asarray(nh, dtype=np.int32)
        cb.now_lo = np.asarray(nl, dtype=np.int32)

    def _encode_list_columns(self, cb: ColumnBatch, plans, active) -> None:
        """String-list membership columns: per path, pad each input's list of
        interned sids to the batch max length; non-lists / non-string
        elements error (state 2), missing attrs are state 0.

        Interned sid vectors memoize per concrete list value — request
        corpora repeat a small set of role/location lists, so the per-
        element intern loop runs once per distinct list, not per input."""
        B = cb.size
        interner = self.lt.interner
        memo = self._list_memo
        native = native_mod.get()
        use_native = native is not None and hasattr(native, "encode_list_column")
        for p in sorted(self.lt.list_paths):
            fused = self._fused_mode(p) if use_native else None
            if fused is not None:
                # oracle flags may have flipped during scalar encoding;
                # re-filter so oracled inputs don't intern into device space
                live = [(bi, plan) for bi, plan in active if not plan.oracle]
                nl = len(live)
                mode, root, leaf = fused
                lstate = np.zeros(nl, dtype=np.uint8)
                width, sids_bytes = native.encode_list_column(
                    [plan.input for _, plan in live], mode, root, leaf,
                    interner.ids, _MISSING_SENTINEL, memoryview(lstate),
                )
                arr = np.zeros((B, width), dtype=np.int32)
                state = np.zeros(B, dtype=np.int8)
                if nl:
                    ix = np.fromiter((bi for bi, _ in live), dtype=np.int64, count=nl)
                    mat = np.frombuffer(sids_bytes, dtype=np.int32).reshape(nl, width)
                    dicts = lstate == 3
                    if dicts.any():
                        for si in np.nonzero(dicts)[0]:
                            live[int(si)][1].oracle = True
                        lstate = np.where(dicts, 0, lstate)
                        mat = np.where(dicts[:, None], 0, mat)
                    arr[ix] = mat
                    state[ix] = lstate.astype(np.int8)
                cb.list_sids[p] = arr
                cb.list_states[p] = state
                continue
            accessor = self._path_accessor(p)
            per_input: list[Optional[list[int]]] = [None] * B
            state = np.zeros(B, dtype=np.int8)
            max_len = 1
            for bi, plan in active:
                if plan.oracle:
                    continue
                v = accessor(plan.input)
                if v is _MISSING_SENTINEL:
                    continue  # state 0
                if isinstance(v, dict):
                    # CEL `in` over a map is KEY membership — different
                    # semantics; route to the oracle like scalar-path
                    # fallback tags do
                    plan.oracle = True
                    continue
                if not isinstance(v, list):
                    state[bi] = 2
                    continue
                try:
                    mk = tuple(v)
                    sids = memo.get(mk)
                except TypeError:
                    mk, sids = None, None
                if sids is None:
                    sids = []
                    for el in v:
                        if isinstance(el, str):
                            sids.append(interner.intern(el))
                        else:
                            # a non-string element can never equal the string
                            # constant; slot 0 (reserved) never matches
                            sids.append(0)
                    if mk is not None:
                        _memo_put(memo, mk, sids)
                state[bi] = 1
                per_input[bi] = sids
                if len(sids) > max_len:
                    max_len = len(sids)
            # bucket the list axis so jit traces are reused across batches
            # with different max lengths
            max_len = _pow2(max(max_len, 4))
            arr = np.zeros((B, max_len), dtype=np.int32)
            for bi, sids in enumerate(per_input):
                if sids:
                    arr[bi, : len(sids)] = sids
            cb.list_sids[p] = arr
            cb.list_states[p] = state

    def _encode_preds(self, cb: ColumnBatch, plans, active, params) -> None:
        B = cb.size
        preds = self.lt.compiler.preds
        if not preds:
            return
        live = [(bi, plan) for bi, plan in active if not plan.oracle]
        out = {
            spec.pred_id: (np.zeros(B, dtype=bool), np.zeros(B, dtype=bool))
            for spec in preds
        }

        # Closed-form vectorized predicates first (fastpred): no activation
        # objects, no interpreter, no value-combination grouping — a
        # memo-cold batch with globally unique attributes costs one Python
        # loop per AST op instead of a full CEL evaluation per input.
        fast_specs: list[tuple[Any, Any]] = []
        gen_specs: list = []
        for spec in preds:
            prog = self._fast_pred_prog(spec)
            if prog is not None:
                fast_specs.append((spec, prog))
            else:
                gen_specs.append(spec)
        if fast_specs and live:
            n = len(live)
            gathered: dict[tuple[str, ...], list] = {}
            for _, prog in fast_specs:
                for p in prog.paths:
                    if p not in gathered:
                        acc = self._path_accessor(p)
                        gathered[p] = [acc(plan.input) for _, plan in live]
            bis = np.fromiter((bi for bi, _ in live), dtype=np.int64, count=n)
            for spec, prog in fast_specs:
                v_list, e_list = prog.eval(gathered, n)
                vals, errs = out[spec.pred_id]
                vals[bis] = v_list
                errs[bis] = e_list
        preds = gen_specs
        if not preds:
            for spec_id, (vals, errs) in out.items():
                cb.pred_vals[spec_id] = vals
                cb.pred_errs[spec_id] = errs
            return

        # Vectorized grouping: encode every referenced path's value to its
        # canonical (tag, hi, lo, sid) key columns, group the batch with one
        # np.unique over the key matrix, and evaluate each predicate ONCE per
        # distinct value combination. Inputs carrying container values
        # (TAG_OTHER collapses distinct lists/maps) and time-dependent specs
        # drop to per-input evaluation; everything else is O(unique combos).
        native = native_mod.get()
        group_specs = [s for s in preds if not s.time_dependent]
        grouped_rows: Optional[np.ndarray] = None
        if native is not None and hasattr(native, "encode_attr_column") and group_specs and len(live) >= 32:
            paths = sorted({p for spec in group_specs for p in spec.ref_paths})
            modes = [self._fused_mode(p) for p in paths]
            if all(m is not None for m in modes):
                n = len(live)
                inputs_list = [plan.input for _, plan in live]
                scratch = self._pred_scratch
                if len(scratch) > 65536:
                    scratch.clear()
                cols: list[np.ndarray] = []
                groupable = np.ones(n, dtype=bool)
                for (mode, root, leaf) in modes:  # type: ignore[misc]
                    t = np.zeros(n, dtype=np.uint8)
                    h = np.zeros(n, dtype=np.int32)
                    l = np.zeros(n, dtype=np.int32)
                    s = np.zeros(n, dtype=np.int32)
                    nn = np.zeros(n, dtype=np.uint8)
                    st = np.zeros(n, dtype=np.uint8)
                    native.encode_attr_column(
                        inputs_list, mode, root, leaf, scratch,
                        _MISSING_SENTINEL, _ERR_SENTINEL,
                        memoryview(t), memoryview(h), memoryview(l),
                        memoryview(s), memoryview(nn), memoryview(st),
                    )
                    groupable &= t != TAG_OTHER  # containers don't key
                    # ints the double key can't represent exactly never
                    # group; the subtype column keeps int 1 and double 1.0
                    # (CEL-distinct) in separate groups
                    groupable &= st != 3
                    cols.extend((t.astype(np.int32), h, l, s, nn.astype(np.int32), st.astype(np.int32)))
                key_mat = np.ascontiguousarray(np.stack(cols, axis=1), dtype=np.int32)
                g_idx = np.nonzero(groupable)[0]
                if g_idx.size:
                    # group by raw row bytes with one dict pass — O(n) hashing
                    # beats np.unique's O(n log n) argsort on every batch
                    rows = np.ascontiguousarray(key_mat[g_idx])
                    row_w = rows.shape[1] * 4
                    buf = rows.tobytes()
                    seen: dict[bytes, int] = {}
                    n_g = g_idx.size
                    inverse = np.empty(n_g, dtype=np.int64)
                    rep: list[int] = []
                    for i in range(n_g):
                        rb = buf[i * row_w : (i + 1) * row_w]
                        u = seen.get(rb)
                        if u is None:
                            u = len(rep)
                            seen[rb] = u
                            rep.append(i)
                        inverse[i] = u
                    bis = np.fromiter(
                        (live[int(i)][0] for i in g_idx), dtype=np.int64, count=n_g
                    )
                    n_u = len(rep)
                    for spec in group_specs:
                        vals, errs = out[spec.pred_id]
                        uv = np.empty(n_u, dtype=bool)
                        ue = np.empty(n_u, dtype=bool)
                        for u in range(n_u):
                            _, plan_rep = live[int(g_idx[rep[u]])]
                            uv[u], ue[u] = self._eval_pred(spec, plan_rep, params)
                        vals[bis] = uv[inverse]
                        errs[bis] = ue[inverse]
                    grouped_rows = groupable

        for si, (bi, plan) in enumerate(live):
            is_grouped = grouped_rows is not None and grouped_rows[si]
            for spec in preds:
                if is_grouped and not spec.time_dependent:
                    continue
                vals, errs = out[spec.pred_id]
                vals[bi], errs[bi] = self._eval_pred(spec, plan, params)
        for spec_id, (vals, errs) in out.items():
            cb.pred_vals[spec_id] = vals
            cb.pred_errs[spec_id] = errs

    def _fast_pred_prog(self, spec):
        """Compile-once cache of fastpred programs (None = generic path)."""
        hit = self._fast_preds.get(spec.pred_id, _MISSING_SENTINEL)
        if hit is not _MISSING_SENTINEL:
            return hit
        from . import fastpred

        fastpred.configure(_MISSING_SENTINEL, _ERR_SENTINEL)
        prog = fastpred.compile_fast_pred(spec)
        self._fast_preds[spec.pred_id] = prog
        return prog

    def _fused_mode(self, path: tuple[str, ...]) -> Optional[tuple[int, str, str]]:
        """(mode, root, leaf) for paths the C fused gather+encode handles;
        None → Python gather. Mirrors _path_accessor's fast shapes (scope is
        excluded: it needs namer.scope_value)."""
        if len(path) == 3 and path[0] in ("aux_data", "auxData") and path[1] == "jwt":
            return (1, "aux_data", path[2])
        if len(path) == 3 and path[0] in ("principal", "resource") and path[1] == "attr":
            return (0, path[0], path[2])
        if (
            len(path) == 2
            and path[0] in ("principal", "resource")
            and path[1] in ("id", "kind", "roles", "attr", "policyVersion")
        ):
            leaf = {"policyVersion": "policy_version"}.get(path[1], path[1])
            return (2, path[0], leaf)
        return None

    def _encode_columns_native(self, cb: ColumnBatch, plans, active, native) -> None:
        """Whole-column encoding in C, stored ONCE: the matrices the encoders
        write ARE the columns. For the common path shapes the value gather
        (attribute access on input objects) AND the type dispatch +
        key/interning loop both run natively, one pass over the batch for
        every such path at once (encode_attr_columns_multi: the per-input
        resolution of principal/resource objects, attr and jwt dicts is
        shared by all of them); other paths (scope, deep paths) gather values
        in Python and encode via encode_column, into their rows of the same
        matrices. ``cb``'s dictionaries get row views, and the matrices stay
        on ``cb.scalars`` for the transfer format's block copies."""
        sp = self._scalar_plan
        if sp is None:
            sp = self._scalar_plan = _ScalarPlan(self.lt, self._fused_mode, native)
        paths = sp.paths
        P, B, na = len(paths), cb.size, len(active)
        if not P:
            return
        interner = self.lt.interner
        M32 = np.zeros((3, P, B), dtype=np.int32)  # his, los, sids
        MT = np.zeros((P, B), dtype=np.int8)
        MN = np.zeros((P, B), dtype=bool)
        all_active = na == B
        # only ACTIVE inputs are gathered/encoded: trivial/oracle inputs stay
        # TAG_MISSING and must not intern their strings into the device
        # string space
        if sp.specs and na:
            act_ix = None
            if all_active:
                act_inputs = [plan.input for plan in plans]
            else:
                act_inputs = [plan.input for _, plan in active]
                act_ix = np.fromiter((bi for bi, _ in active), dtype=np.int64, count=na)
            Pf = len(sp.specs)
            whole = all_active and Pf == P
            if whole:
                F32, FT, FN = M32, MT, MN
            else:
                F32 = np.zeros((3, Pf, na), dtype=np.int32)
                FT = np.zeros((Pf, na), dtype=np.int8)
                FN = np.zeros((Pf, na), dtype=bool)
            # the C pass writes bytes: tags and nans go to it as uint8 views
            native.encode_attr_columns_multi(
                act_inputs, sp.specs, interner.ids, _MISSING_SENTINEL, _ERR_SENTINEL,
                memoryview(FT.view(np.uint8)), memoryview(F32[0]), memoryview(F32[1]),
                memoryview(F32[2]), memoryview(FN.view(np.uint8)),
            )
            if not whole:
                # one scatter of the whole matrix, not five a path
                if all_active:
                    ix: tuple = (sp.fused_ix,)
                elif Pf == P:
                    ix = (slice(None), act_ix)
                else:
                    ix = (sp.fused_ix[:, None], act_ix)
                MT[ix] = FT
                MN[ix] = FN
                M32[(slice(None),) + ix] = F32

        for i in sp.rest if na else ():
            accessor = self._path_accessor(paths[i])
            if all_active:
                values = [accessor(plan.input) for plan in plans]
            else:
                values = [_MISSING_SENTINEL] * B
                for bi, plan in active:
                    values[bi] = accessor(plan.input)
            native.encode_column(
                values, interner.ids, _MISSING_SENTINEL, _ERR_SENTINEL,
                memoryview(MT[i].view(np.uint8)), memoryview(M32[0, i]), memoryview(M32[1, i]),
                memoryview(M32[2, i]), memoryview(MN[i].view(np.uint8)),
            )

        # fallback-tag oracle routing, once a flight on the whole matrix
        if len(sp.trig_lut):
            bad = sp.trig_lut[sp.trig_ix, MT.view(np.uint8)[sp.trig_rows]].any(axis=0)
            for bi in np.nonzero(bad)[0]:
                plan = plans[int(bi)]
                if not (plan.trivial or plan.oracle):
                    plan.oracle = True

        cb.tags.update(zip(paths, MT))
        cb.his.update(zip(paths, M32[0]))
        cb.los.update(zip(paths, M32[1]))
        cb.sids.update(zip(paths, M32[2]))
        cb.nans.update(zip(paths, MN))
        cb.scalars = (paths, M32, MT, MN)

    def _pred_key_accessors(self, spec):
        accs = self._pred_accessors.get(spec.pred_id)
        if accs is None:
            accs = [self._path_accessor(p) for p in spec.ref_paths]
            self._pred_accessors[spec.pred_id] = accs
        return accs

    def _eval_pred(self, spec, plan: InputPlan, params: T.EvalParams) -> tuple[bool, bool]:
        cache_key = None
        if not spec.time_dependent:
            try:
                vals = []
                for acc in self._pred_key_accessors(spec):
                    v = acc(plan.input)
                    # typed scalars pass through (True/1/1.0 must not
                    # collide); containers freeze
                    if v is None or type(v) in (str, bool, int, float):
                        vals.append((type(v), v) if type(v) in (bool, int, float) else v)
                    else:
                        vals.append(_freeze(v))
                cache_key = (spec.pred_id, tuple(vals))
            except TypeError:
                cache_key = None
        if cache_key is not None:
            hit = self._pred_cache.get(cache_key)
            if hit is not None:
                return hit
        request, principal, resource = build_request_messages(plan.input)
        ec = EvalContext(params, request, principal, resource)

        def act_factory(pparams):
            variables = ec.evaluate_variables(pparams.constants, pparams.ordered_variables)
            return ec.activation(pparams.constants, variables)

        result = evaluate_pred_host(spec, plan.input, act_factory)
        if cache_key is not None:
            self._pred_cache[cache_key] = result
        return result


class _Sentinel:
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


_MISSING_SENTINEL = _Sentinel("missing")
_ERR_SENTINEL = _Sentinel("err")


def _walk_view(view: dict, path: tuple[str, ...]):
    """Generic path walk distinguishing leaf-missing from intermediate
    failures (has() semantics — see condcompile TAG_ERR)."""
    cur: Any = view
    for i, seg in enumerate(path):
        if isinstance(cur, dict):
            if seg not in cur:
                return _MISSING_SENTINEL if i == len(path) - 1 else _ERR_SENTINEL
            cur = cur[seg]
        else:
            return _ERR_SENTINEL
    return cur


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _freeze(v: Any):
    """Hashable cache key preserving CEL type distinctions: True/1/1.0 are
    equal as Python dict keys but NOT as CEL values, so scalars carry a type
    tag at every nesting level."""
    if isinstance(v, (tuple, list)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((str(k), _freeze(x)) for k, x in v.items()))
    if isinstance(v, (bool, int, float)):
        return (type(v).__name__, v)
    return v
