"""Batched device evaluator: condition kernels + effect-resolution lattice.

The device computes ``sat_cond[B, C]`` (every distinct condition over every
input) and resolves effects as a masked reduction over
(policy-type, role-slot, scope-depth) — the reference's sequential
short-circuits (check.go:183-438) become "evaluate everything, select by
priority", which is sound because conditions are pure. The host then
assembles CheckOutputs, reconstructing policy attribution, outputs and
effective derived roles from the device's winning (pt, role, depth, j).

Sharding: the batch axis shards over a jax Mesh ("data" axis); candidate
tensors are batch-aligned so the same jit works single-chip or multi-chip
(see cerbos_tpu.parallel.mesh).
"""

from __future__ import annotations

import atexit
import contextlib
import logging
import math
import threading
import time
from typing import Any, Optional

import numpy as np

from .. import namer
from .. import native as native_mod
from ..engine import drainclock
from ..engine import types as T
from ..observability import metrics, start_span
from ..ruletable.check import EvalContext, build_request_messages, check_input
from ..ruletable.table import RuleTable
from ..schema import Tally
from . import compilestats
from .condcompile import Refs
from .lowering import (
    EFFECT_ALLOW_CODE,
    EFFECT_DENY_CODE,
    LoweredTable,
    SP_OVERRIDE,
    lower_table,
)
from .packer import LayoutClass, PackedBatch, Packer, PT_PRINCIPAL, PT_RESOURCE

_log = logging.getLogger("cerbos_tpu.evaluator")

# cerbos_tpu_assemble_memo_total{result}: what the assembly memo did for a device-served input
_MEMO_RESULTS = ("hit", "miss", "bypass_validation", "bypass_other")

def _clone_output(template: "T.CheckOutput", inp: "T.CheckInput") -> "T.CheckOutput":
    """Fresh CheckOutput from a memoized assembly (ids swapped). ActionEffect
    values are immutable once assembly returns (only the oracle mutates its
    own in-flight effects), so the clone shares them with the template.
    Built via __new__: the dataclass __init__'s default factories cost ~3x
    on this per-input path. Templates are only memoized when the table has
    no outputs and no validation errors, so those fields start empty."""
    out = T.CheckOutput.__new__(T.CheckOutput)
    out.request_id = inp.request_id
    out.resource_id = inp.resource.id
    out.actions = dict(template.actions)
    out.effective_derived_roles = list(template.effective_derived_roles)
    out.validation_errors = []
    out.outputs = []
    out.effective_policies = dict(template.effective_policies)
    return out


CODE_NO_MATCH = 0
CODE_ALLOW = 1
CODE_DENY = 2

_BIG = 127


def _scope(xp, name: str):
    """A stable name for a part of the device program (``jax.named_scope``:
    metadata on the traced operations, no operation of its own); nothing on
    the numpy path, which must not import jax."""
    if xp is np:
        return contextlib.nullcontext()
    import jax

    return jax.named_scope(name)


def _sat_groups(xp, compiler, B: int, refs, variant=None):
    """Condition satisfaction per TEMPLATE GROUP — one broadcast subgraph
    per distinct condition structure covers all its members at once (graph
    size is O(templates), not O(conditions)).

    With ``variant`` (a static tuple of
    ``(group_index, member_positions | None)``, None = every member) each
    group is restricted to the members the batch references, and the result
    is a COMPACT [B, A] matrix in variant (concat) order — device work is
    O(active conditions) even when the table holds thousands; the caller
    translates cond ids through its col_map. Without ``variant``, the full
    [B, C] matrix in cond-id order."""
    compiler.build_groups()
    C = len(compiler.kernels)
    if not C:
        return xp.zeros((B, 1), dtype=bool)
    if variant is not None:
        from .condcompile import subset_group_consts

        blocks = []
        for gi, sel in variant:
            g = compiler.groups[gi]
            if sel is None:
                blocks.append(xp.broadcast_to(g.emit(refs, g.gc), (B, g.gc.size)))
            else:
                sub = subset_group_consts(g.gc, sel)
                blocks.append(xp.broadcast_to(g.emit(refs, sub), (B, len(sel))))
        if not blocks:
            return xp.zeros((B, 1), dtype=bool)
        # COMPACT [B, A] in variant (concat) order — the caller translates
        # cond ids through its col_map; dead/unreferenced columns simply
        # don't exist here
        return xp.concatenate(blocks, axis=1)
    blocks = [
        xp.broadcast_to(g.emit(refs, g.gc), (B, g.gc.size))
        for g in compiler.groups
    ]
    if not blocks:
        return xp.zeros((B, C), dtype=bool)
    allsat = xp.concatenate(blocks, axis=1)
    sat_cond = allsat[:, compiler.perm]
    if compiler.dead.any():
        sat_cond = sat_cond & ~xp.asarray(compiler.dead)[None, :]
    return sat_cond


def _compute(
    xp,
    compiler,
    K: int,
    J: int,
    D: int,
    tags,
    his,
    los,
    sids,
    nans,
    pred_vals,
    pred_errs,
    ba_input,
    cand_cond,
    cand_drcond,
    cand_effect,
    cand_pt,
    cand_depth,
    cand_valid,
    scope_sp,
    list_sids=None,
    list_states=None,
    ts_his=None,
    ts_los=None,
    ts_states=None,
    now_hi=None,
    now_lo=None,
    variant=None,
):
    """Pure array computation: jittable with `xp=jnp`, testable with numpy.

    Returns (final [BA,4], role_results [BA,K,2,2], win_j [BA,K,2],
    sat_cond [B,C]) — see module docstring for the lattice.

    With ``variant`` (static group-member subsets — see _sat_groups), the
    sat matrix is compact over the referenced columns and the cand id
    arrays must already be remapped into that compact space.
    """
    refs = Refs(xp, tags, his, los, sids, nans, pred_vals, pred_errs,
                list_sids=list_sids, list_states=list_states,
                ts_his=ts_his, ts_los=ts_los, ts_states=ts_states,
                now_hi=now_hi, now_lo=now_lo)
    # scope_sp is always [B, 2, D]; column dicts can all be empty when the
    # policy set has only unconditional rules, so B must not come from them
    B = scope_sp.shape[0]
    with _scope(xp, "sat_groups"):
        sat_cond = _sat_groups(xp, compiler, B, refs, variant=variant)
    with _scope(xp, "lattice"):
        return _lattice(
            xp, K, J, D, sat_cond, ba_input, cand_cond, cand_drcond, cand_effect,
            cand_pt, cand_depth, cand_valid, scope_sp,
        )


def _lattice(
    xp, K: int, J: int, D: int, sat_cond, ba_input, cand_cond, cand_drcond, cand_effect,
    cand_pt, cand_depth, cand_valid, scope_sp,
):
    """Effect resolution over (policy type, role slot, scope depth): the
    second half of :func:`_compute`, named ``lattice`` in the device program."""
    BA = cand_cond.shape[0]
    sat_by_input = sat_cond[ba_input]  # [BA, C]

    ba_idx = xp.arange(BA)[:, None, None]
    cond_ok = cand_cond >= 0
    drcond_ok = cand_drcond >= 0
    cond_safe = xp.where(cond_ok, cand_cond, 0)
    drcond_safe = xp.where(drcond_ok, cand_drcond, 0)
    sat_c = xp.where(cond_ok, sat_by_input[ba_idx, cond_safe], True)
    sat_dr = xp.where(drcond_ok, sat_by_input[ba_idx, drcond_safe], True)
    sat = cand_valid & sat_c & sat_dr  # [BA, K, J]

    deny_mask = sat & (cand_effect == EFFECT_DENY_CODE)
    allow_mask = sat & (cand_effect == EFFECT_ALLOW_CODE)

    sp_by_ba = scope_sp[ba_input]  # [BA, 2, D]

    role_codes = []
    role_depths = []
    winjs = []
    for pt in (PT_PRINCIPAL, PT_RESOURCE):
        pt_mask = cand_pt == pt
        # per-depth any / first-j
        code = xp.zeros((BA, K), dtype=xp.int8)
        depth_out = xp.full((BA, K), D, dtype=xp.int8)
        wj = xp.full((BA, K), -1, dtype=xp.int8)
        decided = xp.zeros((BA, K), dtype=bool)
        for d in range(D):
            at_d = pt_mask & (cand_depth == d)
            deny_d = (deny_mask & at_d).any(axis=2)  # [BA, K]
            allow_d = (allow_mask & at_d).any(axis=2)
            sp_d = sp_by_ba[:, pt, d][:, None]  # [BA, 1]
            allow_ok = allow_d & (sp_d == SP_OVERRIDE)
            # first satisfied deny/allow j at this depth — the winning-rule
            # column (ISSUE 20) is this one extra min-reduction over the
            # already-computed activation masks, not a second pass
            j_idx = xp.arange(J)[None, None, :]
            deny_j = xp.where(deny_mask & at_d, j_idx, _BIG).min(axis=2)  # [BA, K]
            allow_j = xp.where(allow_mask & at_d, j_idx, _BIG).min(axis=2)
            newly_deny = ~decided & deny_d
            newly_allow = ~decided & ~deny_d & allow_ok
            code = xp.where(newly_deny, CODE_DENY, xp.where(newly_allow, CODE_ALLOW, code))
            depth_out = xp.where(newly_deny | newly_allow, d, depth_out)
            wj = xp.where(
                newly_deny,
                deny_j.astype(xp.int8),
                xp.where(newly_allow, allow_j.astype(xp.int8), wj),
            )
            decided = decided | newly_deny | newly_allow
        role_codes.append(code)
        role_depths.append(depth_out)
        winjs.append(wj)

    role_results = xp.stack(
        [xp.stack([role_codes[0], role_depths[0]], axis=-1), xp.stack([role_codes[1], role_depths[1]], axis=-1)],
        axis=2,
    )  # [BA, K, 2(pt), 2(code,depth)]
    win_j = xp.stack(winjs, axis=2)  # [BA, K, 2]

    # merge roles within each policy type:
    #   first role with ALLOW wins; else first role with any non-NO_MATCH
    def merge(codes, depths, wjs, single_role: bool):
        if single_role:
            return codes[:, 0], depths[:, 0], wjs[:, 0], xp.zeros(codes.shape[0], dtype=xp.int8)
        k_idx = xp.arange(K)[None, :]
        allow_k = xp.where(codes == CODE_ALLOW, k_idx, _BIG).min(axis=1)
        nonmatch_k = xp.where(codes != CODE_NO_MATCH, k_idx, _BIG).min(axis=1)
        pick = xp.where(allow_k < _BIG, allow_k, xp.where(nonmatch_k < _BIG, nonmatch_k, 0))
        pick = pick.astype(xp.int32)
        rows = xp.arange(codes.shape[0])
        return codes[rows, pick], depths[rows, pick], wjs[rows, pick], pick.astype(xp.int8)

    p_code, p_depth, p_wj, p_k = merge(role_codes[0], role_depths[0], winjs[0], single_role=True)
    r_code, r_depth, r_wj, r_k = merge(role_codes[1], role_depths[1], winjs[1], single_role=False)

    use_p = p_code != CODE_NO_MATCH
    f_code = xp.where(use_p, p_code, r_code)
    f_pt = xp.where(use_p, PT_PRINCIPAL, PT_RESOURCE).astype(xp.int8)
    f_depth = xp.where(use_p, p_depth, r_depth)
    f_k = xp.where(use_p, p_k, r_k)
    final = xp.stack([f_code.astype(xp.int8), f_pt, f_depth.astype(xp.int8), f_k], axis=1)

    return final, role_results, win_j, sat_cond


def _next_bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class _StackLayout:
    """Static description of how column families were stacked for transfer.

    ``sig`` participates in the jit-cache key: two batches share a trace only
    when the path orders, list widths and presence flags line up."""

    __slots__ = ("paths", "ts_paths", "list_paths", "list_widths", "pred_ids",
                 "D", "has_now", "sig", "cuts")

    def __init__(self, paths, ts_paths, list_paths, list_widths, pred_ids, D, has_now):
        self.paths = paths
        self.ts_paths = ts_paths
        self.list_paths = list_paths
        self.list_widths = list_widths
        self.pred_ids = pred_ids
        self.D = D
        self.has_now = has_now
        self.sig = (paths, ts_paths, list_paths, list_widths, pred_ids, D, has_now)
        self.cuts: dict = {}  # (B_pad, BA_pad, K, J) -> _TransferCut: see _TransferCut.of


class _TransferCut:
    """Where the sections of a flight's ONE staging buffer lie.

    The buffer is a vector of int32 words, and its length and its cut follow
    from the jit key alone (``B_pad``, ``BA_pad``, ``K``, ``J`` and the
    layout), so the flight that fills it, the trace that cuts it and the
    preloader that builds a zero one from a manifest entry agree with no word
    passed between them. The int32 sections come first and are plain slices,
    on the host and in the trace; the one-byte sections follow, each a whole
    number of words because ``B_pad`` is (a bucket is a power of two from 16
    up), so every host view is aligned and nothing lies between sections."""

    __slots__ = ("lay", "B_pad", "BA_pad", "K", "J", "sections", "words")

    def __init__(self, lay: _StackLayout, B_pad: int, BA_pad: int, K: int, J: int):
        if B_pad % 4:
            raise ValueError(f"B_pad {B_pad} is not a whole number of words")
        self.lay = lay
        self.B_pad, self.BA_pad, self.K, self.J = B_pad, BA_pad, K, J
        P, Tn, L, Q = len(lay.paths), len(lay.ts_paths), len(lay.list_paths), len(lay.pred_ids)
        at = 0
        self.sections = {}
        for name, dtype, shape in (
            ("i32_cols", np.int32, (3 * P + 2 * Tn, B_pad)),
            ("lists", np.int32, (L, B_pad, max(lay.list_widths, default=1))),
            ("cand_i32", np.int32, (2, BA_pad, K, J)),
            ("ba_input", np.int32, (BA_pad,)),
            ("now", np.int32, (2,)),
            ("i8_cols", np.int8, (P + Tn + L + 2 * lay.D, B_pad)),
            ("cand_i8", np.int8, (4, BA_pad, K, J)),
            ("bool_cols", np.bool_, (P + 2 * Q, B_pad)),
        ):
            dtype = np.dtype(dtype)
            n = math.prod(shape) * dtype.itemsize // 4
            self.sections[name] = (at, at + n, dtype, shape)
            at += n
        self.words = at

    @classmethod
    def of(cls, lay: _StackLayout, B_pad: int, BA_pad: int, K: int, J: int) -> "_TransferCut":
        """The layout's cut for one shape bucket, made once (layouts are
        memoized, and a flight meets a handful of buckets)."""
        key = (B_pad, BA_pad, K, J)
        cut = lay.cuts.get(key)
        if cut is None:
            cut = lay.cuts[key] = cls(lay, *key)
        return cut

    @property
    def sig(self):
        return self.lay.sig

    def split(self, xp, buf) -> dict:
        """The typed sections of ``buf``: host views to fill with ``xp`` numpy,
        and inside the trace static slices and ``lax.bitcast_convert_type``
        (free: XLA fuses them into the consumers, as it does the row slices
        that follow)."""
        if xp is np:
            return {name: buf[lo:hi].view(dtype).reshape(shape) for name, (lo, hi, dtype, shape) in self.sections.items()}
        from jax import lax

        out = {}
        for name, (lo, hi, dtype, shape) in self.sections.items():
            words = buf[lo:hi]
            if dtype.itemsize == 1 and hi > lo:
                words = lax.bitcast_convert_type(words, xp.int8)  # (words, 4) int8, low byte first
            out[name] = words.reshape(shape).astype(dtype)
        return out


def _unstack_padded(xp, cut: _TransferCut, kw: dict) -> dict:
    """Inverse of _pad_stack, executed INSIDE the traced graph (slices of
    traced arrays are free — XLA fuses them into the consumers)."""
    lay = cut.lay
    sec = cut.split(xp, kw["buf"])
    i32 = sec["i32_cols"]
    i8 = sec["i8_cols"]
    bools = sec["bool_cols"]
    lists = sec["lists"]
    cand_i32 = sec["cand_i32"]
    cand_i8 = sec["cand_i8"]
    P = len(lay.paths)
    T = len(lay.ts_paths)
    L = len(lay.list_paths)
    his = {p: i32[i] for i, p in enumerate(lay.paths)}
    los = {p: i32[P + i] for i, p in enumerate(lay.paths)}
    sids = {p: i32[2 * P + i] for i, p in enumerate(lay.paths)}
    ts_his = {p: i32[3 * P + i] for i, p in enumerate(lay.ts_paths)}
    ts_los = {p: i32[3 * P + T + i] for i, p in enumerate(lay.ts_paths)}
    tags = {p: i8[i] for i, p in enumerate(lay.paths)}
    ts_states = {p: i8[P + i] for i, p in enumerate(lay.ts_paths)}
    list_states = {p: i8[P + T + i] for i, p in enumerate(lay.list_paths)}
    B = i8.shape[1]
    scope_sp = i8[P + T + L :].reshape(2, lay.D, B).transpose(2, 0, 1)
    nans = {p: bools[i] for i, p in enumerate(lay.paths)}
    Q = len(lay.pred_ids)
    pred_vals = {q: bools[P + i] for i, q in enumerate(lay.pred_ids)}
    pred_errs = {q: bools[P + Q + i] for i, q in enumerate(lay.pred_ids)}
    list_sids = {
        p: lists[i][:, : lay.list_widths[i]] for i, p in enumerate(lay.list_paths)
    }
    now_hi = sec["now"][0] if lay.has_now else None
    now_lo = sec["now"][1] if lay.has_now else None
    return dict(
        tags=tags, his=his, los=los, sids=sids, nans=nans,
        pred_vals=pred_vals, pred_errs=pred_errs,
        ba_input=sec["ba_input"],
        cand_cond=cand_i32[0], cand_drcond=cand_i32[1],
        cand_effect=cand_i8[0], cand_pt=cand_i8[1], cand_depth=cand_i8[2],
        cand_valid=cand_i8[3].astype(bool),
        scope_sp=scope_sp,
        list_sids=list_sids, list_states=list_states,
        ts_his=ts_his, ts_los=ts_los, ts_states=ts_states,
        now_hi=now_hi, now_lo=now_lo,
    )


def _variant_remap(variant, compiler, C, cand_cond, cand_drcond):
    """col_map + compact-space remap of the candidate id arrays for one
    group-member variant. Single source of truth for both the primary
    variant and the budget-fallback full variant."""
    cols_parts = []
    for gi, sel in variant:
        g = compiler.groups[gi]
        if sel is None:
            cols_parts.append(g.cond_id_arr)
        else:
            cols_parts.append(g.cond_id_arr[np.asarray(sel, dtype=np.int64)])
    colcat = np.concatenate(cols_parts) if cols_parts else np.zeros(0, dtype=np.int64)
    A = int(colcat.size)
    col_map = np.full(max(C, 1), -1, dtype=np.int64)
    if A:
        col_map[colcat] = np.arange(A, dtype=np.int64)
        safe = np.clip(cand_cond, 0, max(C - 1, 0))
        cand_cond_c = np.where(cand_cond >= 0, col_map[safe], -1).astype(np.int32)
        safe = np.clip(cand_drcond, 0, max(C - 1, 0))
        cand_drcond_c = np.where(cand_drcond >= 0, col_map[safe], -1).astype(np.int32)
    else:
        cand_cond_c = np.full_like(cand_cond, -1)
        cand_drcond_c = np.full_like(cand_drcond, -1)
    return col_map, cand_cond_c, cand_drcond_c


def _zero_result(B: int, K: int, C: int):
    return (
        np.zeros((0, 4), dtype=np.int8),
        np.zeros((0, K, 2, 2), dtype=np.int8),
        np.zeros((0, K, 2), dtype=np.int8),
        np.zeros((B, 1), dtype=bool),
        np.full(max(C, 1), -1, dtype=np.int64),
    )


def _active_variant(lt: LoweredTable, batch: PackedBatch):
    """Group-member variant for one batch: per template group, the members
    the batch references (None = all of them). Active columns are the
    candidates + synthetic denies (both live in the cand arrays) plus every
    derived-role condition (host assembly reads those off sat regardless of
    candidates). Static structure — the jit cache keys on it; the numpy
    path just iterates it."""
    compiler = lt.compiler
    C = len(compiler.kernels)
    active = np.zeros(max(C, 1), dtype=bool)
    for arr in (batch.cand_cond, batch.cand_drcond):
        ids = arr[arr >= 0]
        if ids.size:
            active[ids] = True
    if lt.dr_cond_id_arr.size:
        active[lt.dr_cond_id_arr] = True
    variant: list[tuple[int, Optional[tuple[int, ...]]]] = []
    for gi, g in enumerate(compiler.groups):
        mask = active[g.cond_id_arr]
        if mask.all():
            variant.append((gi, None))
        elif mask.any():
            variant.append((gi, tuple(int(i) for i in np.nonzero(mask)[0])))
    return tuple(variant)


def _select_variant(lt: LoweredTable, batch: PackedBatch, jit_cache: dict):
    """Pick the (static) group-member variant for a jitted evaluation.

    Small tables ride one full-variant trace per shape bucket: computing
    every condition costs microseconds on device, while every distinct
    member subset is a separate trace — a fresh multi-second XLA compile
    and a persistent-cache miss. Large tables keep the O(active) compact
    variants, with a budget of DISTINCT VARIANTS (not cache entries:
    shape-bucket churn must not evict sparse variants that are already
    compiled); past the budget, new subsets ride the full variant."""
    compiler = lt.compiler
    C = len(compiler.kernels)
    full_variant = tuple((gi, None) for gi in range(len(compiler.groups)))
    if C <= 256:
        return full_variant
    variant_key = _active_variant(lt, batch)
    seen_variants = jit_cache.setdefault(("_variant_budget",), set())
    if (
        variant_key != full_variant
        and variant_key not in seen_variants
        and len(seen_variants) >= 32
    ):
        compilestats.stats().record_variant_fallback()
        return full_variant
    seen_variants.add(variant_key)
    return variant_key


def _host_or_mesh_eval(lt: LoweredTable, batch: PackedBatch, mesh, jit_cache: dict):
    """Run the condition kernels + lattice SYNCHRONOUSLY, off the
    single-device jit path (that one is _device_dispatch/_device_finalize),
    returning ``(final, role_results, win_j, sat_arr, col_map)``.

    ``sat_arr`` is COMPACT: [B, A] over only the condition columns this
    batch references (candidates, synthetic denies, derived-role
    conditions); ``col_map`` [C] maps cond_id -> compact column (-1 for
    columns not computed — assembly never reads those by construction).
    Keeping sat compact makes device and host work O(active conditions)
    even when the table holds thousands.

    With ``mesh`` None, numpy on the host (with the native lattice where the
    extension has it). With one, a shape-bucketed ``jax.jit`` cache whose
    key includes the group-member subset (static trace structure):
    batch-axis arrays are placed with a NamedSharding over the mesh's "data"
    axis (padded bucket sizes are powers of two >=16, so they divide evenly
    over 2/4/8-device meshes) and XLA partitions the computation across
    devices.
    """
    compiler = lt.compiler
    K, J, D = batch.K, batch.J, batch.D
    BA = batch.cand_cond.shape[0]
    B = batch.columns.size

    compiler.build_groups()
    C = len(compiler.kernels)

    if BA == 0:
        return _zero_result(B, K, C)

    if mesh is not None:
        # decide the (static trace structure) variant BEFORE remapping /
        # padding / sharding so those all see the final choice
        variant_key = _select_variant(lt, batch, jit_cache)
    else:
        # the numpy path pays no compile cost: always evaluate compactly
        # over just the columns this batch references
        variant_key = _active_variant(lt, batch)

    # remap candidate cond ids into compact columns (-1 preserved); by the
    # active-set construction every referenced id has a compact column
    col_map, cand_cond_c, cand_drcond_c = _variant_remap(
        variant_key, compiler, C, batch.cand_cond, batch.cand_drcond
    )
    cols = batch.columns

    if mesh is None:
        native = native_mod.get()
        if native is not None and hasattr(native, "resolve_effects"):
            # fused C lattice: sat via the template groups as usual, then one
            # memory pass replaces ~40 small-array numpy kernels
            refs = Refs(np, cols.tags, cols.his, cols.los, cols.sids, cols.nans,
                        cols.pred_vals, cols.pred_errs,
                        list_sids=cols.list_sids, list_states=cols.list_states,
                        ts_his=cols.ts_his, ts_los=cols.ts_los, ts_states=cols.ts_states,
                        now_hi=cols.now_hi, now_lo=cols.now_lo)
            sat_arr = np.ascontiguousarray(
                _sat_groups(np, compiler, B, refs, variant=variant_key), dtype=bool
            )
            final = np.empty((BA, 4), dtype=np.int8)
            role_results = np.empty((BA, K, 2, 2), dtype=np.int8)
            win_j = np.empty((BA, K, 2), dtype=np.int8)
            native.resolve_effects(
                BA, K, J, D, sat_arr.shape[1],
                np.ascontiguousarray(batch.ba_input, dtype=np.int32),
                np.ascontiguousarray(cand_cond_c, dtype=np.int32),
                np.ascontiguousarray(cand_drcond_c, dtype=np.int32),
                np.ascontiguousarray(batch.cand_effect, dtype=np.int8),
                np.ascontiguousarray(batch.cand_pt, dtype=np.int8),
                np.ascontiguousarray(batch.cand_depth, dtype=np.int8),
                np.ascontiguousarray(batch.cand_valid, dtype=bool),
                np.ascontiguousarray(batch.scope_sp, dtype=np.int8),
                sat_arr,
                EFFECT_ALLOW_CODE, EFFECT_DENY_CODE, SP_OVERRIDE,
                memoryview(final), memoryview(role_results), memoryview(win_j),
            )
            return final, role_results, win_j, sat_arr, col_map

        final, role_results, win_j, sat_arr = _compute(
            np, compiler, K, J, D,
            tags=cols.tags, his=cols.his, los=cols.los, sids=cols.sids, nans=cols.nans,
            pred_vals=cols.pred_vals, pred_errs=cols.pred_errs,
            ba_input=batch.ba_input, cand_cond=cand_cond_c, cand_drcond=cand_drcond_c,
            cand_effect=batch.cand_effect, cand_pt=batch.cand_pt, cand_depth=batch.cand_depth,
            cand_valid=batch.cand_valid, scope_sp=batch.scope_sp,
            list_sids=cols.list_sids, list_states=cols.list_states,
            ts_his=cols.ts_his, ts_los=cols.ts_los, ts_states=cols.ts_states,
            now_hi=cols.now_hi, now_lo=cols.now_lo,
            variant=variant_key,
        )
        return (
            np.asarray(final), np.asarray(role_results), np.asarray(win_j),
            np.asarray(sat_arr), col_map,
        )

    import jax
    import jax.numpy as jnp

    # per-path arrays shard independently over the mesh's batch axis;
    # transfer fusion doesn't apply (and would fight the shardings), so
    # call _compute directly
    from ..parallel.mesh import shard_packed_arrays

    B_pad = _next_bucket(B)
    BA_pad = _next_bucket(BA)
    padded = shard_packed_arrays(
        _pad_arrays(batch, cols, cand_cond_c, cand_drcond_c, B_pad, BA_pad), mesh
    )
    key = (B_pad, BA_pad, K, J, D, variant_key)
    fn = jit_cache.get(key)
    if fn is None:
        vt = variant_key  # bind the static variant into the trace
        fn = jax.jit(lambda **kw: _compute(jnp, compiler, K, J, D, variant=vt, **kw))
        jit_cache[key] = fn
        compilestats.stats().record_miss()
        # the first call runs trace + XLA compile synchronously
        final, role_results, win_j, sat_arr = compilestats.timed_first_call(
            f"B{B_pad}xBA{BA_pad}", fn, padded, trace_key=key
        )
    else:
        compilestats.stats().record_hit()
        final, role_results, win_j, sat_arr = fn(**padded)
    return (
        np.asarray(final)[:BA],
        np.asarray(role_results)[:BA],
        np.asarray(win_j)[:BA],
        np.asarray(sat_arr)[:B],
        col_map,
    )


def _pad_arrays(batch: PackedBatch, cols, cand_cond_c, cand_drcond_c, B_pad: int, BA_pad: int) -> dict:
    """Pad every batch-axis array to its shape bucket so jit traces are
    reused across batches."""

    def pad_b(a: np.ndarray) -> np.ndarray:
        if a.shape[0] == B_pad:
            return a
        return np.concatenate([a, np.zeros((B_pad - a.shape[0],) + a.shape[1:], dtype=a.dtype)])

    def pad_ba(a: np.ndarray, fill=0) -> np.ndarray:
        if a.shape[0] == BA_pad:
            return a
        pad = np.full((BA_pad - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
        return np.concatenate([a, pad])

    return dict(
        list_sids={p: pad_b(a) for p, a in cols.list_sids.items()},
        list_states={p: pad_b(a) for p, a in cols.list_states.items()},
        ts_his={p: pad_b(a) for p, a in cols.ts_his.items()},
        ts_los={p: pad_b(a) for p, a in cols.ts_los.items()},
        ts_states={p: pad_b(a) for p, a in cols.ts_states.items()},
        now_hi=cols.now_hi,
        now_lo=cols.now_lo,
        tags={p: pad_b(a) for p, a in cols.tags.items()},
        his={p: pad_b(a) for p, a in cols.his.items()},
        los={p: pad_b(a) for p, a in cols.los.items()},
        sids={p: pad_b(a) for p, a in cols.sids.items()},
        nans={p: pad_b(a) for p, a in cols.nans.items()},
        pred_vals={i: pad_b(a) for i, a in cols.pred_vals.items()},
        pred_errs={i: pad_b(a) for i, a in cols.pred_errs.items()},
        ba_input=pad_ba(batch.ba_input),
        cand_cond=pad_ba(cand_cond_c, -1),
        cand_drcond=pad_ba(cand_drcond_c, -1),
        cand_effect=pad_ba(batch.cand_effect),
        cand_pt=pad_ba(batch.cand_pt),
        cand_depth=pad_ba(batch.cand_depth, -1),
        cand_valid=pad_ba(batch.cand_valid),
        scope_sp=pad_b(batch.scope_sp),
    )


class _BufferPool:
    """Bounded free-lists of host staging buffers keyed by (shape, dtype).

    A device batch is laid into ONE such buffer (:func:`_pad_stack`), whose
    length follows from the batch's jit key, so batches of one layout need
    byte-identical buffers: recycle them instead of reallocating. A buffer
    is leased at dispatch and released at finalize — by then the single
    output fetch has completed, so the host->device transfer that read the
    buffer is done (and outputs never alias inputs: nothing is donated)."""

    MAX_FREE = 4  # per key: bounds idle memory at ~one in-flight window

    def __init__(self):
        self._free: dict = {}
        self._lock = threading.Lock()

    def lease(self, shape, dtype) -> np.ndarray:
        key = (shape, np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            if free:
                return free.pop()
        return np.empty(shape, dtype=dtype)

    def release(self, arrs) -> None:
        with self._lock:
            for a in arrs:
                free = self._free.setdefault((a.shape, a.dtype.str), [])
                if len(free) < self.MAX_FREE:
                    free.append(a)


_buffer_pool = _BufferPool()

_layout_memo: dict = {}


def _marshal_layout(cols, scope_D: int, has_now: bool) -> _StackLayout:
    """Memoized _StackLayout marshalling: the sorted row orders only depend
    on which columns the packer emitted, so key on the raw insertion-order
    key tuples — cheap to build — and sort once per distinct signature."""
    raw = (
        tuple(cols.tags), tuple(cols.ts_his), tuple(cols.list_sids),
        tuple(int(a.shape[1]) for a in cols.list_sids.values()),
        tuple(cols.pred_vals), scope_D, has_now,
    )
    lay = _layout_memo.get(raw)
    if lay is None:
        if len(_layout_memo) > 512:
            _layout_memo.clear()
        list_paths = tuple(sorted(cols.list_sids))
        lay = _StackLayout(
            tuple(sorted(cols.tags)),
            tuple(sorted(cols.ts_his)),
            list_paths,
            tuple(int(cols.list_sids[p].shape[1]) for p in list_paths),
            tuple(sorted(cols.pred_vals)),
            scope_D,
            has_now,
        )
        _layout_memo[raw] = lay
    return lay


def _fill_rows(dst: np.ndarray, rows: list, native) -> None:
    """Copy unpadded rows into the leading slots of dst's row stride,
    zeroing each padded tail. Rows pad along their leading axis, so for
    contiguous byte-compatible arrays this is a prefix memcpy + tail memset
    — one native call per column family instead of a Python loop."""
    if native is not None and all(
        r.flags["C_CONTIGUOUS"]
        and (r.dtype == dst.dtype or (dst.dtype == np.int8 and r.dtype == np.bool_))
        for r in rows
    ):
        try:
            native.stack_pad_rows(dst, rows)
            return
        except Exception:  # noqa: BLE001  (fall through to numpy)
            pass
    for i, r in enumerate(rows):
        nv = r.shape[0]
        dst[i, :nv] = r
        dst[i, nv:] = 0


def _fill_block(dst: np.ndarray, block: np.ndarray) -> None:
    """Copy a whole ``(..., P, B)`` matrix into the leading columns of dst's
    ``(..., P, B_pad)`` rows and zero the padded tails: what :func:`_fill_rows`
    does for ``P`` rows one by one, in two stores."""
    B = block.shape[-1]
    dst[..., :B] = block
    if B < dst.shape[-1]:
        dst[..., B:] = 0


def _pad_stack(batch: PackedBatch, cols, cand_cond_c, cand_drcond_c, B_pad: int, BA_pad: int):
    """The transfer format of the single-device path: every column padded to
    its shape bucket (the fills of _pad_arrays) and laid, section by section,
    into ONE pooled staging buffer, so a device dispatch costs one
    host->device transfer (see _device_dispatch); _unstack_padded cuts the
    sections back out inside the trace.

    Each column's bytes are written exactly once, straight into typed views
    of the buffer (:class:`_TransferCut`). Where the packer kept its scalar
    matrices and their rows are in the layout's path order, the five scalar
    families are five block copies; else the rows go one by one. Returns
    (stacked, cut, leased); hand ``leased`` back to ``_buffer_pool`` once the
    device is done with the batch (see _device_finalize)."""
    native = native_mod.get()
    if native is not None and not hasattr(native, "stack_pad_rows"):
        native = None
    has_now = cols.now_hi is not None
    D = batch.scope_sp.shape[2]
    B = batch.scope_sp.shape[0]
    lay = _marshal_layout(cols, D, has_now)
    P, Tn, L = len(lay.paths), len(lay.ts_paths), len(lay.list_paths)
    cut = _TransferCut.of(lay, B_pad, BA_pad, *cand_cond_c.shape[1:])
    buf = _buffer_pool.lease((cut.words,), np.int32)
    sec = cut.split(np, buf)
    scalars = cols.scalars if cols.scalars is not None and cols.scalars[0] == lay.paths else None

    i32_cols = sec["i32_cols"]
    rows = [cols.ts_his[p] for p in lay.ts_paths] + [cols.ts_los[p] for p in lay.ts_paths]
    if scalars is not None:
        _fill_block(i32_cols[: 3 * P].reshape(3, P, B_pad), scalars[1])
        i32_cols = i32_cols[3 * P :]
    else:
        rows = (
            [cols.his[p] for p in lay.paths]
            + [cols.los[p] for p in lay.paths]
            + [cols.sids[p] for p in lay.paths]
            + rows
        )
    if rows:
        _fill_rows(i32_cols, rows, native)

    i8_cols = sec["i8_cols"]
    if D:
        sp = i8_cols[P + Tn + L :]
        sp[:, :B] = batch.scope_sp.transpose(1, 2, 0).reshape(2 * D, B)
        sp[:, B:] = 0
    i8_cols = i8_cols[: P + Tn + L]
    rows = [cols.ts_states[p] for p in lay.ts_paths] + [cols.list_states[p] for p in lay.list_paths]
    if scalars is not None:
        _fill_block(i8_cols[:P], scalars[2])
        i8_cols = i8_cols[P:]
    else:
        rows = [cols.tags[p] for p in lay.paths] + rows
    if rows:
        _fill_rows(i8_cols, rows, native)

    bool_cols = sec["bool_cols"]
    rows = [cols.pred_vals[q] for q in lay.pred_ids] + [cols.pred_errs[q] for q in lay.pred_ids]
    if scalars is not None:
        _fill_block(bool_cols[:P], scalars[3])
        bool_cols = bool_cols[P:]
    else:
        rows = [cols.nans[p] for p in lay.paths] + rows
    if rows:
        _fill_rows(bool_cols, rows, native)

    lists = sec["lists"]
    wmax = lists.shape[2]
    for i, p in enumerate(lay.list_paths):
        a = cols.list_sids[p]
        nb, w = a.shape
        lists[i, :nb, :w] = a
        if w < wmax:
            lists[i, :nb, w:] = 0
        if nb < B_pad:
            lists[i, nb:] = 0

    BA = cand_cond_c.shape[0]
    cand_i32 = sec["cand_i32"]
    cand_i32[0, :BA] = cand_cond_c
    cand_i32[1, :BA] = cand_drcond_c
    cand_i32[:, BA:] = -1  # pad_ba fill for cond ids
    cand_i8 = sec["cand_i8"]
    cand_i8[0, :BA] = batch.cand_effect
    cand_i8[1, :BA] = batch.cand_pt
    cand_i8[2, :BA] = batch.cand_depth
    cand_i8[3, :BA] = batch.cand_valid
    cand_i8[:, BA:] = 0
    cand_i8[2, BA:] = -1  # pad_ba fill for depth

    ba_input = sec["ba_input"]
    ba_input[:BA] = batch.ba_input
    ba_input[BA:] = 0

    sec["now"][:] = (int(cols.now_hi), int(cols.now_lo)) if has_now else 0
    return {"buf": buf}, cut, [buf]


class _DeviceHandle:
    """An in-flight device batch: the queued output array (device->host copy
    already started) plus everything needed to slice results back apart.
    ``ready`` short-circuits degenerate batches that never touch the device."""

    __slots__ = ("ready", "out", "BA", "B", "K", "BA_pad", "B_pad", "col_map", "leased", "puts", "put_bytes",
                 "fetch_bytes")

    def __init__(self):
        self.ready = None
        self.out = None
        self.leased = ()
        self.puts = 0         # how many arrays the jitted call was handed: the one staging buffer
        self.put_bytes = 0    # and their bytes, padding included
        self.fetch_bytes = 0  # the one result vector, once _device_finalize has fetched it


def _jit_run(compiler, D: int, variant, cut: _TransferCut):
    """The jitted device program of one layout: everything static in the jit
    key bound into the trace, the staging buffer's cut included. The one
    spelling for a flight that meets a new layout (:func:`_device_dispatch`)
    and for the preloader that builds it ahead of traffic, so both ask XLA
    for the same program."""
    import jax
    import jax.numpy as jnp

    K, J, BA_pad = cut.K, cut.J, cut.BA_pad

    def run(**kw):
        with jax.named_scope("unstack"):
            parts = _unstack_padded(jnp, cut, kw)
        final, role_results, win_j, sat_arr = _compute(
            jnp, compiler, K, J, D, variant=variant, **parts
        )
        with jax.named_scope("pack_result"):
            out = jnp.concatenate(
                [
                    final.reshape(BA_pad, -1).astype(jnp.int8),
                    role_results.reshape(BA_pad, -1).astype(jnp.int8),
                    win_j.reshape(BA_pad, -1).astype(jnp.int8),
                ],
                axis=1,
            )
            return jnp.concatenate(
                [out.ravel(), sat_arr.astype(jnp.int8).ravel()]
            )

    return jax.jit(run)


def _manifest_entry(key: tuple, lay: _StackLayout) -> dict:
    """What the layout manifest keeps of one jit key: enough to build the
    key, the layout and with them the staging buffer's cut (so a zero buffer
    of the right length) again without a batch."""
    B_pad, BA_pad, K, J, D, variant, _sig = key
    return {
        "shape": [B_pad, BA_pad],
        "depth": [K, J, D],
        "variant": [[gi, None if sel is None else list(sel)] for gi, sel in variant],
        "layout": {
            "paths": [list(p) for p in lay.paths],
            "ts_paths": [list(p) for p in lay.ts_paths],
            "list_paths": [list(p) for p in lay.list_paths],
            "list_widths": list(lay.list_widths),
            "pred_ids": list(lay.pred_ids),
            "D": lay.D,
            "has_now": lay.has_now,
        },
    }


def _entry_parts(entry: dict):
    """Inverse of :func:`_manifest_entry`: ``(key, cut, zero arguments)``.
    Raises on an entry that does not have the form (the preloader counts it
    as failed)."""
    B_pad, BA_pad = (int(x) for x in entry["shape"])
    K, J, D = (int(x) for x in entry["depth"])
    variant = tuple(
        (int(gi), None if sel is None else tuple(int(i) for i in sel)) for gi, sel in entry["variant"]
    )
    lay_doc = entry["layout"]
    lay = _StackLayout(
        tuple(tuple(p) for p in lay_doc["paths"]),
        tuple(tuple(p) for p in lay_doc["ts_paths"]),
        tuple(tuple(p) for p in lay_doc["list_paths"]),
        tuple(int(w) for w in lay_doc["list_widths"]),
        tuple(lay_doc["pred_ids"]),
        int(lay_doc["D"]),
        bool(lay_doc["has_now"]),
    )
    cut = _TransferCut(lay, B_pad, BA_pad, K, J)
    return (B_pad, BA_pad, K, J, D, variant, lay.sig), cut, {"buf": np.zeros(cut.words, dtype=np.int32)}


class _LayoutPreloader:
    """Loads the layouts this table's traffic is known to meet, ahead of it.

    One per evaluator. The first layout one of its flights has to build (a
    process's first device flight always builds one) starts ONE daemon
    thread, once that layout's key is in the jit cache, which walks the
    layout manifest's entries for this
    table's identity (:mod:`layoutmanifest`), most-met first, and for each
    key the jit cache does not hold builds the function a flight would build
    (:func:`_jit_run`), calls it once with zero-filled arguments of the
    recorded shapes (its own arrays, never the buffer pool's), waits for the
    result, discards it and publishes the function in the evaluator's jit
    cache. Nothing starts at boot, before a fork, or in a process that never
    dispatches: a mix the oracle serves never reads the manifest. The
    interpreter's exit ends the walk and waits for the entry in hand
    (:meth:`close`).

    A flight that meets a key the walk has not reached builds it itself, as
    it always did, and files it (:meth:`met`); whichever of the two finishes
    second finds the key held and drops its copy. ``stop()`` (the
    evaluator's ``invalidate()``, so every cutover) ends the walk: the
    generation is checked under the lock that publishes, so a function built
    for one table is never published once another is in place. It also arms
    the preloader again: the new table's first device flight starts a walk
    of the entries filed under ITS identity, once the old walk has let go of
    the entry it had in hand.

    The manifest also holds the table's layout class (``packer.LayoutClass``),
    and :meth:`restore` hands it to the packer ahead of the table's first
    device-route pack: a file read, no load and no compile. Not where the
    evaluator is built: that may be a pool's parent, before the fork and
    before any process has opened the device whose kind the manifest's scope
    names. The walk skips an entry of another class than the table's own: no
    flight will ask for it.
    """

    def __init__(self, evaluator: "TpuEvaluator"):
        self._ev = evaluator
        self._lock = threading.Lock()
        self._gen = 0
        self._started = False
        self.thread: Optional[threading.Thread] = None

    def flight(self) -> None:
        """Called by every flight that has to build a layout, once its key is
        in the jit cache; a table's first call starts the walk."""
        if self._started:
            return
        self._started = True
        from . import layoutmanifest

        if layoutmanifest.path() is None:
            return
        self.thread = threading.Thread(
            target=self._walk, args=(self._gen, self.thread), name="xla-preload", daemon=True
        )
        # a daemon thread that the interpreter's exit finds inside XLA is torn
        # down with it and aborts the process: an exit waits for the entry in hand
        atexit.register(self.close)
        self.thread.start()

    def stop(self) -> None:
        with self._lock:
            self._gen += 1
            self._started = False

    def restore(self) -> None:
        """Ahead of a table's first device-route pack: its packer starts at
        the class the manifest holds for it. Once a table's life."""
        cls = self._ev.packer.layout_class
        if not cls.restored:
            cls.restore(self._filed_class)

    def _filed_class(self) -> Optional[tuple]:
        from . import layoutmanifest

        if layoutmanifest.path() is None:
            return None
        try:
            scope = self._scope()
            return layoutmanifest.layout_class(scope) if scope is not None else None
        except Exception:  # noqa: BLE001  (the manifest is never worth a request)
            _log.debug("layout manifest: class not restored", exc_info=True)
            return None

    def close(self) -> None:
        """End the walk and wait for the entry it has in hand."""
        self.stop()
        if self.thread is not None and self.thread is not threading.current_thread():
            self.thread.join()

    def _scope(self) -> Optional[str]:
        import jax

        from ..engine.rollout import bundle_hash_of
        from . import layoutmanifest

        identity = bundle_hash_of(self._ev.rule_table)
        if not identity:
            return None
        dev = self._ev.device if self._ev.device is not None else jax.devices()[0]
        return layoutmanifest.scope(identity, dev.device_kind)

    def met(self, key: tuple, lay: _StackLayout) -> None:
        """A flight built ``key`` itself: file it for the next process."""
        from . import layoutmanifest

        if layoutmanifest.path() is None:
            return
        try:
            scope = self._scope()
            if scope is not None:
                layoutmanifest.record(scope, _manifest_entry(key, lay))
        except Exception:  # noqa: BLE001  (the manifest is never worth a request)
            _log.debug("layout manifest: entry not recorded", exc_info=True)

    def _walk(self, gen: int, before: Optional[threading.Thread]) -> None:
        from . import layoutmanifest

        if before is not None:
            before.join()  # the walk of the table before this one, stopped, may have an entry in hand
        stats = compilestats.stats()
        counts = dict.fromkeys(compilestats.PRELOAD_OUTCOMES, 0)
        t0 = time.perf_counter()
        try:
            scope = self._scope()
            for entry in layoutmanifest.entries(scope) if scope is not None else ():
                if self._gen != gen:
                    break
                e0 = time.perf_counter()
                outcome = self._load(entry, gen)
                if outcome is not None:
                    counts[outcome] += 1
                    stats.record_preload(outcome, time.perf_counter() - e0)
        except Exception:  # noqa: BLE001  (a walk that fails is a process without a manifest)
            _log.warning("layout preload: walk abandoned", exc_info=True)
        if self.thread is threading.current_thread():  # not under a later table's walk, which waits for this one
            atexit.unregister(self.close)
        stats.record_preload_done(counts, time.perf_counter() - t0, stopped=self._gen != gen)

    def _load(self, entry: dict, gen: int) -> Optional[str]:
        """One entry: its outcome, or None when the walk was stopped under it
        or the entry is of another class than the table's."""
        ev = self._ev
        try:
            key, cut, zeros = _entry_parts(entry)
            if key[2:5] != ev.packer.layout_class.kjd:
                return None  # filed before this table's class grew: no flight asks for it again
            if key in ev._jit_cache:
                return "held"
            B_pad, BA_pad, _K, _J, D, variant, _sig = key
            fn = _jit_run(ev.lowered.compiler, D, variant, cut)
            t0 = time.perf_counter()
            with ev._device_scope(), compilestats.cache_events() as seen:
                out = fn(**zeros)
                out.block_until_ready()
            dt = time.perf_counter() - t0
        except Exception:  # noqa: BLE001  (a stale or foreign entry: skip it)
            if self._gen != gen:
                return None
            _log.debug("layout preload: entry not buildable", exc_info=True)
            return "failed"
        source = compilestats.source_of(seen) or "fresh"
        with self._lock:
            stopped = self._gen != gen
            publish = not stopped and key not in ev._jit_cache
            if publish:
                ev._jit_cache[key] = fn
        # XLA ran or loaded whatever became of the function: since boot, like
        # a flight's own compile, but a deliberate walk is not a storm
        compilestats.stats().record_compile(
            f"B{B_pad}xBA{BA_pad}", dt, source=source, trace_key=key, storm=False,
            put_bytes=sum(a.nbytes for a in zeros.values()), fetch_bytes=out.nbytes,
        )
        if stopped:
            return None
        if not publish:
            return "held"
        return "loaded" if source == "persistent" else "fresh"


def _device_dispatch(
    lt: LoweredTable, batch: PackedBatch, jit_cache: dict, preloader: _LayoutPreloader
) -> _DeviceHandle:
    """Queue one packed batch on the single device WITHOUT blocking.

    FUSE TRANSFERS: every host->device put and device->host fetch is its
    own transfer with a fixed per-transfer cost (PERF.md records the
    measured put/fetch figures), and the naive call ships ~5 arrays per
    column path (100+ puts) and fetches 4 results. Lay every column and
    every candidate array into ONE staging buffer host-side — cutting it
    back apart INSIDE the traced graph is free (XLA fuses) — and pack every
    result into one int8 vector on device, so a batch costs 1 put + 1
    fetch regardless of how many columns the table has.

    HIDE LATENCY: jax dispatch is async — ``fn(**stacked)`` returns before
    the device runs — and the device->host copy is started eagerly with
    ``copy_to_host_async``, so the caller can pack/assemble other batches
    while this one's transfers and compute are in flight; only
    ``_device_finalize`` blocks.

    ``preloader`` (the evaluator's) is told of every layout the flight has
    to build itself: before the build, which starts its walk of the layout
    manifest the first time (a process's first device flight always builds),
    and after it, to file the layout. A flight that finds its layout in the
    cache does not touch it.
    """
    drainclock.to(drainclock.STACK)
    compiler = lt.compiler
    K, J, D = batch.K, batch.J, batch.D
    BA = batch.cand_cond.shape[0]
    B = batch.columns.size
    compiler.build_groups()
    C = len(compiler.kernels)

    h = _DeviceHandle()
    if BA == 0:
        h.ready = _zero_result(B, K, C)
        return h

    B_pad = _next_bucket(B)
    BA_pad = _next_bucket(BA)
    variant_key = _select_variant(lt, batch, jit_cache)

    col_map, cand_cond_c, cand_drcond_c = _variant_remap(
        variant_key, compiler, C, batch.cand_cond, batch.cand_drcond
    )
    stacked, cut, leased = _pad_stack(
        batch, batch.columns, cand_cond_c, cand_drcond_c, B_pad, BA_pad
    )
    key = (B_pad, BA_pad, K, J, D, variant_key, cut.sig)
    h.puts = len(stacked)
    h.put_bytes = sum(a.nbytes for a in stacked.values())
    fn = jit_cache.get(key)
    if fn is None:
        fn = _jit_run(compiler, D, variant_key, cut)
        jit_cache[key] = fn
        # a process's first device flight always lands here: the walk starts
        # once the flight's own key is in the cache, and never builds it too
        preloader.flight()
        compilestats.stats().record_miss()
        # jit defers trace+compile to the first call: time it there so the
        # compile histogram sees the real XLA cost (dispatch of the compiled
        # program stays async and costs microseconds by comparison)
        drainclock.to(drainclock.COMPILE)
        out = compilestats.timed_first_call(
            f"B{B_pad}xBA{BA_pad}", fn, stacked, trace_key=key
        )
        preloader.met(key, cut.lay)
        # the call was the compile's: of ``dispatch`` this flight has the copy alone
        drainclock.to(drainclock.DISPATCH, drainclock.DISPATCH_COPY)
    else:
        compilestats.stats().record_hit()
        drainclock.to(drainclock.DISPATCH, drainclock.DISPATCH_CALL)
        out = fn(**stacked)
        drainclock.part(drainclock.DISPATCH_COPY)
    out.copy_to_host_async()  # start the (single) fetch immediately
    h.out = out
    h.BA, h.B, h.K = BA, B, K
    h.BA_pad, h.B_pad = BA_pad, B_pad
    h.col_map = col_map
    h.leased = leased
    return h


def _device_finalize(h: _DeviceHandle):
    """Block on one in-flight batch and slice its results apart."""
    if h.ready is not None:
        drainclock.to(drainclock.ASSEMBLE, drainclock.ASSEMBLE_OUTPUTS)
        return h.ready
    K, BA = h.K, h.BA
    drainclock.to(drainclock.FETCH)
    flat = np.asarray(h.out)  # ONE device->host fetch: the wait for the device is here
    drainclock.to(drainclock.ASSEMBLE, drainclock.ASSEMBLE_OUTPUTS)
    h.fetch_bytes = flat.nbytes
    if h.leased:
        # the output is materialized, so every transfer that read the staging
        # buffers has completed — recycle them for the next batch
        _buffer_pool.release(h.leased)
        h.leased = ()
    per_ba = 4 + K * 2 * 2 + K * 2
    cut = h.BA_pad * per_ba
    out_mat = flat[:cut].reshape(h.BA_pad, per_ba)
    A_sat = max((flat.size - cut) // h.B_pad, 1)
    final = out_mat[:BA, :4]
    role_results = out_mat[:BA, 4 : 4 + K * 4].reshape(BA, K, 2, 2)
    win_j = out_mat[:BA, 4 + K * 4 :].reshape(BA, K, 2)
    sat_arr = flat[cut:].reshape(h.B_pad, A_sat)[: h.B].astype(bool)
    return final, role_results, win_j, sat_arr, h.col_map


class CheckTicket:
    """An in-flight batch submitted via TpuEvaluator.submit."""

    __slots__ = ("parts", "ready", "params", "occupancy", "layout_key", "padded_rows")

    def __init__(self):
        self.parts = None  # [(PackedBatch, _DeviceHandle)]
        self.ready = None
        self.params = None
        # device-economics attribution read by the serving batcher: real/padded
        # row ratio and the padded layout shape (a flight's pack seconds are the
        # drain clock's: engine/drainclock.py)
        self.occupancy = None  # None = no packed device layout (sync path)
        self.layout_key = None
        self.padded_rows = None


class TpuEvaluator:
    """Batched evaluator over a lowered rule table.

    Drop-in for the engine's ``tpu_evaluator`` hook: bit-exact effects vs the
    CPU oracle, with automatic per-input oracle fallback for anything outside
    device coverage.
    """

    def __init__(
        self,
        rule_table: RuleTable,
        globals_: Optional[dict[str, Any]] = None,
        schema_mgr: Any = None,
        max_roles: int = 8,
        max_candidates: int = 32,
        max_depth: int = 8,
        use_jax: bool = True,
        min_device_batch: int = 16,
        mesh=None,
        pipeline_chunk: int = 4096,
        device=None,
        shard_id: Optional[int] = None,
        _lowered: Optional[LoweredTable] = None,
        _layout_class: Optional[LayoutClass] = None,
    ):
        self.rule_table = rule_table
        self.schema_mgr = schema_mgr
        # lowering is the expensive part of construction; shard clones pass
        # the shared LoweredTable in so a pool of N evaluators lowers ONCE
        self.lowered = _lowered if _lowered is not None else lower_table(rule_table, globals_)
        # one layout class a table, as one lowered table: a lane is handed its owner's and never restarts it
        self._owns_class = _layout_class is None
        self.packer = Packer(
            self.lowered, max_roles=max_roles, max_candidates=max_candidates, max_depth=max_depth,
            layout_class=_layout_class,
        )
        self.use_jax = use_jax
        self.min_device_batch = min_device_batch
        self.mesh = mesh
        # pin this evaluator's dispatches to one jax device (a shard of the
        # pool); None = jax's default device (single-evaluator serving)
        self.device = device
        self.shard_id = shard_id
        self.pipeline_chunk = pipeline_chunk
        if use_jax:
            from .jitcache import enable as _enable_jit_cache

            _enable_jit_cache()  # persistent XLA cache: restart = load, not recompile
        self.stats = {"device_inputs": 0, "oracle_inputs": 0, "trivial_inputs": 0}
        transfer = metrics().histogram_vec(
            "cerbos_tpu_batch_transfer_bytes",
            "bytes of one device-served call, observed as its result is collected: dir=put, the one staging "
            "buffer the jitted call was handed (nbytes, padding included); dir=fetch, the one result "
            "vector; by shard",
            label=("dir", "shard"),
            buckets=[4096, 16384, 65536, 262144, 1048576, 4194304, 16777216, 67108864],
        )
        shard_label = str(shard_id) if shard_id is not None else "0"
        self._m_put_bytes = transfer.labels(("put", shard_label))
        self._m_fetch_bytes = transfer.labels(("fetch", shard_label))
        self._m_puts = metrics().histogram_vec(
            "cerbos_tpu_batch_device_puts",
            "arrays one device-served call handed the device (host->device transfers of one flight's inputs), "
            "observed beside batch_transfer_bytes as the call's result is collected; by shard",
            label="shard",
            buckets=[1, 2, 4, 8, 16, 32, 64, 128],
        ).labels(shard_label)
        self._m_memo = metrics().counter_vec(
            "cerbos_tpu_assemble_memo_total",
            "device-served inputs by what the assembly memo did for them: hit (a remembered output cloned), miss "
            "(assembled in full and remembered), bypass_validation (the input carries validation errors, so it is "
            "assembled in full or denied by reject and never remembered), bypass_other (no memo key: a table with "
            "outputs, or a derived-role condition the host evaluates)",
            label="result",
        )
        for result in _MEMO_RESULTS:
            self._m_memo.inc(result, 0.0)
        self._jit_cache: dict = {}
        self._preloader = _LayoutPreloader(self)
        self._dr_table_cache: dict = {}
        self._roles_cache: dict = {}
        self._edr_memo: dict = {}
        self._assemble_memo: dict = {}
        self._dr_cids_cache: dict = {}
        self._dr_cids_canon: dict[bytes, "np.ndarray"] = {}

    def refresh(self) -> None:
        """Re-lower after a policy reload (storage event hook)."""
        self.lowered.refresh()
        self.invalidate()

    def invalidate(self) -> None:
        """Drop every per-instance cache derived from the lowered table.
        ``refresh()`` re-lowers and then calls this; shard clones sharing the
        lowered table call only this after the owner re-lowered."""
        self.packer.invalidate()
        if self._owns_class:
            # after the packer's stores are empty: the table now in place starts from its own class
            self.packer.layout_class.restart()
        self._preloader.stop()  # before the clear: nothing built for the old table is published after it
        self._jit_cache.clear()
        self._dr_table_cache.clear()
        self._roles_cache.clear()
        self._edr_memo.clear()
        self._assemble_memo.clear()
        self._dr_cids_cache.clear()
        self._dr_cids_canon.clear()

    def shard_clone(self, devices, shard_id: int) -> "TpuEvaluator":
        """A pool-shard evaluator over the SAME lowered rule table.

        The clone shares the read-only artifacts (rule table, lowered
        tables, schema manager) but owns everything mutated on the serving
        path — packer, jit cache, memo caches, stats — so each shard's
        worker thread runs lock-free against its siblings. ``devices`` is
        the shard's placement from ``parallel.mesh.shard_devices``: one
        device pins via ``jax.default_device``, several become a per-shard
        data-parallel mesh slice."""
        device = None
        mesh = None
        if devices is not None:
            devs = list(devices)
            if len(devs) == 1:
                device = devs[0]
            elif len(devs) > 1:
                from ..parallel.mesh import make_mesh_for

                mesh = make_mesh_for(devs)
        clone = TpuEvaluator(
            self.rule_table,
            schema_mgr=self.schema_mgr,
            max_roles=self.packer.K,
            max_candidates=self.packer.J,
            max_depth=self.packer.D,
            use_jax=self.use_jax,
            min_device_batch=self.min_device_batch,
            mesh=mesh,
            pipeline_chunk=self.pipeline_chunk,
            device=device,
            shard_id=shard_id,
            _lowered=self.lowered,
            _layout_class=self.packer.layout_class,
        )
        return clone

    def _device_scope(self):
        """Context manager pinning jax dispatch to this shard's device."""
        if self.device is None:
            import contextlib

            return contextlib.nullcontext()
        import jax

        return jax.default_device(self.device)

    def check(self, inputs: list[T.CheckInput], params: Optional[T.EvalParams] = None) -> list[T.CheckOutput]:
        """Evaluate one batch and wait for it: :meth:`submit` and
        :meth:`collect` in one call, so a direct caller (warm-up, an engine
        with no batcher, the bisect, explain) takes the route a served
        flight takes, to the same jit-cache entries."""
        return self.collect(self.submit(inputs, params))

    def submit(self, inputs: list[T.CheckInput], params: Optional[T.EvalParams] = None) -> "CheckTicket":
        """Queue one batch WITHOUT waiting for its results: the one route
        from a list of inputs to the device.

        The batch is cut by :meth:`_chunk_inputs` (one chunk up to
        ``pipeline_chunk``), and each chunk is packed and dispatched in
        turn. The device work (transfers + compute + result copy) runs
        asynchronously; the caller keeps submitting further batches — or
        assembling earlier ones via :meth:`collect` — while this one is in
        flight. This is how a serving loop hides the interconnect's
        per-batch latency: N batches in flight amortize transfer latency
        the way the reference's ghz load (hundreds of concurrent requests)
        amortizes per-request overhead. What does not go to the single
        device is evaluated here and now, and the ticket is already
        complete: a batch under ``min_device_batch`` by the oracle, the
        numpy backend and a mesh by :func:`_host_or_mesh_eval`."""
        params = params or T.EvalParams()
        t = CheckTicket()
        t.params = params
        if len(inputs) < self.min_device_batch:
            # device dispatch has a fixed cost; tiny batches are faster on
            # the serial oracle (the reference's parallelismThreshold analogue)
            drainclock.to(drainclock.ORACLE)
            self.stats["oracle_inputs"] += len(inputs)
            t.ready = [check_input(self.rule_table, i, params, self.schema_mgr) for i in inputs]
            return t
        if not self.use_jax or self.mesh is not None:
            drainclock.to(drainclock.ORACLE)
            batch = self.packer.pack(inputs, params)
            with self._device_scope():
                res = _host_or_mesh_eval(
                    self.lowered, batch, self.mesh if self.use_jax else None, self._jit_cache
                )
            t.ready = self._assemble_batch(batch, *res, params)
            return t
        chunks = self._chunk_inputs(inputs)
        t.parts = []
        self._preloader.restore()
        with start_span("batch.pack", inputs=len(inputs), chunks=len(chunks)), self._device_scope():
            for ch in chunks:
                drainclock.to(drainclock.PACK, drainclock.PACK_PLAN)
                batch = self.packer.pack(ch, params)
                t.parts.append(
                    (batch, _device_dispatch(self.lowered, batch, self._jit_cache, self._preloader))
                )
        # a chunk with no candidate rows (every input trivial) never reached the device
        sent = [h for _, h in t.parts if h.ready is None]
        padded = sum(h.B_pad for h in sent)
        if padded:
            t.occupancy = sum(h.B for h in sent) / padded
            t.padded_rows = padded
            t.layout_key = "+".join(f"B{h.B_pad}xBA{h.BA_pad}" for h in sent)
        return t

    def collect(self, ticket: "CheckTicket") -> list[T.CheckOutput]:
        """Block on one submitted batch and assemble its CheckOutputs, chunk
        by chunk in the order they were dispatched."""
        if ticket.ready is not None:
            return ticket.ready
        out: list[T.CheckOutput] = []
        for batch, handle in ticket.parts:
            res = _device_finalize(handle)
            if handle.fetch_bytes:
                # once per device-served call: what it put and what it fetched
                self._m_puts.observe(handle.puts)
                self._m_put_bytes.observe(handle.put_bytes)
                self._m_fetch_bytes.observe(handle.fetch_bytes)
            out.extend(self._assemble_batch(batch, *res, ticket.params))
        ticket.ready = out
        ticket.parts = None
        return out

    def _chunk_inputs(self, inputs: list[T.CheckInput]) -> list[list[T.CheckInput]]:
        """Pipeline-chunk boundaries shared by check() and submit(): a batch
        that fits one pipeline_chunk is ONE chunk (every stage of a chunk has
        a fixed cost far above its per-input cost); a larger one is cut into
        pipeline_chunk-sized slices, with a tail smaller than the device
        threshold riding with its neighbor rather than paying a dispatch
        (or an oracle walk) of its own."""
        chunk = self.pipeline_chunk if self.pipeline_chunk > 0 else len(inputs)
        chunks = [inputs[b : b + chunk] for b in range(0, len(inputs), chunk)]
        if len(chunks) > 1 and len(chunks[-1]) < self.min_device_batch:
            chunks[-2] = chunks[-2] + chunks[-1]
            chunks.pop()
        return chunks

    def _assemble_batch(
        self, batch: PackedBatch, final, role_results, win_j, sat_arr, col_map, params
    ) -> list[T.CheckOutput]:
        # one contiguous int8 matrix of all per-(input,action) decision state,
        # exported to bytes ONCE; the memo key for input bi is then a pure
        # bytes slice (no per-input ndarray views or copies)
        dec_buf = None
        dec_w = 0
        if not self.lowered.has_outputs and final.shape[0]:
            BA = final.shape[0]
            dec_bytes = np.concatenate(
                [
                    np.asarray(final).reshape(BA, -1),
                    np.asarray(role_results).reshape(BA, -1),
                    np.asarray(win_j).reshape(BA, -1),
                ],
                axis=1,
            )
            dec_w = dec_bytes.shape[1] * dec_bytes.itemsize
            dec_buf = dec_bytes.tobytes()
        dr_bits_by_bi = (
            self._batch_dr_bits(batch, sat_arr, col_map, params) if dec_buf is not None else None
        )

        validated = self._validate_batch(batch)
        memo_hit = memo_miss = bypass_validation = bypass_other = 0
        outputs: list[T.CheckOutput] = []
        for bi, plan in enumerate(batch.plans):
            inp = plan.input
            if plan.oracle:
                self.stats["oracle_inputs"] += 1
                outputs.append(check_input(self.rule_table, inp, params, self.schema_mgr))
                continue
            if plan.trivial:
                self.stats["trivial_inputs"] += 1
                out = T.CheckOutput(request_id=inp.request_id, resource_id=inp.resource.id)
                for action in inp.actions:
                    out.actions[action] = T.ActionEffect(
                        effect=T.EFFECT_DENY, policy=T.NO_POLICY_MATCH, source="device"
                    )
                outputs.append(out)
                continue
            self.stats["device_inputs"] += 1
            # schema validation runs on host per input, mirroring the
            # oracle's pre-loop check (check.go:129-151); a reject means
            # every action denies without evaluating rules
            vr_errors: list = []
            if validated is not None:
                vr_errors, reject = validated[bi]
                if reject:
                    out = T.CheckOutput(request_id=inp.request_id, resource_id=inp.resource.id)
                    for action in inp.actions:
                        out.actions[action] = T.ActionEffect(
                            effect=T.EFFECT_DENY, policy=plan.resource_policy_key, source="device"
                        )
                    out.validation_errors = vr_errors
                    outputs.append(out)
                    bypass_validation += 1
                    continue
            key = None
            if vr_errors:
                bypass_validation += 1
            elif dec_buf is not None:
                dr_bits = dr_bits_by_bi.get(bi)
                if dr_bits is not None:
                    start, end = plan.ba_range
                    key = (plan.sig, dec_buf[start * dec_w : end * dec_w], dr_bits)
            if key is not None:
                hit = self._assemble_memo.get(key)
                if hit is not None:
                    outputs.append(_clone_output(hit, inp))
                    memo_hit += 1
                    continue
                memo_miss += 1
            elif not vr_errors:
                bypass_other += 1
            out = self._assemble(plan, bi, batch, final, role_results, win_j, sat_arr, col_map, params)
            out.validation_errors = vr_errors
            if key is not None:
                if len(self._assemble_memo) > 65536:
                    self._assemble_memo.clear()
                self._assemble_memo[key] = out
            outputs.append(out)
        # once a batch, not once an input: four increments under the registry's locks
        for result, n in zip(_MEMO_RESULTS, (memo_hit, memo_miss, bypass_validation, bypass_other)):
            if n:
                self._m_memo.inc(result, n)
        return outputs

    def _validate_batch(self, batch: PackedBatch) -> Optional[list]:
        """``(errors, reject)`` of every device-served input of ``batch`` (None
        at the others), or None where nothing is validated. On the drain
        thread this is the ``assemble_schema`` part of ``assemble``: all of a
        flight's validations in one stretch, so the clock is read twice a
        flight and not twice an input."""
        mgr = self.schema_mgr
        if mgr is None or not mgr.enabled:
            return None
        drainclock.part(drainclock.ASSEMBLE_SCHEMA)
        get_schema = self.rule_table.get_schema
        tally = Tally()  # booked once a flight: the instruments' locks are not taken 43 times a page
        validated = [
            None
            if plan.oracle or plan.trivial
            else mgr.validate_check_input(get_schema(plan.resource_policy_fqn), plan.input, route="device", tally=tally)
            for plan in batch.plans
        ]
        mgr.book(tally)
        drainclock.part(drainclock.ASSEMBLE_OUTPUTS)
        return validated

    def _batch_dr_bits(self, batch: PackedBatch, sat_arr, col_map, params) -> dict[int, bytes]:
        """Per-input derived-role condition bits (part of the assembly memo
        key: inputs with the same shape sig, decision rows and DR bits
        assemble to identical outputs modulo ids). Gathered per shape group
        in one fancy-index instead of per input. Inputs whose scope chain has
        host-evaluated DR conditions are absent (their outcome depends on raw
        attrs — not memoizable)."""
        plans = batch.plans
        out: dict[int, bytes] = {}
        cache = self._dr_cids_cache
        # group by the CONTENT of the cid vector, not the shape sig — many
        # sigs (same chain, different action sets) share one gather
        groups: dict[int, list[int]] = {}
        arr_by_gid: dict[int, np.ndarray] = {}
        canon_by_content: dict[bytes, np.ndarray] = self._dr_cids_canon
        for bi, plan in enumerate(plans):
            if plan.oracle or plan.trivial:
                continue
            cids = cache.get(plan.sig)
            if cids is None:
                inp = plan.input
                version = T.effective_version(inp.resource.policy_version, params)
                all_cids: list[int] = []
                for scope in plan.resource_scopes:
                    for _, _, cid, dr in self._dr_table(inp.resource.kind, version, scope):
                        if cid >= 0:
                            all_cids.append(cid)
                        elif dr.condition is not None:
                            all_cids = None  # host-evaluated DR: not memoizable
                            break
                    if all_cids is None:
                        break
                if all_cids is None:
                    cids = "host"
                else:
                    arr = np.asarray(all_cids, dtype=np.int64)
                    cids = canon_by_content.setdefault(arr.tobytes(), arr)
                # sigs regenerate after packer shape-memo evictions, so this
                # cache must be bounded too (canon stays content-bounded)
                if len(cache) > 65536:
                    cache.clear()
                cache[plan.sig] = cids
            if isinstance(cids, str):
                continue
            if not cids.size:
                out[bi] = b""
                continue
            gid = id(cids)
            g = groups.get(gid)
            if g is None:
                groups[gid] = [bi]
                arr_by_gid[gid] = cids
            else:
                g.append(bi)
        for gid, bis in groups.items():
            cids = arr_by_gid[gid]
            rows = np.ascontiguousarray(sat_arr[np.asarray(bis, dtype=np.int64)][:, col_map[cids]])
            w = rows.shape[1] * rows.itemsize
            buf = rows.tobytes()
            for i, bi in enumerate(bis):
                out[bi] = buf[i * w : (i + 1) * w]
        return out

    # -- host assembly -----------------------------------------------------

    def _assemble(self, plan, bi, batch: PackedBatch, final, role_results, win_j, sat_arr, col_map, params) -> T.CheckOutput:
        inp = plan.input
        out = T.CheckOutput(request_id=inp.request_id, resource_id=inp.resource.id)
        start, end = plan.ba_range
        action_to_ba = {batch.ba_action[ci]: ci for ci in range(start, end)}

        processed_scopes: set[int] = set()  # resource-chain depths processed
        output_entries: list[T.OutputEntry] = []
        effective_policies: dict[str, Any] = {}
        ec_cache: dict[Any, Any] = {}

        def eval_ctx():
            if "ec" not in ec_cache:
                request, principal, resource = build_request_messages(inp)
                ec_cache["ec"] = EvalContext(params, request, principal, resource)
            return ec_cache["ec"]

        emit_outputs = self.lowered.has_outputs

        def bookkeep_depth(depth: int):
            """EDR bookkeeping for a newly visited resource-chain scope: the
            current context is REPLACED with that scope's activated set, and
            later rule visits — including other roles re-walking already
            processed scopes — keep whatever context is current, mirroring
            the oracle's processedScopedDerivedRoles statefulness
            (check.go:231-271 / check.py:321-341). Tables without outputs
            never read the context (only processed_scopes feeds
            effective_derived_roles), so skip the per-input EvalContext."""
            if depth in processed_scopes:
                return
            processed_scopes.add(depth)
            if not emit_outputs:
                return
            edr = self._edr_at_depth(plan, bi, depth, params, eval_ctx, sat_arr, col_map)
            ec_cache["cur"] = eval_ctx().with_effective_derived_roles(edr)

        def current_ctx():
            return ec_cache.get("cur") or eval_ctx()

        for action in inp.actions:
            ci = action_to_ba.get(action)
            if ci is None:
                out.actions[action] = T.ActionEffect(
                    effect=T.EFFECT_DENY, policy=T.NO_POLICY_MATCH, source="device"
                )
                continue
            code, pt, depth, k = (int(x) for x in final[ci])

            chain = plan.principal_scopes if pt == PT_PRINCIPAL else plan.resource_scopes
            main_key = plan.principal_policy_key if pt == PT_PRINCIPAL else plan.resource_policy_key
            exists = plan.scoped_principal_exists if pt == PT_PRINCIPAL else plan.scoped_resource_exists

            if code in (CODE_ALLOW, CODE_DENY):
                # winning-rule attribution (ISSUE 20): win_j carries the
                # first-match j for BOTH effects now, so the decision names
                # the rule row that produced it
                policy = main_key if (code == CODE_ALLOW or exists) else T.NO_POLICY_MATCH
                matched_rule, row_id = "", -1
                wj = int(win_j[ci, k, pt])
                if 0 <= wj:
                    entry = self._entry_at(batch, ci, k, wj)
                    if entry is not None:
                        if code == CODE_DENY and entry.from_role_policy:
                            policy = namer.policy_key_from_fqn(entry.origin_fqn)
                        if entry.row is not None:
                            matched_rule = self._rule_src(entry)
                            row_id = entry.row.id
                ae = T.ActionEffect(
                    effect=T.EFFECT_ALLOW if code == CODE_ALLOW else T.EFFECT_DENY,
                    policy=policy,
                    scope=chain[depth] if depth < len(chain) else "",
                    matched_rule=matched_rule,
                    rule_row_id=row_id,
                    source="device",
                )
            else:
                # NO_MATCH → default deny (resource-pass attribution)
                policy = plan.resource_policy_key if plan.scoped_resource_exists else T.NO_POLICY_MATCH
                ae = T.ActionEffect(effect=T.EFFECT_DENY, policy=policy, source="device")
            out.actions[action] = ae

            # reconstruct processed resource-chain depths + emitted outputs
            self._reconstruct(
                plan, bi, batch, ci, role_results, win_j, sat_arr, col_map,
                output_entries, eval_ctx, bookkeep_depth, current_ctx,
                effective_policies,
            )

        # effective derived roles for processed resource scopes
        if processed_scopes:
            out.effective_derived_roles = self._effective_derived_roles(
                plan, bi, sorted(processed_scopes), params, eval_ctx, sat_arr, col_map
            )
        out.outputs = output_entries
        out.effective_policies = {
            namer.policy_key_from_fqn(fqn): attrs for fqn, attrs in effective_policies.items()
        }
        return out

    def _entry_at(self, batch: PackedBatch, ci: int, k: int, j: int):
        per_k = batch.cand_entries[ci]
        if k < len(per_k) and j < len(per_k[k]):
            return per_k[k][j]
        return None

    def _reconstruct(self, plan, bi, batch, ci, role_results, win_j, sat_arr, col_map, output_entries, eval_ctx, bookkeep_depth, current_ctx, effective_policies):
        """Mirror the visit order: per role, walk resource-chain depths in
        order, bookkeeping each newly visited scope's derived roles BEFORE
        evaluating that scope's rule outputs, so outputs see the same
        (stateful) runtime.effectiveDerivedRoles context as the oracle."""
        inp = plan.input
        sat_b = sat_arr[bi]
        # principal pass decided?
        p_code = int(role_results[ci, 0, PT_PRINCIPAL, 0])
        passes = [(PT_PRINCIPAL, [0])]
        if p_code == CODE_NO_MATCH:
            ks = list(range(min(len(plan.roles), batch.K)))
            passes.append((PT_RESOURCE, ks))

        emit_outputs = self.lowered.has_outputs
        for pt, ks in passes:
            chain = plan.principal_scopes if pt == PT_PRINCIPAL else plan.resource_scopes
            if not emit_outputs and pt == PT_RESOURCE:
                # no outputs anywhere in the table: only the processed-depth
                # bookkeeping and policy provenance matter; the max depth
                # over roles covers both
                overall = -1
                last_k = 0
                for k in ks:
                    code = int(role_results[ci, k, pt, 0])
                    depth = int(role_results[ci, k, pt, 1])
                    overall = max(overall, min(depth, len(chain) - 1) if code != CODE_NO_MATCH else len(chain) - 1)
                    last_k = k
                    if code == CODE_ALLOW:
                        break
                for d in range(0, overall + 1):
                    bookkeep_depth(d)
                for k in ks[: last_k + 1]:
                    entries = batch.cand_entries[ci][k] if k < len(batch.cand_entries[ci]) else []
                    code = int(role_results[ci, k, pt, 0])
                    depth = int(role_results[ci, k, pt, 1])
                    maxd = min(depth, len(chain) - 1) if code != CODE_NO_MATCH else len(chain) - 1
                    self._collect_effective(entries, pt, maxd, effective_policies)
                continue
            for k in ks:
                code = int(role_results[ci, k, pt, 0])
                depth = int(role_results[ci, k, pt, 1])
                max_depth = min(depth, len(chain) - 1) if code != CODE_NO_MATCH else len(chain) - 1
                entries = batch.cand_entries[ci][k] if k < len(batch.cand_entries[ci]) else []
                wj = int(win_j[ci, k, pt]) if code == CODE_DENY else -1
                self._collect_effective(entries, pt, max_depth, effective_policies)
                for d in range(0, max_depth + 1):
                    if pt == PT_RESOURCE:
                        bookkeep_depth(d)
                    if not emit_outputs:
                        continue
                    for j, e in enumerate(entries):
                        if e is None or e.pt != pt or e.depth != d:
                            continue
                        if code == CODE_DENY and e.depth == depth and wj >= 0 and j > wj:
                            continue
                        if not e.has_output or e.row is None or e.row.emit_output is None:
                            continue
                        sat = True
                        if e.cond_id >= 0:
                            sat = bool(sat_b[col_map[e.cond_id]])
                        if e.drcond_id >= 0 and not bool(sat_b[col_map[e.drcond_id]]):
                            continue  # derived-role condition unmet: rule skipped entirely
                        emit = e.row.emit_output
                        expr = emit.rule_activated if sat else emit.condition_not_met
                        if expr is None:
                            continue
                        ec = current_ctx() if pt == PT_RESOURCE else eval_ctx()
                        constants, variables = {}, {}
                        if e.row.params is not None:
                            constants = e.row.params.constants
                            variables = ec.evaluate_variables(constants, e.row.params.ordered_variables)
                        src = self._rule_src(e)
                        output_entries.append(
                            ec.evaluate_output(e.row.name, src, batch.ba_action[ci], expr, constants, variables)
                        )
                # stop visiting further roles if this role allowed
                if code == CODE_ALLOW:
                    break

    def _collect_effective(self, entries, pt, max_depth, effective_policies) -> None:
        """Policy provenance for every binding in a visited scope — the
        oracle records source attributes for all QUERIED bindings, satisfied
        or not (check.py:356-358 / check.go effectivePolicies)."""
        rt = self.rule_table
        for e in entries:
            if e is None or e.pt != pt or e.depth > max_depth:
                continue
            if e.origin_fqn in effective_policies:
                continue
            for f, attrs in rt.get_chain_source_attributes(e.origin_fqn).items():
                effective_policies.setdefault(f, dict(attrs))

    def _rule_src(self, e) -> str:
        meta = self.rule_table.get_meta(e.origin_fqn)
        b = e.row
        if meta is None:
            return f"{namer.policy_key_from_fqn(e.origin_fqn)}#{b.name}"
        if meta.kind == "PRINCIPAL":
            fqn = namer.principal_policy_fqn(meta.name, meta.version, b.scope)
        elif meta.kind == "RESOURCE":
            fqn = namer.resource_policy_fqn(meta.name, meta.version, b.scope)
        else:
            fqn = namer.role_policy_fqn(meta.name, meta.version, b.scope)
        return f"{namer.policy_key_from_fqn(fqn)}#{b.name}"

    def _dr_table(self, kind: str, version: str, scope: str):
        """Cached per-(kind, version, scope): [(name, parent_roles, cond_id, dr)]."""
        key = (kind, version, scope)
        hit = self._dr_table_cache.get(key)
        if hit is None:
            drs = self.rule_table.get_derived_roles(namer.resource_policy_fqn(kind, version, scope))
            hit = []
            if drs:
                for name, dr in drs.items():
                    cid = self.lowered.dr_cond_ids.get(id(dr), -1)
                    device_ok = cid >= 0 and self.lowered.compiler.kernels[cid].emit is not None
                    hit.append((name, dr.parent_roles, cid if device_ok else -1, dr))
            self._dr_table_cache[key] = hit
        return hit

    def _edr_at_depth(self, plan, bi, depth, params, eval_ctx, sat_arr, col_map) -> set[str]:
        """Derived roles activated at one resource-chain scope depth.

        Memoized per (scope fqn, principal roles, device condition bits) —
        inputs sharing role sets and condition outcomes (the common case in
        large batches) reuse the set. Tables with host-evaluated derived-role
        conditions bypass the cache (their outcome depends on raw attrs)."""
        inp = plan.input
        if depth >= len(plan.resource_scopes):
            return set()
        resource_version = T.effective_version(inp.resource.policy_version, params)
        rt = self.rule_table
        roles_key = (T.effective_scope(inp.resource.scope, params), tuple(inp.principal.roles))
        all_roles = self._roles_cache.get(roles_key)
        if all_roles is None:
            all_roles = set(rt.idx.add_parent_roles([roles_key[0]], list(inp.principal.roles)))
            if len(self._roles_cache) > 65536:
                self._roles_cache.clear()
            self._roles_cache[roles_key] = all_roles
        edr: set[str] = set()
        sat_b = sat_arr[bi]
        table = self._dr_table(inp.resource.kind, resource_version, plan.resource_scopes[depth])
        cacheable = all(cid >= 0 or dr.condition is None for _, _, cid, dr in table)
        if cacheable:
            bits = tuple(bool(sat_b[col_map[cid]]) for _, _, cid, _ in table if cid >= 0)
            mkey = (inp.resource.kind, resource_version, plan.resource_scopes[depth], roles_key, bits)
            hit = self._edr_memo.get(mkey)
            if hit is not None:
                return hit
        else:
            mkey = None
        for name, parent_roles, cid, dr in table:
            if name in edr:
                continue
            # literal "*" parent role matches any principal role
            # (internal/utils.go:56-68), mirroring the oracle
            if "*" not in parent_roles and not (parent_roles & all_roles):
                continue
            if dr.condition is None:
                edr.add(name)
            elif cid >= 0:
                if bool(sat_b[col_map[cid]]):
                    edr.add(name)
            else:
                # condition outside device coverage: host-evaluate
                ec = eval_ctx()
                variables = ec.evaluate_variables(dr.params.constants, dr.params.ordered_variables)
                if ec.satisfies_condition(dr.condition, dr.params.constants, variables):
                    edr.add(name)
        if mkey is not None:
            if len(self._edr_memo) > 65536:
                self._edr_memo.clear()
            self._edr_memo[mkey] = edr
        return edr

    def _effective_derived_roles(self, plan, bi, depths, params, eval_ctx, sat_arr, col_map) -> list[str]:
        edr: set[str] = set()
        for d in depths:
            edr |= self._edr_at_depth(plan, bi, d, params, eval_ctx, sat_arr, col_map)
        return sorted(edr)
