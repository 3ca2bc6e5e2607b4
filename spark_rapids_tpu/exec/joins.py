"""Join operators.

TPU analog of the reference's join execs (`GpuShuffledHashJoinExec`,
`GpuBroadcastHashJoinExec`, `GpuSortMergeJoinMeta` — rewritten to a hash
join there, a sort join here — `GpuBroadcastNestedLoopJoinExec`,
`GpuCartesianProductExec`; SURVEY.md §2.2-B; reference mount empty).

Single-partition local join core: the build (right) side is concatenated
once; each stream (left) batch runs the staged sort-join kernel
(ops/join.py). Shuffled/broadcast distribution wraps this core at the
exchange layer. Extra non-equi conditions are applied as a post-filter for
inner/cross joins (other types report unsupported and fall back).
"""
from __future__ import annotations

import math
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.arrow_bridge import arrow_schema
from ..columnar.batch import TpuBatch, bucket_bytes, bucket_rows
from ..columnar.column import TpuColumnVector
from ..expr.base import Expression, bind_expr
from ..ops.concat import concat_batches
from ..ops.gather import compact_batch, gather_columns
from ..ops.join import (JOIN_TYPES, join_counts, join_gather, join_indices,
                        join_output_bytes, join_total, probe_unique,
                        unique_build_analysis, unique_build_probe,
                        unique_union_lookup)
from ..programs import named_jit
from .base import ExecCtx, OpContract, TpuExec
from .basic import bind_all

# join types the unique-build fast path serves (each live stream row
# emits at most one output row, so output capacity == stream capacity)
_FAST_JOIN_TYPES = ("inner", "left_outer", "left_semi", "left_anti")
# ceiling on a fast-path right-side string char allocation
# (stream capacity x max build string length); beyond it the staged
# path's exact per-batch sizing is the better trade
_FAST_MAX_CHAR_CAP = 1 << 28

__all__ = ["TpuShuffledHashJoinExec", "TpuBroadcastHashJoinExec",
           "TpuCartesianProductExec", "TpuBroadcastNestedLoopJoinExec"]


def _join_output_schema(left: dt.Schema, right: dt.Schema,
                        join_type: str) -> dt.Schema:
    if join_type in ("left_semi", "left_anti"):
        return left
    lf = list(left.fields)
    rf = list(right.fields)
    if join_type in ("right_outer", "full_outer"):
        lf = [dt.StructField(f.name, f.dtype, True) for f in lf]
    if join_type in ("left_outer", "full_outer"):
        rf = [dt.StructField(f.name, f.dtype, True) for f in rf]
    return dt.Schema(lf + rf)


def _and_sel(batch: TpuBatch, mask):
    """Selection for an output sharing `batch`'s row layout: AND the new
    mask into any existing lazy selection."""
    return mask if batch.selection is None else batch.selection & mask


class _BaseJoinExec(TpuExec):
    """Shared staged-join execution over a built right side."""

    FUSION_NOTE = ("barrier: two-input operator (build side "
                   "materializes; probe output size is data-dependent "
                   "— staged kernels with capacity syncs)")

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 build_unique_hint: bool = False,
                 left_out: Optional[Sequence[int]] = None,
                 right_out: Optional[Sequence[int]] = None):
        super().__init__()
        if join_type not in JOIN_TYPES:
            raise ValueError(f"unknown join type {join_type}")
        # UNCHECKED planner/user contract that build keys are unique
        # (primary-key build side): skips the one-readback build
        # analysis so a whole query can run with zero host syncs. A
        # false hint silently drops duplicate matches — like Spark
        # broadcast hints, trust is the caller's responsibility.
        self.build_unique_hint = build_unique_hint
        self.children = (left, right)
        self.join_type = join_type
        self.left_keys = bind_all(left_keys, left.output_schema)
        self.right_keys = bind_all(right_keys, right.output_schema)
        for lk, rk in zip(self.left_keys, self.right_keys):
            if lk.dtype != rk.dtype:
                raise TypeError(
                    f"join key type mismatch: {lk.dtype.simple_string()} "
                    f"vs {rk.dtype.simple_string()}")
        # the PAYLOAD: the ordinals of each child that are gathered
        # into the output (all of them unless column pruning narrowed
        # the join: a column that is only a key is not gathered)
        self.left_out = list(range(len(left.output_schema.fields))) \
            if left_out is None else list(left_out)
        self.right_out = list(range(len(right.output_schema.fields))) \
            if right_out is None else list(right_out)
        lpay, rpay = self._pay_schemas = self._payload_schemas()
        self._schema = _join_output_schema(lpay, rpay, join_type)
        # conditions see both sides' payload even when the output is
        # left-only
        self._cond_schema = dt.Schema(list(lpay.fields)
                                      + list(rpay.fields))
        self.condition = bind_expr(condition, self._cond_schema) \
            if condition is not None else None
        self._jit_a = None
        self._jit_b: Dict[int, object] = {}
        self._jit_c: Dict[tuple, object] = {}
        self._jit_fast: Dict[tuple, object] = {}
        self._jit_analysis = None
        self._jit_probe = None
        self._jit_dup = None

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    @property
    def output_schema(self):
        return self._schema

    def _payload_schemas(self):
        """(left, right) schemas of the gathered columns, from the
        CURRENT children."""
        lf = self.left.output_schema.fields
        rf = self.right.output_schema.fields
        return (dt.Schema([lf[i] for i in self.left_out]),
                dt.Schema([rf[i] for i in self.right_out]))

    def _cut(self, batch: TpuBatch, side: int) -> TpuBatch:
        """One side's batch cut to its gathered columns (the same
        buffers; keys are evaluated over the whole batch)."""
        out = self.right_out if side else self.left_out
        if len(out) == len(batch.columns):
            return batch
        return batch.with_columns([batch.columns[i] for i in out],
                                  schema=self._pay_schemas[side])

    def _payload(self, lbatch: TpuBatch, rbatch: TpuBatch):
        return self._cut(lbatch, 0), self._cut(rbatch, 1)

    # --- column pruning (exec/pruning.py) ---------------------------------
    PRUNING_NOTE = ("requires both key lists, its condition's inputs and "
                    "the payload its parent reads from each side; a "
                    "column that is only a key is not gathered")

    def _split_required(self, required):
        """Output ordinals the parent reads -> (left child ordinals,
        right child ordinals) of the payload."""
        nl = len(self.left_out)
        semi = self.join_type in ("left_semi", "left_anti")
        lreq = {self.left_out[o] for o in required if o < nl}
        rreq = set() if semi else \
            {self.right_out[o - nl] for o in required if o >= nl}
        if self.condition is not None:
            from .pruning import refs
            for o in refs([self.condition]):
                if o < nl:
                    lreq.add(self.left_out[o])
                else:
                    rreq.add(self.right_out[o - nl])
        return lreq, rreq

    def child_requirements(self, required):
        from .pruning import refs
        lreq, rreq = self._split_required(required)
        return [lreq | refs(self.left_keys), rreq | refs(self.right_keys)]

    def _rebuilt(self, left, right, left_keys, right_keys, condition,
                 left_out, right_out):
        """This join's class over new children, keys and payload."""
        return type(self)(left_keys, right_keys, self.join_type, left,
                          right, condition,
                          build_unique_hint=self.build_unique_hint,
                          left_out=left_out, right_out=right_out)

    def pruned(self, children, maps, required):
        from .pruning import narrowed, remap
        from .pruning import refs
        lreq, rreq = self._split_required(required)
        need = [lreq | refs(self.left_keys), rreq | refs(self.right_keys)]
        left, lm = narrowed(children[0], maps[0], need[0])
        right, rm = narrowed(children[1], maps[1], need[1])
        # (the pass never asks for no column at all: a row count keeps
        # the cheapest one, so one side's payload may be empty, not both)
        lkeep, rkeep = sorted(lreq), sorted(rreq)
        semi = self.join_type in ("left_semi", "left_anti")
        # old output ordinal -> new one, and the condition's old ordinal
        # space (old payload) -> the new payload's
        nl_old = len(self.left_out)
        lpos = {c: i for i, c in enumerate(lkeep)}
        rpos = {c: i for i, c in enumerate(rkeep)}
        out_map = {}
        for o, c in enumerate(self.left_out):
            if c in lpos:
                out_map[o] = lpos[c]
        for o, c in enumerate(self.right_out):
            if c in rpos:
                out_map[nl_old + o] = len(lkeep) + rpos[c]
        new_lout, new_rout = [lm[c] for c in lkeep], [rm[c] for c in rkeep]
        if left is self.left and right is self.right \
                and new_lout == self.left_out \
                and new_rout == self.right_out:
            return self, out_map
        node = self._rebuilt(
            left, right,
            [remap(k, lm) for k in self.left_keys],
            [remap(k, rm) for k in self.right_keys],
            remap(self.condition, out_map), new_lout, new_rout)
        if semi:
            out_map = {o: n for o, n in out_map.items() if o < nl_old}
        return node, out_map

    def tpu_supported(self):
        if self.condition is not None and \
                self.join_type not in ("inner", "cross"):
            return (f"non-equi condition on {self.join_type} join not yet "
                    "on device")
        for schema in (self.left.output_schema, self.right.output_schema):
            for f in schema.fields:
                if dt.is_nested(f.dtype):
                    # join gathers duplicate rows; nested payload sizing
                    # is top-level only (gather_list keeps the child cap)
                    return (f"join over nested column {f.name} "
                            f"({f.dtype.simple_string()}) not on device")
        return None

    def expressions(self):
        out = list(self.left_keys) + list(self.right_keys)
        if self.condition is not None:
            out.append(self.condition)
        return out

    def expected_output_schema(self):
        lpay, rpay = self._payload_schemas()
        return _join_output_schema(lpay, rpay, self.join_type)

    def expr_bindings(self):
        # left keys bind against the left child, right keys against the
        # right child, the extra condition against both sides' columns
        out = [(k, self.left.output_schema) for k in self.left_keys]
        out += [(k, self.right.output_schema) for k in self.right_keys]
        if self.condition is not None:
            # rebuilt from the CURRENT children (not the cached
            # _cond_schema): the check must see what the tree is now
            lpay, rpay = self._payload_schemas()
            cond = dt.Schema(list(lpay.fields) + list(rpay.fields))
            out.append((self.condition, cond))
        return out

    def payload_cols(self) -> int:
        """Columns gathered into the join's output or its condition."""
        return len(self._cond_schema.fields)

    def describe(self):
        c = f" cond={self.condition!r}" if self.condition is not None \
            else ""
        narrowed = len(self.left_out) + len(self.right_out) < \
            len(self.left.output_schema.fields) \
            + len(self.right.output_schema.fields)
        p = f" payload={self._cond_schema.names}" if narrowed else ""
        return (f"{self.pretty_name()} [{self.join_type}] "
                f"keys={list(zip(self.left_keys, self.right_keys))}{c}{p}")

    # --- staged device execution -----------------------------------------

    def _cross(self):
        return self.join_type == "cross" or not self.left_keys

    def _stage_a(self, lbatch: TpuBatch, rbatch: TpuBatch, ectx, jt: str):
        """Stage A: match plan + total output rows + per-string-column
        output byte counts — everything sizing needs, in ONE program, so
        the staged join pays a single host sync per stream batch."""
        lkeys = [k.eval_tpu(lbatch, ectx) for k in self.left_keys]
        rkeys = [k.eval_tpu(rbatch, ectx) for k in self.right_keys]
        plan = join_counts(lkeys, rkeys, lbatch.live_mask(),
                           rbatch.live_mask(), cross=self._cross())
        lpay, rpay = self._payload(lbatch, rbatch)
        return plan, join_total(plan, jt), \
            join_output_bytes(plan, lpay, rpay, jt)

    def _stage_b(self, jt: str, out_cap: int, plan):
        return join_indices(plan, jt, out_cap)

    def _stage_bc(self, jt: str, out_cap: int, char_caps: tuple, plan,
                  lbatch, rbatch):
        """Stages B+C fused: output indices and the gather in one
        program (the second sync the old pipeline paid between them is
        gone — sizing came from stage A)."""
        lidx, ridx, lvalid, rvalid, total = join_indices(plan, jt, out_cap)
        if jt in ("left_semi", "left_anti"):
            from ..ops.gather import gather_batch
            return gather_batch(lbatch, lidx, total,
                                char_capacities=list(char_caps))
        return join_gather(lbatch, rbatch, lidx, ridx, lvalid, rvalid,
                           total, self._schema, char_caps)

    def _char_caps(self, nbytes: List[int], lbatch: TpuBatch,
                   rbatch: TpuBatch, jt: str) -> tuple:
        char_caps = []
        bi = 0
        semi = jt in ("left_semi", "left_anti")
        cols = list(lbatch.columns) + ([] if semi else
                                       list(rbatch.columns))
        for c in cols:
            if c.is_string_like:
                char_caps.append(bucket_bytes(max(nbytes[bi], 1)))
                bi += 1
            else:
                char_caps.append(0)
        return tuple(char_caps)

    def _sized_stage_a(self, lbatch: TpuBatch, rbatch: TpuBatch,
                       ctx: ExecCtx, jt: str):
        """Stage A + THE single host size sync: (plan, out_cap,
        char_caps). One source of truth for the sizing protocol shared
        by the hash-join and nested-loop paths."""
        if self._jit_a is None:
            self._jit_a = named_jit("join_count", self._stage_a,
                                    static_argnums=(2, 3))
        plan, total_dev, bytes_dev = self._jit_a(lbatch, rbatch,
                                                 ctx.eval_ctx, jt)
        total, nbytes = jax.device_get((total_dev, bytes_dev))
        out_cap = bucket_rows(int(total))
        char_caps = self._char_caps([int(v) for v in nbytes],
                                    *self._payload(lbatch, rbatch), jt)
        return plan, out_cap, char_caps

    def _stage_ab(self, lbatch: TpuBatch, rbatch: TpuBatch, ctx: ExecCtx,
                  jt: str):
        """_sized_stage_a + output indices — the nested-loop pair path's
        entry (the hash join uses _join_batch, which fuses the index
        build into the gather program instead)."""
        plan, out_cap, char_caps = self._sized_stage_a(lbatch, rbatch,
                                                       ctx, jt)
        bkey = (jt, out_cap)
        bfn = self._jit_b.get(bkey)
        if bfn is None:
            bfn = named_jit("join_indices",
                            partial(self._stage_b, jt, out_cap))
            self._jit_b[bkey] = bfn
        lidx, ridx, lvalid, rvalid, total_d = bfn(plan)
        return plan, out_cap, lidx, ridx, lvalid, rvalid, total_d, \
            char_caps

    def _join_batch(self, lbatch: TpuBatch, rbatch: TpuBatch,
                    ctx: ExecCtx, jt: Optional[str] = None,
                    want_matched: bool = False):
        """Join one stream batch against the build batch with join type
        `jt` (defaults to the exec's type — the chunked outer-join loop
        passes the per-chunk type). With want_matched, also returns the
        per-build-row matched mask for cross-batch accumulation."""
        jt = jt or self.join_type
        plan, out_cap, char_caps = self._sized_stage_a(lbatch, rbatch,
                                                       ctx, jt)
        ckey = (jt, out_cap, char_caps)
        cfn = self._jit_c.get(ckey)
        if cfn is None:
            cfn = named_jit("join_gather", partial(
                self._stage_bc, jt, out_cap, char_caps))
            self._jit_c[ckey] = cfn
        out = cfn(plan, *self._payload(lbatch, rbatch))
        if self.condition is not None:
            ectx = ctx.eval_ctx
            pred = self.condition.eval_tpu(out, ectx)
            out = compact_batch(out, pred.data & pred.validity)
        if want_matched:
            return out, plan.matched_r
        return out

    # --- sync-free fast path (unique build side) --------------------------

    def _fast_build_info(self, rbatch: TpuBatch, ctx: ExecCtx):
        """None, or a dict describing the unique-build fast path for this
        build side. Costs at most ONE small host readback per build
        (zero with build_unique_hint on a string-free build) — vs one
        readback per stream batch on the staged path. Each readback
        drains the dispatch stream (0.6 ms per dispatch+block round trip
        on the v5e, with no lasting change of regime — chip run of
        PR 21), so its count, not its bytes, is the price."""
        jt = self.join_type
        if jt not in _FAST_JOIN_TYPES or self._cross():
            return None
        if self.condition is not None and jt != "inner":
            return None  # staged path rejects these too (tpu_supported)
        if rbatch.capacity == 0:
            return None
        semi = jt in ("left_semi", "left_anti")
        has_strings = not semi and any(
            rbatch.columns[i].is_string_like for i in self.right_out)
        from ..config import JOIN_VERIFY_UNIQUE_HINT
        verify = ctx.conf.get(JOIN_VERIFY_UNIQUE_HINT)
        maxlens: List[int] = []
        analyzed = False
        if not (self.build_unique_hint and not has_strings):
            if self._jit_analysis is None:
                self._jit_analysis = named_jit(
                    "join_build_analysis",
                    lambda rb, ectx: unique_build_analysis(
                        [k.eval_tpu(rb, ectx) for k in self.right_keys],
                        rb.live_mask(),
                        [] if semi else [rb.columns[i]
                                         for i in self.right_out]),
                    static_argnums=1)
            facts = [int(v) for v in jax.device_get(
                self._jit_analysis(rbatch, ctx.eval_ctx))]
            max_dup, maxlens = facts[0], facts[1:]
            analyzed = True
            if max_dup > 1:
                # a duplicated build key: the staged path is the one
                # that handles duplicates. With a (false) hint this is
                # the free eager validation — the analysis readback
                # already happened (ADVICE r4 #4: the value was being
                # computed and discarded). verifyUniqueHint=false keeps
                # the trust-me contract symmetric with the zero-
                # readback path: the hint is honored unchecked.
                if self.build_unique_hint and not verify:
                    pass  # documented unchecked mode
                else:
                    if self.build_unique_hint:
                        import warnings
                        warnings.warn(
                            f"build_unique hint is FALSE on "
                            f"{self.node_label()} (max key duplication "
                            f"{max_dup}); reverting to the staged join "
                            "path", RuntimeWarning)
                    return None
        probe = None
        dup_flag = None
        kd = self.right_keys[0].dtype
        if len(self.left_keys) == 1 and kd.np_dtype is not None \
                and not dt.is_nested(kd) \
                and not isinstance(kd, dt.NullType):
            if self._jit_probe is None:
                self._jit_probe = named_jit(
                    "join_build_probe",
                    lambda rb, ectx: unique_build_probe(
                        self.right_keys[0].eval_tpu(rb, ectx),
                        rb.live_mask()),
                    static_argnums=1)
            rk_sorted, perm, n_elig, dup_flag = \
                self._jit_probe(rbatch, ctx.eval_ctx)
            probe = (rk_sorted, perm, n_elig)
        if self.build_unique_hint and verify and not analyzed:
            # zero-readback regime: record the device-side duplicate
            # probe; a false hint raises at the query's first natural
            # download instead of silently dropping matches
            if dup_flag is None:
                from ..ops.join import build_dup_flag
                if self._jit_dup is None:
                    self._jit_dup = named_jit(
                        "join_build_dup",
                        lambda rb, ectx: build_dup_flag(
                            [k.eval_tpu(rb, ectx)
                             for k in self.right_keys],
                            rb.live_mask()),
                        static_argnums=1)
                dup_flag = self._jit_dup(rbatch, ctx.eval_ctx)
            ctx.add_deferred_check(
                dup_flag,
                f"build_unique hint violated on {self.node_label()}: "
                "the build side has duplicate join keys, so fast-path "
                "results dropped matches. Remove build_unique=True or "
                "set spark.rapids.sql.join.verifyUniqueHint=false to "
                "accept the hint unchecked.")
        return {"probe": probe, "maxlens": maxlens}

    def _fast_kernel(self, jt: str, char_caps: tuple, has_cond: bool,
                     lbatch, rbatch, probe, ectx):
        """The whole per-batch join in ONE program with NO size sync:
        output capacity = stream capacity, emitted rows marked by a lazy
        selection mask (TpuBatch docstring) that downstream mask-aware
        consumers read through for free."""
        live_l = lbatch.live_mask()
        lkeys = [k.eval_tpu(lbatch, ectx) for k in self.left_keys]
        eligible_l = live_l
        for k in lkeys:
            eligible_l = eligible_l & k.validity
        if probe is not None:
            rk_sorted, perm_r, n_elig = probe
            ridx, matched = probe_unique(lkeys[0], eligible_l, rk_sorted,
                                         perm_r, n_elig)
        else:
            live_r = rbatch.live_mask()
            rkeys = [k.eval_tpu(rbatch, ectx) for k in self.right_keys]
            eligible_r = live_r
            for k in rkeys:
                eligible_r = eligible_r & k.validity
            ridx, matched = unique_union_lookup(
                lkeys, rkeys, live_l, live_r, eligible_l, eligible_r)
        lpay, rpay = self._payload(lbatch, rbatch)
        if jt == "left_semi":
            return TpuBatch(lpay.columns, self._schema,
                            lbatch.row_count,
                            selection=_and_sel(lbatch, matched))
        if jt == "left_anti":
            return TpuBatch(lpay.columns, self._schema,
                            lbatch.row_count,
                            selection=_and_sel(lbatch, live_l & ~matched))
        rcols = gather_columns(rpay.columns, ridx, matched,
                               list(char_caps))
        out_cols = list(lpay.columns) + rcols
        if jt == "inner":
            sel = matched
            if has_cond:
                tmp = TpuBatch(out_cols, self._cond_schema,
                               lbatch.row_count, selection=sel)
                pred = self.condition.eval_tpu(tmp, ectx)
                sel = sel & pred.data & pred.validity
            return TpuBatch(out_cols, self._schema, lbatch.row_count,
                            selection=_and_sel(lbatch, sel))
        # left_outer: every live stream row emits exactly once
        return TpuBatch(out_cols, self._schema, lbatch.row_count,
                        selection=lbatch.selection)

    def _fast_join_batch(self, lbatch: TpuBatch, rbatch: TpuBatch,
                         ctx: ExecCtx, info) -> Optional[TpuBatch]:
        """Fast-path join of one stream batch; None when this batch's
        string sizing falls outside the fast envelope (caller reverts to
        the staged path for it)."""
        jt = self.join_type
        char_caps: List[int] = []
        if jt not in ("left_semi", "left_anti"):
            mi = 0
            for c in self._cut(rbatch, 1).columns:
                if c.is_string_like:
                    need = lbatch.capacity * max(info["maxlens"][mi], 1)
                    if need > _FAST_MAX_CHAR_CAP:
                        return None
                    char_caps.append(bucket_bytes(need))
                    mi += 1
                else:
                    char_caps.append(0)
        key = (jt, lbatch.capacity, rbatch.capacity, tuple(char_caps),
               self.condition is not None, info["probe"] is not None)
        fn = self._jit_fast.get(key)
        if fn is None:
            fn = named_jit("join_probe", partial(
                self._fast_kernel, jt, tuple(char_caps),
                self.condition is not None), static_argnums=3)
            self._jit_fast[key] = fn
        return fn(lbatch, rbatch, info["probe"], ctx.eval_ctx)

    def _build_right(self, ctx: ExecCtx):
        """(spillable build batch, owned): the build side registers in the
        spill catalog (ledger-accounted; evictable until pinned). A
        broadcast child shares its existing catalog handle instead of
        re-registering the same buffers. Returns (None, False) for an
        empty build side."""
        from .exchange import TpuBroadcastExchangeExec
        if isinstance(self.right, TpuBroadcastExchangeExec):
            sb = self.right.spillable(ctx)
            if sb is not None:
                sb.pin()  # refcounted; routed to the OWNING manager
            owned = False
        else:
            batches = list(self.right.execute(ctx))
            if not batches:
                return None, False
            # bounded concat: sync-free (a row-count readback here would
            # drain the dispatch stream before the stream loop starts);
            # pinned at registration so eviction must not pick the batch
            # we are about to stream against
            from ..ops.concat import concat_batches_bounded
            sb = ctx.mm.register(concat_batches_bounded(batches),
                                 pinned=True)
            owned = True
        return sb, owned

    @staticmethod
    def _empty_batch(schema: dt.Schema) -> TpuBatch:
        from ..columnar.arrow_bridge import arrow_to_device
        rb = pa.RecordBatch.from_arrays(
            [pa.array([], type=dt.to_arrow(f.dtype)) for f in schema],
            schema=arrow_schema(schema))
        return arrow_to_device(rb, schema)

    def _acquire_build(self, ctx: ExecCtx):
        """(rsb, owned): the pinned spillable build side, with the
        empty-build fallback applied. rsb None means the join's result
        is already decided empty (semi/inner/cross/right-outer with an
        empty build)."""
        rsb, owned = self._build_right(ctx)
        if rsb is None:
            # nothing can match; for semi/inner/cross/right-outer the
            # result is empty, for the others every left row is unmatched
            if self.join_type in ("inner", "cross", "left_semi",
                                  "right_outer"):
                return None, False
            rsb = ctx.mm.register(
                self._empty_batch(self.right.output_schema), pinned=True)
            owned = True
        return rsb, owned

    def execute(self, ctx: ExecCtx):
        if self.tpu_supported() is not None:
            # device post-filtering is wrong for outer joins and
            # out-of-range for semi/anti (left-only output vs left+right
            # cond schema); fail loudly on the DEVICE path instead of
            # trusting the planner to honor tpu_supported(). The CPU
            # oracle (execute_cpu) handles these correctly.
            raise NotImplementedError(self.tpu_supported())
        op_time = ctx.metric(self, "opTime")
        label = self.node_label()

        def span(phase):  # spark:op, with the width the join gathers
            return ctx.tracer.span(label, cat="op", kind="op", args={
                "op": label, "phase": phase,
                "payload_cols": self.payload_cols()})

        t0 = time.perf_counter()
        with span("build"):
            rsb, owned = self._acquire_build(ctx)
        if rsb is None:
            return
        op_time.value += time.perf_counter() - t0
        try:
            if self.join_type in ("right_outer", "full_outer"):
                yield from self._execute_outer_build(rsb, ctx, op_time)
                return
            t0 = time.perf_counter()
            with span("build"):
                fast = self._fast_build_info(rsb.get(), ctx)
            op_time.value += time.perf_counter() - t0
            for lbatch in self.left.execute(ctx):
                t0 = time.perf_counter()
                out = None
                with span("probe"):
                    if fast is not None:
                        out = self._fast_join_batch(lbatch, rsb.get(),
                                                    ctx, fast)
                    if out is None:
                        out = self._join_batch(lbatch, rsb.get(), ctx)
                    if ctx.sync_metrics:
                        out.block_until_ready()
                op_time.value += time.perf_counter() - t0
                yield out
        finally:
            rsb.unpin()
            if owned:
                rsb.release()

    def _execute_outer_build(self, rsb, ctx: ExecCtx, op_time):
        """right/full outer with a STREAMED stream side: each stream
        batch joins as inner (right) / left_outer (full) while the
        per-build-row matched mask accumulates across batches; the
        unmatched build rows are emitted once at the end via a
        right_outer join against an empty stream batch (reusing the
        staged kernel's sizing machinery). This replaces the old
        concat-the-whole-stream-side call — the stream side no longer
        materializes (SURVEY.md §5.7)."""
        chunk_jt = "inner" if self.join_type == "right_outer" \
            else "left_outer"
        any_matched = None
        for lbatch in self.left.execute(ctx):
            t0 = time.perf_counter()
            out, m = self._join_batch(lbatch, rsb.get(), ctx, chunk_jt,
                                      want_matched=True)
            any_matched = m if any_matched is None else any_matched | m
            if ctx.sync_metrics:
                out.block_until_ready()
            op_time.value += time.perf_counter() - t0
            yield out
        t0 = time.perf_counter()
        rbatch = rsb.get()
        if any_matched is None:
            unmatched = jnp.ones((rbatch.capacity,), jnp.bool_)
        else:
            unmatched = ~any_matched
        lempty = self._empty_batch(self.left.output_schema)
        out = self._join_batch(lempty, rbatch.with_selection(unmatched),
                               ctx, "right_outer")
        op_time.value += time.perf_counter() - t0
        yield out

    # --- CPU oracle -------------------------------------------------------

    def execute_cpu(self, ctx: ExecCtx):
        lt = [rb for rb in self.left.execute_cpu(ctx)]
        rt = [rb for rb in self.right.execute_cpu(ctx)]
        lrows, lkeys = self._cpu_rows(lt, self.left_keys, ctx,
                                      self.left_out)
        rrows, rkeys = self._cpu_rows(rt, self.right_keys, ctx,
                                      self.right_out)
        jt = self.join_type
        cross = self._cross()

        index: Dict[object, List[int]] = {}
        for j, key in enumerate(rkeys):
            if key is None and not cross:
                continue
            index.setdefault(key if not cross else 0, []).append(j)

        out: List[tuple] = []
        matched_right = set()
        for i, key in enumerate(lkeys):
            matches = index.get(key if not cross else 0, []) \
                if (key is not None or cross) else []
            if jt == "left_semi":
                if self._any_cond_match(lrows[i], rrows, matches, ctx):
                    out.append(lrows[i])
                continue
            if jt == "left_anti":
                if not self._any_cond_match(lrows[i], rrows, matches, ctx):
                    out.append(lrows[i])
                continue
            emitted = False
            for j in matches:
                row = lrows[i] + rrows[j]
                if self.condition is not None and \
                        not self._cond_ok(row, ctx):
                    continue
                out.append(row)
                matched_right.add(j)
                emitted = True
            if not emitted and jt in ("left_outer", "full_outer"):
                out.append(lrows[i] + (None,) * len(self.right_out))
        if jt in ("right_outer", "full_outer"):
            nl = len(self.left_out)
            for j, row in enumerate(rrows):
                if j not in matched_right:
                    out.append((None,) * nl + row)
        yield self._rows_to_batch(out)

    def _cpu_rows(self, rbs, key_exprs, ctx, out):
        """(payload rows, key tuples) of one side: the rows hold the
        ``out`` columns only, the keys are read from the whole batch."""
        rows: List[tuple] = []
        keys: List[object] = []
        for rb in rbs:
            cols = [rb.column(i).to_pylist() for i in out]
            kcols = [k.eval_cpu(rb, ctx.eval_ctx).to_pylist()
                     for k in key_exprs]
            for r in range(rb.num_rows):
                rows.append(tuple(c[r] for c in cols))
                key = []
                has_null = False
                for kc in kcols:
                    v = kc[r]
                    if v is None:
                        has_null = True
                        break
                    if isinstance(v, float):
                        if math.isnan(v):
                            v = "\x00__NaN__"
                        elif v == 0.0:
                            v = 0.0
                    key.append(v)
                keys.append(None if has_null else tuple(key))
        return rows, keys

    def _cond_ok(self, row, ctx) -> bool:
        arrays = [pa.array([row[i]], type=dt.to_arrow(f.dtype))
                  for i, f in enumerate(self._cond_schema.fields)]
        rb = pa.RecordBatch.from_arrays(
            arrays, schema=arrow_schema(self._cond_schema))
        res = self.condition.eval_cpu(rb, ctx.eval_ctx).to_pylist()[0]
        return bool(res)

    def _any_cond_match(self, lrow, rrows, matches, ctx) -> bool:
        if self.condition is None:
            return bool(matches)
        return any(self._cond_ok(lrow + rrows[j], ctx) for j in matches)

    def _rows_to_batch(self, rows: List[tuple]) -> pa.RecordBatch:
        schema = self._schema  # for semi/anti this is the left schema
        arrays = []
        for i, f in enumerate(schema.fields):
            arrays.append(pa.array([r[i] for r in rows],
                                   type=dt.to_arrow(f.dtype)))
        return pa.RecordBatch.from_arrays(arrays,
                                          schema=arrow_schema(schema))


class TpuShuffledHashJoinExec(_BaseJoinExec):
    """Local equi-join core (both sides materialized on this chip)."""

    CONTRACT = OpContract(
        requires_copartition=True,
        notes="children that are both shuffle exchanges must agree on "
              "partitioning scheme and partition count; join keys must "
              "be primitive")


class TpuBroadcastHashJoinExec(_BaseJoinExec):
    """Same core; the build side is a broadcast table (exchange layer)."""


class TpuCartesianProductExec(_BaseJoinExec):
    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 left_out=None, right_out=None):
        super().__init__([], [], "cross", left, right, condition,
                         left_out=left_out, right_out=right_out)

    def _rebuilt(self, left, right, left_keys, right_keys, condition,
                 left_out, right_out):
        return TpuCartesianProductExec(left, right, condition,
                                       left_out=left_out,
                                       right_out=right_out)


class TpuBroadcastNestedLoopJoinExec(_BaseJoinExec):
    """Nested-loop join: every (stream row, build row) pair is tested
    against the condition — the path for non-equi-only joins of EVERY
    type (GpuBroadcastNestedLoopJoinExec analog; the hash-join exec
    still rejects non-equi on outer/semi types and plans route here).

    Device kernel per stream batch: the cross-product machinery emits
    all pairs, the condition evaluates over the pair batch, and per-row
    matched masks drive outer/semi/anti emission; matched-build masks
    accumulate across stream batches like the hash join's streamed
    outer path."""

    def __init__(self, join_type: str, left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 left_out=None, right_out=None):
        super().__init__([], [], join_type, left, right, condition,
                         left_out=left_out, right_out=right_out)

    def _rebuilt(self, left, right, left_keys, right_keys, condition,
                 left_out, right_out):
        return TpuBroadcastNestedLoopJoinExec(
            self.join_type, left, right, condition, left_out=left_out,
            right_out=right_out)

    def tpu_supported(self):
        # condition allowed for every join type here; nested columns
        # still can't ride the pair gather
        for schema in (self.left.output_schema, self.right.output_schema):
            for f in schema.fields:
                if dt.is_nested(f.dtype):
                    return (f"nested loop join over nested column "
                            f"{f.name} not on device")
        return None

    def _pairs(self, lbatch: TpuBatch, rbatch: TpuBatch, ctx: ExecCtx):
        """(pair batch | None, ok mask | None, matched_l | None,
        matched_r | None) — each computed only when the exec's join type
        consumes it (semi/anti never materializes payload pairs beyond
        the condition's needs; inner skips the matched masks)."""
        jt = self.join_type
        _, out_cap, lidx, ridx, lvalid, rvalid, total_d, char_caps = \
            self._stage_ab(lbatch, rbatch, ctx, "cross")
        need_pair = jt in ("inner", "cross", "left_outer", "right_outer",
                           "full_outer")
        need_ml = jt in ("left_outer", "full_outer", "left_semi",
                         "left_anti")
        need_mr = jt in ("right_outer", "full_outer")
        ckey = ("pairs", jt, out_cap, char_caps, ctx.eval_ctx)
        cfn = self._jit_c.get(ckey)
        if cfn is None:
            def build(caps, ectx, lb, rb, li, ri, lv, rv, tot):
                from ..ops.join import join_gather
                pair = join_gather(*self._payload(lb, rb), li, ri, lv,
                                   rv, tot, self._cond_schema, caps)
                pred = self.condition.eval_tpu(pair, ectx)
                ok = pred.data & pred.validity & pair.live_mask()
                okl = ok.astype(jnp.int32)
                nl, nr = lb.capacity, rb.capacity
                matched_l = jax.ops.segment_max(
                    okl, jnp.clip(li, 0, nl - 1),
                    num_segments=nl) > 0 if need_ml else None
                matched_r = jax.ops.segment_max(
                    okl, jnp.clip(ri, 0, nr - 1),
                    num_segments=nr) > 0 if need_mr else None
                return (pair if need_pair else None, ok, matched_l,
                        matched_r)
            cfn = named_jit("join_pairs",
                            partial(build, char_caps, ctx.eval_ctx))
            self._jit_c[ckey] = cfn
        return cfn(lbatch, rbatch, lidx, ridx, lvalid, rvalid, total_d)

    def _null_side_batch(self, batch: TpuBatch, keep, left_side: bool,
                         ctx: ExecCtx) -> TpuBatch:
        """Rows of one side (masked by `keep`) joined to nulls of the
        other side, in the output schema."""
        from ..columnar.column import TpuColumnVector
        from ..ops.gather import compact_batch
        lpay, rpay = self._pay_schemas
        kept = compact_batch(batch, keep)
        other = rpay if left_side else lpay
        nulls = [TpuColumnVector.nulls(f.dtype, kept.capacity)
                 for f in other.fields]
        cols = (list(kept.columns) + nulls) if left_side \
            else (nulls + list(kept.columns))
        return TpuBatch(cols, self._schema, kept.row_count)

    def execute(self, ctx: ExecCtx):
        if self.condition is None:
            # pure cross product: the base staged path handles it
            yield from super().execute(ctx)
            return
        if self.tpu_supported() is not None:
            raise NotImplementedError(self.tpu_supported())
        jt = self.join_type
        op_time = ctx.metric(self, "opTime")
        rsb, owned = self._acquire_build(ctx)
        if rsb is None:
            return
        try:
            any_matched_r = None
            for lbatch in self.left.execute(ctx):
                t0 = time.perf_counter()
                rbatch = rsb.get()
                pair, ok, matched_l, matched_r = \
                    self._pairs(lbatch, rbatch, ctx)
                if matched_r is not None:
                    any_matched_r = matched_r if any_matched_r is None \
                        else any_matched_r | matched_r
                if jt in ("inner", "cross", "left_outer", "right_outer",
                          "full_outer"):
                    out = compact_batch(pair, ok)
                    # pair batches carry the cond schema; the output
                    # schema differs in outer-side nullability
                    out = TpuBatch(out.columns, self._schema,
                                   out.row_count)
                    op_time.value += time.perf_counter() - t0
                    yield out
                    t0 = time.perf_counter()
                lpay = self._cut(lbatch, 0)
                if jt in ("left_outer", "full_outer"):
                    unmatched = lbatch.live_mask() & ~matched_l
                    yield self._null_side_batch(lpay, unmatched, True,
                                                ctx)
                elif jt == "left_semi":
                    yield compact_batch(lpay, matched_l
                                        & lbatch.live_mask())
                elif jt == "left_anti":
                    yield compact_batch(lpay, ~matched_l
                                        & lbatch.live_mask())
                op_time.value += time.perf_counter() - t0
            if jt in ("right_outer", "full_outer"):
                rbatch = rsb.get()
                if any_matched_r is None:
                    unmatched = rbatch.live_mask()
                else:
                    unmatched = rbatch.live_mask() & ~any_matched_r
                yield self._null_side_batch(self._cut(rbatch, 1),
                                            unmatched, False, ctx)
        finally:
            rsb.unpin()
            if owned:
                rsb.release()
