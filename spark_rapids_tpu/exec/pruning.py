"""Required-column pushdown (Spark's ``ColumnPruning``).

Upstream spark-rapids gets pruned plans from Catalyst before
``GpuOverrides`` sees them; this engine has no Catalyst, so
``TpuOverrides.apply`` runs this pass over the exec tree every frontend
builds (SQL text, the DataFrame API, hand-built plans) before it wraps,
tags and converts it. No conf key switches it.

Two hooks on ``TpuExec`` (exec/base.py) carry it:

- ``child_requirements(required)`` — top-down: given the output
  ordinals the parent reads, the ordinals of each child this operator
  reads (its own bound expressions, found where ``expr_bindings`` finds
  them, plus what it passes through). ``None`` states no requirement:
  the operator requires everything of every child and is kept as built;
  pruning goes on below it with the full requirement.
- ``pruned(children, maps, required)`` — bottom-up: the operator
  rebuilt over its narrowed children, every ``BoundReference`` re-bound
  through one old->new ordinal map per child, and the map of its own
  output. A rebuilt operator may keep MORE than ``required`` (a filter
  passes its predicate's columns on); its parent re-binds through the
  map and never reads the extras.

Plans are bound by ordinal, so a stale ordinal is the failure mode; the
static verifier (analysis/plan_verifier.py) runs after the pass on every
plan and rejects one by name before any kernel runs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, Iterable, List, Sequence, Tuple

from .. import datatypes as dt
from ..expr.base import BoundReference

__all__ = ["refs", "remap", "remap_order", "identity_map", "narrowed",
           "projected", "prune_plan"]

Map = Dict[int, int]


def refs(exprs: Iterable) -> set:
    """Ordinals of every ``BoundReference`` in the expression trees."""
    out = set()
    stack = [e for e in exprs if e is not None]
    while stack:
        e = stack.pop()
        if isinstance(e, BoundReference):
            out.add(e.ordinal)
        stack.extend(getattr(e, "children", ()))
    return out


def remap(expr, mapping: Map):
    """``expr`` with every reference re-bound old -> new ordinal."""
    if expr is None or all(k == v for k, v in mapping.items()):
        return expr

    def rebind(node):
        if isinstance(node, BoundReference) \
                and mapping[node.ordinal] != node.ordinal:
            return BoundReference(mapping[node.ordinal], node.dtype,
                                  node.nullable, node.name)
        return node

    return expr.transform(rebind)


def remap_order(order, mapping: Map):
    """A ``SortOrder`` whose key is re-bound through ``mapping``."""
    return dataclasses.replace(order, child=remap(order.child, mapping))


def identity_map(n: int) -> Map:
    return {i: i for i in range(n)}


def _is_identity(mapping: Map, width: int, new_width: int) -> bool:
    return width == new_width and len(mapping) == width \
        and all(k == v for k, v in mapping.items())


def projected(node, mapping: Map, ordinals: Sequence[int]):
    """``node`` under a ``TpuProjectExec`` that emits exactly the old
    ``ordinals``, in order; the map of the projection's output."""
    from .basic import TpuProjectExec
    fields = node.output_schema.fields
    exprs = []
    for o in ordinals:
        f = fields[mapping[o]]
        exprs.append(BoundReference(mapping[o], f.dtype, f.nullable,
                                    f.name))
    return TpuProjectExec(exprs, node), {o: i for i, o in
                                         enumerate(ordinals)}


def narrowed(node, mapping: Map, needed: Iterable[int],
             force: bool = False):
    """Drop what a rebuilt child emits beyond ``needed`` (a filter's
    predicate columns under a sort, an exchange or a join build) where
    that costs no program: the child is a fusable row-wise map, so the
    projection joins its chain (``fused_batches``). ``force`` projects
    over any child (union: every child must come out in one layout)."""
    needed = sorted(needed)
    exact = len(node.output_schema.fields) == len(needed) \
        and all(mapping[o] == i for i, o in enumerate(needed))
    if exact:
        return node, {o: i for i, o in enumerate(needed)}
    if force or node.device_fn() is not None:
        return projected(node, mapping, needed)
    return node, mapping


def _cheapest(schema: dt.Schema) -> int:
    """The column kept where no column is required but the rows still
    count (``select count(*)``): the narrowest fixed-width one, the
    first among equals; a string only where nothing else is there."""
    best, best_w = 0, None
    for i, f in enumerate(schema.fields):
        npd = getattr(f.dtype, "np_dtype", None)
        fixed = npd is not None and not dt.is_nested(f.dtype) \
            and not isinstance(f.dtype, (dt.StringType, dt.BinaryType))
        w = npd.itemsize if fixed else 1 << 20
        if best_w is None or w < best_w:
            best, best_w = i, w
    return best


def _prune(node, required: FrozenSet[int], memo: dict) -> Tuple[object, Map]:
    width = len(node.output_schema.fields)
    if not required and width:
        required = frozenset((_cheapest(node.output_schema),))
    key = (id(node), required)
    hit = memo.get(key)
    if hit is not None:
        return hit
    reqs = node.child_requirements(required)
    if reqs is None:
        # no stated requirement: everything of every child
        if node.PRUNE_BELOW:
            kids = []
            for c in node.children:
                n = len(c.output_schema.fields)
                k, m = _prune(c, frozenset(range(n)), memo)
                if not _is_identity(m, n, len(k.output_schema.fields)):
                    k, _ = projected(k, m, list(range(n)))
                kids.append(k)
            out = node.with_new_children(kids)
        else:
            out = node
        result = (out, identity_map(width))
    else:
        kids, maps = [], []
        for c, r in zip(node.children, reqs):
            k, m = _prune(c, frozenset(r), memo)
            kids.append(k)
            maps.append(m)
        # the same plan pruned again (a DataFrame collected twice) gives
        # the same operator objects, so their compiled programs are kept
        stable = node.__dict__.setdefault("_pruned_memo", {})
        kept = stable.get(required)
        if kept is not None and len(kept[0]) == len(kids) \
                and all(a is b for a, b in zip(kept[0], kids)):
            result = kept[1]
        else:
            result = node.pruned(kids, maps, required)
            stable[required] = (tuple(kids), result)
    memo[key] = result
    return result


def prune_plan(root):
    """The plan with every operator reading, and every scan decoding,
    only the columns some operator above it reads. The root keeps its
    whole output, in order."""
    width = len(root.output_schema.fields)
    memo: dict = {}
    out, mapping = _prune(root, frozenset(range(width)), memo)
    if not _is_identity(mapping, width, len(out.output_schema.fields)):
        out, _ = projected(out, mapping, list(range(width)))
    return out
