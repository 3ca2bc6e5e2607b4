"""Shuffle partitioning strategies.

TPU analog of the reference's `GpuPartitioning.scala` /
`GpuHashPartitioningBase` / `GpuRangePartitioning` (SURVEY.md §2.2-B
"Exchanges"; reference mount empty). Each strategy computes a partition id
per row on device; the split into per-partition batches is stream
compaction per partition (the contiguous_split analog). The same
partition-id logic runs on numpy for the CPU oracle, so row placement is
identical on both paths.
"""
from __future__ import annotations

import bisect
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.batch import TpuBatch
from ..expr.base import Expression
from ..ops.hash import hash_columns_device, hash_columns_numpy, pmod

__all__ = ["Partitioning", "HashPartitioning", "RoundRobinPartitioning",
           "SinglePartitioning", "RangePartitioning"]


class Partitioning:
    """Base: maps each live row to a partition id in [0, num_partitions)."""

    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def bind(self, schema: dt.Schema) -> "Partitioning":
        return self

    def key_expressions(self) -> list:
        """The bound expressions the partition id is computed from."""
        return []

    def map_expressions(self, fn) -> "Partitioning":
        """This partitioning with ``fn`` applied to each key expression
        (column pruning re-binds them to a narrowed child)."""
        return self

    def partition_ids_device(self, batch: TpuBatch, ectx) -> jax.Array:
        raise NotImplementedError

    def partition_ids_cpu(self, rb: pa.RecordBatch, ectx) -> np.ndarray:
        raise NotImplementedError


class SinglePartitioning(Partitioning):
    def __init__(self):
        super().__init__(1)

    def partition_ids_device(self, batch, ectx):
        return jnp.zeros((batch.capacity,), jnp.int32)

    def partition_ids_cpu(self, rb, ectx):
        return np.zeros(rb.num_rows, np.int32)


class RoundRobinPartitioning(Partitioning):
    """Deterministic round-robin (start position 0 per batch)."""

    def partition_ids_device(self, batch, ectx):
        return (jnp.arange(batch.capacity, dtype=jnp.int32)
                % self.num_partitions)

    def partition_ids_cpu(self, rb, ectx):
        return np.arange(rb.num_rows, dtype=np.int32) % self.num_partitions


class HashPartitioning(Partitioning):
    """Spark murmur3-hash partitioning: pmod(hash(keys...), n)."""

    def __init__(self, key_exprs: Sequence[Expression],
                 num_partitions: int):
        super().__init__(num_partitions)
        self.key_exprs = list(key_exprs)

    def bind(self, schema: dt.Schema) -> "HashPartitioning":
        from ..exec.basic import bind_all
        p = HashPartitioning(bind_all(self.key_exprs, schema),
                             self.num_partitions)
        return p

    def key_expressions(self):
        return list(self.key_exprs)

    def map_expressions(self, fn):
        return HashPartitioning([fn(e) for e in self.key_exprs],
                                self.num_partitions)

    def partition_ids_device(self, batch, ectx):
        cols = [e.eval_tpu(batch, ectx) for e in self.key_exprs]
        h = hash_columns_device(cols)
        return pmod(h, self.num_partitions, jnp)

    def partition_ids_cpu(self, rb, ectx):
        arrays = [e.eval_cpu(rb, ectx) for e in self.key_exprs]
        types = [e.dtype for e in self.key_exprs]
        h = hash_columns_numpy(arrays, types, rb.num_rows)
        return np.asarray(pmod(h, self.num_partitions, np))


class RangePartitioning(Partitioning):
    """Range partitioning over sort keys. Bounds are computed once from a
    host-side sample (the caller feeds them via set_bounds) and shared by
    both paths, mirroring the reference's driver-side sampled bounds."""

    def __init__(self, orders, num_partitions: int):
        super().__init__(num_partitions)
        self.orders = list(orders)
        self.bounds: Optional[List[tuple]] = None

    def bind(self, schema: dt.Schema):
        import dataclasses
        from ..expr.base import bind_expr
        p = RangePartitioning(
            [dataclasses.replace(o, child=bind_expr(o.child, schema))
             for o in self.orders], self.num_partitions)
        p.bounds = self.bounds
        return p

    def key_expressions(self):
        return [o.child for o in self.orders]

    def map_expressions(self, fn):
        import dataclasses
        p = RangePartitioning(
            [dataclasses.replace(o, child=fn(o.child))
             for o in self.orders], self.num_partitions)
        p.bounds = self.bounds
        return p

    def compute_bounds(self, sample_rbs: List[pa.RecordBatch], ectx):
        """Sample rows -> (n-1) upper bounds per key tuple."""
        from ..exec.sort import cpu_sort_table
        if not sample_rbs:
            self.bounds = []
            return
        table = pa.Table.from_batches(sample_rbs).combine_chunks()
        rb = table.to_batches()[0] if table.num_rows else None
        if rb is None:
            self.bounds = []
            return
        keys = [o.child.eval_cpu(rb, ectx) for o in self.orders]
        kt = pa.Table.from_arrays(keys,
                                  names=[f"k{i}" for i in range(len(keys))])
        sorted_kt = cpu_sort_table(kt, keys, self.orders)
        n = sorted_kt.num_rows
        bounds = []
        for p in range(1, self.num_partitions):
            idx = min(n - 1, (p * n) // self.num_partitions)
            bounds.append(tuple(sorted_kt.column(i)[idx].as_py()
                                for i in range(len(keys))))
        self.bounds = bounds

    def _row_partition(self, key_tuple) -> int:
        from ..exec.sort import _cpu_pass_key
        lo = 0
        for b in self.bounds or []:
            if _tuple_leq(key_tuple, b, self.orders):
                return lo
            lo += 1
        return lo

    def partition_ids_cpu(self, rb, ectx):
        keys = [o.child.eval_cpu(rb, ectx).to_pylist()
                for o in self.orders]
        out = np.empty(rb.num_rows, np.int32)
        for r in range(rb.num_rows):
            out[r] = self._row_partition(tuple(k[r] for k in keys))
        return out

    def partition_ids_device(self, batch, ectx):
        """Device range ids from the sampled bounds: per key, rows and
        the (k-1) host bounds map into one shared orderable lane space
        (numeric/date/decimal: `orderable_int` over an uploaded bounds
        lane; strings: joint rank refinement over the virtual concat of
        column + bounds), then pid = count of bounds strictly below the
        row tuple — a vectorized (n, k-1) lexicographic compare, the
        searchsorted analog under arbitrary direction/null placement.
        Matches `_row_partition`'s host comparison exactly (null==null,
        NaN largest, -0.0==0.0, direction on values only)."""
        import jax.numpy as jnp
        from ..columnar.column import TpuColumnVector
        from ..expr.base import _np_to_scalar_lane
        from ..ops.sort_keys import (key_lanes_vs_bounds,
                                     normalize_float_key_col)
        if self.bounds is None:
            raise RuntimeError("compute_bounds before the device split")
        cap = batch.capacity
        nb = len(self.bounds)
        if nb == 0:
            return jnp.zeros((cap,), jnp.int32)
        lt = jnp.zeros((cap, nb), jnp.bool_)
        eq = jnp.ones((cap, nb), jnp.bool_)
        for j, o in enumerate(self.orders):
            col = normalize_float_key_col(o.child.eval_tpu(batch, ectx))
            t = o.child.dtype
            bvals = [b[j] for b in self.bounds]
            bvalid = np.array([v is not None for v in bvals], np.bool_)
            if col.is_string_like:
                enc = [v.encode() if isinstance(v, str)
                       else (bytes(v) if v is not None else b"")
                       for v in bvals]
                offs = np.zeros(nb + 1, np.int32)
                offs[1:] = np.cumsum([len(e) for e in enc])
                chars = np.frombuffer(b"".join(enc), np.uint8)
                bcol = TpuColumnVector.from_string_parts(
                    t, offs, chars, bvalid, nb, max(len(chars), 1))
            else:
                lane_np = np.array(
                    [_np_to_scalar_lane(v, t) if v is not None else 0
                     for v in bvals], t.np_dtype)
                bcol = TpuColumnVector.from_numpy(t, lane_np, bvalid, nb)
            rows, bounds = key_lanes_vs_bounds(col, bcol, o.spec)
            for a, b in zip(rows, bounds):
                av, bv = a[:, None], b[None, :]
                lt = lt | (eq & (av < bv))
                eq = eq & (av == bv)
        # bounds ascend, so pid = #bounds with row > bound
        return jnp.sum(~(lt | eq), axis=1).astype(jnp.int32)


def _tuple_leq(a, b, orders) -> bool:
    """a <= b under the sort orders (null/NaN aware)."""
    for av, bv, o in zip(a, b, orders):
        c = _cmp_one(av, bv, o)
        if c != 0:
            return c < 0
    return True


def _cmp_one(av, bv, o) -> int:
    if av is None and bv is None:
        c = 0
    elif av is None:
        c = -1 if o.nulls_first else 1
    elif bv is None:
        c = 1 if o.nulls_first else -1
    else:
        if isinstance(av, float) and math.isnan(av):
            an = True
        else:
            an = False
        if isinstance(bv, float) and math.isnan(bv):
            bn = True
        else:
            bn = False
        if an and bn:
            c = 0
        elif an or bn:
            c = 1 if an else -1
        else:
            c = -1 if av < bv else (1 if av > bv else 0)
        if not o.ascending:
            c = -c
        return c
    return c
