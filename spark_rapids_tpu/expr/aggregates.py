"""Aggregate functions.

TPU analog of the reference's `aggregate/` + `GpuAggregateFunction.scala`
(SURVEY.md §2.2-C; reference mount empty). Each function defines the
classic three-phase contract over *segmented* device data (the sort-based
group-by — SURVEY.md §7.1.3):

- ``update_device``   — raw sorted input rows -> per-group partial buffers
- ``merge_device``    — sorted partial buffers -> merged buffers
- ``evaluate_device`` — merged buffers -> final result column
- ``cpu_agg``         — Spark-semantics oracle over one group's python
  values (complete mode), for the dual-run harness.

Rows arrive sorted by group key; ``seg`` is the segment id per sorted row,
``sorted_live`` masks padding, and buffers live in output rows
[0, num_groups) of the same static capacity.
"""
from __future__ import annotations

import decimal
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import datatypes as dt
from ..columnar.column import TpuColumnVector
from .base import Expression

__all__ = ["AggregateFunction", "Sum", "Count", "Min", "Max", "Average",
           "First", "Last", "StddevSamp", "StddevPop", "VarianceSamp",
           "VariancePop", "CollectList", "CollectSet", "ApproxPercentile"]

_I64 = jnp.int64
_F64 = jnp.float64

# Global (no-key) aggregates pass seg=None: one segment, reduced with
# plain jnp reductions into a tiny fixed lane count — segment_* lowers to
# scatter-add, which costs ~100ms/2M rows on TPU, vs ~0 for a reduce.
GLOBAL_LANES = 128


def _lane0(value, dtype):
    out = jnp.zeros((GLOBAL_LANES,), dtype)
    return out.at[0].set(value.astype(dtype))


def _out_cap(seg):
    return GLOBAL_LANES if seg is None else seg.shape[0]


# seg is ALWAYS the sorted segment ids from segment_ids_for_keys here
# (the aggregate exec sorts by keys first), so the reductions use the
# scatter-free sorted-segment kernels — jax.ops.segment_* scatters cost
# ~100ms/2M rows on TPU and dominated the whole join+agg pipeline.
from ..ops.segments import seg_reduce_sorted


def _seg_sum(vals, seg, cap):
    if seg is None:
        return _lane0(jnp.sum(vals), vals.dtype)
    return seg_reduce_sorted(vals, seg, cap, "sum")


def _seg_min(vals, seg, cap):
    if seg is None:
        return _lane0(jnp.min(vals), vals.dtype)
    return seg_reduce_sorted(vals, seg, cap, "min")


def _seg_max(vals, seg, cap):
    if seg is None:
        return _lane0(jnp.max(vals), vals.dtype)
    return seg_reduce_sorted(vals, seg, cap, "max")


def _type_extreme(np_dtype, largest: bool):
    if jnp.issubdtype(np_dtype, jnp.floating):
        return jnp.inf if largest else -jnp.inf
    info = jnp.iinfo(np_dtype)
    return info.max if largest else info.min


class AggregateFunction(Expression):
    """Base aggregate. children = input value expressions."""

    is_aggregate = True

    @property
    def nullable(self):
        # aggregates are null over an empty (global) group; Count overrides
        return True

    @property
    def buffer_fields(self) -> List[dt.StructField]:
        raise NotImplementedError

    def update_device(self, vals: List[TpuColumnVector], seg, sorted_live,
                      out_live) -> List[TpuColumnVector]:
        raise NotImplementedError

    def merge_device(self, bufs: List[TpuColumnVector], seg, sorted_live,
                     out_live) -> List[TpuColumnVector]:
        raise NotImplementedError

    def evaluate_device(self, bufs: List[TpuColumnVector]) \
            -> TpuColumnVector:
        raise NotImplementedError

    def cpu_agg(self, values: List, ectx=None):
        raise NotImplementedError


def _masked(col: TpuColumnVector, seg, sorted_live):
    """(data, valid) with padding/null rows excluded from valid."""
    valid = col.validity & sorted_live
    return col.data, valid


def _seg_count_valid(valid, seg, cap):
    return _seg_sum(valid.astype(_I64), seg, cap)


def _sum_lanes(col, seg, sorted_live, cap, acc_dtype):
    data, valid = _masked(col, seg, sorted_live)
    contrib = jnp.where(valid, data.astype(acc_dtype),
                        jnp.zeros((), acc_dtype))
    s = _seg_sum(contrib, seg, cap)
    cnt = _seg_count_valid(valid, seg, cap)
    return s, cnt


class Sum(AggregateFunction):
    """Spark sum: integral->long (wrapping when non-ANSI), float->double,
    decimal(p,s)->decimal(p+10,s)."""

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        t = self.children[0].dtype
        if isinstance(t, dt.DecimalType):
            return dt.DecimalType(min(t.precision + 10, 38), t.scale)
        if dt.is_floating(t):
            return dt.FLOAT64
        return dt.INT64

    def tpu_supported(self):
        t = self.dtype
        if isinstance(t, dt.DecimalType) \
                and t.precision > dt.DecimalType.MAX_INT64_PRECISION:
            return f"sum result {t.simple_string()} exceeds device decimal"
        return None

    @property
    def buffer_fields(self):
        return [dt.StructField("sum", self.dtype, True)]

    def _acc(self):
        return _F64 if dt.is_floating(self.dtype) else _I64

    def _null_overflowed(self, s, valid):
        """Decimal sum overflow -> NULL (Spark non-ANSI): null groups whose
        unscaled |sum| exceeds the result precision's max. Detectable up to
        int64 wrap (|sum| < 2^63); beyond that the accumulator itself
        wrapped — same bound as a 128-bit cudf accumulator overflowing."""
        t = self.dtype
        if not isinstance(t, dt.DecimalType):
            return valid
        max_unscaled = 10 ** min(t.precision,
                                 dt.DecimalType.MAX_INT64_PRECISION) - 1
        return valid & (jnp.abs(s) <= max_unscaled)

    def update_device(self, vals, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        s, cnt = _sum_lanes(vals[0], seg, sorted_live, cap, self._acc())
        valid = self._null_overflowed(s, (cnt > 0) & out_live)
        return [TpuColumnVector(self.dtype, data=s, validity=valid)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        s, cnt = _sum_lanes(bufs[0], seg, sorted_live, cap, self._acc())
        valid = self._null_overflowed(s, (cnt > 0) & out_live)
        return [TpuColumnVector(self.dtype, data=s, validity=valid)]

    def evaluate_device(self, bufs):
        return bufs[0]

    def cpu_agg(self, values, ectx=None):
        vals = [v for v in values if v is not None]
        if not vals:
            return None
        t = self.dtype
        if isinstance(t, dt.DecimalType):
            with decimal.localcontext() as dctx:
                dctx.prec = 60  # default 28 rounds/overflows wide sums
                total = sum(vals, decimal.Decimal(0))
                unscaled = int(total.scaleb(t.scale))
                # Spark semantics: overflow past the RESULT precision
                # (p+10, up to 38) -> NULL (non-ANSI) / error (ANSI). The
                # device cap of 18 digits does not leak into the oracle;
                # result types wider than 18 are device-unsupported
                # (tpu_supported) and run through this CPU path only.
                if abs(unscaled) > 10 ** t.precision - 1:
                    if ectx is not None and ectx.ansi:
                        from .base import ExprError
                        raise ExprError("decimal sum overflow (ANSI mode)")
                    return None  # Spark non-ANSI: overflow -> NULL
                return total.quantize(decimal.Decimal(1).scaleb(-t.scale))
        if dt.is_floating(t):
            return float(sum(float(v) for v in vals))
        total = sum(int(v) for v in vals)
        if ectx is not None and ectx.ansi and not (
                -(1 << 63) <= total < (1 << 63)):
            from .base import ExprError
            raise ExprError("long sum overflow (ANSI mode)")
        total &= (1 << 64) - 1  # java long wrap-around (non-ANSI)
        return total - (1 << 64) if total >= (1 << 63) else total


class Count(AggregateFunction):
    """count(expr) counts non-null; count(*) (no child) counts rows."""

    def __init__(self, child: Optional[Expression] = None):
        self.children = (child,) if child is not None else ()

    @property
    def dtype(self):
        return dt.INT64

    @property
    def nullable(self):
        return False

    @property
    def buffer_fields(self):
        return [dt.StructField("count", dt.INT64, False)]

    def update_device(self, vals, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        if vals:
            _, valid = _masked(vals[0], seg, sorted_live)
        else:
            valid = sorted_live
        cnt = _seg_count_valid(valid, seg, cap)
        return [TpuColumnVector(dt.INT64, data=cnt, validity=out_live)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        data, valid = _masked(bufs[0], seg, sorted_live)
        s = _seg_sum(jnp.where(valid, data, 0), seg, cap)
        return [TpuColumnVector(dt.INT64, data=s, validity=out_live)]

    def evaluate_device(self, bufs):
        return bufs[0]

    def cpu_agg(self, values, ectx=None):
        if not self.children:
            return len(values)
        return sum(1 for v in values if v is not None)


class _MinMax(AggregateFunction):
    largest = False

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return self.children[0].dtype

    def tpu_supported(self):
        if self.children[0].dtype.is_variable_width:
            return "min/max over strings not yet on device"
        return None

    @property
    def buffer_fields(self):
        return [dt.StructField("m", self.dtype, True)]

    def _reduce(self, col, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        data, valid = _masked(col, seg, sorted_live)
        t = self.dtype
        if dt.is_floating(t):
            # Spark: NaN is the largest value; -0.0 == 0.0 (keep either)
            key_col = TpuColumnVector(t, data=data, validity=valid)
            from ..ops.sort_keys import (orderable_int,
                                         orderable_int_to_float)
            keys = orderable_int(key_col)
            fill = jnp.iinfo(keys.dtype).min if self.largest else \
                jnp.iinfo(keys.dtype).max
            keys = jnp.where(valid, keys, fill)
            red = _seg_max(keys, seg, cap) if self.largest else \
                _seg_min(keys, seg, cap)
            out = orderable_int_to_float(red, t.np_dtype)
            cnt = _seg_count_valid(valid, seg, cap)
            return TpuColumnVector(t, data=out,
                                   validity=(cnt > 0) & out_live)
        is_bool = isinstance(t, dt.BooleanType)
        if is_bool:
            data = data.astype(jnp.int8)
        fill = _type_extreme(data.dtype, largest=not self.largest)
        vals2 = jnp.where(valid, data, jnp.array(fill, data.dtype))
        red = _seg_max(vals2, seg, cap) if self.largest else \
            _seg_min(vals2, seg, cap)
        if is_bool:
            red = red.astype(jnp.bool_)
        cnt = _seg_count_valid(valid, seg, cap)
        return TpuColumnVector(self.dtype, data=red,
                               validity=(cnt > 0) & out_live)

    def update_device(self, vals, seg, sorted_live, out_live):
        return [self._reduce(vals[0], seg, sorted_live, out_live)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        return [self._reduce(bufs[0], seg, sorted_live, out_live)]

    def evaluate_device(self, bufs):
        return bufs[0]

    def cpu_agg(self, values, ectx=None):
        vals = [v for v in values if v is not None]
        if not vals:
            return None
        if dt.is_floating(self.dtype):
            def key(v):
                return (1, 0.0) if math.isnan(v) else (0, v + 0.0)
            return max(vals, key=key) if self.largest \
                else min(vals, key=key)
        return max(vals) if self.largest else min(vals)


class Max(_MinMax):
    largest = True


class Min(_MinMax):
    largest = False


class Average(AggregateFunction):
    """Spark avg: numeric -> double (sum accumulated in double);
    decimal(p,s) -> decimal(p+4, s+4)."""

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        t = self.children[0].dtype
        if isinstance(t, dt.DecimalType):
            return dt.DecimalType(min(t.precision + 4, 38),
                                  min(t.scale + 4, 38))
        return dt.FLOAT64

    def tpu_supported(self):
        t = self.children[0].dtype
        if isinstance(t, dt.DecimalType):
            # evaluate scales the int64 sum by 1e4 before dividing, so the
            # sum buffer needs p+10+4 digits of headroom
            if t.precision + 14 > dt.DecimalType.MAX_INT64_PRECISION:
                return "decimal average exceeds device decimal range"
        return None

    @property
    def buffer_fields(self):
        t = self.children[0].dtype
        sum_t = dt.DecimalType(min(t.precision + 10, 38), t.scale) \
            if isinstance(t, dt.DecimalType) else dt.FLOAT64
        return [dt.StructField("sum", sum_t, True),
                dt.StructField("count", dt.INT64, False)]

    def _is_decimal(self):
        return isinstance(self.children[0].dtype, dt.DecimalType)

    def update_device(self, vals, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        acc = _I64 if self._is_decimal() else _F64
        s, cnt = _sum_lanes(vals[0], seg, sorted_live, cap, acc)
        sum_t = self.buffer_fields[0].dtype
        return [TpuColumnVector(sum_t, data=s,
                                validity=(cnt > 0) & out_live),
                TpuColumnVector(dt.INT64, data=cnt, validity=out_live)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        acc = _I64 if self._is_decimal() else _F64
        s, scnt = _sum_lanes(bufs[0], seg, sorted_live, cap, acc)
        cdata, cvalid = _masked(bufs[1], seg, sorted_live)
        cnt = _seg_sum(jnp.where(cvalid, cdata, 0), seg, cap)
        sum_t = self.buffer_fields[0].dtype
        return [TpuColumnVector(sum_t, data=s,
                                validity=(scnt > 0) & out_live),
                TpuColumnVector(dt.INT64, data=cnt,
                                validity=out_live)]

    def evaluate_device(self, bufs):
        s, cnt = bufs
        valid = s.validity & (cnt.data > 0)
        if self._is_decimal():
            # result scale = input scale + 4: scale the int sum up by 1e4
            # before the rounded divide (HALF_UP like Spark). jnp // floors,
            # so rem is in [0, den); HALF_UP (away from zero) means bump
            # when rem > den/2, or exactly half on a positive quotient.
            t = self.dtype
            num = s.data * 10_000
            den = jnp.where(cnt.data > 0, cnt.data, 1)
            quot = num // den
            rem = num - quot * den
            up = (2 * rem > den) | ((2 * rem == den) & (num > 0))
            out = quot + up.astype(_I64)
            return TpuColumnVector(t, data=out.astype(_I64),
                                   validity=valid)
        den = jnp.where(cnt.data > 0, cnt.data, 1).astype(_F64)
        return TpuColumnVector(dt.FLOAT64, data=s.data / den,
                               validity=valid)

    def cpu_agg(self, values, ectx=None):
        vals = [v for v in values if v is not None]
        if not vals:
            return None
        if self._is_decimal():
            t = self.dtype
            with decimal.localcontext() as ctx2:
                ctx2.prec = 60  # default 28 rounds wide totals
                ctx2.rounding = decimal.ROUND_HALF_UP
                total = sum(vals, decimal.Decimal(0))
                return (total / len(vals)).quantize(
                    decimal.Decimal(1).scaleb(-t.scale),
                    rounding=decimal.ROUND_HALF_UP)
        return float(sum(float(v) for v in vals)) / len(vals)


class _FirstLast(AggregateFunction):
    take_last = False

    def __init__(self, child: Expression, ignore_nulls: bool = False):
        self.children = (child,)
        self.ignore_nulls = ignore_nulls

    @property
    def dtype(self):
        return self.children[0].dtype

    def tpu_supported(self):
        if self.children[0].dtype.is_variable_width:
            return "first/last over strings not yet on device"
        return None

    @property
    def buffer_fields(self):
        return [dt.StructField("v", self.dtype, True)]

    def _pick(self, col, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        data, valid = _masked(col, seg, sorted_live)
        n_in = valid.shape[0]  # input rows; != cap on the global path
        candidate = sorted_live & (valid if self.ignore_nulls
                                   else jnp.ones_like(valid))
        pos = jnp.arange(n_in, dtype=jnp.int32)
        if self.take_last:
            marked = jnp.where(candidate, pos, -1)
            picked = _seg_max(marked, seg, cap)
            found = picked >= 0
        else:
            marked = jnp.where(candidate, pos, n_in)
            picked = _seg_min(marked, seg, cap)
            found = picked < n_in
        idx = jnp.clip(picked, 0, n_in - 1)
        if col.data is None:
            return TpuColumnVector(self.dtype,
                                   validity=jnp.zeros((cap,), jnp.bool_))
        out = data[idx]
        out_valid = found & valid[idx] & out_live
        return TpuColumnVector(self.dtype, data=out, validity=out_valid)

    def update_device(self, vals, seg, sorted_live, out_live):
        return [self._pick(vals[0], seg, sorted_live, out_live)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        return [self._pick(bufs[0], seg, sorted_live, out_live)]

    def evaluate_device(self, bufs):
        return bufs[0]

    def cpu_agg(self, values, ectx=None):
        seq = values if not self.take_last else list(reversed(values))
        for v in seq:
            if v is not None or not self.ignore_nulls:
                return v
        return None


class First(_FirstLast):
    take_last = False


class Last(_FirstLast):
    take_last = True


class _CentralMoment(AggregateFunction):
    """stddev/variance via mergeable (n, mean, M2) buffers — the parallel
    Welford formulation, exact two-pass within a segment, so no
    sum-of-squares catastrophic cancellation."""

    sample = True
    take_sqrt = False

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return dt.FLOAT64

    @property
    def buffer_fields(self):
        return [dt.StructField("n", dt.FLOAT64, False),
                dt.StructField("mean", dt.FLOAT64, False),
                dt.StructField("m2", dt.FLOAT64, False)]

    def update_device(self, vals, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        data, valid = _masked(vals[0], seg, sorted_live)
        x = jnp.where(valid, data.astype(_F64), 0.0)
        n = _seg_sum(valid.astype(_F64), seg, cap)
        s = _seg_sum(x, seg, cap)
        mean = s / jnp.where(n > 0, n, 1.0)
        # second pass: exact centered sum of squares per segment
        mu = mean[0] if seg is None else mean[seg]
        d = jnp.where(valid, x - mu, 0.0)
        m2 = _seg_sum(d * d, seg, cap)
        return [TpuColumnVector(dt.FLOAT64, data=lane, validity=out_live)
                for lane in (n, mean, m2)]

    def merge_device(self, bufs, seg, sorted_live, out_live):
        cap = _out_cap(seg)
        ndata, nvalid = _masked(bufs[0], seg, sorted_live)
        mdata, _ = _masked(bufs[1], seg, sorted_live)
        m2data, _ = _masked(bufs[2], seg, sorted_live)
        n_i = jnp.where(nvalid, ndata, 0.0)
        mdata = jnp.where(nvalid, mdata, 0.0)  # 0*garbage could be NaN
        N = _seg_sum(n_i, seg, cap)
        wsum = _seg_sum(n_i * mdata, seg, cap)
        MEAN = wsum / jnp.where(N > 0, N, 1.0)
        delta = mdata - (MEAN[0] if seg is None else MEAN[seg])
        M2 = _seg_sum(jnp.where(nvalid, m2data + n_i * delta * delta, 0.0),
                      seg, cap)
        return [TpuColumnVector(dt.FLOAT64, data=lane, validity=out_live)
                for lane in (N, MEAN, M2)]

    def evaluate_device(self, bufs):
        n, _, m2 = (b.data for b in bufs)
        m2 = jnp.maximum(m2, 0.0)
        if self.sample:
            # Spark 3.1+ (spark.sql.legacy.statisticalAggregate=false):
            # sample variance of a single value is NULL, not NaN
            var = m2 / jnp.where(n > 1, n - 1, 1.0)
            valid = bufs[0].validity & (n > 1)
        else:
            var = m2 / jnp.where(n > 0, n, 1.0)
            valid = bufs[0].validity & (n > 0)
        out = jnp.sqrt(var) if self.take_sqrt else var
        return TpuColumnVector(dt.FLOAT64, data=out, validity=valid)

    def cpu_agg(self, values, ectx=None):
        vals = [float(v) for v in values if v is not None]
        n = len(vals)
        if n == 0:
            return None
        mean = sum(vals) / n
        m2 = sum((v - mean) ** 2 for v in vals)
        if self.sample:
            if n <= 1:
                return None  # nullOnDivideByZero (Spark 3.1+ default)
            var = m2 / (n - 1)
        else:
            var = m2 / n
        return math.sqrt(var) if self.take_sqrt else var


class VarianceSamp(_CentralMoment):
    sample = True
    take_sqrt = False


class VariancePop(_CentralMoment):
    sample = False
    take_sqrt = False


class StddevSamp(_CentralMoment):
    sample = True
    take_sqrt = True


class StddevPop(_CentralMoment):
    sample = False
    take_sqrt = True


class _Collect(AggregateFunction):
    """collect_list / collect_set: group values into an array column.

    Single-pass aggregates (``single_pass = True``): their result is
    variable-length per group, so they skip the partial/merge pipeline
    (whose buffers concat on device) and the aggregate exec computes
    them in one sorted pass over the whole input (exec/aggregate.py).
    Spark's order is nondeterministic; both paths here emit elements
    value-sorted so the dual-run harness can compare exactly. Nulls are
    skipped; the result is never null (empty array for all-null groups).
    """

    single_pass = True
    dedupe = False

    def __init__(self, child: Expression):
        self.children = (child,)

    @property
    def dtype(self):
        return dt.ArrayType(self.children[0].dtype)

    @property
    def nullable(self):
        return False

    @property
    def buffer_fields(self):
        return []  # no partial buffers: single-pass only

    def tpu_supported(self):
        if dt.is_nested(self.children[0].dtype):
            return (f"{self.pretty_name().lower()} of nested elements "
                    "not on device")
        return None

    def cpu_agg(self, values, ectx=None):
        vals = [v for v in values if v is not None]
        if self.dedupe:
            # tuple-tagged keys: the string "NaN" must never collide
            # with float NaN
            seen, out = set(), []
            for v in vals:
                if isinstance(v, float):
                    k = ("fnan",) if math.isnan(v) else ("f", v + 0.0)
                    canon = float("nan") if math.isnan(v) else v + 0.0
                else:
                    k = ("v", v)
                    canon = v
                if k in seen:
                    continue
                seen.add(k)
                out.append(canon)
            vals = out

        def key(v):
            if isinstance(v, float):
                return (1, 0.0) if math.isnan(v) else (0, v + 0.0)
            if isinstance(v, str):
                return v.encode()  # device sorts by UTF-8 bytes
            return v
        return sorted(vals, key=key)


class CollectList(_Collect):
    dedupe = False


class CollectSet(_Collect):
    dedupe = True


class ApproxPercentile(AggregateFunction):
    """approx_percentile(col, percentage[, accuracy]) — reference:
    GpuApproximatePercentile over a t-digest sketch (SURVEY.md:177).

    TWO device strategies:

    - EXACT single-pass (default, spark.rapids.sql.approxPercentile
      .exact): the group-sort pipeline (exec/aggregate.py) already
      orders each group's values, so the percentile is a rank gather —
      rank error 0, within any accuracy the caller requests.
    - MERGEABLE sketch (conf off, VERDICT r4 #6): a fixed-width
      quantile summary per group — K points at evenly spaced weighted
      ranks (actual data values, endpoints included) + the group count.
      update builds a summary per partial batch, merge unions member
      summaries point-weighted and re-extracts K ranks, evaluate picks
      the point nearest Spark's ceil(p*n) rank. Buffers are K+1
      ordinary fixed-width lanes, so the sketch partials/merges/rides
      exchanges like any other aggregate — a distributed percentile
      moves O(K) values per group, not the group (the property the
      reference's t-digest exists for; this summary IS a t-digest with
      uniform centroid mass). Rank error per merge level <= ~1/K.

    Percentage may be a scalar (returns the input type) or a list
    (returns array<input type>)."""

    single_pass = True  # exact path preference; exec consults the conf

    def __init__(self, child: Expression, percentage,
                 accuracy: int = 10000):
        self.children = (child,)
        self.is_list = isinstance(percentage, (list, tuple))
        ps = list(percentage) if self.is_list else [percentage]
        for p in ps:
            if not (0.0 <= float(p) <= 1.0):
                raise ValueError(f"percentage {p} not in [0, 1]")
        self.percentages = tuple(float(p) for p in ps)
        self.accuracy = accuracy
        # sketch width: sqrt(accuracy) balances buffer width against
        # rank error (~1/K per merge level); Spark default 10000 -> 64
        self.K = int(min(64, max(16, round(accuracy ** 0.5))))

    @property
    def dtype(self):
        t = self.children[0].dtype
        return dt.ArrayType(t) if self.is_list else t

    @property
    def buffer_fields(self):
        t = self.children[0].dtype
        return [dt.StructField(f"q{k}", t, True) for k in range(self.K)] \
            + [dt.StructField("cnt", dt.INT64, True)]

    def tpu_supported(self):
        t = self.children[0].dtype
        if t.is_variable_width or dt.is_nested(t) \
                or isinstance(t, (dt.BooleanType, dt.NullType)):
            return (f"approx_percentile over "
                    f"{t.simple_string()} not supported")
        return None

    # --- mergeable sketch (K quantile points + count) ---------------------

    # compound-key stride (seg, mass) — a plain int, NOT a jnp scalar:
    # a class-level device computation would initialize the XLA backend
    # at import, breaking jax.distributed.initialize for mesh workers
    _MASS_SCALE = 1 << 42

    def update_device(self, vals, seg, sorted_live, out_live):
        from ..ops.sort_keys import orderable_int
        col = vals[0]
        cap = sorted_live.shape[0]
        out_cap = _out_cap(seg)
        segl = seg if seg is not None else jnp.zeros((cap,), jnp.int32)
        valid = col.validity & sorted_live
        lane = jnp.where(valid, orderable_int(col).astype(jnp.int64), 0)
        drop = jnp.where(valid, jnp.int8(0), jnp.int8(1))
        idx = jnp.arange(cap, dtype=jnp.int32)
        sdrop, sseg, _, perm = jax.lax.sort(
            (drop, segl, lane, idx), num_keys=3)
        kseg = jnp.where(sdrop == 0, sseg, jnp.int32(out_cap))
        g = jnp.arange(out_cap, dtype=jnp.int32)
        lo = jnp.searchsorted(kseg, g, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(kseg, g, side="right").astype(jnp.int32)
        n_g = (hi - lo).astype(jnp.int64)
        t = self.children[0].dtype
        qvalid = out_live & (n_g > 0)
        out = []
        for k in range(self.K):
            r = ((n_g - 1) * k) // (self.K - 1)
            pos = jnp.clip(lo + r.astype(jnp.int32), 0, cap - 1)
            v = col.data[perm[pos]]
            out.append(TpuColumnVector(t, data=v, validity=qvalid))
        out.append(TpuColumnVector(dt.INT64, data=n_g,
                                   validity=out_live))
        return out

    def merge_device(self, bufs, seg, sorted_live, out_live):
        from ..ops.gather import exclusive_cumsum
        from ..ops.segments import seg_reduce_sorted
        from ..ops.sort_keys import orderable_int
        K = self.K
        qcols, cnt = bufs[:K], bufs[K]
        rows = sorted_live.shape[0]
        out_cap = _out_cap(seg)
        segl = seg if seg is not None else jnp.zeros((rows,), jnp.int32)
        live_row = sorted_live & cnt.validity & (cnt.data > 0)
        # expand each member summary into K weighted points
        vord = jnp.stack([orderable_int(q).astype(jnp.int64)
                          for q in qcols], axis=1).reshape(-1)
        vorig = jnp.stack([q.data for q in qcols], axis=1).reshape(-1)
        seg_pt = jnp.repeat(segl, K)
        w_pt = jnp.repeat(jnp.where(live_row, cnt.data, 0), K)
        drop = jnp.repeat(jnp.where(live_row, jnp.int8(0),
                                    jnp.int8(1)), K)
        idx = jnp.arange(rows * K, dtype=jnp.int32)
        sdrop, sseg, _, perm = jax.lax.sort(
            (drop, seg_pt, vord, idx), num_keys=3)
        sw = w_pt[perm]
        sseg_c = jnp.clip(sseg, 0, out_cap - 1)
        kept = sdrop == 0
        sw = jnp.where(kept, sw, 0)
        totals = seg_reduce_sorted(sw, sseg_c, out_cap, "sum") \
            if seg is not None else _lane0(jnp.sum(sw), _I64)
        starts_mass = exclusive_cumsum(totals)
        cum_within = jnp.cumsum(sw) - starts_mass[sseg_c]
        SCALE = int(self._MASS_SCALE)
        imax = (1 << 63) - 1
        if out_cap * SCALE > imax:
            # (out_cap-1) * SCALE + SCALE-1 would wrap int64 negative
            # and scramble the compound-key sort; shrink the mass stride
            # to the largest power of two that fits. Masses clip at
            # SCALE-1, so rank resolution inside monster segments
            # degrades gracefully instead of corrupting every segment.
            # (Plans sized like this normally never get here: the exec
            # falls back to the exact single-pass path first.)
            SCALE = 1 << max(1, (imax // out_cap).bit_length() - 1)
        SCALE = jnp.int64(SCALE)
        compound = jnp.where(
            kept,
            sseg_c.astype(jnp.int64) * SCALE
            + jnp.clip(cum_within, 0, SCALE - 1),
            jnp.int64(0x7FFFFFFFFFFFFFFF))
        g = jnp.arange(out_cap, dtype=jnp.int64)
        t = self.children[0].dtype
        qvalid = out_live & (totals > 0)
        out = []
        total_c = jnp.maximum(totals, 1)
        for k in range(K):
            # mass rank of fraction k/(K-1), 1-based, endpoints exact;
            # clipped to the stride so a clamped-SCALE segment's probe
            # cannot bleed into the next segment's key range
            tgt = jnp.clip(1 + ((total_c - 1) * k) // (K - 1),
                           1, SCALE - 1)
            pos = jnp.searchsorted(compound, g * SCALE + tgt,
                                   side="left").astype(jnp.int32)
            pos = jnp.clip(pos, 0, rows * K - 1)
            v = vorig[perm[pos]]
            out.append(TpuColumnVector(t, data=v, validity=qvalid))
        # the mass space weights each of a member's K points by the
        # member's full count, so totals = K x true row count; the count
        # lane must stay a COUNT or it inflates K-fold per merge level
        # until the 2^42 compound-key headroom collapses
        out.append(TpuColumnVector(dt.INT64, data=totals // K,
                                   validity=out_live))
        return out

    def evaluate_device(self, bufs):
        K = self.K
        qcols, cnt = bufs[:K], bufs[K]
        n = cnt.data
        t = self.children[0].dtype
        qmat = jnp.stack([q.data for q in qcols], axis=1)
        has = cnt.validity & (n > 0)
        picked = []
        for p in self.percentages:
            r = jnp.clip(jnp.ceil(p * n).astype(jnp.int64) - 1, 0,
                         jnp.maximum(n - 1, 0))  # Spark's 0-based rank
            # exact integer ceil-division: the smallest point index k
            # whose summary rank floor((n-1)k/(K-1)) reaches r — the
            # "smallest value with rank >= target" direction Spark's
            # definition takes (float round here picks the wrong
            # neighbor when r(K-1)/(n-1) is near an integer)
            den = jnp.maximum(n - 1, 1)
            k = jnp.clip(((r * (K - 1) + den - 1) // den)
                         .astype(jnp.int32), 0, K - 1)
            picked.append(jnp.take_along_axis(
                qmat, k[:, None], axis=1)[:, 0])
        if not self.is_list:
            return TpuColumnVector(t, data=picked[0], validity=has)
        m = len(self.percentages)
        out_cap = n.shape[0]
        elem = jnp.stack(picked, axis=1).reshape(-1)
        elem_valid = jnp.repeat(has, m)
        offsets = jnp.arange(out_cap + 1, dtype=jnp.int32) * m
        child = TpuColumnVector(t, data=elem, validity=elem_valid)
        return TpuColumnVector(self.dtype, validity=has,
                               offsets=offsets, children=[child])

    @staticmethod
    def rank0(p: float, n: int) -> int:
        """0-based rank of percentile p among n ordered values (Spark's
        ceil(p*n) 1-based, clamped) — the single definition both the
        device kernel and the CPU oracle use."""
        import math as _m
        return min(max(int(_m.ceil(p * n)) - 1, 0), n - 1)

    def cpu_agg(self, values, ectx=None):
        vals = [v for v in values if v is not None]

        def key(v):
            if isinstance(v, float):
                return (1, 0.0) if math.isnan(v) else (0, v + 0.0)
            return (0, v)
        vals.sort(key=key)
        if not vals:
            return None
        out = [vals[self.rank0(p, len(vals))] for p in self.percentages]
        return out if self.is_list else out[0]
