-- TPC-H v3 Q6 (Forecasting Revenue Change), validation parameters
-- DATE 1994-01-01, DISCOUNT 0.06, QUANTITY 24.
-- Departure from the published text: its two constant expressions are
-- folded (date '1994-01-01' + interval '1' year -> date '1995-01-01';
-- 0.06 - 0.01 and 0.06 + 0.01 -> 0.05 and 0.07), because the engine's
-- parser refuses INTERVAL arithmetic today. Lines that start with "--"
-- are not sent to the engine.
select
	sum(l_extendedprice * l_discount) as revenue
from
	lineitem
where
	l_shipdate >= date '1994-01-01'
	and l_shipdate < date '1995-01-01'
	and l_discount between 0.05 and 0.07
	and l_quantity < 24
