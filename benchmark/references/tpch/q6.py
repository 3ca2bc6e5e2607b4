"""Plain reference for TPC-H Q6: numpy on the host, from the files.

Imports nothing of the engine. ``precision`` is the type DOUBLE columns
are computed in: ``float64`` is the reference (summed exactly, with
``math.fsum``); ``float32`` is the CONTROL — the nearest precision below
the one the configuration states — and has to come out as not correct.
"""
import math

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

#: compared exactly, row by row in the result's order
KEYS = []
#: widest relative gap allowed per value column; PERF.md section 2 gives
#: the readings each limit was set from
VALUES = {"revenue": 2e-12}

_D0 = int((np.datetime64("1994-01-01") - np.datetime64("1970-01-01"))
          .astype(int))
_D1 = int((np.datetime64("1995-01-01") - np.datetime64("1970-01-01"))
          .astype(int))


def reference(paths: dict, precision: str = "float64") -> pa.Table:
    ft = np.dtype(precision).type
    t = pa.concat_tables(
        pq.read_table(p, columns=["l_quantity", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
        for p in paths["lineitem"])
    ship = t.column("l_shipdate").cast(pa.int32()).to_numpy()
    qty, price, disc = (t.column(c).to_numpy().astype(ft) for c in
                        ("l_quantity", "l_extendedprice", "l_discount"))
    keep = ((ship >= _D0) & (ship < _D1) & (disc >= ft(0.05))
            & (disc <= ft(0.07)) & (qty < ft(24)))
    products = price[keep] * disc[keep]
    revenue = math.fsum(products) if precision == "float64" \
        else float(products.sum(dtype=ft))
    return pa.table({"revenue": pa.array([revenue], pa.float64())})
