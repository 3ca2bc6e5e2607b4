"""Plain reference for TPC-DS query 3: pandas on the host, from the files.

Imports nothing of the engine. ``precision`` is the type DOUBLE columns
are computed in: ``float64`` is the reference; ``float32`` is the
CONTROL (the nearest precision below the one the configuration states)
and has to come out as not correct.
"""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

#: compared exactly, row by row in the result's order (ORDER BY is part
#: of the answer)
KEYS = ["d_year", "brand_id", "brand"]
#: widest relative gap allowed per value column; PERF.md section 2 gives
#: the readings each limit was set from
VALUES = {"sum_agg": 5e-9}


def _read(paths, columns):
    return pa.concat_tables(pq.read_table(p, columns=columns)
                            for p in paths).to_pandas()


def reference(paths: dict, precision: str = "float64") -> pa.Table:
    ft = np.dtype(precision).type
    dt = _read(paths["date_dim"], ["d_date_sk", "d_year", "d_moy"])
    item = _read(paths["item"], ["i_item_sk", "i_brand_id", "i_brand",
                                 "i_manufact_id"])
    ss = _read(paths["store_sales"], ["ss_sold_date_sk", "ss_item_sk",
                                      "ss_ext_sales_price"])
    ss["ss_ext_sales_price"] = ss["ss_ext_sales_price"].astype(ft)
    j = ss.merge(dt[dt.d_moy == 11], left_on="ss_sold_date_sk",
                 right_on="d_date_sk")
    j = j.merge(item[item.i_manufact_id == 128], left_on="ss_item_sk",
                right_on="i_item_sk")
    g = (j.groupby(["d_year", "i_brand", "i_brand_id"], as_index=False)
         ["ss_ext_sales_price"].sum())
    g = g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand",
                          "ss_ext_sales_price": "sum_agg"})
    g = g.sort_values(["d_year", "sum_agg", "brand_id"],
                      ascending=[True, False, True], kind="stable")
    g = g.head(100)
    return pa.table({
        "d_year": pa.array(g["d_year"].to_numpy(), pa.int32()),
        "brand_id": pa.array(g["brand_id"].to_numpy(), pa.int32()),
        "brand": pa.array(g["brand"].tolist(), pa.string()),
        "sum_agg": pa.array(g["sum_agg"].to_numpy().astype(np.float64)),
    })
