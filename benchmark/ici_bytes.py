"""The bytes a query must send between chips, from its data and its text.

The rule of ``query_bytes.py`` (what the work needs, never what the
program launches) for the one thing that crosses chips in a star join run
as one task a chip: the PARTIAL aggregates. Chip ``k`` of ``n`` holds
share ``k`` of the fact table's row groups (contiguous, in file order:
the configuration's guarantee) and every dimension table whole; it must
send each group it found (its grouping columns and its partial sum, at the
source's widths: ``query_bytes.column_bytes``) to the chip that owns the
group, which is itself for one group in ``n``. So a chip sends at least

    (its groups BEFORE the limit) x (row width) x (n - 1) / n

bytes, and the query's least time on the interconnect is the MEAN of that
over the chips (they send at once, each over its own links) over one
chip's peak. Computed with pyarrow and pandas from the files; nothing of
the engine. The query's shape is data: ``STARS`` below, one entry per
query text that has such a cell.
"""
from __future__ import annotations

import glob
import os

import pyarrow as pa
import pyarrow.parquet as pq

import query_bytes

#: query -> its star: the fact table, its dimension joins (table, fact
#: key, dimension key, dimension predicate as pandas reads it), and the
#: columns of a partial aggregate's row (group keys, then the sums)
STARS = {
    "tpcds/q3": {
        "fact": "store_sales",
        "joins": [("date_dim", "ss_sold_date_sk", "d_date_sk", "d_moy == 11"),
                  ("item", "ss_item_sk", "i_item_sk",
                   "i_manufact_id == 128")],
        "group": [("date_dim", "d_year"), ("item", "i_brand"),
                  ("item", "i_brand_id")],
        "sums": [("store_sales", "ss_ext_sales_price")],
    },
}


def row_group_shares(paths, chips: int):
    """``chips`` lists of ``(path, row group)``: contiguous shares of the
    files' row groups in file order, disjoint, together all of them."""
    tasks = [(p, g) for p in paths
             for g in range(pq.ParquetFile(p).metadata.num_row_groups)]
    return [tasks[k * len(tasks) // chips:(k + 1) * len(tasks) // chips]
            for k in range(chips)]


def partial_rows(query: str, paths: dict, chips: int) -> list:
    """Groups (before any limit) each chip's share of the fact table
    holds, one number a chip."""
    star = STARS[query]
    dims = []
    for table, fk, dk, predicate in star["joins"]:
        keep = [dk] + [c for t, c in star["group"] if t == table]
        cols = sorted(set(keep) | set(predicate.split()[:1]))
        df = pa.concat_tables(pq.read_table(p, columns=cols)
                              for p in paths[table]).to_pandas()
        dims.append((fk, dk, df.query(predicate)[keep]))
    fact_cols = [fk for fk, _, _ in dims]
    group = [c for _, c in star["group"]]
    counts = []
    for share in row_group_shares(paths[star["fact"]], chips):
        if not share:
            counts.append(0)
            continue
        j = pa.concat_tables(
            pq.ParquetFile(p).read_row_group(g, columns=fact_cols)
            for p, g in share).to_pandas()
        for fk, dk, df in dims:
            j = j.merge(df, left_on=fk, right_on=dk)
        counts.append(int(len(j.drop_duplicates(group))))
    return counts


def row_bytes(query: str, schemas: dict) -> int:
    """One partial aggregate's row at the source's widths."""
    star = STARS[query]
    total = 0
    for table, name in star["group"] + star["sums"]:
        column = next(c for c in schemas[table]["columns"]
                      if c["name"] == name)
        total += query_bytes.column_bytes(column)
    return total


def least_bytes_per_chip(query: str, schemas: dict, paths: dict,
                         chips: int) -> float:
    """The mean over the chips of the bytes a chip must send to others."""
    rows = partial_rows(query, paths, chips)
    return (sum(rows) / chips) * row_bytes(query, schemas) \
        * (chips - 1) / chips


def newest_tables(data_root: str, config: dict):
    """``{table: [paths]}`` of the newest data directory of ``config``
    under ``data_root`` (``<cache>/data/<config>[-rows<n>]``, written anew
    by every run), or ``None`` where there is none."""
    dirs = [d for d in glob.glob(os.path.join(
        data_root, "data", config["name"] + "*")) if os.path.isdir(d)
        and os.path.basename(d)[len(config["name"]):][:5] in ("", "-rows")]
    if not dirs:
        return None
    newest = max(dirs, key=os.path.getmtime)
    return {t: [os.path.join(newest, f"{t}-{k:02d}.parquet")
                for k in range(spec["files"])]
            for t, spec in config["tables"].items()}
