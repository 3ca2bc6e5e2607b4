"""The roofline's byte function against bytes counted by hand."""
import pytest

import run
from query_bytes import least_bytes, named_columns


def _schemas(config_name):
    config = run.load_json(run.HERE, "configs", config_name + ".json")
    return {t: run.load_json(run.HERE, "schemas", spec["schema"] + ".json")
            for t, spec in config["tables"].items()}


def test_q6_reads_three_doubles_and_a_date_per_row_and_writes_a_double():
    schemas = _schemas("tpch-sf1")
    columns = named_columns(run.read_query("tpch/q6"), schemas)
    assert columns == {"lineitem": ["l_quantity", "l_extendedprice",
                                    "l_discount", "l_shipdate"]}
    rows = {"lineitem": schemas["lineitem"]["rows"]}
    assert rows["lineitem"] == 6001215
    # 6,001,215 rows x (8 + 8 + 8 + 4) bytes + one float64 of result
    assert least_bytes(schemas, rows, columns, 8) == 168034020 + 8


def test_q3_counts_the_named_columns_of_its_three_tables():
    schemas = _schemas("tpcds-sf1-store")
    columns = named_columns(run.read_query("tpcds/q3"), schemas)
    assert {t: sorted(c) for t, c in columns.items()} == {
        "store_sales": ["ss_ext_sales_price", "ss_item_sk",
                        "ss_sold_date_sk"],
        "date_dim": ["d_date_sk", "d_moy", "d_year"],
        "item": ["i_brand", "i_brand_id", "i_item_sk", "i_manufact_id"]}
    rows = {"store_sales": 2880404, "date_dim": 73049, "item": 18000}
    # ints 4, the double 8, i_brand CHAR(50)
    want = 2880404 * (4 + 4 + 8) + 73049 * 12 + 18000 * (4 + 4 + 4 + 50)
    assert least_bytes(schemas, rows, columns, 0) == want


def test_a_string_column_without_a_declared_width_is_an_error():
    schemas = {"t": {"columns": [{"name": "s", "type": "string"}]}}
    with pytest.raises(ValueError, match="chars"):
        least_bytes(schemas, {"t": 1}, {"t": ["s"]}, 0)
