"""The bytes a query must move, from the configuration's schema alone.

The least a scan-and-aggregate can do is read every column the query
names once, decoded, at the source's widths, and write its result:
``rows x width`` per column (``long`` and ``double`` 8 bytes, ``int``
and ``date`` 4, a ``string`` its declared ``chars``: the source's CHAR(n)
or VARCHAR(n)) plus the result table's bytes. Nothing here looks at the
files, the plan or what the program launches, so the number reads the
same work whatever programs implement it.
"""
from __future__ import annotations

import re

WIDTH = {"long": 8, "double": 8, "int": 4, "date": 4}


def column_bytes(column: dict) -> int:
    if column["type"] == "string":
        if "chars" not in column:
            raise ValueError(f"string column {column['name']!r} states no "
                             f"'chars' (the source's declared width)")
        return int(column["chars"])
    return WIDTH[column["type"]]


def least_bytes(schemas: dict, rows: dict, columns: dict,
                result_bytes: int) -> int:
    """``schemas``: ``{table: schema file}``; ``rows``: ``{table: rows
    generated}``; ``columns``: ``{table: [names the query touches]}``."""
    total = int(result_bytes)
    for table, names in columns.items():
        by_name = {c["name"]: c for c in schemas[table]["columns"]}
        total += rows[table] * sum(column_bytes(by_name[n]) for n in names)
    return total


def named_columns(text: str, schemas: dict) -> dict:
    """``{table: [columns the query text names]}`` for the tables it
    names (identifiers of the text that are columns of those tables)."""
    words = set(w.lower() for w in re.findall(r"[A-Za-z_][A-Za-z0-9_]*",
                                              text))
    return {t: [c["name"] for c in s["columns"]
                if not c.get("hidden") and c["name"].lower() in words]
            for t, s in schemas.items() if t.lower() in words}
