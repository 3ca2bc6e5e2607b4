"""One generic column generator: a table is a schema file, never code.

A schema file (``benchmark/schemas/<source>/<table>.json``) names the
table's row count and its columns in the source's order, each with a
type (``long int double string date``) and a ``dist``:

  seq           start [, step]            start + step * row
  runs          lo, hi                    rows in runs of lo..hi rows (lengths
                                          drawn uniformly): the run's number,
                                          counted through the table's files
  uniform_int   lo, hi                    integers, both ends included
  uniform_cents lo, hi                    lo..hi in steps of 0.01 (money)
  choice        values [, weights]        one of a fixed list
  pool_text     pool, min_len, max_len    strings drawn by index from a
                                          pool of pseudo-text (seeded)
  strfmt        of, fmt [, args]          fmt % the value ``u`` of an integer
                                          column (or % expressions of ``u``)
  expr          expr [, values]           numpy expression over the columns
                                          generated so far, the row index
                                          ``i`` and the index in the file
                                          ``j`` (helpers: see gen_chunk;
                                          ``pos_in_run(c)`` is a row's place
                                          in its run of equal values of c);
                                          with ``values`` the result indexes
                                          that list of strings

A column with ``"hidden": true`` is generated for later ``expr`` columns
and not written. ``"dictionary": false`` writes a column PLAIN: set on
columns with so many distinct values that the writer would abandon its
dictionary part-way through a row group, at a row that follows the seed
(every encoded size is then the same for every seed, and only values
differ). Row counts, row groups and every list of values are the
schema's; only the values drawn depend on the seed, column by column
(``default_rng([seed, crc32(table), column index])``), so adding a column
or a table never changes the data of another.
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

_EPOCH = np.datetime64("1970-01-01", "D")
_WORDS = ("furiously quickly slyly carefully blithely fluffily final pending "
          "regular special express ironic bold even silent unusual deposits "
          "packages requests accounts instructions foxes pinto beans ideas "
          "theodolites dependencies platelets asymptotes courts dolphins "
          "sleep nag haggle wake cajole boost detect among above across "
          "the of to about against").split()


def days(iso: str) -> int:
    """ISO date -> days since 1970-01-01 (Parquet DATE, Arrow date32)."""
    return int((np.datetime64(iso, "D") - _EPOCH).astype(np.int64))


def _calendar(part):
    def f(d):
        d64 = _EPOCH + np.asarray(d).astype("timedelta64[D]")
        if part == "year":
            return d64.astype("datetime64[Y]").astype(np.int64) + 1970
        if part == "month":
            return d64.astype("datetime64[M]").astype(np.int64) % 12 + 1
        if part == "day":
            return (d64 - d64.astype("datetime64[M]")).astype(np.int64) + 1
        # day of week, 0 = Sunday (1970-01-01 was a Thursday)
        return (np.asarray(d).astype(np.int64) + 4) % 7
    return f


def _run_lengths(rng, lo, hi, rows):
    """Lengths of the runs that fill ``rows`` rows, the last one cut."""
    lengths = rng.integers(lo, hi + 1, -(-rows // lo))
    ends = np.cumsum(lengths)
    n = int(np.searchsorted(ends, rows)) + 1
    lengths = lengths[:n]
    lengths[-1] -= ends[n - 1] - rows
    return lengths


def _pos_in_run(x):
    x = np.asarray(x)
    first = np.flatnonzero(np.r_[True, x[1:] != x[:-1]])
    return np.arange(len(x)) - np.repeat(first,
                                         np.diff(np.r_[first, len(x)]))


def _load_schema(schema_dir: str, ref: str) -> dict:
    with open(os.path.join(schema_dir, ref + ".json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=8)
def _pool(seed_key, size, min_len, max_len):
    rng = np.random.default_rng(list(seed_key))
    out = []
    for n in rng.integers(min_len, max_len + 1, size):
        words, length = [], 0
        while length < n:
            w = _WORDS[int(rng.integers(0, len(_WORDS)))]
            words.append(w)
            length += len(w) + 1
        out.append(" ".join(words)[:int(n)])
    return out


def _dictionary(codes, values) -> pa.Array:
    """Strings as Arrow dictionary arrays: written without the Arrow
    schema (``store_schema=False``) the files hold plain UTF8 columns,
    and no 6M-row string column is ever materialised here."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes).astype(np.int32)),
        pa.array(list(values), pa.string()))


def gen_chunk(schema: dict, seed: int, chunk: int, start: int,
              rows: int) -> pa.Table:
    """Rows ``start .. start + rows`` of one table for one seed: the
    content of its ``chunk``-th file. Tables are made file by file so
    that the generator's working set stays small and is reused."""
    table_key = zlib.crc32(schema["table"].encode())
    made, fields, arrays = {}, [], []
    for idx, col in enumerate(schema["columns"]):
        rng = np.random.default_rng([int(seed), table_key, idx, chunk])
        ctype, dist = col["type"], col["dist"]
        if dist == "seq":
            v = col["start"] + col.get("step", 1) * np.arange(
                start, start + rows, dtype=np.int64)
        elif dist == "runs":  # earlier files' runs come first
            seen = sum(len(_run_lengths(np.random.default_rng(
                [int(seed), table_key, idx, c]), col["lo"], col["hi"],
                start // chunk)) for c in range(chunk))
            lengths = _run_lengths(rng, col["lo"], col["hi"], rows)
            v = seen + np.repeat(np.arange(len(lengths)), lengths)
        elif dist == "uniform_int":
            v = rng.integers(col["lo"], col["hi"] + 1, rows)
        elif dist == "uniform_cents":
            v = rng.integers(round(col["lo"] * 100),
                             round(col["hi"] * 100) + 1, rows) / 100.0
        elif dist == "choice":
            w = col.get("weights")
            p = None if w is None else np.asarray(w, float) / sum(w)
            codes = rng.choice(len(col["values"]), rows, p=p)
            v = (codes, col["values"]) if ctype == "string" else \
                np.asarray(col["values"])[codes]
        elif dist == "pool_text":
            v = (rng.integers(0, col["pool"], rows),
                 _pool((int(seed), table_key, idx), col["pool"],
                       col["min_len"], col["max_len"]))
        elif dist == "strfmt":
            uniq, codes = np.unique(made[col["of"]], return_inverse=True)
            args = col.get("args", ["u"])
            v = (codes, [col["fmt"] % tuple(
                eval(a, {"__builtins__": {}}, {"u": int(u)})  # noqa: S307
                for a in args) for u in uniq])
        elif dist == "expr":
            ns = dict(made, np=np, i=np.arange(start, start + rows,
                                               dtype=np.int64),
                      j=np.arange(rows, dtype=np.int64),
                      where=np.where, date=days, year=_calendar("year"),
                      month=_calendar("month"), day=_calendar("day"),
                      dow=_calendar("dow"), pos_in_run=_pos_in_run,
                      rand_int=lambda lo, hi: rng.integers(lo, hi + 1, rows))
            v = eval(col["expr"], {"__builtins__": {}}, ns)  # noqa: S307
            if "values" in col:
                v = (v, col["values"])
        else:
            raise ValueError(f"{schema['table']}.{col['name']}: "
                             f"unknown dist {dist!r}")
        if isinstance(v, tuple):
            if ctype != "string":
                raise ValueError(f"{col['name']}: {dist} gives strings")
            arr = _dictionary(*v)
            made[col["name"]] = np.asarray(v[0])
        else:
            v = np.asarray(v)
            np_type = {"long": np.int64, "int": np.int32,
                       "double": np.float64, "date": np.int32}[ctype]
            v = v.astype(np_type)
            arr = pa.array(v, pa.int32()).cast(pa.date32()) \
                if ctype == "date" else pa.array(v)
            made[col["name"]] = v
        if not col.get("hidden"):
            fields.append(pa.field(col["name"], arr.type, nullable=False))
            arrays.append(arr)
    table = pa.Table.from_arrays(arrays, schema=pa.schema(fields))
    # ``columns`` is in generation order (an expr reads earlier columns);
    # ``order``, where given, is the source's column order on disk
    return table.select(schema["order"]) if "order" in schema else table


def table_paths(config: dict, data_root: str,
                rehearse_rows: int | None = None):
    """``(directory, {table: [parquet paths]})`` of a configuration: ONE
    directory per configuration, rewritten by every run, so that a run's
    set-up does the same work whether or not its seed was seen before."""
    tag = config["name"] + (f"-rows{rehearse_rows}" if rehearse_rows else "")
    out_dir = os.path.join(data_root, "data", tag)
    return out_dir, {t: [os.path.join(out_dir, f"{t}-{k:02d}.parquet")
                         for k in range(spec["files"])]
                     for t, spec in config["tables"].items()}


def table_rows(schema: dict, spec: dict, rehearse_rows: int | None) -> int:
    if rehearse_rows and spec.get("cut_in_rehearsal"):
        return min(schema["rows"], rehearse_rows)
    return schema["rows"]


def write_tables(config: dict, schema_dir: str, data_root: str, seed: int,
                 rehearse_rows: int | None = None) -> None:
    """Generate and write every table of the configuration."""
    out_dir, paths = table_paths(config, data_root, rehearse_rows)
    os.makedirs(out_dir, exist_ok=True)
    for t, spec in config["tables"].items():
        schema = _load_schema(schema_dir, spec["schema"])
        rows = table_rows(schema, spec, rehearse_rows)
        per = -(-rows // spec["files"])
        for k, path in enumerate(paths[t]):
            part = gen_chunk(schema, seed, k, k * per,
                             max(0, min(per, rows - k * per)))
            pq.write_table(part, path, row_group_size=spec["row_group_rows"],
                           compression="snappy", store_schema=False,
                           use_dictionary=[c["name"] for c in
                                           schema["columns"]
                                           if c.get("dictionary", True)
                                           and not c.get("hidden")])


SCHEMA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "schemas")


def make_tables(config_file: str, data_root: str, seed: int,
                rehearse_rows: int | None = None):
    """``({table: [parquet paths]}, {table: schema}, {table: rows})``,
    written anew from the seed. Generation runs in a CHILD process that
    never imports JAX: it tunes its own allocator to reuse freed pages (a
    fresh page costs ~20 us on these hosts, 2 s per 6M-row column), which
    must not leak into the process whose host allocations are being
    measured."""
    with open(config_file) as f:
        config = json.load(f)
    _, paths = table_paths(config, data_root, rehearse_rows)
    subprocess.run([sys.executable, os.path.abspath(__file__), config_file,
                    data_root, str(int(seed)), str(int(rehearse_rows or 0))],
                   check=True)
    schemas = {t: _load_schema(SCHEMA_DIR, spec["schema"])
               for t, spec in config["tables"].items()}
    rows = {t: table_rows(schemas[t], spec, rehearse_rows)
            for t, spec in config["tables"].items()}
    return paths, schemas, rows


def main(argv):
    config_file, data_root, seed, rehearse_rows = argv
    try:  # glibc: keep freed memory mapped (M_MMAP_THRESHOLD, M_TRIM_THRESHOLD)
        import ctypes
        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-3, 1 << 30)
        libc.mallopt(-1, 1 << 31)
    except OSError:
        pass
    with open(config_file) as f:
        config = json.load(f)
    write_tables(config, SCHEMA_DIR, data_root, int(seed),
                 int(rehearse_rows) or None)


if __name__ == "__main__":
    main(sys.argv[1:])
