"""Scan: of the column chunks in the row groups the traced query read,
the share it took from the files: the sum of ``columns`` over the sum of
``file_columns`` of its ``spark:scan.read`` spans, which the engine
writes per row group (chunks planned for the device or decoded on the
host, and the columns the file's footer lists). 100 where the scan reads
every column of a table as it is registered; column pruning lowers it. A
program whose spans carry no ``file_columns`` gives no reading."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or "spark:scan.read" not in r["spans"]:
        return None
    args = r["spans"]["spark:scan.read"]["args"]
    if not args.get("file_columns"):
        return None
    return 100.0 * args.get("columns", 0) / args["file_columns"]
