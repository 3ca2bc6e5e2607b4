"""The cell these tests add to BENCHMARK.json by entries alone.

``tpcds-sf1-store.q3.files`` (TPC-DS q3 over the full-width store
channel) is not a cell of BENCHMARK.json: registered as the files are
written, one warm q3 takes 116 s on the chip until the engine prunes
columns (PERF.md section 7). Its configuration, schema, query, reference
and traffic files are in ``benchmark/`` all the same; the tests add the
entries below to a copy of BENCHMARK.json, as the PR that brings the
cell will, and rehearse it on the CPU beside the committed cells: the
generator's multi-table path, the keyed comparison, the control and the
faults of a join + group-by + order-by + limit.
"""
import copy

CELL = "tpcds-sf1-store.q3.files"
CONFIG = {
    "name": "tpcds-sf1-store",
    "source": "TPC-DS v3.2.0 (tpc.org) store channel: STORE_SALES, DATE_DIM,"
              " ITEM at scale factor 1, query 3",
    "file": "benchmark/configs/tpcds-sf1-store.json",
    "reduced": ["scale_factor"], "why": "a join cell, added by a test"}
WORKLOAD = {"name": CELL, "config": "tpcds-sf1-store", "traffic": "q3.files",
            "chips": 1, "why": "a join cell, added by a test"}


def with_later_cell(bench: dict) -> dict:
    """``bench`` plus the later cell: a ``configs`` entry, a ``workloads``
    entry, and the cell's name under every metric the warm cell lists."""
    bench = copy.deepcopy(bench)
    warm = next(w["name"] for w in bench["workloads"]
                if w["traffic"] == "q6.files")
    bench["configs"].append(CONFIG)
    bench["workloads"].append(WORKLOAD)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if warm in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench
