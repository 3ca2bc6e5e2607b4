"""Readings that limits are set from: the program's and the control's.

    python3 benchmark/readings.py --workload <cell> --seeds 11,12,13

ONE process, on the chip, at the cell's own size: for each seed the
cell's data is made, each query is served once through the cell's own
set-up (``run.open_session``), and its table and the CONTROL's (the plain
reference computed in float32, the nearest precision below the float64
the configuration states) are both held against the float64 reference.
Prints one JSON line per seed: the numbers compared for the program (the
lower readings) and for the control (the upper readings), the query's
wall seconds and the compile requests it sent (0 after the first seed
unless a program's shape follows the data). ``--rehearse-rows`` as run.py.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    a = ap.parse_args(argv)
    _, cell, config, config_file, traffic = run.resolve_cell(a.workload)
    run.require_devices(cell["chips"], a.rehearse_rows)
    run.place_compile_cache(traffic.get("compile_cache", "persistent"))
    import spark_rapids_tpu  # noqa: F401  (x64 on before any array)
    import datagen
    from compare import compare_table
    from compile_meter import CompileMeter
    queries = [(ref, run.read_query(ref)) for ref in traffic["queries"]]
    out = []
    with CompileMeter() as meter:
        for seed in (int(s) for s in a.seeds.split(",")):
            paths, _, _ = datagen.make_tables(
                config_file, run.CACHE_ROOT, seed, a.rehearse_rows)
            session = run.open_session(config, paths, queries)
            for ref, text in queries:
                before = meter.snapshot()
                got, stats = run.execute(session, text)
                mod = run.load_reference(ref)
                want = mod.reference(paths)
                control = mod.reference(paths, "float32")
                line = {"seed": seed, "query": ref, "rows": got.num_rows,
                        "wall_s": stats["wall_s"],
                        "fallback_chunks": stats["scan"]["fallbackChunks"],
                        "compile": meter.since(before),
                        "program": compare_table(got, want, mod.KEYS,
                                                 mod.VALUES),
                        "control": compare_table(control, want, mod.KEYS,
                                                 mod.VALUES)}
                out.append(line)
                print(json.dumps(line), flush=True)
            del session
    return out


if __name__ == "__main__":
    main()
