"""The benchmark: one cell of BENCHMARK.json, one process, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``): make the configuration's Parquet files
from the seed, build a ``TpuSession``, register every table with
``session.read_parquet`` under the files' own schema (every column),
check that the planner places each query wholly on the device, and serve the
mix's ``warmup_queries`` passes over its queries. Window: one client
hands ``TpuSession.sql`` the SQL TEXT and collects the Arrow table, back
to back, until ``--seconds`` have passed and the query in flight has
returned (or, where the mix says ``queries_per_window``, after that
many). After the window: the memory peak is read, the session is
dropped, and every table the window returned is held against the plain
reference (``compare.py``).

Everything that belongs to ONE configuration, traffic mix, table, query
or per-layer metric is a file of its own found by name (README.md); this
file knows none of them. It measures on a TPU only: without one (or with
fewer chips than the cell asks for) it exits 3 and prints no result.
``--rehearse-rows N`` cuts the fact tables to N rows to drive the whole
control flow on a CPU; it prints the platform and is refused on a TPU.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")
CACHE_ROOT = os.path.join(ROOT, ".bench_cache", "benchmark")
XLA_CACHE_DIR = os.path.join(ROOT, ".bench_cache", "xla")
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_CHIP, EXIT_BAD_PLAN, EXIT_USAGE = 3, 4, 2
TAIL_MIN_QUERIES = 20  # fewer walls, and a percentile is a maximum by another name


class Refused(Exception):
    """The run cannot be measured; carries the exit code."""

    def __init__(self, code, message):
        super().__init__(message)
        self.code = code


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve_cell(workload: str):
    """The cell's entries and files, by the names in BENCHMARK.json."""
    bench = load_json(BENCH_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(EXIT_USAGE, f"no workload {workload!r} in "
                                  f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_file = os.path.join(ROOT, entry["file"])
    config = load_json(config_file)
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if traffic.get("clients", 1) != 1 or traffic.get("loop") != "closed":
        raise Refused(EXIT_USAGE, "the generator drives one closed-loop "
                                  "client; this traffic file asks for more")
    if traffic.get("source", "files") != "files":
        raise Refused(EXIT_USAGE, f"unknown source {traffic['source']!r}")
    if traffic.get("compile_cache", "persistent") not in ("persistent",
                                                          "off"):
        raise Refused(EXIT_USAGE, f"unknown compile_cache "
                                  f"{traffic['compile_cache']!r}")
    return bench, cell, config, config_file, traffic


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def require_devices(chips: int, rehearse_rows):
    """The chips the cell asks for, or no run. (The peak table refuses
    an unknown TPU later: ``peaks.json``.)"""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse_rows:
        print(f"REHEARSAL on device.platform={platform!r}: rows cut to "
              f"{rehearse_rows}; no number below is a device number",
              file=sys.stderr)
        if platform == "tpu":
            raise Refused(EXIT_USAGE, "--rehearse-rows is refused on a TPU")
        return devices[:chips]
    if platform != "tpu" or len(devices) < chips:
        raise Refused(EXIT_NO_CHIP,
                      f"needs {chips} TPU chip(s); JAX found platform="
                      f"{platform!r} device_kind={devices[0].device_kind!r} "
                      f"x{len(devices)}")
    return devices[:chips]


def place_compile_cache(mode: str = "persistent"):
    """``persistent``: the compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed checkout path; programs that compile in under
    a second are served from it too (37 of them, 25 s a process: PERF.md).
    ``off``: this process neither reads nor writes a persistent cache,
    whatever the environment names, so every program it needs is
    compiled where it is first used."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", mode == "persistent")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    compilation_cache.reset_cache()  # the switch is read when it is first used


def read_query(ref: str) -> str:
    """The text sent to the engine: the file less its ``--`` header."""
    with open(os.path.join(HERE, "queries", ref + ".sql")) as f:
        lines = [ln for ln in f.read().splitlines()
                 if not ln.lstrip().startswith("--")]
    return "\n".join(lines).strip()


def open_session(config, paths, queries):
    """A ``TpuSession`` with every table of the configuration registered
    as ``spark.read.parquet`` registers it: all its files, under the
    files' own schema, every column. A query the planner does not place
    wholly on the device refuses the run (never ``execute_cpu``)."""
    from spark_rapids_tpu.session import TpuSession
    session = TpuSession(conf=dict(config.get("session_conf", {})))
    for table, files in paths.items():
        session.register_table(table, session.read_parquet(files))
    for ref, text in queries:
        pp = plan_of(session, text)
        if not pp.root_on_device or pp.fallback_nodes():
            raise Refused(EXIT_BAD_PLAN,
                          f"{ref}: the plan leaves the device "
                          f"(fallback nodes {pp.fallback_nodes()}):\n"
                          + pp.explain("ALL"))
    return session


def load_reference(ref: str):
    return load_module(os.path.join(HERE, "references", ref + ".py"),
                       "reference_" + re.sub(r"\W", "_", ref))


def plan_of(session, text):
    """SQL text -> physical plan: ``session.sql(text)`` then exactly
    what ``DataFrame.collect`` builds before it executes."""
    from spark_rapids_tpu.planner import TpuOverrides
    return TpuOverrides(session.conf).apply(session.sql(text)._node)


def scan_counters(pp) -> dict:
    """Scan coverage summed over the last collect's operators (copied
    from ``chip_smoke.py::scan_counters``)."""
    tot = {"deviceChunks": 0, "fallbackChunks": 0, "scanPrograms": 0,
           "fusedDispatches": 0}
    for node_metrics in pp.last_ctx.metrics.values():
        for k in tot:
            if k in node_metrics:
                tot[k] += int(node_metrics[k].value)
    return tot


def execute(session, text):
    """One served query, SQL text in, ``pyarrow.Table`` out: the steps of
    ``session.sql(text).collect()`` with the plan kept in hand so that
    its placement and the scan's counters can be read afterwards. The
    two spans land in a profiler trace when one is being taken."""
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("plan"):
        pp = plan_of(session, text)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation("collect"):
        table = pp.collect()
    t2 = time.perf_counter()
    return table, {"wall_s": t2 - t0, "plan_ms": (t1 - t0) * 1e3,
                   "fallback_nodes": len(pp.fallback_nodes())
                   + (0 if pp.root_on_device else 1),
                   "scan": scan_counters(pp)}


def memory_peak(devices):
    peak = limit = 0
    for d in devices:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) >= peak:
            peak = int(stats.get("peak_bytes_in_use", 0))
            limit = int(stats.get("bytes_limit", 0))
    return peak, limit


def metric_reader(name: str):
    """``metrics/<name>.py``; a metric split by the end-to-end metric it
    moves (``device.idle_share.cold``) and with no file of its own is
    read by the file of the name it splits (``device.idle_share``)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(HERE, "metrics", ".".join(parts[:n]) + ".py")
        if os.path.exists(path):
            return load_module(path, "metric_" + re.sub(r"\W", "_", name))
    raise Refused(EXIT_USAGE, f"no reader metrics/{name}.py")


def per_layer_values(bench, workload, reading) -> dict:
    """Each per-layer metric of the cell through its own reader; a
    reader that finds nothing to read leaves its metric out."""
    values = {}
    for m in bench["per_layer"]:
        if not applies(m, workload):
            continue
        v = metric_reader(m["name"]).read(reading)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    return values


def run(args) -> dict:
    bench, cell, config, config_file, traffic = resolve_cell(args.workload)
    devices = require_devices(cell["chips"], args.rehearse_rows)
    place_compile_cache(traffic.get("compile_cache", "persistent"))
    import spark_rapids_tpu  # noqa: F401  (x64 on before any array)
    from compile_meter import CompileMeter
    peaks = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if devices[0].platform == "tpu" and kind not in peaks:
        raise Refused(EXIT_USAGE, f"no peak recorded for device_kind "
                                  f"{kind!r}: add it to peaks.json with its "
                                  f"source")
    with CompileMeter() as meter:
        return measure(args, bench, config, config_file, traffic, devices,
                       peaks.get(kind), meter)


def nearest_rank(values, percent: int) -> float:
    """The nearest-rank percentile: the ceil(percent / 100 x n)-th smallest
    (the 42nd of 46 for 90: the fifth largest)."""
    ordered = sorted(values)
    return ordered[max(-(-percent * len(ordered) // 100), 1) - 1]


def warm_metrics(walls, window_s: float) -> dict:
    """The warm window's end-to-end numbers (PERF.md section 2).
    ``query_s`` is taken over all the work and all the time of the window,
    so one stalled query moves it by its share; ``query_p50_s``, the median
    of the queries' wall seconds, does not see that query at all.
    ``query_p90_s`` is their nearest-rank tail, there only where the window
    completed ``TAIL_MIN_QUERIES``."""
    measured = {"query_s": window_s / len(walls),
                "query_p50_s": statistics.median(walls)}
    if len(walls) >= TAIL_MIN_QUERIES:
        measured["query_p90_s"] = nearest_rank(walls, 90)
    return measured


def measure(args, bench, config, config_file, traffic, devices, peak,
            meter) -> dict:
    import jax
    import datagen
    from compare import judge
    from query_bytes import least_bytes, named_columns
    # ---- set-up ----------------------------------------------------------
    paths, schemas, rows = datagen.make_tables(config_file, CACHE_ROOT,
                                               args.seed, args.rehearse_rows)
    queries = [(ref, read_query(ref)) for ref in traffic["queries"]]
    session = open_session(config, paths, queries)
    at_start = meter.snapshot()
    for _ in range(int(traffic.get("warmup_queries", 1))):
        for _, text in queries:
            execute(session, text)
    in_setup = meter.since(at_start)
    trace_dir = os.path.join(CACHE_ROOT, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
    per_window = traffic.get("queries_per_window")
    gc.collect()

    # ---- window ----------------------------------------------------------
    done, results, failed, attempted = [], [], 0, 0
    at_window = meter.snapshot()
    setup_s = time.perf_counter() - _T0
    w0 = time.perf_counter()
    closed = False
    while not closed:
        for ref, text in queries:
            attempted += 1
            tracing = bool(args.trace) and attempted == 1
            if tracing:
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                table, stats = execute(session, text)
            except Exception as e:  # a failed query ends the window
                print(f"query {ref} failed: {e!r}", file=sys.stderr)
                failed += 1
                closed = True
                break
            finally:
                if tracing:
                    jax.profiler.stop_trace()
            stats["name"] = ref
            done.append(stats)
            results.append((ref, table))
            if (len(done) >= per_window if per_window
                    else time.perf_counter() - w0 >= args.seconds):
                closed = True
                break
    window_s = time.perf_counter() - w0
    in_window = meter.since(at_window)

    # ---- after the window: memory, then the reference --------------------
    peak_bytes, bytes_limit = memory_peak(devices)
    del session
    gc.collect()
    compared = {
        "fallback_nodes": {"value": float(max(
            (q["fallback_nodes"] for q in done), default=0)), "limit": 0.0},
        "fallback_chunks": {"value": float(max(
            (q["scan"]["fallbackChunks"] for q in done), default=0)),
            "limit": 0.0}}
    correct = failed == 0 and bool(results)
    result_bytes = {}
    for ref, _ in queries:
        mod = load_reference(ref)
        want = mod.reference(paths)
        result_bytes[ref] = want.nbytes
        ok, numbers = judge([t for r, t in results if r == ref], want,
                            mod.KEYS, mod.VALUES)
        correct = correct and ok
        prefix = "" if len(queries) == 1 else ref.replace("/", ".") + "."
        for k, v in numbers.items():  # JSON has no Infinity
            v["value"] = min(v["value"], sys.float_info.max)
            compared[prefix + k] = v
    correct = correct and all(c["value"] <= c["limit"]
                              for c in compared.values())

    # ---- metrics ---------------------------------------------------------
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed}
    walls = [q["wall_s"] for q in done]
    cold = traffic.get("compile_cache") == "off" \
        and not traffic.get("warmup_queries", 1)
    if not args.trace:
        measured = {"setup_s": setup_s}
        if walls and not cold:
            measured.update(warm_metrics(walls, window_s))
        if walls and cold:  # the first query of its shape in this process
            measured["cold_query_s"] = walls[0]
        listed = [m for m in bench["end_to_end"]
                  if applies(m, args.workload)]
        line["metrics"] = {
            m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in listed if m["name"] in measured}
        for m in listed:
            if m["name"] not in measured:
                print(f"{m['name']} is not printed: the window completed "
                      f"{len(walls)} queries (a tail is printed from "
                      f"{TAIL_MIN_QUERIES} on)", file=sys.stderr)
    else:
        from trace_reduce import reduce_trace
        trace = reduce_trace(trace_dir)
        ref0, text0 = queries[0]  # the traced execution
        reading = {
            "queries": done, "trace": trace,
            "compile": {"setup": in_setup, "window": in_window},
            "traced_query": {"least_bytes": least_bytes(
                schemas, rows, named_columns(text0, schemas),
                result_bytes[ref0])},
            "device": dict(device, bytes_limit=bytes_limit),
            "peaks": peak}
        line["metrics"] = per_layer_values(bench, args.workload, reading)
    line["device"] = device
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(f"queries_done = {len(done)} window_s = {window_s!r} "
          f"walls = {walls!r}", file=sys.stderr)
    print(f"compile setup = {in_setup} window = {in_window}",
          file=sys.stderr)
    line["compared"] = compared  # comes last: each number beside its limit
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="CPU rehearsal only: cut the fact tables to N rows")
    args = ap.parse_args(argv)
    try:
        line = run(args)
    except Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return e.code
    sys.stdout.flush()
    print(f"correct = {line['correct']}", file=sys.stderr)
    for name, c in line["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
