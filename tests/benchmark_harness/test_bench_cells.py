"""CPU rehearsal of every cell of BENCHMARK.json at a few thousand rows:
the whole control flow of a run (set-up, window, reference, result
line) through the harness's own functions, with and without the trace.
No number it prints is a device number."""
import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
WARM = [c for c in CELLS if not c.endswith(".cold")]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def compile_counts(err):
    """``(setup, window)`` off the run's ``compile setup = ...`` line."""
    m = re.search(r"^compile setup = (\{.*?\}) window = (\{.*?\})$", err,
                  re.M)
    return ast.literal_eval(m.group(1)), ast.literal_eval(m.group(2))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_is_correct(bench_run, cell):
    rc, line, err = bench_run(cell)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    want = {m["name"] for m in BENCH["end_to_end"] if run.applies(m, cell)}
    if line["attempted"] < run.TAIL_MIN_QUERIES:  # a window too short for a tail
        want -= {"query_p90_s"}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # the contract's keys and, last, each number compared beside its limit
    assert list(line) == CONTRACT_KEYS + ["compared"]
    compared = [ln for ln in err.strip().splitlines()
                if ln.startswith("compared ")]
    assert err.strip().splitlines()[-len(compared):] == compared
    assert len(compared) == len(line["compared"]) >= 4
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_counters_and_no_device_number(bench_run, cell):
    rc, line, err = bench_run(cell, trace=1)
    assert rc == 0 and line["correct"] is True, err[-2000:]
    m = line["metrics"]
    listed = {p["name"] for p in BENCH["per_layer"] if run.applies(p, cell)}
    assert m and set(m) <= listed
    if cell in WARM:
        assert m["scan.fallback_chunks"]["value"] == 0.0
        assert m["plan.fallback_nodes"]["value"] == 0.0
        assert m["plan_ms"]["value"] > 0
        assert m["exec.compiles_per_query"]["value"] >= 1
        assert m["compile.programs"]["value"] >= 2
    else:
        assert m["compile.window_programs"]["value"] >= 2
        assert m["compile.window_s"]["value"] > 0
    # no device plane in a CPU trace: those readers return nothing
    assert not [n for n in m if n.startswith("device.idle_share")
                or n.endswith("_roofline")]
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_without_a_tpu_a_measurement_is_refused(bench_run):
    rc, line, err = bench_run(CELLS[0], rows=None)
    assert rc == run.EXIT_NO_CHIP and line is None
    assert "needs 1 TPU chip" in err


def test_a_fallback_node_refuses_the_run(bench_run, monkeypatch):
    from spark_rapids_tpu.planner import PhysicalPlan
    monkeypatch.setattr(PhysicalPlan, "fallback_nodes",
                        lambda self: ["FileScan"])
    rc, line, _ = bench_run(CELLS[0])
    assert rc == run.EXIT_BAD_PLAN and line is None


@pytest.mark.parametrize("cell", WARM)
def test_two_seeds_ask_for_the_same_programs(bench_run, cell):
    """Row counts, row groups and dictionaries do not follow the seed,
    so a second seed asks the compiler for exactly the programs the
    first did and finds every one of them in the cache. (At 60,000 rows:
    at 6,000 a file's encoded segments are so short that one seed in six
    straddles a bucket edge of the scan's shapes, as 8 files did on the
    chip at the real size: PERF.md section 4.)"""
    counts = []
    for seed in (21, 21, 2147483693):
        rc, line, err = bench_run(cell, seed=seed, seconds=0.01, rows=60000)
        assert rc == 0 and line["correct"], err[-2000:]
        assert line["attempted"] == 1
        counts.append(compile_counts(err))
    (_, _), (s1, w1), (s2, w2) = counts
    assert s2["requests"] == s1["requests"] > 0
    assert w2["requests"] == w1["requests"] > 0
    assert s2["hits"] == s2["requests"] and w2["hits"] == w2["requests"]


def test_cold_mix_compiles_inside_the_window_and_warm_mix_does_not(bench_run):
    _, _, err = bench_run("tpch-sf1.q6.files")          # fills the cache
    _, line, err = bench_run("tpch-sf1.q6.files")
    setup, window = compile_counts(err)
    assert setup["requests"] > 0
    assert window["requests"] == window["hits"] > 0     # nothing compiled
    _, line, err = bench_run("tpch-sf1.q6.cold")
    setup, window = compile_counts(err)
    assert line["attempted"] == 1 and setup["requests"] == 0
    assert window["requests"] >= 2 and window["hits"] == 0
    assert line["metrics"]["cold_query_s"]["value"] > window["seconds"] > 0


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell, each
    a NEW file or entry in a copy of the benchmark; no file that is there
    is edited, and the copy's own command runs the new cell."""
    root = str(tmp_path)
    shutil.copytree(run.HERE, os.path.join(root, "benchmark"))
    bench = json.loads(json.dumps(BENCH))
    config = run.load_json(run.HERE, "configs", "tpch-sf1.json")
    config.update(name="dummy-config", source="a test's own deployment")
    config["tables"]["lineitem"]["files"] = 2
    with open(os.path.join(root, "benchmark/configs/dummy-config.json"),
              "w") as f:
        json.dump(config, f)
    traffic = run.load_json(run.HERE, "traffic", "q6.files.json")
    traffic.update(warmup_queries=0, queries_per_window=2)
    with open(os.path.join(root, "benchmark/traffic/dummy-mix.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark/metrics/dummy.metric.py"),
              "w") as f:
        f.write("def read(reading):\n"
                "    return float(len(reading['queries']))\n")
    bench["configs"].append({
        "name": "dummy-config", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/dummy-config.json", "why": "a test"})
    bench["workloads"].append({
        "name": "dummy.cell", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "dummy.metric", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "scan", "moves": "setup_s",
        "workloads": ["dummy.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=run.ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "dummy.cell", "--seed", "7", "--seconds", "1",
         "--trace", "1", "--rehearse-rows", "6000"],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 2
    assert line["metrics"] == {"dummy.metric": {"value": 2.0,
                                                "unit": "queries"}}
    assert os.path.isdir(os.path.join(root, ".bench_cache"))


def test_lineitem_has_one_to_seven_lines_per_order_and_the_rows_asked_for():
    """dbgen's lines per order (the ``runs`` distribution): every count
    from 1 to 7 about as often, line numbers count up inside an order,
    order numbers go on from one file to the next, and a file holds
    exactly the rows it was asked for, whatever the seed drew."""
    import numpy as np

    import datagen
    schema = run.load_json(run.HERE, "schemas", "tpch", "lineitem.json")
    files = [datagen.gen_chunk(schema, 2147483659, k, k * 30001, 30001)
             for k in range(2)]
    assert [t.num_rows for t in files] == [30001, 30001]
    key = np.concatenate([t.column("l_orderkey").to_numpy() for t in files])
    line = np.concatenate([t.column("l_linenumber").to_numpy()
                           for t in files])
    assert (np.diff(key) >= 0).all() and len(np.unique(key)) > 60002 / 4.5
    _, first, lines = np.unique(key, return_index=True, return_counts=True)
    assert lines.min() == 1 and lines.max() == 7
    assert np.bincount(lines)[1:].min() > len(lines) / 7 * 0.85
    assert (line[first] == 1).all() and line.max() == 7
    same = np.diff(key) == 0
    assert (np.diff(line)[same] == 1).all()
