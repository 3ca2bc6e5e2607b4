"""The warm window's end-to-end statistics (``run.warm_metrics``) on
hand-made walls with exact answers, what a rehearsal run of each warm
cell prints under them, and the entries of BENCHMARK.json they rest on."""
import json
import os

import pytest

import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {w["name"]: w for w in BENCH["workloads"]}
WARM = [c for c in CELLS if not c.endswith(".cold")]
ENTRIES = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("walls, centre", [
    ([3.0], 3.0),
    ([3.0, 1.0, 2.0], 2.0),                      # odd: the middle one
    ([4.0, 1.0, 3.0, 2.0], 2.5),                 # even: the middle two's mean
    ([1.25] * 10 + [1.0] * 36, 1.0),             # a fall through the window
    ([3.0] * 7 + [60.0] + [3.0] * 8, 3.0),       # one stall among fifteen
    ([3.1, 2.9] * 7 + [60.0], 3.1),                # the eighth of fifteen
])
def test_query_p50_s_is_the_median_and_query_s_the_windows_mean(walls, centre):
    window_s = sum(walls) + 0.5  # the loop's own time between two queries
    got = run.warm_metrics(walls, window_s)
    assert got["query_p50_s"] == centre
    assert got["query_s"] == window_s / len(walls)


def test_one_stalled_query_of_sixteen_moves_the_median_by_nothing():
    calm = run.warm_metrics([3.0] * 16, 48.0)
    stalled = run.warm_metrics([3.0] * 15 + [60.0], 105.0)
    assert stalled["query_p50_s"] == calm["query_p50_s"] == 3.0
    # the median's blind spot is what query_s is kept for: all the work
    # and all the time of the window, a sixteenth of the 57 s stall
    assert stalled["query_s"] - calm["query_s"] == pytest.approx(57 / 16)


@pytest.mark.parametrize("n, percent, rank", [
    (20, 95, 19), (21, 95, 20), (40, 95, 38), (46, 95, 44), (49, 95, 47),
    (100, 95, 95), (20, 90, 18), (46, 90, 42), (1, 95, 1), (3, 50, 2),
])
def test_nearest_rank_is_the_ceiling_of_the_share(n, percent, rank):
    walls = [float(k) for k in range(n, 0, -1)]  # k-th smallest is k
    assert run.nearest_rank(walls, percent) == float(rank)
    assert run.nearest_rank(walls[::-1], percent) == float(rank)


@pytest.mark.parametrize("n, printed", [(1, False), (15, False), (19, False),
                                        (20, True), (46, True)])
def test_a_tail_is_printed_from_twenty_walls_on(n, printed):
    walls = [1.0 + k / 100 for k in range(n)]
    got = run.warm_metrics(walls, sum(walls))
    assert ("query_p90_s" in got) is printed
    assert "query_max_s" not in got and "query_p95_s" not in got
    if printed:  # never the maximum under a percentile's name
        assert got["query_p90_s"] == run.nearest_rank(walls, 90) < max(walls)
    assert set(got) <= {"query_s", "query_p50_s", "query_p90_s"}


@pytest.mark.parametrize("cell", WARM)
def test_a_warm_cell_prints_exactly_the_names_it_lists(bench_run, monkeypatch,
                                                       cell):
    """With a window that holds enough queries for a tail (one, here) the
    line's names are the cell's ``end_to_end`` entries, no more, no less."""
    monkeypatch.setattr(run, "TAIL_MIN_QUERIES", 1)
    rc, line, err = bench_run(cell, seconds=0.01)
    assert rc == 0 and line["attempted"] == 1, err[-2000:]
    listed = {m["name"] for m in BENCH["end_to_end"] if run.applies(m, cell)}
    assert set(line["metrics"]) == listed
    assert "query_max_s" not in listed and "is not printed" not in err


@pytest.mark.parametrize("cell", WARM)
def test_a_window_of_fewer_than_twenty_prints_no_tail_and_says_so(bench_run,
                                                                  cell):
    rc, line, err = bench_run(cell, seconds=0.01)
    assert rc == 0 and line["attempted"] == 1
    listed = {m["name"] for m in BENCH["end_to_end"] if run.applies(m, cell)}
    assert set(line["metrics"]) == listed - {"query_p90_s"}
    said = "query_p90_s is not printed: the window completed 1 queries" in err
    assert said == ("query_p90_s" in listed)  # a cell that lists no tail


def test_the_cold_cell_prints_what_it_printed(bench_run):
    rc, line, err = bench_run("tpch-sf1.q6.cold")
    assert rc == 0 and set(line["metrics"]) == {"cold_query_s", "setup_s"}
    assert "is not printed" not in err


@pytest.mark.parametrize("entry", ENTRIES, ids=[m["name"] for m in ENTRIES])
def test_an_entry_names_only_cells_that_exist(entry):
    assert entry["name"] != "query_max_s"
    cells = entry.get("workloads", list(CELLS))
    assert cells and set(cells) <= set(CELLS)
    assert len(set(cells)) == len(cells)
    if "moves" in entry:  # each of its cells reports the metric it moves
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == entry["moves"])
        assert all(run.applies(moved, c) for c in cells)


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cell_reports_setup_one_query_metric_and_a_layer(cell):
    e2e = {m["name"] for m in BENCH["end_to_end"] if run.applies(m, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2  # and one query metric
    assert [m for m in BENCH["per_layer"] if run.applies(m, cell)]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == CELLS[cell]["config"])
    assert "status" not in run.load_json(run.ROOT, config["file"])


def test_spreads_of_kept_runs_are_a_checks_arithmetic():
    """``spreads.py``: the quartiles are ``statistics.quantiles``' (wider
    than numpy's), the trimmed spread drops the farthest run only where
    that narrows it, and a late wall is one that STARTS after 10 s."""
    import spreads
    values = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]     # quartiles 1.75, 3.5, 5.25
    assert spreads.spread(values) == pytest.approx(3.5 / 3.5)
    assert spreads.trimmed_spread(values) == pytest.approx(3.0 / 4.0)
    far = [3.0, 3.0, 3.0, 3.0, 3.0, 9.0]        # one far-off run in a set
    assert spreads.spread(far) == pytest.approx(1.5 / 3.0)
    assert spreads.trimmed_spread(far) == 0.0
    assert spreads.late([4.0, 4.0, 4.0, 1.0, 2.0]) == [1.0, 2.0]
    assert spreads.late([11.0, 5.0]) == [5.0]
    runs = [{"cell": "c", "tag": t, "window_s": 4.0 * k, "walls": [k] * 4}
            for t in "AB" for k in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0)]
    runs.append({"cell": "c", "tag": "prime", "window_s": 1.0, "walls": [9.0]})
    got = spreads.table(runs)["c"]
    assert got["median"]["A"][:3] == pytest.approx((3.5, 1.0, 0.75))
    assert got["window_s/n"]["B"][0] == got["max"]["B"][0] == 3.5
    # a check admits bounds from twice the mean trimmed spread (75 %) up
    # to eight times the wider spread (100 %)
    assert spreads.admits(got["median"]) == pytest.approx((150.0, 800.0))
    assert got["mean.late"] == {}  # nothing starts late in a 4 s window
