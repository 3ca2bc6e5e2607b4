"""The three readers of the re-jit (``exec.jit_misses``,
``exec.jit_host_s``, ``exec.jit_idle_s``) and ``program_jit.py``'s table
on a trace written here by hand, with exact numbers: two chips, a query
whose caller misses once and hits once, and two gang members missing at
once. A trace with no ``spark:program`` span, or no device plane, gives
``None``."""
import json
import os

import pytest

import mesh_busy
import module_busy
import program_jit
import run
import span_reduce
from jax.profiler import ProfileData

METRICS = ("exec.jit_misses", "exec.jit_host_s", "exec.jit_idle_s")
WARM = ["tpch-sf1.q6.files", "tpcds-sf1-store.q3.files",
        "tpcds-sf1-store-4chip.q3.ici", "tpch-sf1.q1.files"]
STATS = ("span", "parent", "op", "program", "miss", "trace_s", "lower_s",
         "compile_s", "cache_hits")


def _stat(k, v):
    i = STATS.index(k) + 1
    if isinstance(v, str):
        return f' stats {{ metadata_id: {i} str_value: "{v}" }}'
    if isinstance(v, float):
        return f" stats {{ metadata_id: {i} double_value: {v!r} }}"
    return f" stats {{ metadata_id: {i} int64_value: {v} }}"


def _ev(meta, start_us, end_us, **stats):
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * 1e6)} duration_ps: "
            f"{int((end_us - start_us) * 1e6)}"
            + "".join(_stat(k, v) for k, v in stats.items()) + " }\n")


def _chip(n, busy):
    ops = "".join(_ev(1, s, e) for s, e in busy)
    return f"""
planes {{
  id: {n + 1} name: "/device:TPU:{n}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
{ops}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[] fusion(%p)" }} }}
}}"""


def _line(k, name, events):
    return f"  lines {{ id: {k} name: \"{name}\" timestamp_ns: 1000\n" \
        + "".join(events) + "  }\n"


def _miss(t, l, c, hits=0):
    return dict(miss=1, trace_s=t, lower_s=l, compile_s=c, cache_hits=hits)


HIT = dict(miss=0, trace_s=0.0, lower_s=0.0, compile_s=0.0, cache_hits=0)
# window 0..20 us. Chip 0 busy 1..6 and 10..13, chip 1 busy 2..6 and
# 10..12: both idle 6..10 and 13..20. The caller's join misses 6..10 (a
# gap it owns) and its fused stage hits 13..15 (a gap it does not own);
# two members miss 15..18 and 16..18 at once (the union, 15..18, owns 3).
HAND = _chip(0, [(1, 6), (10, 13)]) + _chip(1, [(2, 6), (10, 12)]) + (
    "\nplanes {\n  id: 9 name: \"/host:CPU\"\n"
    + _line(7, "python", [
        _ev(1, 0, 20), _ev(2, 0, 20, span="s1"),
        _ev(3, 1, 19, span="s2", parent="s1", op="Join:op3"),
        _ev(4, 6, 10, span="s3", parent="s2", program="join_count",
            **_miss(0.5, 0.25, 0.125, hits=1)),
        _ev(4, 13, 15, span="s4", parent="s2", program="fused_stage",
            **HIT)])
    + "".join(_line(8 + k, f"gang-member_{k}", [
        _ev(5, 0.5, 19, span=f"m{k}", parent="s1"),
        _ev(4, 15 + k, 18, span=f"p{k}", parent=f"m{k}",
            program="join_probe", **_miss(0.25, 0.0, 0.0))])
        for k in range(2))
    + "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: '
              f'"{n}" }} }}\n' for i, n in enumerate(
                  ("collect", "spark:query", "spark:op", "spark:program",
                   "spark:gang.member"), 1))
    + "".join(f'  stat_metadata {{ key: {i} value {{ id: {i} name: '
              f'"{n}" }} }}\n' for i, n in enumerate(STATS, 1))
    + "}\n")


def _profile(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def _reading_over(monkeypatch, root, text):
    d = os.path.join(root, "trace", "cell", "plugins", "profile", "t0")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(span_reduce, "TRACE_ROOT",
                        os.path.join(root, "trace"))
    for m in (span_reduce, module_busy, mesh_busy):
        m._MEMO.clear()
    return {"trace": {"busy_s": 7e-6, "window_s": 20e-6},
            "device": {"kind": "TPU v5 lite", "count": 2}}


def test_a_miss_over_a_gap_owns_it_and_a_hit_owns_nothing():
    # 6..10 (4 us) and 15..18 (3 us) on each chip; the hit's 13..15 and
    # the members' overlap 16..18 are not counted again
    assert program_jit.miss_idle_s(_profile(HAND)) == pytest.approx(7e-6)
    host_only = HAND[HAND.index('planes {\n  id: 9'):]
    assert program_jit.miss_idle_s(_profile(host_only)) is None
    assert program_jit.by_program(_profile(host_only)) is None


def test_the_table_by_program_and_parent():
    rows = program_jit.by_program(_profile(HAND))
    assert [(r["program"], r["parent"], r["calls"], r["misses"])
            for r in rows] == [
        ("join_count", "spark:op Join:op3", 1, 1),
        ("join_probe", "spark:gang.member", 2, 2),
        ("fused_stage", "spark:op Join:op3", 1, 0)]
    assert [r["idle_s"] for r in rows] == pytest.approx([4e-6, 3e-6, 0.0])
    join = rows[0]
    assert (join["trace_s"], join["lower_s"], join["compile_s"],
            join["cache_hits"]) == (0.5, 0.25, 0.125, 1)
    assert rows[1]["trace_s"] == 0.5


def test_the_three_readers(monkeypatch, tmp_path):
    reading = _reading_over(monkeypatch, str(tmp_path), HAND)
    got = {n: run.metric_reader(n).read(reading) for n in METRICS}
    assert got["exec.jit_misses"] == 3  # the hit counts 0
    assert got["exec.jit_host_s"] == pytest.approx(0.875 + 0.5)
    assert got["exec.jit_idle_s"] == pytest.approx(7e-6)
    assert got["exec.jit_idle_s"] <= 20e-6 - 7e-6  # under the query's idle


def test_no_program_span_or_no_device_plane_gives_none(monkeypatch,
                                                       tmp_path):
    for n in METRICS:  # a CPU rehearsal: the harness found no chip
        assert run.metric_reader(n).read(
            {"trace": None, "device": {"kind": "cpu", "count": 1}}) is None
    # a program from before the span (the parent commit under these
    # readers): the other spans are there, none of this name
    reading = _reading_over(monkeypatch, str(tmp_path),
                            HAND.replace("spark:program", "spark:other"))
    assert [run.metric_reader(n).read(reading) for n in METRICS] == \
        [None] * 3


def test_the_entries_list_the_warm_cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert list(entries)[-3:] == list(METRICS)
    for n in METRICS:
        m = entries[n]
        assert m["workloads"] == WARM and m["moves"] == "query_s"
        assert m["layer"] == entries["exec.compiles_per_query"]["layer"]
        assert os.path.exists(os.path.join(run.HERE, "metrics", n + ".py"))
    assert [entries[n]["source"] for n in METRICS] == [
        "program_counter", "host_clock", "device_trace"]
