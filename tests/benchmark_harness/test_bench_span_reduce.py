"""``span_reduce``: the program's ``spark:`` spans laid over the chip's
idle time, on a trace written here by hand (exact numbers) and on the
small trace recorded on a TPU v5e with the spans in it (``data/``), and
the eight per-layer readers on both."""
import json
import os

import pytest

import run
import span_reduce
from jax.profiler import ProfileData

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_METRICS = ("device.idle_owned_share", "device.idle_owned_share.cold",
               "scan.read_idle_s", "scan.assemble_idle_s",
               "scan.upload_idle_s", "scan.dispatch_idle_s",
               "scan.host_overlap_share", "scan.upload_bytes")


def _ev(meta, start_us, dur_us, **stats):
    """One event; ``stats`` by the ids of ``stat_metadata`` below."""
    ids = {"bytes": 1, "rows": 2, "on": 3}
    body = "".join(
        f" stats {{ metadata_id: {ids[k]} "
        + (f'str_value: "{v}"' if isinstance(v, str)
           else f"int64_value: {v}") + " }" for k, v in stats.items())
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)}"
            f"{body} }}\n")


# one chip, busy 2..4 and 7..8 us of a 10 us window (idle 7 us); the
# caller's thread, one feeder, one pool thread; times in microseconds
HAND = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.42 = (u32[]) while(%p)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_scan_decode_chain(123)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
""" + _ev(1, 0, 10) + _ev(2, 0, 10) + _ev(3, 1, 4, on="upload") \
    + _ev(4, 5, 1) + _ev(5, 6, 3, rows=1, bytes=8) + _ev(6, 9.2, 0.4) + """  }
  lines { id: 8 name: "scan-upload_0" timestamp_ns: 1000
""" + _ev(7, 0.5, 1, rows=100, bytes=4000000) + _ev(8, 1.5, 0.25,
                                                   bytes=4000000) \
    + _ev(9, 2.5, 0.5) + _ev(8, 3, 0.25, bytes=2000000) \
    + _ev(7, 4, 1, rows=50, bytes=2000000) + """  }
  lines { id: 9 name: "scan-plan_0" timestamp_ns: 1000
""" + _ev(10, 0, 1, bytes=1000) + _ev(10, 4.5, 1, bytes=500) + """  }
  event_metadata { key: 1 value { id: 1 name: "collect" } }
  event_metadata { key: 2 value { id: 2 name: "spark:query" } }
  event_metadata { key: 3 value { id: 3 name: "spark:scan.wait" } }
  event_metadata { key: 4 value { id: 4 name: "spark:op" } }
  event_metadata { key: 5 value { id: 5 name: "spark:download" } }
  event_metadata { key: 6 value { id: 6 name: "TransferFromDevice" } }
  event_metadata { key: 7 value { id: 7 name: "spark:scan.assemble" } }
  event_metadata { key: 8 value { id: 8 name: "spark:scan.upload" } }
  event_metadata { key: 9 value { id: 9 name: "spark:scan.dispatch" } }
  event_metadata { key: 10 value { id: 10 name: "spark:scan.read" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
  stat_metadata { key: 2 value { id: 2 name: "rows" } }
  stat_metadata { key: 3 value { id: 3 name: "on" } }
}
"""


def _profile(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_hand_written_trace_shares_idle_time_out_exactly():
    r = span_reduce.reduce_spans(_profile(HAND))
    us = pytest.approx
    assert r["window_s"] == us(10e-6)
    assert r["busy_s"] == us(3e-6)
    assert r["idle_s"] == us(7e-6)
    sp = r["spans"]
    owned = {n: rec["idle_owned_s"] for n, rec in sp.items()}
    # idle 0..2: read alone 0..0.5, read+assemble 0.5..1 (halved),
    # assemble 1..1.5, upload 1.5..1.75, then only the consumer's wait
    # is open until the chip starts at 2
    # idle 4..7: assemble 4..4.5, assemble+read 4.5..5, read 5..5.5,
    # nothing works 5.5..6 (the operator span contains it), download 6..7
    # idle 8..10: download 8..9, then the query span alone
    assert owned["spark:scan.read"] == us(1.5e-6)
    assert owned["spark:scan.assemble"] == us(1.5e-6)
    assert owned["spark:scan.upload"] == us(0.25e-6)
    assert owned["spark:scan.dispatch"] == 0.0  # the chip was busy
    assert owned["spark:download"] == us(2e-6)
    # a wait owns only where nothing works, and it is the most specific
    # of the spans open there that takes the instant
    assert owned["spark:scan.wait"] == us(0.25e-6)
    assert owned["spark:op"] == us(0.5e-6)
    assert owned["spark:query"] == us(1e-6)
    # nothing is counted twice, nothing is lost
    assert sum(owned.values()) + r["idle_unowned_s"] == us(r["idle_s"])
    assert r["idle_unowned_s"] == 0.0
    assert r["idle_by_working_s"] == us(5.25e-6)
    # counts, seconds and summed arguments per name; the runtime's own
    # event between the spans is not the program's
    assert sp["spark:scan.read"]["count"] == 2
    assert sp["spark:scan.read"]["seconds"] == us(2e-6)
    assert sp["spark:scan.read"]["args"] == {"bytes": 1500}
    assert sp["spark:scan.assemble"]["args"] == {"rows": 150,
                                                 "bytes": 6000000}
    assert sp["spark:scan.upload"]["args"]["bytes"] == 6000000
    assert sp["spark:scan.wait"]["args"] == {}  # "on" is not a number
    assert sp["spark:scan.read"]["threads"] == ["scan-plan_0"]
    assert sp["spark:scan.upload"]["threads"] == ["scan-upload_0"]
    assert "TransferFromDevice" not in sp
    # the scan's host stages: 0..1.75, 2.5..3.25, 4..5.5 on any thread,
    # of which 2.5..3.25 lies under device work
    assert r["scan_host"]["union_s"] == us(4e-6)
    assert r["scan_host"]["under_busy_s"] == us(0.75e-6)


def test_a_program_without_spans_or_a_trace_without_device_gives_nothing():
    cut = HAND.index('  lines { id: 8 name: "scan-upload_0"')
    end = HAND.index("  event_metadata { key: 1 value { id: 1 name: "
                     '"collect"')
    no_spans = (HAND[:cut] + HAND[end:]).replace("spark:", "other:")
    assert span_reduce.reduce_spans(_profile(no_spans)) is None
    host_only = HAND[HAND.index('planes {\n  id: 2'):]
    assert span_reduce.reduce_spans(_profile(host_only)) is None


def _reading_over(monkeypatch, tmp_path, profile_bytes):
    """A reading whose trace is the given one, found where ``run.py``
    would have put it."""
    d = tmp_path / "cell" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(profile_bytes)
    monkeypatch.setattr(span_reduce, "TRACE_ROOT", str(tmp_path))
    span_reduce._MEMO.clear()
    return {"trace": {"busy_s": 1.0, "window_s": 2.0}}


def test_the_eight_readers_on_the_hand_written_trace(monkeypatch, tmp_path):
    reading = _reading_over(
        monkeypatch, tmp_path,
        ProfileData.text_proto_to_serialized_xspace(HAND))
    got = {n: run.metric_reader(n).read(reading) for n in NEW_METRICS}
    assert got["device.idle_owned_share"] == pytest.approx(75.0)
    assert got["device.idle_owned_share.cold"] == pytest.approx(75.0)
    assert got["scan.read_idle_s"] == pytest.approx(1.5e-6)
    assert got["scan.assemble_idle_s"] == pytest.approx(1.5e-6)
    assert got["scan.upload_idle_s"] == pytest.approx(0.25e-6)
    assert got["scan.dispatch_idle_s"] == 0.0
    assert got["scan.host_overlap_share"] == pytest.approx(18.75)
    assert got["scan.upload_bytes"] == pytest.approx(6.0)
    # no device plane in the harness's reduction (a CPU rehearsal):
    # nothing is looked for and every reader returns None
    for n in NEW_METRICS:
        assert run.metric_reader(n).read({"trace": None}) is None


def test_every_new_metric_is_an_entry_with_its_cells():
    """Each reader lists at least its first cell, and only cells of
    BENCHMARK.json: a later cell lists itself under the existing entry."""
    with open(os.path.join(os.path.dirname(run.HERE),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for n in NEW_METRICS:
        first = "tpch-sf1.q6.cold" if n.endswith(".cold") \
            else "tpch-sf1.q6.files"
        assert entries[n]["workloads"][0] == first
        assert set(entries[n]["workloads"]) <= cells


def test_recorded_v5e_trace_with_spans(monkeypatch, tmp_path):
    """A rehearsal-size Q6 (2 files x 1000 rows, four dispatches from one
    feeder thread) traced on a TPU v5e with the harness's options and cut
    to the planes, lines and arguments the readers read (the expected
    JSON says how): every span of the local query path is in it, on its
    thread, and the reduction gives the numbers kept beside it."""
    path = os.path.join(DATA, "v5e_spans.xplane.pb")
    with open(os.path.join(DATA, "v5e_spans.expected.json")) as f:
        want = json.load(f)
    r = span_reduce.reduce_spans(ProfileData.from_file(path))
    for k in ("window_s", "busy_s", "idle_s", "idle_unowned_s",
              "idle_by_working_s"):
        assert r[k] == pytest.approx(want[k]), k
    assert 0 < r["busy_s"] < r["window_s"]
    assert set(r["spans"]) == set(want["spans"]) == {
        "spark:query", "spark:admit", "spark:op", "spark:scan.read",
        "spark:scan.wait", "spark:scan.assemble", "spark:scan.arena_wait",
        "spark:scan.upload", "spark:scan.dispatch", "spark:download",
        "spark:finish"}
    for n, w in want["spans"].items():
        got = r["spans"][n]
        assert got["count"] == w["count"], n
        assert got["seconds"] == pytest.approx(w["seconds"]), n
        assert got["idle_owned_s"] == pytest.approx(w["idle_owned_s"]), n
        assert got["args"] == pytest.approx(w["args"]), n
        assert got["threads"] == w["threads"], n
    sp = r["spans"]
    assert sp["spark:scan.read"]["count"] == 4
    assert sp["spark:scan.dispatch"]["count"] == 4
    assert sp["spark:scan.arena_wait"]["count"] == 3
    assert all(t.startswith("scan-upload")
               for t in sp["spark:scan.dispatch"]["threads"])
    assert sp["spark:scan.upload"]["args"]["bytes"] > 0
    owned = sum(rec["idle_owned_s"] for rec in sp.values())
    assert owned + r["idle_unowned_s"] == pytest.approx(r["idle_s"])
    assert r["scan_host"] == pytest.approx(want["scan_host"])
    with open(path, "rb") as f:
        reading = _reading_over(monkeypatch, tmp_path, f.read())
    for n in NEW_METRICS:
        assert run.metric_reader(n).read(reading) == \
            pytest.approx(want["metrics"][n]), n
