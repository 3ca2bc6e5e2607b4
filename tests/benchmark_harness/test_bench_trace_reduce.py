"""The reduction from a profiler trace to busy seconds, top operations
and idle gaps, on a trace written here by hand (exact numbers) and on
the small trace recorded on a TPU v5e kept under ``data/``."""
import os

import pytest

import trace_reduce
from jax.profiler import ProfileData

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# two chips; times in picoseconds from each line's timestamp_ns
HAND = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = (f32[8]{0}, s32[8]{0}) sort(%fusion.1, %iota)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(123456789)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "plan" } }
  event_metadata { key: 2 value { id: 2 name: "collect" } }
  event_metadata { key: 3 value { id: 3 name: "TransferFromDevice" } }
}
"""


def test_hand_written_trace_reduces_to_known_numbers():
    prof = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(HAND))
    r = trace_reduce.reduce_profile(prof)
    # window: plan starts at 0, collect ends at 10 us
    assert r["window_s"] == pytest.approx(10e-6)
    # chip 0: [0,3] and [6,7] busy = 4 us; chip 1: [2,4] = 2 us; mean 3
    assert r["busy_s"] == pytest.approx(3e-6)
    assert r["chips"] == 2
    # named <XLA module>/<HLO op name>: no shapes, no fingerprint; chip 1
    # recorded no program run, so its operation's module is unknown
    ops = dict(r["device_ops"])
    assert ops["jit_step/fusion.1"] == pytest.approx(3e-6)
    assert ops["jit_step/sort.2"] == pytest.approx(2e-6)
    assert ops["?/fusion.1"] == pytest.approx(2e-6)
    assert len(ops) == 3  # the module line is not an operation
    gaps = dict(r["idle_gaps"])
    # chip 0: 3..6 (mid 4.5, inside TransferFromDevice) and 7..10;
    # chip 1: 0..2 (mid 1, still the plan span) and 4..10 (mid 7)
    assert gaps["collect: TransferFromDevice"] == pytest.approx(3e-6)
    assert gaps["collect"] == pytest.approx(3e-6 + 6e-6)
    assert gaps["plan"] == pytest.approx(2e-6)


def test_a_trace_without_device_operations_gives_nothing():
    host_only = HAND[HAND.index('planes {\n  id: 3'):]
    prof = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    assert trace_reduce.reduce_profile(prof) is None


def test_recorded_v5e_trace():
    path = os.path.join(DATA, "v5e_small.xplane.pb")
    r = trace_reduce.reduce_profile(ProfileData.from_file(path))
    with open(os.path.join(DATA, "v5e_small.expected.json")) as f:
        import json
        want = json.load(f)
    assert r["chips"] == want["chips"]
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert 0 < r["busy_s"] <= r["window_s"]
    assert [n for n, _ in r["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert r["device_ops"][0][0] == "jit__lambda/sort.6"
    for (_, got), (_, w) in zip(r["device_ops"], want["device_ops"]):
        assert got == pytest.approx(w)
    assert dict(r["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
