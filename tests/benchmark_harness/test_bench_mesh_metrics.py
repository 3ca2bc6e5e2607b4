"""The six readers of the four-chip cell (``mesh.*``, ``exchange.*``) on
a trace written here by hand, with exact numbers: two chips, the
all-to-all's module by name, the gang's spans on their threads. A trace
with no device plane gives ``None`` from every one of them."""
import json
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import ici_bytes
import mesh_busy
import module_busy
import run
import span_reduce
from jax.profiler import ProfileData

MESH_METRICS = ("mesh.chips_busy", "mesh.busy_balance",
                "exchange.collective_busy_s", "exchange.bytes",
                "exchange.wait_idle_s", "exchange.ici_roofline")
CELL = "tpcds-sf1-store-4chip.q3.ici"


def _ev(meta, start_us, dur_us, **stats):
    ids = {"bytes": 1, "member": 2}
    body = "".join(f" stats {{ metadata_id: {ids[k]} int64_value: {v} }}"
                   for k, v in stats.items())
    return (f"    events {{ metadata_id: {meta} offset_ps: "
            f"{int(start_us * 1e6)} duration_ps: {int(dur_us * 1e6)}"
            f"{body} }}\n")


def _chip(n, ops):
    """A device plane: ``ops`` are (start, duration, module id) in us;
    module 2 is a fused stage, 3 the all-to-all."""
    lines = "".join(_ev(1, s, d) for s, d, _ in ops)
    mods = "".join(_ev(m, s, d) for s, d, m in ops)
    return f"""
planes {{
  id: {n + 1} name: "/device:TPU:{n}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
{lines}  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
{mods}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[] fusion(%p)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_fused_stage(77)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_exchange_all_to_all(99)" }} }}
}}"""


# window 0..20 us. chip 0: stage 1..5, all-to-all 10..12 (busy 6);
# chip 1: stage 2..5, all-to-all 10..12 (busy 5). Member 1 waits at the
# epoch 6..10 (its chip idle throughout); member 0 runs the epoch 8..13
# (its chip idle 8..10 and 12..13).
HAND = _chip(0, [(1, 4, 2), (10, 2, 3)]) + _chip(1, [(2, 3, 2), (10, 2, 3)]) \
    + """
planes {
  id: 9 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
""" + _ev(1, 0, 20) + _ev(2, 0, 20) + """  }
  lines { id: 8 name: "gang-member_0" timestamp_ns: 1000
""" + _ev(3, 0.5, 14, member=0) + _ev(4, 8, 5, bytes=3000000) + """  }
  lines { id: 9 name: "gang-member_1" timestamp_ns: 1000
""" + _ev(3, 0.5, 14, member=1) + _ev(5, 6, 4) + _ev(5, 13, 0.5) + """  }
  event_metadata { key: 1 value { id: 1 name: "collect" } }
  event_metadata { key: 2 value { id: 2 name: "spark:query" } }
  event_metadata { key: 3 value { id: 3 name: "spark:gang.member" } }
  event_metadata { key: 4 value { id: 4 name: "spark:exchange.ici" } }
  event_metadata { key: 5 value { id: 5 name: "spark:exchange.wait" } }
  stat_metadata { key: 1 value { id: 1 name: "bytes" } }
  stat_metadata { key: 2 value { id: 2 name: "member" } }
}
"""


def _profile(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_per_chip_busy_and_idle_under_the_exchanges_spans():
    p = _profile(HAND)
    assert mesh_busy.per_chip_busy_s(p) == pytest.approx(
        {"/device:TPU:0": 6e-6, "/device:TPU:1": 5e-6})
    # the union of the exchange's spans is 6..13.5: chip 0 is idle in it
    # 6..10 and 12..13.5 (5.5), chip 1 the same (5.5)
    assert mesh_busy.idle_under(p, ("spark:exchange.ici",
                                    "spark:exchange.wait")) == \
        pytest.approx(5.5e-6)
    # the epoch alone, 8..13: idle 8..10 and 12..13 on both chips
    assert mesh_busy.idle_under(p, ("spark:exchange.ici",)) == \
        pytest.approx(3e-6)
    assert mesh_busy.idle_under(p, ("spark:no.such",)) == 0.0
    assert module_busy.by_module(p) == pytest.approx(
        {"jit_fused_stage": 3.5e-6, "jit_exchange_all_to_all": 2e-6})
    host_only = HAND[HAND.index('planes {\n  id: 9'):]
    assert mesh_busy.per_chip_busy_s(_profile(host_only)) is None
    assert mesh_busy.idle_under(_profile(host_only), ("spark:op",)) is None


def test_a_chip_that_ran_nothing_counts_as_not_busy():
    idle_chip = HAND.replace(_chip(1, [(2, 3, 2), (10, 2, 3)]),
                             _chip(1, [(30, 1, 2)]))  # outside the window
    busy = mesh_busy.per_chip_busy_s(_profile(idle_chip))
    assert busy == pytest.approx({"/device:TPU:0": 6e-6,
                                  "/device:TPU:1": 0.0})


@pytest.fixture
def star(tmp_path):
    """Two chips' worth of a star small enough to count by hand: four
    fact rows a row group, two row groups, one group of two in each."""
    d = tmp_path / "bench" / "data" / "tpcds-sf1-store-4chip-rows8"
    d.mkdir(parents=True)
    config = run.load_json(run.HERE, "configs", "tpcds-sf1-store-4chip.json")
    date_sk = [2450816 + 320, 2450816 + 321, 2450816, 2450816 + 320,
               2450816 + 320, 2450816 + 686, 2450816 + 686, 2450816 + 1]
    item_sk = [1, 1, 1, 2, 2, 2, 1, 1]
    for k in range(4):
        pq.write_table(pa.table({
            "ss_sold_date_sk": pa.array(date_sk[2 * k:2 * k + 2], pa.int32()),
            "ss_item_sk": pa.array(item_sk[2 * k:2 * k + 2], pa.int32()),
            "ss_ext_sales_price": [1.0, 2.0]}),
            str(d / f"store_sales-{k:02d}.parquet"))
    pq.write_table(pa.table({
        "d_date_sk": pa.array([2450816, 2450816 + 1, 2450816 + 320,
                               2450816 + 321, 2450816 + 686], pa.int32()),
        "d_year": pa.array([1998, 1998, 1998, 1998, 1999], pa.int32()),
        "d_moy": pa.array([1, 1, 11, 11, 11], pa.int32())}),
        str(d / "date_dim-00.parquet"))
    pq.write_table(pa.table({
        "i_item_sk": pa.array([1, 2, 3], pa.int32()),
        "i_brand_id": pa.array([7, 8, 9], pa.int32()),
        "i_brand": ["b7", "b8", "b9"],
        "i_manufact_id": pa.array([128, 128, 5], pa.int32())}),
        str(d / "item-00.parquet"))
    return str(tmp_path / "bench"), config


def test_least_ici_bytes_are_the_shares_groups_at_the_sources_widths(star):
    root, config = star
    paths = ici_bytes.newest_tables(root, config)
    assert [os.path.basename(p) for p in paths["store_sales"]] == \
        [f"store_sales-{k:02d}.parquet" for k in range(4)]
    # chip 0 holds files 0 and 1: rows (nov 98, item 1), (nov 98, item 1),
    # (jan, -), (nov 98, item 2): the groups (1998, b7) and (1998, b8);
    # chip 1 holds files 2 and 3: (nov 98, item 2), (nov 99, item 2),
    # (nov 99, item 1), (jan, -): three groups
    assert ici_bytes.row_group_shares(paths["store_sales"], 2) == [
        [(paths["store_sales"][0], 0), (paths["store_sales"][1], 0)],
        [(paths["store_sales"][2], 0), (paths["store_sales"][3], 0)]]
    assert ici_bytes.partial_rows("tpcds/q3", paths, 2) == [2, 3]
    schemas = {t: run.load_json(run.HERE, "schemas", s["schema"] + ".json")
               for t, s in config["tables"].items()}
    # d_year 4 + i_brand CHAR(50) + i_brand_id 4 + the DOUBLE sum 8
    assert ici_bytes.row_bytes("tpcds/q3", schemas) == 66
    assert ici_bytes.least_bytes_per_chip("tpcds/q3", schemas, paths, 2) \
        == pytest.approx(2.5 * 66 * 0.5)
    assert ici_bytes.newest_tables(str(root) + "-none", config) is None


def _reading_over(monkeypatch, root, text):
    d = os.path.join(root, "trace", "cell", "plugins", "profile", "t0")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(span_reduce, "TRACE_ROOT",
                        os.path.join(root, "trace"))
    for m in (span_reduce, module_busy, mesh_busy):
        m._MEMO.clear()
    return {"trace": {"busy_s": 5.5e-6, "window_s": 20e-6},
            "device": {"kind": "TPU v5 lite", "count": 2}}


def test_the_six_readers_on_the_hand_written_trace(monkeypatch, star):
    root, _ = star
    reading = _reading_over(monkeypatch, root, HAND)
    got = {n: run.metric_reader(n).read(reading) for n in MESH_METRICS}
    assert got["mesh.chips_busy"] == 2.0
    assert got["mesh.busy_balance"] == pytest.approx(100 * 5 / 6)
    assert got["exchange.collective_busy_s"] == pytest.approx(2e-6)
    assert got["exchange.bytes"] == pytest.approx(3.0)
    assert got["exchange.wait_idle_s"] == pytest.approx(5.5e-6)
    # 82.5 bytes a chip at 200 GB/s over 2 us of the program
    assert got["exchange.ici_roofline"] == pytest.approx(
        100 * 82.5 / 200e9 / 2e-6)
    assert 0 < got["exchange.ici_roofline"] < 100
    # a device the peak table does not know: no share
    assert run.metric_reader("exchange.ici_roofline").read(
        dict(reading, device={"kind": "TPU v9", "count": 2})) is None


def test_no_device_plane_or_no_such_span_gives_none(monkeypatch, star):
    root, _ = star
    for n in MESH_METRICS:  # a CPU rehearsal: the harness found no chip
        assert run.metric_reader(n).read(
            {"trace": None, "device": {"kind": "cpu", "count": 4}}) is None
    # a program from before the gang: a device plane, none of its spans,
    # no all-to-all module (the parent commit under this PR's readers)
    before = HAND.replace("spark:exchange.", "spark:other.") \
        .replace("jit_exchange_all_to_all", "jit_fused_stage")
    reading = _reading_over(monkeypatch, root, before)
    got = {n: run.metric_reader(n).read(reading) for n in MESH_METRICS}
    assert got["mesh.chips_busy"] == 2.0 and got["mesh.busy_balance"] > 0
    assert [got[n] for n in MESH_METRICS[2:]] == [None] * 4


def test_the_new_entries_list_the_cell_and_the_cell_lists_no_hbm_roofline():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for n in MESH_METRICS:
        assert entries[n]["workloads"] == [CELL]
        assert entries[n]["moves"] == "query_s"
        assert os.path.exists(os.path.join(run.HERE, "metrics", n + ".py"))
    assert entries["exchange.ici_roofline"]["unit"] == "%"
    # its reader divides by ONE chip's peak over the MEAN chip's busy
    # seconds: four times too high on four chips (PERF.md section 7)
    assert CELL not in entries["query_hbm_roofline"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "q3.files"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert not [m["name"] for m in bench["per_layer"]
                if m["name"].endswith(".mesh")]
