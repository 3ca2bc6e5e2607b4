"""One task per chip from the conf alone (``spark.rapids.shuffle.mode=ICI``):
the session's mesh and transport, the gang of member tasks
(``exec/gang.py``), its exchange (``shuffle/ici.py::IciGang``), and the
shapes it refuses, each against the plain reference or numpy on the
virtual CPU mesh at a few thousand seeded rows. No number here is a
device number."""
import json
import os
import sys
import threading

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.planner import TpuOverrides
from spark_rapids_tpu.session import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

with open(os.path.join(BENCH, "configs", "tpcds-sf1-store-4chip.json")) as f:
    CONFIG = json.load(f)
CONF = CONFIG["session_conf"]
NDEV = 4
ONE_TASK = {"spark.sql.shuffle.partitions": "1",
            "spark.sql.adaptive.enabled": "false"}


def test_the_configuration_states_three_keys_that_exist():
    from spark_rapids_tpu.config import RapidsConf
    assert CONF == {"spark.rapids.shuffle.mode": "ICI",
                    "spark.sql.shuffle.partitions": "4",
                    "spark.sql.adaptive.enabled": "false"}
    conf = RapidsConf(CONF)
    assert conf.get("spark.rapids.shuffle.mode") == "ICI"
    assert conf.get("spark.sql.shuffle.partitions") == 4
    assert conf.get("spark.sql.adaptive.enabled") is False


def _plan(session, text):
    return TpuOverrides(session.conf).apply(session.sql(text)._node)


def _session(conf, paths):
    s = TpuSession(conf=dict(conf))
    for name, files in paths.items():
        s.register_table(name, s.read_parquet(files))
    return s


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """The store channel's three tables from the benchmark's own
    generator: 240,000 fact rows in the configuration's four files."""
    import datagen
    root = str(tmp_path_factory.mktemp("gang"))
    paths, _, _ = datagen.make_tables(
        os.path.join(BENCH, "configs", "tpcds-sf1-store-4chip.json"), root,
        2147483659, 240000)
    return paths


@pytest.fixture(scope="module")
def q3_text():
    import run
    return run.read_query("tpcds/q3")


# (a) the conf alone brings up mesh and transport; q3 equals the reference
def test_q3_from_the_conf_alone_runs_as_a_gang_and_equals_the_reference(
        star, q3_text):
    import run
    from compare import judge
    from spark_rapids_tpu.shuffle.ici import (IciShuffleTransport,
                                              local_transport)
    s = _session(CONF, star)
    assert isinstance(s.ici_transport, IciShuffleTransport)
    assert s.ici_transport is local_transport(s.conf)  # ONE transport
    assert [d.id for d in s.ici_transport.slot_devices()] == \
        [d.id for d in jax.local_devices()[:NDEV]]
    pp = _plan(s, q3_text)
    assert pp.gang_verdict().startswith("gang of 4 member tasks")
    assert pp.explain("ALL").splitlines()[-1] == "ici: " + pp.gang_verdict()
    got = pp.collect()
    mod = run.load_reference("tpcds/q3")
    ok, numbers = judge([got], mod.reference(star), mod.KEYS, mod.VALUES)
    assert ok and numbers["rows_differ"]["value"] == 0.0, numbers
    assert not pp.fallback_nodes() and run.scan_counters(pp)[
        "fallbackChunks"] == 0
    text = pp.explain_analyze()
    assert "gangMembers=4" in text and "iciEpochs=1" in text
    assert "iciBytes=" in text and text.splitlines()[-1].startswith("ici: ")
    # every file's every row group was read once, by one member
    scan = next(ln for ln in text.splitlines() if "parquet x4" in ln)
    assert "rows=240000," in scan and "slice=" not in scan


def _witness(monkeypatch):
    """Where the members' operators ran and where the partitions were
    aggregated, read off the arrays as ``landed_devices`` does."""
    from spark_rapids_tpu.exec import gang as gang_mod
    from spark_rapids_tpu.shuffle import ici
    seen = {"landed": None, "final_in": {}}
    real_read = ici.IciGangMember.read_partition

    class Recorded(ici.IciGang):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen["gang"] = self

    def read_partition(self, sid, p):
        for b in real_read(self, sid, p):
            seen["final_in"].setdefault(self._k, set()).update(
                ici._devices_of([b]))
            yield b
    monkeypatch.setattr(ici, "IciGang", Recorded)
    monkeypatch.setattr(ici.IciGangMember, "read_partition", read_partition)
    real_run = gang_mod.run

    def run(gs, ctx):
        out = real_run(gs, ctx)
        seen["landed"] = seen["gang"].landed_devices()
        return out
    monkeypatch.setattr(gang_mod, "run", run)
    return seen


# (b) compute follows the partition: each member on its own device
def test_every_member_works_on_its_own_device_and_partitions_stay_where_they_land(
        star, q3_text, monkeypatch):
    from spark_rapids_tpu.shuffle import ici
    seen = _witness(monkeypatch)
    written = {}
    real_write = ici._IciWriter.write_unsplit

    def write_unsplit(self, batch, pids):
        if isinstance(self._t, ici.IciGangMember):
            written.setdefault(self._t._k, set()).update(
                ici._devices_of([batch]))
        return real_write(self, batch, pids)
    monkeypatch.setattr(ici._IciWriter, "write_unsplit", write_unsplit)
    s = _session(CONF, star)
    assert _plan(s, q3_text).collect().num_rows > 0
    ids = [d.id for d in jax.local_devices()[:NDEV]]
    # the partial aggregates a member wrote were computed on its device
    assert written == {k: {ids[k]} for k in range(NDEV)}
    # partition p landed on device p, and was handed to member p there
    assert seen["landed"] == [[ids[p]] for p in range(NDEV)]
    assert seen["final_in"] == {k: {ids[k]} for k in range(NDEV)}


# (c) the share test: members' partials add up to the one-task answer
def test_members_partials_with_dimensions_counted_once_add_up_to_the_whole(
        star, q3_text):
    """Each member's share of the fact table against the WHOLE dimension
    tables, run alone as one task, summed by group, is what one task gives
    for the whole table (before the limit)."""
    text = q3_text[:q3_text.lower().rindex("limit")].rstrip()
    whole = _plan(_session(ONE_TASK, star), text).collect().to_pandas()
    import ici_bytes
    shares = ici_bytes.row_group_shares(star["store_sales"], NDEV)
    parts = []
    for k, share in enumerate(shares):
        d = os.path.join(os.path.dirname(star["item"][0]), f"share{k}")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "ss.parquet")
        pq.write_table(pa.concat_tables(
            pq.ParquetFile(p).read_row_group(g) for p, g in share), path)
        paths = dict(star, store_sales=[path])
        parts.append(_plan(_session(ONE_TASK, paths), text)
                     .collect().to_pandas())
    import pandas as pd
    keys = ["d_year", "brand_id", "brand"]
    summed = pd.concat(parts).groupby(keys, as_index=False)["sum_agg"].sum()
    whole = whole.sort_values(keys).reset_index(drop=True)
    summed = summed.sort_values(keys).reset_index(drop=True)
    assert len(whole) > 5
    assert whole[keys].equals(summed[keys])
    np.testing.assert_allclose(summed["sum_agg"], whole["sum_agg"],
                               rtol=1e-12)
    # and the partial rows the exchange must carry are the shares' groups
    assert ici_bytes.partial_rows("tpcds/q3", star, NDEV) == \
        [len(p) for p in parts]


def _int_files(tmp_path, files, rows_per_group, groups_per_file, seed=5):
    rng = np.random.default_rng(seed)
    paths, ks, vs, names = [], [], [], []
    for i in range(files):
        n = rows_per_group * groups_per_file
        k = rng.integers(0, 37, n).astype(np.int32)
        v = rng.integers(1, 1000, n).astype(np.int64)
        name = np.array([f"name-{x % 11}" for x in k], object)
        path = os.path.join(str(tmp_path), f"t-{i}.parquet")
        pq.write_table(pa.table({"k": k, "v": v, "name": name}), path,
                       row_group_size=rows_per_group)
        paths.append(path)
        ks.append(k), vs.append(v), names.append(name)
    return paths, np.concatenate(ks), np.concatenate(vs), \
        np.concatenate(names)


# (d) the slices are disjoint and cover every row group
@pytest.mark.parametrize("files, groups", [(4, 2), (3, 1), (5, 3), (1, 1),
                                           (2, 1), (8, 1)])
def test_scan_slices_are_disjoint_and_cover_every_row_group(tmp_path, files,
                                                            groups):
    paths, _, _, _ = _int_files(tmp_path, files, 50, groups)
    s = TpuSession()
    scan = s.read_parquet(paths)._node
    whole = scan._device_rg_tasks()
    assert len(whole) == files * groups
    shares = [scan.sliced(k, NDEV)._device_rg_tasks() for k in range(NDEV)]
    assert [t for share in shares for t in share] == whole  # in file order
    assert len({t for share in shares for t in share}) == len(whole)
    sizes = [len(share) for share in shares]
    assert max(sizes) - min(sizes) <= 1
    import ici_bytes
    assert ici_bytes.row_group_shares(paths, NDEV) == shares


def _want(k, v, name=None):
    out = []
    for g in np.unique(k if name is None else name):
        pick = (k if name is None else name) == g
        out.append((g if name is None else str(g), int(pick.sum()),
                    int(v[pick].sum())))
    return sorted(out)


def _rows(table):
    return sorted(tuple(r.values()) for r in table.to_pylist())


# (e) a string-keyed group-by, and one with an empty member
@pytest.mark.parametrize("files, query, by_name", [
    (4, "select name, count(*) c, sum(v) s from t group by name", True),
    (2, "select k, count(*) c, sum(v) s from t group by k", False),
    (1, "select name, count(*) c, sum(v) s from t group by name", True),
])
def test_group_by_through_the_gang_exchange(tmp_path, files, query, by_name):
    paths, k, v, name = _int_files(tmp_path, files, 400, 1)
    s = _session(CONF, {"t": paths})
    pp = _plan(s, query)
    assert pp.gang_verdict().startswith("gang of 4"), pp.gang_verdict()
    got = pp.collect()
    assert _rows(got) == _want(k, v, name if by_name else None)
    assert "gangMembers=4" in pp.explain_analyze()


# (f) a refused shape runs as one task, says so, and is right
def test_union_all_with_a_table_whole_in_every_member_runs_as_one_task(
        tmp_path):
    paths, k, v, _ = _int_files(tmp_path, 4, 300, 1)
    small = os.path.join(str(tmp_path), "w.parquet")
    pq.write_table(pa.table({"k": k[:100], "v": v[:100]}), small)
    s = _session(CONF, {"t": paths, "w": [small]})
    pp = _plan(s, "select k, count(*) c, sum(v) s from (select k, v from t "
                  "union all select k, v from w) g group by k")
    assert pp.gang_verdict().startswith("one task: Union")
    assert "counted once per member" in pp.gang_verdict()
    assert pp.explain("ALL").splitlines()[-1].startswith("ici: one task")
    got = pp.collect()  # through the transport's one-task path
    assert _rows(got) == _want(np.concatenate([k, k[:100]]),
                               np.concatenate([v, v[:100]]))
    assert "gangMembers" not in pp.explain_analyze()


def test_other_plans_say_why_they_run_as_one_task(tmp_path):
    paths, k, v, _ = _int_files(tmp_path, 4, 100, 1)
    s = _session(CONF, {"t": paths})
    assert _plan(s, "select k, v from t where v > 10").gang_verdict() \
        .startswith("one task: FileScanExec above the aggregate")
    local = _session(ONE_TASK, {"t": paths})
    pp = _plan(local, "select k, sum(v) s from t group by k")
    assert pp.gang_verdict() == "" and "ici:" not in pp.explain("ALL")
    adaptive = _session(dict(CONF, **{"spark.sql.adaptive.enabled": "true"}),
                        {"t": paths})
    pp = _plan(adaptive, "select k, sum(v) s from t group by k")
    assert pp.gang_verdict().startswith("one task: the aggregate does not "
                                        "read a hash exchange")
    assert sorted(r["s"] for r in pp.collect().to_pylist()) == \
        sorted(int(v[k == g].sum()) for g in np.unique(k))


# (g) a member that raises fails the query and leaves nothing behind
def test_a_member_that_raises_fails_the_query_and_leaves_nothing_behind(
        tmp_path, monkeypatch):
    from spark_rapids_tpu.io import TpuFileScanExec
    paths, k, v, _ = _int_files(tmp_path, 4, 300, 1)
    s = _session(CONF, {"t": paths})
    real = TpuFileScanExec._plan_row_group

    def fail_one(self, path, g, fetched):
        if getattr(self, "_slice", (0, 1))[0] == 2:
            raise OSError("member 2 cannot read its slice")
        return real(self, path, g, fetched)
    monkeypatch.setattr(TpuFileScanExec, "_plan_row_group", fail_one)
    before = {t.name for t in threading.enumerate()}
    pp = _plan(s, "select k, count(*) c, sum(v) s from t group by k")
    with pytest.raises(OSError, match="member 2 cannot read its slice"):
        pp.collect()
    left = [t.name for t in threading.enumerate()
            if t.name.startswith("gang-member") and t.is_alive()]
    assert not left, left
    assert {t.name for t in threading.enumerate()
            if t.name.startswith("gang-member")} <= before
    t = s.ici_transport
    assert not t._pending and not t._results and not t._nparts
    # the session is good for the next query
    monkeypatch.setattr(TpuFileScanExec, "_plan_row_group", real)
    got = _plan(s, "select k, count(*) c, sum(v) s from t group by k").collect()
    assert _rows(got) == _want(k, v)


# (h) one local device: the mode runs as one task, and says so
def test_with_one_local_device_the_mode_runs_as_one_task(tmp_path,
                                                          monkeypatch):
    paths, k, v, _ = _int_files(tmp_path, 2, 300, 1)
    one = jax.local_devices()[:1]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **kw: one)
    s = _session(CONF, {"t": paths})
    assert s.ici_transport.ndev == 1
    pp = _plan(s, "select k, count(*) c, sum(v) s from t group by k")
    assert pp.gang_verdict().startswith("one task: the mesh is one device")
    assert _rows(pp.collect()) == _want(k, v)
