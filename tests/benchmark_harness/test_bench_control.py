"""The control has to come out as NOT correct: the plain reference put in
the program's place, computed in float32 — the nearest precision below
the float64 the configurations state. (On the chip, at the cells' own
size: ``benchmark/readings.py``; PERF.md section 2 has those readings.)"""
import glob
import os

import pytest

import datagen
import run
from compare import judge

CONFIGS = sorted(os.path.basename(p)[:-len(".json")] for p in
                 glob.glob(os.path.join(run.HERE, "configs", "*.json")))
ROWS = 200000


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [31, 32, 2147483693])
def test_float32_control_is_not_correct(config, seed):
    config_file = os.path.join(run.HERE, "configs", config + ".json")
    paths, _, _ = datagen.make_tables(config_file, run.CACHE_ROOT, seed, ROWS)
    for ref in run.load_json(config_file)["queries"]:
        mod = run.load_reference(ref)
        want = mod.reference(paths)
        ok, numbers = judge([mod.reference(paths)], want, mod.KEYS,
                            mod.VALUES)
        assert ok, numbers
        ok, numbers = judge([mod.reference(paths, "float32")], want,
                            mod.KEYS, mod.VALUES)
        assert not ok, numbers
        # by the value column, at least three times over its limit
        assert any(c["value"] > 3 * c["limit"] for n, c in numbers.items()
                   if n.endswith("_rel_gap")), numbers


def test_no_result_is_not_correct():
    mod = run.load_reference("tpch/q6")
    import pyarrow as pa
    want = pa.table({"revenue": [1.0]})
    assert not judge([], want, mod.KEYS, mod.VALUES)[0]
    assert not judge([pa.table({"revenue": [float("nan")]})], want,
                     mod.KEYS, mod.VALUES)[0]
    assert not judge([pa.table({"other": [1.0]})], want, mod.KEYS,
                     mod.VALUES)[0]
