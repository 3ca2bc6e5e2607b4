"""chip_smoke.py on the CPU mesh: the same phase functions the chip run
calls, at a few thousand rows — control flow, oracles and the
four-virtual-device ICI phase — plus the two rules around it: no TPU, no
result; and the compile cache is placed from outside."""
import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import chip_smoke  # noqa: E402
from spark_rapids_tpu import compile_cache  # noqa: E402


@pytest.fixture(autouse=True)
def data_under_tmp(tmp_path, monkeypatch):
    """Generated files go under the test's tmp dir, not the checkout."""
    monkeypatch.setattr(chip_smoke, "DATA_DIR", str(tmp_path))


@pytest.mark.parametrize("phase,sizes", [
    ("phase_types", ()),
])
def test_single_chip_phase_runs_and_checks_itself(phase, sizes, capsys):
    getattr(chip_smoke, phase)(*sizes)
    out = capsys.readouterr().out
    assert "bit-exact round trip" in out


@pytest.mark.parametrize("query", chip_smoke.NDS_QUERIES
                         + ("q55", "q96", "q_customer_age"))
def test_nds_phase_query_from_sql_text_matches_oracle(query, capsys):
    chip_smoke.phase_nds(1 << 12, 1 << 10, queries=(query,))
    out = capsys.readouterr().out
    assert f"{query} cold:" in out and "fallbackChunks=0" in out
    # the warm run of the same plan asks the compiler for nothing
    warm = [ln for ln in out.splitlines() if f"{query} warm:" in ln]
    assert warm and "compile_requests=0 " in warm[0], out


def test_ici_phase_spreads_over_four_virtual_devices(capsys):
    devices = jax.devices()[:4]
    assert len(devices) == 4
    chip_smoke.phase_ici(devices, 1 << 13, 1 << 8)
    out = capsys.readouterr().out
    assert "groups equal numpy exactly" in out
    assert "plan on one device with the local transport exactly" in out
    assert "equal the CPU oracle and the local transport exactly" in out
    assert "all-to-all ops over 4 devices" in out
    ids = sorted(d.id for d in devices)
    landed = [ln for ln in out.splitlines() if "had landed on" in ln][0]
    assert all(f"[{i}]" in landed for i in ids), landed


def test_nds_phase_leaves_a_warehouse_row_per_collect_naming_the_device(
        capsys):
    """Two queries, a cold and a warm collect each: four rows, each
    naming the device the query ran on."""
    from spark_rapids_tpu.obs.warehouse import read_rows
    chip_smoke.phase_nds(1 << 12, 1 << 10, queries=("q_topn", "q96"))
    rows = read_rows(chip_smoke.smoke_conf()["spark.rapids.warehouse.dir"])
    assert len(rows) == 4
    assert {r["device_kind"] for r in rows} == {"cpu"}
    assert "nds warehouse rows=4 name device_kind='cpu'" \
        in capsys.readouterr().out


def test_single_chip_phase_list_is_static_and_holds_the_minimum():
    """What the driver's run covers never depends on the clock or on the
    compile cache: one fixed list, with q3 and q_topn among the NDS
    queries. Q6 and the join are cells of the benchmark, not phases."""
    names = [name for name, _, _ in chip_smoke.single_chip_phases()]
    assert names == ["types", "nds"]
    assert {"q3", "q_topn"} <= set(chip_smoke.NDS_QUERIES)


def test_phase_failure_is_not_swallowed(monkeypatch):
    """A wrong answer ends the run: the phases have no try/except."""
    from spark_rapids_tpu.tools import nds
    monkeypatch.setitem(nds.SQL_QUERIES, "q96", nds.SQL_QUERIES["q96"]
                        .replace("BETWEEN 40 AND 60", "BETWEEN 40 AND 59"))
    with pytest.raises(AssertionError):
        chip_smoke.phase_nds(1 << 12, 1 << 10, queries=("q96",))


def test_instruments_are_the_benchmarks_own():
    """One compile meter, one scan-counter reader, one device rule: the
    yardstick's (the harness tests import ``run`` the same way)."""
    import compile_meter
    import run
    assert run.__file__ == os.path.join(chip_smoke.BENCHMARK_DIR, "run.py")
    assert chip_smoke.CompileMeter is compile_meter.CompileMeter
    assert chip_smoke.scan_counters is run.scan_counters
    assert isinstance(chip_smoke.meter(), compile_meter.CompileMeter)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_on_a_cpu_backend_exits_nonzero_naming_the_platform(
        argv, capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main(argv)
    assert ei.value.code not in (0, None)
    assert "platform='cpu'" in str(ei.value.code)
    assert capsys.readouterr().out == ""  # no result line, nothing else


def test_unknown_device_kind_has_no_default_peak():
    """The peak is the benchmark's table's (``benchmark/peaks.json``)."""
    assert chip_smoke.hbm_peak_gbs("TPU v5 lite") == 819
    with pytest.raises(KeyError, match="TPU v9"):
        chip_smoke.hbm_peak_gbs("TPU v9")


# --- the compile-cache helper -------------------------------------------------

@pytest.fixture
def cache_config():
    """Put jax's cache settings back (the tests run without one)."""
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def test_cache_dir_from_environment_is_never_overridden(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    jax.config.update("jax_compilation_cache_dir", "set-by-jax-from-env")
    assert compile_cache.enable_compile_cache() == str(tmp_path / "ext")
    # nothing set in code: jax's own reading of the variable stands
    assert jax.config.jax_compilation_cache_dir == "set-by-jax-from-env"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(compile_cache.__file__)))
    want = os.path.join(checkout, ".bench_cache", "xla")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.exists(os.path.join(checkout, "chip_smoke.py"))


def test_result_line_is_one_json_object_with_the_drivers_keys():
    devices = jax.devices()[:4]
    line = chip_smoke.result_line(devices)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "cpu", "kind": devices[0].device_kind, "count": 4}}
