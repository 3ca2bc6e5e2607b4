"""The benchmark's own tests (BENCHMARK.json ``paths``), collected by
``pytest tests/``. They run on the CPU (``tests/conftest.py`` pins it),
put ``benchmark/`` on the path, keep every file a run writes (tables,
traces, the compile cache) in a directory of this pytest process, put
jax's compile-cache settings back after each test, and read the cells
from a copy of BENCHMARK.json."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402

with open(run.BENCH_FILE) as f:
    BENCH = json.load(f)

_JAX_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(scope="session")
def scratch(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("bench"))
    with open(os.path.join(d, "BENCHMARK.json"), "w") as f:
        json.dump(BENCH, f)
    return d


@pytest.fixture(autouse=True)
def _own_files_and_settings(scratch, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(run, "BENCH_FILE",
                        os.path.join(scratch, "BENCHMARK.json"))
    monkeypatch.setattr(run, "CACHE_ROOT", os.path.join(scratch, "benchmark"))
    monkeypatch.setattr(run, "XLA_CACHE_DIR", os.path.join(scratch, "xla"))
    before = {k: getattr(jax.config, k) for k in _JAX_KEYS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture
def bench_run(capsys):
    """``run.main`` past its look for a chip: ``(exit code, result line or
    None, stderr)`` of one rehearsal run of a cell."""
    def go(cell, trace=0, seed=2147483659, rows=6000, seconds=1):
        args = ["--workload", cell, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", str(trace)]
        if rows:
            args += ["--rehearse-rows", str(rows)]
        rc = run.main(args)
        out = capsys.readouterr()
        lines = out.out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), out.err
    return go
