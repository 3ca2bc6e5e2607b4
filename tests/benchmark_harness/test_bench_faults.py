"""A run with the timed path broken underneath reports ``correct`` false.

Drives ``run.main`` past its look for a chip (``--rehearse-rows`` on the
CPU) with a fault planted in the ENGINE, once for each fault these cells
can have: an answer altered where it is produced, and half of the input
left out (half of a table's files never scanned). A step that returns
its state unchanged and an exchange left out do not exist on this path.
"""
import json
import os

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
ROWS = 60000


def _alter_answer(monkeypatch):
    from spark_rapids_tpu.planner import PhysicalPlan
    real = PhysicalPlan.collect

    def collect(self, *a, **k):
        t = real(self, *a, **k)
        i = next(i for i, f in enumerate(t.schema)
                 if pa.types.is_floating(f.type))
        return t.set_column(i, t.schema[i].name,
                            pc.multiply(t.column(i), 1.0 + 1e-6))
    monkeypatch.setattr(PhysicalPlan, "collect", collect)


def _leave_out_half(monkeypatch):
    from spark_rapids_tpu.session import TpuSession
    real = TpuSession.read_parquet

    def read_parquet(self, paths, schema=None):
        if len(paths) > 1:
            paths = paths[:len(paths) // 2]
        return real(self, paths, schema=schema)
    monkeypatch.setattr(TpuSession, "read_parquet", read_parquet)


@pytest.mark.parametrize("fault", [_alter_answer, _leave_out_half])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(bench_run, monkeypatch, cell,
                                                fault):
    fault(monkeypatch)
    rc, line, err = bench_run(cell, seed=41, rows=ROWS, seconds=0.01)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
    assert "correct = False" in err.splitlines()


def test_a_fallback_chunk_in_the_scan_is_not_correct(bench_run, monkeypatch):
    """The configurations promise a device scan: a chunk decoded on the
    host fails the run even where the answer is right."""
    real = run.scan_counters
    monkeypatch.setattr(run, "scan_counters",
                        lambda pp: dict(real(pp), fallbackChunks=1))
    rc, line, _ = bench_run(CELLS[0], seed=41)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["fallback_chunks"] == {"value": 1.0, "limit": 0.0}
    assert line["compared"]["rows_differ"]["value"] == 0.0
