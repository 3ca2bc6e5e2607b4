"""On the four-chip cell an exchange and a member's slice DO exist
(``test_bench_faults.py`` says of the one-chip cells that they do not):
a whole rehearsal run of the cell with one member's scan slice never read,
and with one chip's partial aggregates dropped at the exchange, reports
``correct`` false each time."""
import pytest

CELL = "tpcds-sf1-store-4chip.q3.ici"
ROWS = 240000


def _a_slice_is_never_read(monkeypatch):
    from spark_rapids_tpu.io import TpuFileScanExec
    real = TpuFileScanExec._device_rg_tasks

    def tasks(self):
        k, n = getattr(self, "_slice", (0, 1))
        return [] if n > 1 and k == 1 else real(self)
    monkeypatch.setattr(TpuFileScanExec, "_device_rg_tasks", tasks)


def _a_chips_partials_are_dropped(monkeypatch):
    from spark_rapids_tpu.shuffle import ici
    real = ici._IciWriter.write_unsplit

    def write_unsplit(self, batch, pids):
        if isinstance(self._t, ici.IciGangMember) and self._t._k == 2:
            return None
        return real(self, batch, pids)
    monkeypatch.setattr(ici._IciWriter, "write_unsplit", write_unsplit)


@pytest.mark.parametrize("fault", [_a_slice_is_never_read,
                                   _a_chips_partials_are_dropped])
def test_a_fault_in_the_gang_is_not_correct(bench_run, monkeypatch, fault):
    fault(monkeypatch)
    rc, line, err = bench_run(CELL, seed=41, rows=ROWS, seconds=0.01)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
    assert "correct = False" in err.splitlines()
    assert line["compared"]["fallback_nodes"]["value"] == 0.0


def test_without_the_fault_the_same_run_is_a_gang_and_correct(bench_run,
                                                             monkeypatch):
    from spark_rapids_tpu.exec import gang
    ran = []
    real = gang.run
    monkeypatch.setattr(gang, "run",
                        lambda gs, ctx: ran.append(1) or real(gs, ctx))
    rc, line, err = bench_run(CELL, seed=41, rows=ROWS, seconds=0.01)
    assert rc == 0 and line["correct"] is True, err[-2000:]
    assert len(ran) == 2  # the warm-up execution and the window's one
    assert line["device"]["count"] == 4
