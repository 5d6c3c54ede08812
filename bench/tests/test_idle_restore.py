"""``device.idle_restore_pct`` on a synthetic trace: the device's idle
seconds under the restoration executor's spans, split as
``device.idle_prefill_pct`` splits them; none without such spans."""
import pytest

from bench.harness import cell as cell_lib
from bench.harness import trace

E = trace.Event


def ctx_with(tr=None):
    return cell_lib.Context(ref={}, peaks=None, window=(0.0, 10.0),
                            traced=None, rec=None, counters={}, trace=tr)


def synthetic():
    """Window 0-10 s; the device busy 0-1, 4-5, 8-9. Idle: 1-4, 5-8,
    9-10. Under the bench's ``engine.restore`` wrapper (1-4): a read
    1-2, an upload 2-2.5, the wrapper alone 2.5-3, a projection 3-4
    (idle only if the kernel has not started). A cache write 5-6.5
    under ``engine.step``; a prefill save 6.5-8; the pump 9-10."""
    return trace.Trace(
        (0.0, 10.0),
        {"/device:TPU:0": [E("a", 0, 1), E("b", 4, 5), E("c", 8, 9)]},
        [E("engine.restore", 1.0, 4.0),
         E("engine.restore.read", 1.0, 2.0),
         E("engine.restore.upload", 2.0, 2.5),
         E("engine.restore.project", 3.0, 4.0),
         E("engine.step", 5.0, 8.0),
         E("engine.restore.write", 5.0, 6.5),
         E("engine.prefill.save", 6.5, 8.0),
         E("engine.pump.wait", 9.0, 10.0)])


def test_idle_restore_pct(capsys):
    read = cell_lib.metric_reader("device.idle_restore_pct")
    # read 1 + upload 0.5 + project 1 + write 1.5 of the 10 s window;
    # the wrapper alone (2.5-3) is the bench's, not the program's
    assert read(ctx_with(tr=synthetic())) == pytest.approx(40.0)
    out = capsys.readouterr().out
    assert "engine.restore.write 1.500000" in out
    assert "engine.restore 0" not in out


def test_idle_restore_pct_without_restore_spans():
    """A window with no restore span of the program, or no trace, gives
    no reading (and does not raise)."""
    tr = synthetic()
    tr.spans = [s for s in tr.spans
                if not s.name.startswith("engine.restore.")]
    read = cell_lib.metric_reader("device.idle_restore_pct")
    assert read(ctx_with(tr=tr)) is None
    assert read(ctx_with()) is None
