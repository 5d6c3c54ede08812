"""The per-layer metric that reads the program's own spans (the split of
device idle time by innermost span, on a synthetic trace), and the
harness's engine counter names against ``EngineMetrics``."""
import dataclasses

import pytest

from bench.harness import cell as cell_lib
from bench.harness import trace

E = trace.Event


def ctx_with(tr=None):
    return cell_lib.Context(ref={}, peaks=None, window=(0.0, 10.0),
                            traced=None, rec=None, counters={}, trace=tr)


def test_engine_counters_are_scalar_fields():
    from repro.serving.engine import EngineMetrics
    fields = {f.name: f for f in dataclasses.fields(EngineMetrics)}
    m = EngineMetrics()
    for name in cell_lib.ENGINE_COUNTERS:
        assert name in fields, name
        assert isinstance(getattr(m, name), (int, float)), name
    assert cell_lib._counters(type("Eng", (), {"metrics": m})) == \
        {k: 0.0 for k in cell_lib.ENGINE_COUNTERS}


def synthetic():
    """Window 0-10 s; the device busy 0-1, 3-4, 6-7. Idle: 1-3, 4-6,
    7-10. Spans: a prefill forward with a gather nested in it over 1-3
    and a save after it; the bench's wrapper around 4-6 with a program
    decode span only over 4-5; nothing over 7-8; the pump waiting 8-10."""
    return trace.Trace(
        (0.0, 10.0),
        {"/device:TPU:0": [E("a", 0, 1), E("b", 3, 4), E("c", 6, 7)]},
        [E("engine.prefill", 0.5, 3.0),
         E("engine.prefill.forward", 0.5, 2.5),
         E("engine.prefill.gather", 1.5, 2.0),
         E("engine.prefill.save", 2.5, 3.0),
         E("engine.step", 3.5, 6.0),
         E("engine.decode.sample", 4.0, 5.0),
         E("engine.pump.wait", 8.0, 10.0)])


def test_idle_by_innermost_span():
    mod = cell_lib.metric_reader("device.idle_prefill_pct").__globals__
    by = mod["idle_by_span"](synthetic())
    assert by == pytest.approx({
        "engine.prefill.forward": 1.0,     # 1-1.5 and 2-2.5
        "engine.prefill.gather": 0.5,
        "engine.prefill.save": 0.5,
        "engine.decode.sample": 1.0,
        "engine.step": 1.0,                # the wrapper alone, 5-6
        "no span": 1.0,                    # 7-8
        "engine.pump.wait": 2.0})
    assert sum(by.values()) == pytest.approx(
        10.0 - trace.busy_s(synthetic()))


def test_idle_prefill_pct(capsys):
    read = cell_lib.metric_reader("device.idle_prefill_pct")
    assert read(ctx_with(tr=synthetic())) == pytest.approx(20.0)
    out = capsys.readouterr().out
    assert "no span 1.000000" in out
    # the program's spans name 5 of the 7 idle seconds
    assert "name 5.000000 of 7.000000 idle s (71.4%)" in out


def test_idle_prefill_pct_without_program_spans():
    """A program with no prefill spans of its own, traced with only the
    bench's wrappers, gives no reading (and does not raise)."""
    tr = synthetic()
    tr.spans = [s for s in tr.spans if "." not in s.name[len("engine."):]
                and s.name != "engine.pump.wait"]
    read = cell_lib.metric_reader("device.idle_prefill_pct")
    assert read(ctx_with(tr=tr)) is None
    assert read(ctx_with()) is None
