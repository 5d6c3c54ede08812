"""The harness's wrappers: a missing wrapped engine name raises, and the
wrappers come off again."""
import types

import pytest

from bench.harness import instrument


class FakeEngine:
    def __init__(self):
        self.adapter = types.SimpleNamespace(prefill_chunk=lambda *a, **k: 0)
        self.mgr = types.SimpleNamespace(begin_restore=lambda *a, **k: None)
        self.kv = types.SimpleNamespace(
            num_blocks=8, allocator=types.SimpleNamespace(free_count=8))
        self.slots = []

    def step(self):
        return "step"

    def _restore_step(self):
        pass

    def _prefill_step(self, seq):
        pass

    def _decode_batch(self):
        pass


@pytest.mark.parametrize("annotate", [False, True])
def test_wraps_and_unwraps(annotate):
    eng = FakeEngine()
    inst = instrument.Instrument(eng, annotate=annotate)
    assert eng.step() == "step"
    assert "_decode_batch" in eng.__dict__
    inst.close()
    assert "_decode_batch" not in eng.__dict__
    assert "step" not in eng.__dict__


def _engine_without(name):
    attrs = {k: v for k, v in FakeEngine.__dict__.items()
             if k not in (name, "__dict__", "__weakref__")}
    return type("Partial", (), attrs)()


@pytest.mark.parametrize("missing", ["_prefill_step", "_restore_step",
                                     "_decode_batch", "step"])
def test_missing_engine_name_raises(missing):
    with pytest.raises(AttributeError, match=missing):
        instrument.Instrument(_engine_without(missing), annotate=True)


def test_missing_adapter_name_raises():
    eng = FakeEngine()
    eng.adapter = types.SimpleNamespace()
    with pytest.raises(AttributeError, match="prefill_chunk"):
        instrument.Instrument(eng, annotate=False)
