"""CPU rehearsal of ``chip_smoke.py``: the same phases — front door,
per-method saves, eviction, restore against never-evicted twins, tp=4
against tp=1 — at a few hundred tokens per session on a narrow qwen2
(bf16, QKV bias, 4 KV heads so that tp=4 owns one head per device).
The Mosaic check needs a TPU and is left to the chip run."""
import dataclasses
import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    from repro.config.arch import reduced_for_smoke
    from repro.configs import get_arch
    return dataclasses.replace(reduced_for_smoke(get_arch("qwen2-7b")),
                               n_kv_heads=4)


def _size(smoke):
    return smoke.Size(pages=96, max_seq=384, prompt_lo=130, prompt_hi=300,
                      follow=16)


def test_one_chip_phases_on_cpu(smoke, cfg, capsys):
    phases = smoke.Phases(smoke.CompileClock())
    smoke.one_chip(cfg, 0, phases, _size(smoke), mosaic=False)
    out = capsys.readouterr().out
    assert sum(line.startswith("restore ") for line in out.splitlines()) \
        == len(smoke.METHODS)
    assert "HTTP 200" in out
    assert [r[0] for r in phases.rows] == ["build", "round1",
                                           "round2_restore", "round2_twins"]


def test_four_chip_phase_on_cpu(smoke, cfg, capsys):
    import jax
    assert len(jax.devices()) >= 4                 # conftest forced devices
    phases = smoke.Phases(smoke.CompileClock())
    smoke.four_chips(cfg, 0, phases, _size(smoke), mosaic=False)
    out = capsys.readouterr().out
    assert out.count("tp=4 vs tp=1") == len(smoke.METHODS)
