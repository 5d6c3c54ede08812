"""Bubble-free scheduler: paper-claim replication + hypothesis properties."""
import dataclasses
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.config.hardware import GB, PAPER_A100, HardwareProfile
from repro.configs import get_arch
from repro.core.cost_model import (layer_costs, method_times,
                                   restoration_time, storage_per_token)
from repro.core.pipeline import restore_timeline, simulate
from repro.core.scheduler import closed_form, solve


# ------------------------------------------------------------- paper claims
def test_mha_compute_speedup_at_least_6x():
    """§3.2: C_RE / C_H >= 6 for MHA, growing with sequence length."""
    cfg = get_arch("llama2-7b")
    prev = 0.0
    for n in (512, 2048, 8192, 32768):
        c = layer_costs(cfg, n)[0]
        ratio = c.c_token / c.c_hidden
        assert ratio >= 6.0, f"n={n}: {ratio}"
        assert ratio >= prev
        prev = ratio


def test_mha_io_exactly_half():
    """§3.2: hidden-state bytes are half the KV bytes for MHA."""
    for name in ("llama2-7b", "llama2-13b", "opt-30b"):
        c = layer_costs(get_arch(name), 1024)[0]
        assert c.io_hidden * 2 == c.io_kv


def test_gqa_inverts_io_ratio():
    """GQA (kv=4): KV is *smaller* than hidden states — the §7 caveat."""
    c = layer_costs(get_arch("qwen2-7b"), 1024)[0]
    assert c.io_kv < c.io_hidden


def test_table3_7b_schedule():
    """Table 3: llama2-7b on A100+4SSD uses H for ~31/32 layers with a
    small KV remainder (we get 30H+2KV with our GEMM-efficiency guess)."""
    s = solve(get_arch("llama2-7b"), 1024, PAPER_A100)
    counts = s.counts
    assert counts["hidden"] >= 29
    assert counts["recompute"] == 0
    assert s.bubble < 0.10


def test_table3_30b_schedule_uses_recompute():
    """Table 3: OPT-30B with 1 SSD/GPU is IO-poor -> recompute fills in."""
    hw = dataclasses.replace(PAPER_A100, storage_bw=6.9 * GB)
    s = solve(get_arch("opt-30b"), 1024, hw)
    assert s.counts["recompute"] >= 4
    assert s.counts["hidden"] >= 36


def test_storage_ratio_band():
    """Table 3: HCache stores 1.92-2.40x less than KV offload (MHA)."""
    for name in ("llama2-7b", "llama2-13b"):
        cfg = get_arch(name)
        s = solve(cfg, 1024, PAPER_A100)
        ratio = (storage_per_token(cfg, ["kv"] * cfg.n_layers)
                 / storage_per_token(cfg, s.methods))
        assert 1.5 <= ratio <= 2.6, ratio


def test_ttft_speedup_bands():
    """§6: HCache vs KV offload 1.3-2.7x; vs recompute >= 2.3x."""
    cfg = get_arch("llama2-7b")
    for n in (1024, 4096, 16384):
        th = restoration_time(cfg, n, PAPER_A100, "hcache")
        tkv = restoration_time(cfg, n, PAPER_A100, "kv_offload")
        tre = restoration_time(cfg, n, PAPER_A100, "recompute")
        assert 1.3 <= tkv / th <= 2.7
        assert tre / th >= 2.3


# ------------------------------------------------------ hypothesis properties
hw_strategy = st.builds(
    HardwareProfile,
    name=st.just("synth"),
    flops=st.floats(1e12, 1e15),
    hbm_bw=st.just(819e9),
    interconnect_bw=st.just(50e9),
    host_link_bw=st.floats(1e9, 1e11),
    storage_bw=st.floats(1e8, 1e11),
    hbm_capacity=st.just(16e9),
)


@settings(max_examples=40, deadline=None)
@given(hw=hw_strategy, n_tokens=st.sampled_from([256, 1024, 8192]))
def test_solver_never_worse_than_pure_methods(hw, n_tokens):
    """The min-max schedule's makespan <= every single-method scheme."""
    cfg = get_arch("llama2-7b")
    s = solve(cfg, n_tokens, hw)
    t = restore_timeline(cfg, n_tokens, hw, s.methods)
    for method, scheme in (("hidden", ["hidden"]), ("kv", ["kv"]),
                           ("recompute", ["recompute"])):
        tm = restore_timeline(cfg, n_tokens, hw,
                              scheme * cfg.n_layers)
        assert t.makespan <= tm.makespan * 1.0001


@settings(max_examples=40, deadline=None)
@given(hw=hw_strategy)
def test_closed_form_near_optimal(hw):
    """Paper's closed form is within one layer of the exact solver when
    restricted to the same two methods."""
    cfg = get_arch("llama2-7b")
    t = method_times(layer_costs(cfg, 1024)[0], hw)
    l_h, l_o = closed_form(cfg.n_layers, t)
    if t.c_h > t.io_h:
        exact = solve(cfg, 1024, hw, allow_recompute=False)
    else:
        exact = solve(cfg, 1024, hw, allow_kv=False)
    assert abs(exact.counts["hidden"] - l_h) <= 2


@settings(max_examples=30, deadline=None)
@given(hw=hw_strategy, n_tokens=st.sampled_from([512, 4096]))
def test_simulated_timeline_consistent(hw, n_tokens):
    """Event simulation: makespan >= both stream busy times; the solver's
    predicted compute/io totals match the simulation's busy times."""
    cfg = get_arch("llama2-13b")
    s = solve(cfg, n_tokens, hw)
    t = restore_timeline(cfg, n_tokens, hw, s.methods)
    assert t.makespan >= t.io_busy - 1e-12
    assert t.makespan >= t.compute_busy - 1e-12
    assert t.io_busy == pytest.approx(s.io_time, rel=1e-6)
    assert t.compute_busy == pytest.approx(s.compute_time, rel=1e-6)


def test_hybrid_schedule_offloads_ssm_states():
    """zamba2: mamba layers should pick state offload ('kv' slot, near-free
    IO) rather than hidden-state rescan, attention layers follow the paper."""
    from repro.config.arch import BlockKind
    cfg = get_arch("zamba2-2.7b")
    s = solve(cfg, 4096, PAPER_A100, allow_recompute=False)
    kinds = cfg.block_kinds()
    mamba_methods = {m for m, k in zip(s.methods, kinds)
                     if k != BlockKind.ATTENTION}
    assert mamba_methods == {"kv"}
