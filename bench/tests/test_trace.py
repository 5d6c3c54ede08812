"""Trace reduction on a small trace recorded on a TPU v5e: one device
plane, three host steps, each running the restore kernel and a matmul
under ``engine.restore`` / ``engine.decode`` spans inside the
``bench.window`` span (recorded on the chip, 35 KB)."""
import os

import pytest

from bench.harness import trace

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "v5e_sample.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(SAMPLE, host_spans=["engine.*"])


def test_planes_and_window(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert 0.0 < tr.window_s < 1.0
    names = {s.name for s in tr.spans}
    assert {"engine.step", "engine.restore", "engine.decode"} <= names


def test_busy_is_the_union_of_ops(tr):
    busy = trace.busy_s(tr)
    ops = tr.devices["/device:TPU:0"]
    assert 0.0 < busy <= sum(e.end - e.start for e in ops) + 1e-12
    assert busy < tr.window_s


def test_kernel_time_by_stable_name(tr):
    secs, n = trace.kernel_seconds(tr, r"^restore_kv_grouped")
    assert n >= 2
    assert 0.0 < secs <= trace.busy_s(tr)
    assert trace.op_seconds(tr)["restore_kv_grouped.1"] == \
        pytest.approx(secs)


def test_breakdown_names_gaps_by_host_span(tr):
    b = trace.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    known = {"engine.step", "engine.restore", "engine.decode", "host idle"}
    assert {n for n, _ in b["idle_gaps"]} <= known
    assert sum(gaps) <= tr.window_s - trace.busy_s(tr) + 1e-9


def test_merged_and_gaps_synthetic():
    E = trace.Event
    t = trace.Trace((0.0, 10.0),
                    {"/device:TPU:0": [E("a", 1, 3), E("b", 2, 4),
                                       E("c", 6, 7)]},
                    [E("engine.prefill", 4.5, 5.5), E("engine.step", 0, 10)])
    assert trace.merged(t.devices["/device:TPU:0"]) == [(1, 4), (6, 7)]
    assert trace.busy_s(t) == 4.0
    assert trace.idle_gaps(t) == [("engine.step", 3.0),
                                  ("engine.prefill", 2.0),
                                  ("engine.step", 1.0)]
