"""BENCHMARK.json against the benchmark's contract, and the harness is
driven by data: a new cell or metric needs only new files and entries."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.harness import cell as cell_lib

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]] + \
        [w["name"] for w in spec["workloads"]] + \
        [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_every_named_file_exists(spec):
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in spec["workloads"]:
        cell = cell_lib.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
    for m in spec["per_layer"]:
        assert callable(cell_lib.metric_reader(m["name"]))


def test_roofline_and_mfu_names(spec):
    per = {m["name"]: m for m in spec["per_layer"]}
    assert any("mfu" in n for n in per)
    for n, m in per.items():
        if n.endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_new_cell_and_metric_need_only_files(tmp_path, spec):
    """Copy the benchmark, add a traffic mix, a cell and a metric as data,
    and the unchanged harness finds and reads them."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    with open(os.path.join(BENCH, "traffic", "fresh_2k.json")) as f:
        mix = json.load(f)
    mix["prompt_tokens"] = [1024, 4096]
    (root / "bench" / "traffic" / "fresh_4k.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "restore.ktok_per_s.py").write_text(
        "def read(ctx):\n"
        "    lo, hi = ctx.window\n"
        "    return ctx.counters['restored_tokens'] / 1e3 / (hi - lo)\n")
    new = dict(spec)
    new["workloads"] = spec["workloads"] + [
        {"name": "qwen2-7b.fresh4k", "config": "qwen2-7b",
         "traffic": "fresh_4k", "chips": 1, "why": "longer prompts"}]
    new["per_layer"] = spec["per_layer"] + [
        {"name": "restore.ktok_per_s", "unit": "ktok/s", "better": "higher",
         "source": "program_counter", "layer": "restoration",
         "moves": "tokens_per_s", "workloads": ["qwen2-7b.fresh4k"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = cell_lib.load_cell("qwen2-7b.fresh4k", root=str(root))
    assert cell.mix["prompt_tokens"] == [1024, 4096]
    assert [m["name"] for m in cell.per_layer if
            m["name"] == "restore.ktok_per_s"]
    read = cell_lib.metric_reader("restore.ktok_per_s", root=str(root))
    ctx = cell_lib.Context(ref={}, peaks=None, window=(0.0, 2.0),
                           traced=None, rec=None,
                           counters={"restored_tokens": 10000.0})
    assert read(ctx) == 5.0


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_without_tpu_fails_before_a_result():
    r = _run(["--workload", "qwen2-7b.returning", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_run_without_the_program_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(["--workload", "qwen2-7b.returning", "--seed", "1",
              "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
