"""A whole run past the look for the chip, at a small size on the CPU:
sound, it is correct; with the timed path broken underneath — a served
token altered where it is sampled, or the restored state spoiled where
it lands in the page pool — ``correct`` comes out false, and so does the
float8 control judged on a sound run's sample."""
import contextlib
import time

import jax
import jax.numpy as jnp
import pytest

from bench.harness import cell as cell_lib
from bench.tests.tiny import full_cell, full_size_plan, tiny_cell

SEED = 2 ** 32 + 77
SECONDS = 3.0


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """One persistent cache for this module's runs (the eager prefill
    recompiles per call; the cache makes later runs load instead)."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


@contextlib.contextmanager
def altered_tokens():
    """Every decoded token is replaced by its successor id."""
    import repro.serving.engine as eng
    orig = eng.sample

    def wrong(logits, **kw):
        tok = orig(logits, **kw)
        return (tok + 1) % logits.shape[-1] if logits.shape[0] > 1 else tok
    eng.sample = wrong
    try:
        yield
    finally:
        eng.sample = orig


@contextlib.contextmanager
def spoiled_restore():
    """Restored K and V land in the pool as zeros."""
    from repro.serving.kv_cache import ViewSink
    put, put_group = ViewSink.put_kv, ViewSink.put_kv_group
    ViewSink.put_kv = lambda self, row, k, v, start=0: put(
        self, row, jnp.zeros_like(k), jnp.zeros_like(v), start)
    ViewSink.put_kv_group = lambda self, rows, k, v, start=0: put_group(
        self, rows, jnp.zeros_like(k), jnp.zeros_like(v), start)
    try:
        yield
    finally:
        ViewSink.put_kv, ViewSink.put_kv_group = put, put_group


def run(name, fault=None, control=False, seconds=SECONDS, compare=None):
    full = full_cell(name).cfg
    cell = tiny_cell(name)
    if compare:
        cell.mix["check_requests"] = compare
    with full_size_plan(full), \
            (fault() if fault else contextlib.nullcontext()):
        out = cell_lib.run(cell, SEED, seconds, trace=False,
                           t_start=time.time(), control=control)
    return out


@pytest.mark.parametrize("name", ["qwen2-7b.returning", "opt-30b.returning",
                                  "qwen2-7b.fresh"])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("name", ["qwen2-7b.returning", "opt-30b.returning"])
def test_control_is_caught(name):
    """The float8 control, judged against the cell's limit on the sample
    that a sound run compares, comes out not correct. At this small size
    the float8 picks part from the exact ones rarely, so the window is
    longer and every request is compared."""
    out = run(name, control=True, seconds=2 * SECONDS, compare=100)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"], out["control"]


@pytest.mark.parametrize("name", ["qwen2-7b.returning", "qwen2-7b.fresh"])
def test_altered_token_is_caught(name):
    out = run(name, altered_tokens)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", ["qwen2-7b.returning", "opt-30b.returning"])
def test_spoiled_restore_is_caught(name):
    out = run(name, spoiled_restore)
    assert not out["correct"], out["checks"]
