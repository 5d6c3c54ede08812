"""The LM prefill chunk as one compiled program per shape
(models/adapter.py ``LMAdapter.prefill``): it computes what an eager
``lm_forward`` computes, it is traced once per (chunk, history) shape and
TP context, and an engine serving shapes it has seen builds nothing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.distributed import tp as tp_lib
from repro.models import Model
from repro.models import transformer as tfm
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.storage import ChunkStore, make_array

CHUNK = 8


@pytest.fixture(scope="module")
def setup(rules):
    cfg = reduced_for_smoke(get_arch("qwen2-7b"))
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _tokens(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)


def _history(model, params, cfg, n):
    """The stacked KV of an ``n``-token history, as a restore leaves it."""
    out = tfm.lm_forward(params, _tokens(cfg, n, 1), model.h, emit_kv=True)
    return out["kv"]


@pytest.mark.parametrize("hist", [0, 16], ids=["fresh", "restored"])
@pytest.mark.parametrize("capture_hidden", [False, True],
                         ids=["no_hidden", "hidden"])
def test_jitted_prefill_matches_eager_forward(setup, hist, capture_hidden):
    cfg, model, params = setup
    toks = _tokens(cfg, CHUNK, 2)
    hist_kv = _history(model, params, cfg, hist) if hist else None
    hist_len = hist if hist else None
    got = model.adapter.prefill(params, {"tokens": toks},
                                capture_hidden=capture_hidden,
                                hist_kv=hist_kv, hist_len=hist_len)
    want = tfm.lm_forward(params, toks, model.h, hist_kv=hist_kv,
                          hist_len=hist_len, capture_hidden=capture_hidden,
                          emit_kv=True, final_logits_only=True)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got["logits"], want["logits"], **tol)
    for g, w in zip(got["kv"], want["kv"]):
        assert g.shape == (cfg.n_layers, 1, CHUNK, cfg.n_kv_heads,
                           cfg.head_dim_)
        np.testing.assert_allclose(g, w, **tol)
    if capture_hidden:
        assert got["hidden"].shape == (cfg.n_layers, 1, CHUNK, cfg.d_model)
        np.testing.assert_allclose(got["hidden"], want["hidden"], **tol)
    else:
        assert got["hidden"] is None and want["hidden"] is None


def test_one_trace_per_shape(setup):
    cfg, model, params = setup
    ad = model.adapter
    toks = _tokens(cfg, CHUNK, 3)
    hist_kv = _history(model, params, cfg, 24)

    def call(kv=hist_kv, n=24):
        return ad.prefill(params, {"tokens": toks}, capture_hidden=True,
                          hist_kv=kv, hist_len=n)

    t0 = ad.prefill_traces
    first = call()
    again = call()
    assert ad.prefill_traces == t0 + 1
    np.testing.assert_array_equal(first["logits"], again["logits"])
    # a new history length is a new program
    call(_history(model, params, cfg, 32), 32)
    assert ad.prefill_traces == t0 + 2
    call()
    assert ad.prefill_traces == t0 + 2


def test_tp_context_gets_its_own_program(setup):
    """A program traced outside a TP context is not reused inside one:
    the seams read the active context at trace time."""
    cfg, model, params = setup
    ad = model.adapter
    toks = _tokens(cfg, CHUNK, 4)
    outside = ad.prefill(params, {"tokens": toks})
    t0 = ad.prefill_traces
    with tp_lib.tp_seam(tp_lib.TPContext(2)):
        inside = ad.prefill(params, {"tokens": toks})
    assert ad.prefill_traces == t0 + 1
    np.testing.assert_array_equal(outside["logits"], inside["logits"])
    # tp=1 activates no context: the outside program serves it
    with tp_lib.tp_seam(tp_lib.TPContext(1)):
        ad.prefill(params, {"tokens": toks})
    assert ad.prefill_traces == t0 + 1


def test_engine_builds_no_program_for_warm_shapes(setup):
    cfg, model, params = setup
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="kv", store_dtype=np.float32)
    engine = InferenceEngine(model, params, mgr, max_batch=2, max_seq=128,
                             prefill_chunk=CHUNK, backend="paged")
    rng = np.random.default_rng(6)

    def serve(sid):
        engine.submit(Request(sid, rng.integers(0, cfg.vocab_size, 20)
                              .astype(np.int32), max_new_tokens=2))
        engine.run()

    # 20 tokens in chunks of 8: (8, 0), (8, 8), (4, 16), each traced once
    # by whichever call saw the shape first; no other test here prefills
    # the last two
    t0 = model.adapter.prefill_traces
    serve("a")
    cold = engine.metrics.prefill_traces
    assert cold == model.adapter.prefill_traces - t0 >= 2
    serve("b")
    engine.close()
    assert engine.metrics.prefill_traces == cold
    assert engine.metrics.prefill_tokens == 40
