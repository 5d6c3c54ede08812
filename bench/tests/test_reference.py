"""The benchmark's float32 reference agrees with the program at a small
size for both configurations; its weights are the program's, bit for
bit; and its float8 control does not agree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import stack, weights as W
from bench.references import dense
from bench.tests.tiny import full_cell, shrink_config

SEED = 2 ** 33 + 5          # wider than 32 bits, as run seeds may be


def _program(cfg, dtype):
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    arch, ref = stack.program_arch(cfg)
    model = Model(arch, rules=default_rules(make_mesh((1, 1),
                                                      ("data", "model"))),
                  model_axis=1, dtype=dtype, remat="none")
    layout = W.dense_layout(ref)
    abstract, _ = model.abstract_params()
    W.check_layout(abstract, layout)
    params = W.program_params(abstract, layout, SEED, jnp.bfloat16)
    return model, ref, layout, params


@pytest.fixture(scope="module", params=["qwen2-7b", "opt-30b"])
def tiny(request):
    cfg = shrink_config(full_cell(
        "qwen2-7b.returning" if request.param == "qwen2-7b"
        else "opt-30b.returning").cfg)
    return cfg


def test_weights_bitwise_shared(tiny):
    model, ref, layout, params = _program(tiny, jnp.bfloat16)
    for li in range(ref["n_layers"]):
        got = W.layer_params(layout, SEED, jnp.bfloat16, li)
        for k, v in got.items():
            part, leaf = k.split("/")
            np.testing.assert_array_equal(
                np.asarray(params["blocks"][part][leaf][li], np.float32),
                np.asarray(v, np.float32))
    glob = W.global_params(layout, SEED, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(params["embed"]["table"], np.float32),
        np.asarray(glob["embed/table"], np.float32))


def _logits(model, params, toks):
    from repro.models import transformer as tfm
    with jax.default_matmul_precision("highest"):
        out = tfm.lm_forward(params, jnp.asarray(toks)[None], model.h)
    return np.asarray(out["logits"][0, :, :model.cfg.vocab_size],
                      np.float32)


def test_reference_matches_program_f32(tiny):
    model, ref, layout, params = _program(tiny, jnp.float32)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, ref["vocab"], 200)
    want = _logits(model, params, toks)
    got = dense.logits_at(ref, SEED, [toks], [list(range(len(toks)))])[0]
    # both float32 at "highest": only summation order differs
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_control_fails_where_bf16_program_passes(tiny):
    """The bf16 program's served (greedy) tokens sit at most a small gap
    below the reference's best; the float8 control's picks sit further
    below. The limit in the configuration lies between at full size."""
    model, ref, layout, params = _program(tiny, jnp.bfloat16)
    toks = np.random.default_rng(1).integers(0, ref["vocab"], 300)
    rows = list(range(len(toks)))
    exact = dense.logits_at(ref, SEED, [toks], [rows])[0]
    low = dense.logits_at(ref, SEED, [toks], [rows], precision="fp8")[0]
    prog = _logits(model, params, toks)
    best = exact.max(-1)
    gap_prog = float((best - exact[np.arange(len(rows)),
                                   prog.argmax(-1)]).max())
    gap_ctrl = float((best - exact[np.arange(len(rows)),
                                   low.argmax(-1)]).max())
    assert gap_ctrl > 3 * gap_prog, (gap_prog, gap_ctrl)
