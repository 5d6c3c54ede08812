"""Communication compression + error-feedback optimizer."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import (dequantize_int8, quantize_int8,
                                           tree_cast_bf16)
from repro.training.optimizer import AdamWConfig, adamw_update, init_opt_state


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_int8_quantization_bounded_error(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * rng.uniform(0.1, 100))
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert float(jnp.abs(back - x).max()) <= float(s) * 0.5 + 1e-9


def test_tree_cast_bf16_preserves_ints():
    tree = {"w": jnp.ones((3,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    out = tree_cast_bf16(tree)
    assert out["w"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32


def test_error_feedback_recovers_bf16_loss():
    """With error feedback, repeated tiny gradients are not lost to bf16
    rounding (they accumulate in the feedback buffer)."""
    params = {"w": jnp.ones((4,), jnp.float32)}
    cfg = AdamWConfig(lr=1e-2, weight_decay=0.0, error_feedback=True)
    state = init_opt_state(params, error_feedback=True)
    g = {"w": jnp.full((4,), 1e-3, jnp.float32)}
    p = params
    for _ in range(5):
        p, state, _ = adamw_update(p, tree_cast_bf16(g), state, cfg)
    assert float(p["w"][0]) < 1.0           # updates actually applied
    assert "ef" in state
