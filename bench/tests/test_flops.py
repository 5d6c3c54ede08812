"""Operations and bytes of the restore kernel and the model, by hand."""
from bench.harness import flops


def test_restore_kv_counts():
    f, b = flops.restore_kv(2, 16, 8, 4, bias=False)
    assert f == 2 * 2 * 2 * 16 * 8 * 4
    assert b == 2 * (2 * 16 * 8 + 2 * 2 * 8 * 4 + 2 * 2 * 16 * 4)
    fb, bb = flops.restore_kv(2, 16, 8, 4, bias=True)
    assert fb == f + 2 * 2 * 16 * 4
    assert bb == b + 2 * 2 * 2 * 4
    fr, br = flops.restore_kv(2, 16, 8, 4, bias=False, rope_head_dim=2)
    assert fr == f + 3 * 2 * 16 * 4          # K only is rotated
    assert br == b + 4 * 2 * 16 * 2


def test_opt_projection_is_compute_bound_on_v5e():
    # ~205 MFLOP per token-layer against a 28 KB KV fetch
    f, b = flops.restore_kv(1, 1, 7168, 7168, bias=True)
    assert abs(f - 205.5e6) / 205.5e6 < 0.01
    S = 1280
    f, b = flops.restore_kv(3, S, 7168, 7168, bias=True)
    assert f / 197e12 > b / 819e9


ARCH = dict(d_model=8, d_ff=16, n_heads=2, n_kv_heads=1, head_dim=4,
            glu=True, n_layers=3, vocab=10)


def test_token_and_prefill_flops():
    per_layer = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8) + 2 * 3 * 8 * 16
    assert flops.token_flops(ARCH, 0) == 3 * per_layer
    assert flops.token_flops(ARCH, 5) == 3 * (per_layer + 2 * 2 * 5 * 8)
    # a chunk of 2 after 3: keys 4 and 5
    want = 2 * 3 * per_layer + 3 * 2 * 2 * 8 * (4 + 5) + 2 * 8 * 10
    assert flops.prefill_flops(ARCH, 2, 3) == want
    assert flops.decode_flops(ARCH, [4, 5]) == \
        flops.token_flops(ARCH, 4) + flops.token_flops(ARCH, 5) + 2 * 160
