"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,D,Kv,hd", [(32, 64, 2, 16), (128, 128, 4, 32),
                                       (64, 256, 1, 64), (96, 64, 2, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bias", [False, True])
def test_restore_kv_sweep(S, D, Kv, hd, dtype, bias):
    h = jnp.asarray(RNG.normal(size=(S, D)), dtype)
    wk = jnp.asarray(RNG.normal(size=(D, Kv * hd)) * D ** -0.5, dtype)
    wv = jnp.asarray(RNG.normal(size=(D, Kv * hd)) * D ** -0.5, dtype)
    bk = jnp.asarray(RNG.normal(size=(Kv * hd,)) * 0.1, dtype) if bias \
        else None
    bv = jnp.asarray(RNG.normal(size=(Kv * hd,)) * 0.1, dtype) if bias \
        else None
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * 10000.0 ** (-jnp.arange(hd // 2) / (hd // 2)))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    got = ops.restore_kv(h, wk, wv, bk, bv, cos, sin, head_dim=hd,
                         use_pallas=True)
    want = ref.restore_kv_ref(h, wk, wv, bk, bv, cos, sin, head_dim=hd)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S,D,Kv,hd", [(32, 64, 10, 96), (32, 64, 3, 80)])
def test_restore_kv_non_pow2_head_dim(S, D, Kv, hd):
    """Regression: the default-block fallback used to halve block_kv
    blindly (KV=960 → 64 < head_dim=96), splitting a head across tiles
    and corrupting the rotate-half pairing. The fallback must stay a
    multiple of head_dim."""
    from repro.kernels.restore_kv import _pick_blocks
    _, bkv = _pick_blocks(S, Kv * hd, D, hd, 4, False, 256, 512)
    assert bkv % hd == 0 and (Kv * hd) % bkv == 0
    h = jnp.asarray(RNG.normal(size=(S, D)), jnp.float32)
    wk = jnp.asarray(RNG.normal(size=(D, Kv * hd)) * D ** -0.5, jnp.float32)
    wv = jnp.asarray(RNG.normal(size=(D, Kv * hd)) * D ** -0.5, jnp.float32)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * 10000.0 ** (-jnp.arange(hd // 2) / (hd // 2)))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    got = ops.restore_kv(h, wk, wv, None, None, cos, sin, head_dim=hd,
                         use_pallas=True)
    want = ref.restore_kv_ref(h, wk, wv, None, None, cos, sin, head_dim=hd)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G,S,D,Kv,hd", [(1, 32, 64, 2, 16),
                                         (4, 32, 64, 2, 16),
                                         (3, 64, 128, 4, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bias", [False, True])
def test_restore_kv_grouped_sweep(G, S, D, Kv, hd, dtype, bias):
    """Grouped kernel (leading weight-stack grid dim) == per-layer oracle
    applied row by row — the batched executor's byte contract."""
    h = jnp.asarray(RNG.normal(size=(G, S, D)), dtype)
    wk = jnp.asarray(RNG.normal(size=(G, D, Kv * hd)) * D ** -0.5, dtype)
    wv = jnp.asarray(RNG.normal(size=(G, D, Kv * hd)) * D ** -0.5, dtype)
    bk = jnp.asarray(RNG.normal(size=(G, Kv * hd)) * 0.1, dtype) if bias \
        else None
    bv = jnp.asarray(RNG.normal(size=(G, Kv * hd)) * 0.1, dtype) if bias \
        else None
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * 10000.0 ** (-jnp.arange(hd // 2) / (hd // 2)))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    for use_pallas in (False, True):
        got = ops.restore_kv_grouped(h, wk, wv, bk, bv, cos, sin,
                                     head_dim=hd, use_pallas=use_pallas)
        for g in range(G):
            want = ref.restore_kv_ref(
                h[g], wk[g], wv[g],
                bk[g] if bias else None, bv[g] if bias else None,
                cos, sin, head_dim=hd)
            for got_part, want_part in zip(got, want):
                np.testing.assert_allclose(
                    np.asarray(got_part[g], np.float32),
                    np.asarray(want_part, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_restore_kv_grouped_biased_multi_tile(dtype):
    """qwen2-7b widths (D=3584, 4 KV heads of 128, QKV bias): the VMEM
    budget splits KV=512 into 128-wide tiles, so every bias block is a
    (1, 128) slice at a nonzero lane offset of the (G, 1, KV) stack."""
    from repro.kernels.restore_kv import _pick_blocks
    G, S, D, Kv, hd = 2, 256, 3584, 4, 128
    KV = Kv * hd
    _, bkv = _pick_blocks(S, KV, D, hd, 2, True, 256, 0)
    assert bkv < KV
    h = jnp.asarray(RNG.normal(size=(G, S, D)), dtype)
    wk = jnp.asarray(RNG.normal(size=(G, D, KV)) * D ** -0.5, dtype)
    wv = jnp.asarray(RNG.normal(size=(G, D, KV)) * D ** -0.5, dtype)
    bk = jnp.asarray(RNG.normal(size=(G, KV)), dtype)
    bv = jnp.asarray(RNG.normal(size=(G, KV)), dtype)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * 1e6 ** (-jnp.arange(hd // 2) / (hd // 2)))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    got = ops.restore_kv_grouped(h, wk, wv, bk, bv, cos, sin, head_dim=hd,
                                 use_pallas=True)
    want = ref.restore_kv_grouped_ref(h, wk, wv, bk, bv, cos, sin,
                                      head_dim=hd)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **_tol(dtype))


@pytest.mark.parametrize("bias", [False, True])
def test_restore_kv_grouped_sharded_launch(bias):
    """tp=4 (conftest's forced host devices): the kernel runs under
    shard_map, one launch per device on its own KV heads, and matches the
    oracle with the outputs left KV-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("model",))
    kv3 = NamedSharding(mesh, P(None, None, "model"))
    G, S, D, Kv, hd = 2, 64, 128, 4, 128
    KV = Kv * hd
    h = jnp.asarray(RNG.normal(size=(G, S, D)), jnp.float32)
    wk = jax.device_put(jnp.asarray(RNG.normal(size=(G, D, KV)) * D ** -0.5,
                                    jnp.float32), kv3)
    wv = jax.device_put(jnp.asarray(RNG.normal(size=(G, D, KV)) * D ** -0.5,
                                    jnp.float32), kv3)
    bk = bv = None
    if bias:
        b2 = NamedSharding(mesh, P(None, "model"))
        bk = jax.device_put(jnp.asarray(RNG.normal(size=(G, KV)),
                                        jnp.float32), b2)
        bv = jax.device_put(jnp.asarray(RNG.normal(size=(G, KV)),
                                        jnp.float32), b2)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * 1e4 ** (-jnp.arange(hd // 2) / (hd // 2)))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    got = ops.restore_kv_grouped(h, wk, wv, bk, bv, cos, sin, head_dim=hd,
                                 use_pallas=True, kv_sharding=kv3)
    want = ref.restore_kv_grouped_ref(h, wk, wv, bk, bv, cos, sin,
                                      head_dim=hd)
    for g, w in zip(got, want):
        assert g.sharding.spec == P(None, None, "model")
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   **_tol(jnp.float32))


@pytest.mark.parametrize("Sq,Skv,hd,group", [(64, 64, 16, 1), (64, 64, 32, 2),
                                             (32, 96, 16, 4)])
@pytest.mark.parametrize("kwargs", [dict(causal=True), dict(causal=False),
                                    dict(causal=True, window=24),
                                    dict(causal=True, softcap=30.0)])
def test_flash_attention_sweep(Sq, Skv, hd, group, kwargs):
    BKv = 2
    q = jnp.asarray(RNG.normal(size=(BKv * group, Sq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(BKv, Skv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(BKv, Skv, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, group=group, use_pallas=True,
                              **kwargs)
    want = ref.flash_attention_ref(q, k, v, group=group, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G,Smax", [(1, 64), (4, 128), (7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(G, Smax, dtype):
    BKv, hd = 3, 32
    q = jnp.asarray(RNG.normal(size=(BKv, G, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(BKv, Smax, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(BKv, Smax, hd)), dtype)
    kl = jnp.asarray(RNG.integers(1, Smax, BKv), jnp.int32)
    got = ops.decode_attention(q, k, v, kl, use_pallas=True)
    want = ref.decode_attention_ref(q, k, v, kl)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def _paged_case(lengths, G, Kv, hd, bs, MB, dtype, L=2):
    """A paged decode input and its dense reference. Each slot's live
    pages land at permuted places in a pool full of junk, its other
    table entries are out-of-range sentinels, and the new token goes to
    the dense side at position ``lengths`` only (a full table drops
    it). Returns (args for decode_attention_paged, dense k, v, kv_len),
    k/v per KV head: (B·Kv, MB·bs, hd)."""
    B, S = len(lengths), MB * bs
    Hp = G * Kv
    NB = B * MB + 3
    q = jnp.asarray(RNG.normal(size=(B, Hp, hd)), dtype)
    new = [jnp.asarray(RNG.normal(size=(B, Kv, hd)), dtype)
           for _ in range(2)]
    dense = [np.zeros((B, S, Kv, hd)) for _ in range(2)]
    pools = [RNG.normal(size=(L, NB, bs, Kv, hd)) for _ in range(2)]
    table = np.full((B, MB), NB + 5, np.int32)
    perm = RNG.permutation(NB)
    for b, n in enumerate(lengths):
        for d, x in zip(dense, new):
            d[b, :n] = RNG.normal(size=(n, Kv, hd))
            if n < S:
                d[b, n] = np.asarray(x[b], np.float64)
        for j in range(-(-n // bs)):
            page = perm[b * MB + j]
            table[b, j] = page
            for d, pool in zip(dense, pools):
                pool[L - 1, page, :min(bs, n - j * bs)] = \
                    d[b, j * bs:min((j + 1) * bs, n)]
    per_head = [jnp.asarray(d.transpose(0, 2, 1, 3).reshape(B * Kv, S, hd),
                            dtype) for d in dense]
    kl = jnp.repeat(jnp.asarray(lengths, jnp.int32) + 1, Kv)
    args = (q, new[0], new[1], jnp.asarray(pools[0], dtype),
            jnp.asarray(pools[1], dtype), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32), jnp.int32(L - 1))
    return args, per_head[0], per_head[1], kl


def _check_paged(args, k, v, kl, dtype, **kw):
    """Kernel and oracle both against the dense reference."""
    q = args[0]
    B, Hp, hd = q.shape
    Kv = args[1].shape[1]
    want = ref.decode_attention_ref(q.reshape(B * Kv, Hp // Kv, hd), k, v,
                                    kl, **kw).reshape(B, Hp, hd)
    window = kw.pop("window", None)
    window = None if window is None else jnp.int32(window)
    for use_pallas in (False, True):
        got = ops.decode_attention_paged(*args, window, use_pallas=use_pallas,
                                         **kw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))


@pytest.mark.parametrize("G,bs,MB", [(1, 16, 4), (4, 8, 8), (7, 32, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_paged_sweep(G, bs, MB, dtype):
    """Paged kernel + its oracle vs the dense reference: pages land in a
    permuted physical pool full of junk, with sentinel table entries past
    each sequence's live pages."""
    lengths = RNG.integers(1, MB * bs, 3).tolist()
    _check_paged(*_paged_case(lengths, G, 2, 32, bs, MB, dtype), dtype)


# the benchmark cells' head shapes, shallow and short: qwen2-7b (GQA, 7
# query heads on each of 4 KV heads) and opt-30b (MHA, 56 heads); pages
# of 16 tokens, head size 128. MB is past one loop step of pages where
# the step is short (qwen2-7b in f32: 16 pages; opt-30b: 2 or 1).
CELL_HEADS = [("qwen2-7b", 7, 4, 20), ("opt-30b", 1, 56, 5)]


@pytest.mark.parametrize("name,G,Kv,MB", CELL_HEADS,
                         ids=[c[0] for c in CELL_HEADS])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_at_cell_head_shapes(name, G, Kv, MB, dtype):
    """Slots of no history, one token, exactly one page, a full table
    with room for the new token, and a full table that drops it."""
    bs = 16
    lengths = [0, 1, bs, MB * bs - 1, MB * bs]
    _check_paged(*_paged_case(lengths, G, Kv, 128, bs, MB, dtype), dtype)


@pytest.mark.parametrize("name,G,Kv,MB", CELL_HEADS,
                         ids=[c[0] for c in CELL_HEADS])
@pytest.mark.parametrize("kw", [dict(window=37), dict(softcap=30.0),
                                dict(window=1, softcap=30.0)],
                         ids=["window", "softcap", "window1-softcap"])
def test_paged_decode_window_and_softcap(name, G, Kv, MB, kw):
    """A sliding window skips the pages before it (a window of one sees
    only the new token); a softcap bounds every score."""
    lengths = [3, 50, MB * 16 - 1]
    _check_paged(*_paged_case(lengths, G, Kv, 128, 16, MB, jnp.float32),
                 jnp.float32, **dict(kw))


def test_paged_decode_head_parallel_matches_single_device():
    """Under a 4-way head sharding the kernel runs per device on its own
    KV heads (shard_map) and agrees with the single-device launch."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
    args, _, _, _ = _paged_case([0, 21, 79], 7, 4, 128, 16, 5, jnp.float32)
    whole = ops.decode_attention_paged(*args, use_pallas=True)
    split = ops.decode_attention_paged(
        *args, use_pallas=True,
        head_sharding=NamedSharding(mesh, P(None, "model", None)))
    assert split.sharding.spec[1] == "model"
    np.testing.assert_allclose(np.asarray(split), np.asarray(whole),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("Bt,I,N", [(1, 64, 16), (2, 128, 8), (3, 96, 4)])
def test_ssm_update_sweep(Bt, I, N):
    h = jnp.asarray(RNG.normal(size=(Bt, I, N)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(Bt, I)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(Bt, I)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(I, N)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(Bt, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bt, N)), jnp.float32)
    dsk = jnp.ones((I,), jnp.float32)
    got = ops.ssm_update(h, dt, x, A, Bm, C, dsk, use_pallas=True)
    want = ref.ssm_update_ref(h, dt, x, A, Bm, C, dsk)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=3e-5, atol=3e-5)


def test_flash_matches_model_attention():
    """Pallas flash kernel == the model's jnp chunked attention path."""
    from repro.models.layers.attention import (AttnHyper,
                                               flash_attention_jnp)
    B, S, Kv, g, hd = 2, 64, 2, 3, 16
    Hp = Kv * g
    q = jnp.asarray(RNG.normal(size=(B, S, Hp, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Kv, hd)), jnp.float32)
    hyp = AttnHyper(n_heads=Hp, n_kv_heads=Kv, head_dim=hd, padded_heads=Hp,
                    chunk=16)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = flash_attention_jnp(q, k, v, hyp, q_positions=pos, causal=True)
    # kernel layout: (B*H, S, hd) grouped by kv head
    qk = q.reshape(B, S, Kv, g, hd).transpose(0, 2, 3, 1, 4).reshape(
        B * Kv * g, S, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * Kv, S, hd)
    vv = v.transpose(0, 2, 1, 3).reshape(B * Kv, S, hd)
    got = ops.flash_attention(qk, kk, vv, group=g, causal=True,
                              use_pallas=True)
    got = got.reshape(B, Kv, g, S, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, S, Hp, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
