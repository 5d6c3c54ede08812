"""Pure-jnp oracles for every Pallas kernel (shape/dtype-sweep targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def restore_kv_ref(hidden, wk, wv, bk, bv, cos, sin, *, head_dim: int,
                   use_rope: bool = True):
    """hidden (S,D) -> K,V (S,KV); K rotated with cos/sin (S, hd/2)."""
    h = hidden.astype(jnp.float32)
    k = h @ wk.astype(jnp.float32)
    v = h @ wv.astype(jnp.float32)
    if bk is not None:
        k = k + bk.astype(jnp.float32)
        v = v + bv.astype(jnp.float32)
    if use_rope:
        S, KV = k.shape
        nh = KV // head_dim
        kh = k.reshape(S, nh, head_dim)
        x1, x2 = kh[..., :head_dim // 2], kh[..., head_dim // 2:]
        c = cos[:, None, :].astype(jnp.float32)
        s = sin[:, None, :].astype(jnp.float32)
        k = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                            axis=-1).reshape(S, KV)
    return k.astype(hidden.dtype), v.astype(hidden.dtype)


def restore_kv_grouped_ref(hidden, wk, wv, bk, bv, cos, sin, *,
                           head_dim: int, use_rope: bool = True):
    """Grouped oracle: hidden (G,S,D), wk/wv (G,D,KV), bk/bv (G,KV) ->
    K,V (G,S,KV). Each group row g must equal restore_kv_ref on the g-th
    slices (the byte-equivalence contract the grouped executor relies
    on), so the math is the per-layer oracle under a batched einsum."""
    h = hidden.astype(jnp.float32)
    k = jnp.einsum("gsd,gdk->gsk", h, wk.astype(jnp.float32))
    v = jnp.einsum("gsd,gdk->gsk", h, wv.astype(jnp.float32))
    if bk is not None:
        k = k + bk.astype(jnp.float32)[:, None, :]
        v = v + bv.astype(jnp.float32)[:, None, :]
    if use_rope:
        G, S, KV = k.shape
        nh = KV // head_dim
        kh = k.reshape(G, S, nh, head_dim)
        x1, x2 = kh[..., :head_dim // 2], kh[..., head_dim // 2:]
        c = cos[None, :, None, :].astype(jnp.float32)
        s = sin[None, :, None, :].astype(jnp.float32)
        k = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                            axis=-1).reshape(G, S, KV)
    return k.astype(hidden.dtype), v.astype(hidden.dtype)


def flash_attention_ref(q, k, v, *, group: int = 1, causal: bool = True,
                        window=None, softcap=None):
    """q (BH,Sq,hd), k/v (BKv,Skv,hd); q row b uses kv row b//group."""
    BH, Sq, hd = q.shape
    kk = jnp.repeat(k, group, axis=0)
    vv = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(kk.shape[1])[None, :]
    mask = jnp.ones((Sq, kk.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, vv.astype(jnp.float32)).astype(
        q.dtype)


def decode_attention_ref(q, k, v, kv_len, *, softcap=None, window=None):
    """q (BKv,G,hd); k/v (BKv,Smax,hd); kv_len (BKv,)."""
    s = jnp.einsum("bgh,bkh->bgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * q.shape[-1] ** -0.5
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(k.shape[1])[None, None, :]
    mask = kpos < kv_len[:, None, None]
    if window is not None:
        mask &= kpos > (kv_len[:, None, None] - 1 - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgk,bkh->bgh", p, v.astype(jnp.float32)).astype(
        q.dtype)


def decode_attention_paged_ref(q, k_new, v_new, k_pool, v_pool,
                               block_table, lengths, layer, window=None, *,
                               softcap=None):
    """jnp oracle for the paged kernel, by the plain route: gather the
    layer's logical layout through the block table (sentinel entries
    clamp; whatever they alias lies past the live length and carries
    exactly-zero probability), put the new token at ``lengths`` (dropped
    when the table is full, as the cache write drops it), then the
    contiguous decode oracle per KV head. q (B,Hp,hd); k_new/v_new
    (B,Kv,hd); pools (L,NB,bs,Kv,hd); block_table (B,MB); lengths (B,)
    tokens already in the pool."""
    L, NB, bs, Kv, hd = k_pool.shape
    B, MB = block_table.shape
    Hp = q.shape[1]
    g = Hp // Kv
    S = MB * bs
    tbl = jnp.minimum(block_table.astype(jnp.int32), NB - 1)
    bidx = jnp.arange(B)

    def logical(pool, new):
        x = pool[layer][tbl].reshape(B, S, Kv, hd)
        x = x.at[bidx, lengths].set(new, mode="drop")
        return x.transpose(0, 2, 1, 3).reshape(B * Kv, S, hd)

    out = decode_attention_ref(
        q.reshape(B * Kv, g, hd), logical(k_pool, k_new),
        logical(v_pool, v_new), jnp.repeat(lengths + 1, Kv),
        softcap=softcap, window=window)
    return out.reshape(B, Hp, hd)


def ssm_update_ref(h, dt, x, A, B, C, d_skip):
    """Mamba1 decode update (see ssm_update.py)."""
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dA = jnp.exp(dtf[:, :, None] * A[None].astype(jnp.float32))
    h_new = dA * h + (dtf * xf)[:, :, None] * B[:, None, :].astype(
        jnp.float32)
    y = (h_new * C[:, None, :].astype(jnp.float32)).sum(-1) \
        + d_skip[None].astype(jnp.float32) * xf
    return h_new, y.astype(x.dtype)
