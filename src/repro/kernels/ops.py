"""Public jit'd wrappers for the Pallas kernels.

``use_pallas`` switches between the kernel (TPU target; interpret=True on
CPU) and the jnp oracle. Model code calls these via the attention/mamba
layers when built with kernels enabled; the dry-run lowers the jnp path
(Mosaic does not target the CPU backend) — see DESIGN.md.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            pages_fit_tiles)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.restore_kv import restore_kv_grouped_pallas
from repro.kernels.ssm_update import ssm_update_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def restore_kv(hidden, wk, wv, bk, bv, cos, sin, *, head_dim,
               use_rope=True, use_pallas=True, interpret=None):
    if not use_pallas:
        return ref.restore_kv_ref(hidden, wk, wv, bk, bv, cos, sin,
                                  head_dim=head_dim, use_rope=use_rope)
    # one layer is a group of one
    interpret = (not on_tpu()) if interpret is None else interpret
    k, v = restore_kv_grouped_pallas(
        hidden[None], wk[None], wv[None],
        None if bk is None else bk[None], None if bv is None else bv[None],
        cos, sin, head_dim=head_dim, use_rope=use_rope,
        interpret=interpret)
    return k[0], v[0]


def restore_kv_grouped(hidden, wk, wv, bk, bv, cos, sin, *, head_dim,
                       use_rope=True, use_pallas=True, interpret=None,
                       kv_sharding=None):
    """Stacked restoration projection for a group of layers — one
    dispatch instead of G (see kernels/restore_kv.py and the batched
    executor in core/restoration.py).

    ``kv_sharding`` (NamedSharding over the flattened KV axis of the
    (G, S, KV) outputs, DESIGN.md §16) keeps each device's projected
    heads local — the kernel runs under ``shard_map``, the jnp oracle
    under a sharding constraint — so the restore sink scatters them into
    a same-sharded page pool with zero cross-device traffic."""
    if not use_pallas:
        k, v = ref.restore_kv_grouped_ref(hidden, wk, wv, bk, bv, cos, sin,
                                          head_dim=head_dim,
                                          use_rope=use_rope)
        if kv_sharding is not None:
            k = jax.lax.with_sharding_constraint(k, kv_sharding)
            v = jax.lax.with_sharding_constraint(v, kv_sharding)
        return k, v
    interpret = (not on_tpu()) if interpret is None else interpret
    return restore_kv_grouped_pallas(hidden, wk, wv, bk, bv, cos, sin,
                                     head_dim=head_dim, use_rope=use_rope,
                                     interpret=interpret,
                                     kv_sharding=kv_sharding)


def flash_attention(q, k, v, *, group=1, causal=True, window=None,
                    softcap=None, use_pallas=True, interpret=None):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, group=group, causal=causal,
                                       window=window, softcap=softcap)
    interpret = (not on_tpu()) if interpret is None else interpret
    return flash_attention_pallas(q, k, v, group=group, causal=causal,
                                  window=window, softcap=softcap,
                                  interpret=interpret)


def decode_attention(q, k, v, kv_len, *, softcap=None, window=None,
                     use_pallas=True, interpret=None):
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v, kv_len, softcap=softcap,
                                        window=window)
    interpret = (not on_tpu()) if interpret is None else interpret
    return decode_attention_pallas(q, k, v, kv_len, softcap=softcap,
                                   window=window, interpret=interpret)


def decode_attention_paged(q, k_new, v_new, k_pool, v_pool, block_table,
                           lengths, layer, window=None, *, softcap=None,
                           use_pallas=True, interpret=None,
                           head_sharding=None):
    """Paged decode attention over the pool read in place — see
    decode_attention.py. ``head_sharding`` runs the kernel head-parallel
    over a tensor-parallel mesh (kernel path; the jnp oracle takes its
    sharding from the caller's constraints)."""
    if not use_pallas:
        return ref.decode_attention_paged_ref(
            q, k_new, v_new, k_pool, v_pool, block_table, lengths, layer,
            window, softcap=softcap)
    interpret = (not on_tpu()) if interpret is None else interpret
    return paged_decode_attention_pallas(
        q, k_new, v_new, k_pool, v_pool, block_table, lengths, layer,
        window, softcap=softcap, interpret=interpret,
        head_sharding=head_sharding)


def ssm_update(h, dt, x, A, B, C, d_skip, *, use_pallas=True,
               interpret=None):
    if not use_pallas:
        return ref.ssm_update_ref(h, dt, x, A, B, C, d_skip)
    interpret = (not on_tpu()) if interpret is None else interpret
    return ssm_update_pallas(h, dt, x, A, B, C, d_skip, interpret=interpret)
