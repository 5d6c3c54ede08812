"""Decoder-only LM stack (dense / GQA / MoE / VLM) with scan-over-layers.

Entry points (all pure functions over boxed-param values):

  init_lm           -> boxed params
  lm_forward        -> full-sequence forward (train / prefill), optionally
                       capturing per-layer hidden states (HCache save path)
                       and emitting stacked KV caches (prefill)
  lm_decode_step    -> single-token continuous-batching decode step
  lm_restore_kv     -> THE PAPER'S OP: stacked per-layer K,V from stacked
                       saved hidden states (norm + projection + RoPE only)

HCache definition: the saved "hidden state" of layer *i* is the residual
stream INPUT to layer *i* (`H_L` in the paper). Restoration recomputes
`K = W_k·RMSNorm(H)` — the norm is part of the (cheap) restoration compute,
keeping restore == original bitwise (§3.1; the paper folds the norm into ε).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.arch import ArchConfig
from repro.distributed import tp as tp_lib
from repro.distributed.sharding import ShardingRules, constrain, pad_heads
from repro.kernels import ops
from repro.models.layers import attention as attn_lib
from repro.models.layers.attention import AttnHyper
from repro.models.layers.embedding import (embed_tokens, init_embedding,
                                           logits as embed_logits, positional)
from repro.models.layers.mlp import apply_mlp, init_mlp
from repro.models.layers.moe import MoEHyper, apply_moe, init_moe
from repro.models.layers.norm import apply_norm, init_norm
from repro.models.module import stacked_init, split

BIG_WINDOW = 1 << 30


@dataclasses.dataclass(frozen=True)
class LMHyper:
    cfg: ArchConfig
    rules: ShardingRules
    model_axis: int = 1
    dtype: Any = jnp.float32
    attn_chunk: int = 1024
    remat: str = "full"              # none | full | dots
    max_positions: int = 8192        # learned-pos archs only
    n_vis: int = 0                   # VLM: patch positions at sequence head
    tri_prefill: bool = False        # §Perf: triangular prefill schedule
    moe_late_combine: bool = False   # §Perf: see layers/moe.py

    @functools.cached_property
    def attn(self) -> AttnHyper:
        c = self.cfg
        padded, _ = pad_heads(c.n_heads, c.n_kv_heads, self.model_axis)
        return AttnHyper(
            n_heads=c.n_heads, n_kv_heads=c.n_kv_heads, head_dim=c.head_dim_,
            padded_heads=padded, qkv_bias=c.qkv_bias, use_rope=c.use_rope,
            rope_theta=c.rope_theta, attn_softcap=c.attn_softcap,
            chunk=self.attn_chunk)

    @functools.cached_property
    def moe(self) -> Optional[MoEHyper]:
        c = self.cfg
        if not c.n_experts:
            return None
        return MoEHyper(n_experts=c.n_experts, top_k=c.experts_per_token,
                        d_model=c.d_model, d_ff=c.d_ff,
                        activation=c.ffn_activation, glu=c.ffn_glu,
                        late_combine=self.moe_late_combine)


# ------------------------------------------------------------------- params
def init_block(rng, h: LMHyper) -> dict:
    c = h.cfg
    r1, r2, r3 = jax.random.split(rng, 3)
    p = {
        "ln1": init_norm(c.norm, c.d_model, h.dtype),
        "attn": attn_lib.init_attention(r1, c.d_model, h.attn, h.dtype),
        "ln2": init_norm(c.norm, c.d_model, h.dtype),
    }
    if h.moe is not None:
        p["moe"] = init_moe(r2, h.moe, h.dtype)
    else:
        p["mlp"] = init_mlp(r3, c.d_model, c.d_ff, c.ffn_glu, h.dtype)
    if c.post_attn_norm:
        p["post_ln1"] = init_norm(c.norm, c.d_model, h.dtype)
        p["post_ln2"] = init_norm(c.norm, c.d_model, h.dtype)
    return p


def init_lm(rng, h: LMHyper) -> dict:
    c = h.cfg
    re, rb = jax.random.split(rng)
    learned_pos = not c.use_rope
    params = {
        "embed": init_embedding(re, c.vocab_size, c.d_model, h.dtype,
                                c.tie_embeddings, h.max_positions,
                                learned_pos),
        "blocks": stacked_init(lambda r: init_block(r, h), c.n_layers, rb),
        "final_norm": init_norm(c.norm, c.d_model, h.dtype),
    }
    return params


def layer_windows(h: LMHyper) -> Optional[jnp.ndarray]:
    """Per-layer attention window (gemma2 local/global); None if uniform."""
    c = h.cfg
    if not c.local_window:
        return None
    from repro.config.arch import AttnKind
    kinds = c.attn_kinds()
    return jnp.asarray([c.local_window if k == AttnKind.LOCAL else BIG_WINDOW
                        for k in kinds], jnp.int32)


# ----------------------------------------------------------------- block fns
def _ffn(p, x, h: LMHyper):
    if h.moe is not None:
        out, probs = apply_moe(p["moe"], x, h.moe, h.rules)
        # GShard load-balance aux: E * sum_e f_e * P_e
        E = h.moe.n_experts
        top1 = jnp.argmax(probs, axis=-1)
        f = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=(0, 1))
        P = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(f * P)
        return out, aux
    return apply_mlp(p["mlp"], x, h.cfg.ffn_activation, h.rules), 0.0


def block_forward(p, x, h: LMHyper, *, positions, window,
                  hist_kv=None, hist_len=None, emit_kv: bool):
    """Full-sequence block. x: (B,S,D). Optional restored history KV
    (B,Sh,Kv,hd) pair prepended to the attention context (HCache prefill).

    Returns (x_out, aux, (k, v) or None, hidden_in)."""
    c = h.cfg
    hidden_in = x
    normed = apply_norm(p["ln1"], x, c.norm, c.norm_eps)
    q, k, v = attn_lib.project_qkv(p["attn"], normed, h.attn, h.rules,
                                   positions)
    if hist_kv is not None:
        hk, hv = hist_kv
        k_all = jnp.concatenate([hk, k], axis=1)
        v_all = jnp.concatenate([hv, v], axis=1)
        kv_len = None if hist_len is None else hist_len + x.shape[1]
    else:
        k_all, v_all, kv_len = k, v, None
    w = None
    if window is not None:
        w = window if not isinstance(window, int) else jnp.asarray(window)
    if h.tri_prefill and hist_kv is None and w is None:
        attn_out = attn_lib.flash_attention_triangular(
            q, k_all, v_all, h.attn, q_positions=positions, causal=True)
    else:
        attn_out = attn_lib.flash_attention_jnp(
            q, k_all, v_all, h.attn, q_positions=positions, causal=True,
            window=w, kv_len=kv_len)
    attn_out = attn_lib.attn_output(p["attn"], attn_out, h.rules)
    if c.post_attn_norm:
        attn_out = apply_norm(p["post_ln1"], attn_out, c.norm, c.norm_eps)
    x = x + attn_out
    normed2 = apply_norm(p["ln2"], x, c.norm, c.norm_eps)
    ff, aux = _ffn(p, normed2, h)
    if c.post_attn_norm:
        ff = apply_norm(p["post_ln2"], ff, c.norm, c.norm_eps)
    x = x + ff
    kv = (k, v) if emit_kv else None
    return x, aux, kv, hidden_in


def block_decode(p, x, h: LMHyper, *, k_cache, v_cache, lengths, window):
    """Single-token block. x: (B,1,D); caches (B,Smax,Kv,hd); lengths (B,)
    count tokens ALREADY in the cache (the new token is written at
    ``lengths``). Returns (x_out, new_k_cache, new_v_cache, hidden_in)."""
    c = h.cfg
    hidden_in = x
    positions = lengths[:, None]                       # (B,1)
    normed = apply_norm(p["ln1"], x, c.norm, c.norm_eps)
    q, k, v = attn_lib.project_qkv(p["attn"], normed, h.attn, h.rules,
                                   positions)
    B = x.shape[0]
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, lengths].set(k[:, 0], mode="drop")
    v_cache = v_cache.at[bidx, lengths].set(v[:, 0], mode="drop")
    k_cache = constrain(k_cache, h.rules, "batch", "kv_seq", "kv_heads",
                        "head_dim")
    v_cache = constrain(v_cache, h.rules, "batch", "kv_seq", "kv_heads",
                        "head_dim")
    w = None
    if window is not None:
        w = window if not isinstance(window, int) else jnp.asarray(window)
    attn_out = attn_lib.decode_attention_jnp(
        q, k_cache, v_cache, h.attn, kv_len=lengths + 1, window=w)
    attn_out = attn_lib.attn_output(p["attn"], attn_out, h.rules)
    if c.post_attn_norm:
        attn_out = apply_norm(p["post_ln1"], attn_out, c.norm, c.norm_eps)
    x = x + attn_out
    normed2 = apply_norm(p["ln2"], x, c.norm, c.norm_eps)
    ff, _ = _ffn(p, normed2, h)
    if c.post_attn_norm:
        ff = apply_norm(p["post_ln2"], ff, c.norm, c.norm_eps)
    x = x + ff
    return x, k_cache, v_cache, hidden_in


def block_decode_paged(p, x, h: LMHyper, *, k_pool, v_pool, layer,
                       block_table, lengths, window):
    """Single-token block over a paged KV cache read in place.

    x: (B,1,D); pools (L, NB, bs, Kv, hd), the whole stack, read at
    ``layer`` and never written here; block_table (B, MB) logical →
    physical page map (entries >= NB are unallocated sentinels); lengths
    (B,) tokens already in the pool. The new token's K/V are attended
    as an operand and returned for the caller's one scatter after the
    layer scan. Returns (x_out, k_new, v_new, hidden_in), k/v (B,Kv,hd).

    On the TPU this is the paged kernel (kernels/decode_attention.py):
    it DMAs only each slot's live pages. Elsewhere, and where a page's
    KV heads do not fill the pool's tiles (``pages_fit_tiles``: bf16
    with one KV head per device, say), it gathers the layer's logical
    layout by table and runs ``decode_attention_jnp`` —
    the contiguous step's arithmetic, so paged and contiguous decode
    agree bitwise there (masked positions carry exactly-zero
    probability, so gathered junk past the live length cannot perturb
    the output)."""
    c = h.cfg
    hidden_in = x
    positions = lengths[:, None]                       # (B,1)
    normed = apply_norm(p["ln1"], x, c.norm, c.norm_eps)
    q, k, v = attn_lib.project_qkv(p["attn"], normed, h.attn, h.rules,
                                   positions)
    B, MB = block_table.shape
    Hp, hd = q.shape[2], q.shape[3]
    ctx = tp_lib.active()
    kv_dev = k_pool.shape[3] // (1 if ctx is None else ctx.tp)
    if ops.on_tpu() and ops.pages_fit_tiles(kv_dev, k_pool.dtype):
        attn_out = ops.decode_attention_paged(
            q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, block_table,
            lengths, layer, window, softcap=c.attn_softcap,
            head_sharding=None if ctx is None else ctx.kv_sharding(3, 1))
        attn_out = attn_out.reshape(B, 1, Hp, hd)
    else:
        NB, bs = k_pool.shape[1], k_pool.shape[2]
        table = jnp.minimum(block_table, NB - 1)       # clamp sentinels
        bidx = jnp.arange(B)

        def logical(pool, new):
            cache = pool[layer][table].reshape(B, MB * bs, *pool.shape[3:])
            cache = cache.at[bidx, lengths].set(new[:, 0], mode="drop")
            cache = constrain(cache, h.rules, None, None, "kv_heads",
                              "head_dim")
            # tensor-parallel seam (DESIGN.md §16): the gather and the
            # token write never index the head axis, so under an active
            # TPContext both stay shard-local
            return tp_lib.kv_seam(cache, 2)

        attn_out = attn_lib.decode_attention_jnp(
            q, logical(k_pool, k), logical(v_pool, v), h.attn,
            kv_len=lengths + 1, window=window)
    # the ONE collective of the sharded decode path: replicate the
    # per-head attention output before the wo contraction so the output
    # projection (and the logits) run the exact single-device program —
    # a head-sharded wo would partial-sum across devices and break
    # bitwise identity with tp=1
    attn_out = tp_lib.logits_seam(attn_out)
    attn_out = attn_lib.attn_output(p["attn"], attn_out, h.rules)
    if c.post_attn_norm:
        attn_out = apply_norm(p["post_ln1"], attn_out, c.norm, c.norm_eps)
    x = x + attn_out
    normed2 = apply_norm(p["ln2"], x, c.norm, c.norm_eps)
    ff, _ = _ffn(p, normed2, h)
    if c.post_attn_norm:
        ff = apply_norm(p["post_ln2"], ff, c.norm, c.norm_eps)
    x = x + ff
    return x, k[:, 0], v[:, 0], hidden_in


def _remat_wrap(fn, h: LMHyper):
    if h.remat == "none":
        return fn
    if h.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ------------------------------------------------------------ full forward
def _embed_input(params, h: LMHyper, tokens, positions, patch_embeds=None):
    c = h.cfg
    x = embed_tokens(params["embed"], tokens, h.rules,
                     scale=c.embedding_scale, d_model=c.d_model)
    if not c.use_rope and "positions" in params["embed"]:
        x = x + positional(params["embed"], positions).astype(x.dtype)
    if patch_embeds is not None:
        n_vis = patch_embeds.shape[1]
        x = jnp.concatenate(
            [patch_embeds.astype(x.dtype), x[:, n_vis:]], axis=1)
    return x.astype(h.dtype)


def lm_forward(params, tokens, h: LMHyper, *, positions=None,
               patch_embeds=None, hist_kv=None, hist_len=None,
               capture_hidden: bool = False, emit_kv: bool = False,
               final_logits_only: bool = False, skip_logits: bool = False):
    """Train / prefill forward.

    tokens: (B,S) int32. hist_kv: optional restored-history KV caches,
    stacked (L,B,Sh,Kv,hd) pair — the HCache prefill path.
    Returns dict(logits, kv, hidden, aux)."""
    c = h.cfg
    B, S = tokens.shape
    if positions is None:
        base = 0 if hist_len is None else hist_len
        positions = base + jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = _embed_input(params, h, tokens, positions, patch_embeds)
    x = constrain(x, h.rules, "batch", "seq", "d_model")
    windows = layer_windows(h)

    def body(carry, xs):
        x, aux = carry
        (bp, win, hkv) = xs
        x, a, kv, hidden = block_forward(
            bp, x, h, positions=positions, window=win,
            hist_kv=hkv, hist_len=hist_len, emit_kv=emit_kv)
        if kv is not None:
            kv = tuple(constrain(t, h.rules, "batch", "kv_seq", "kv_heads",
                                 "head_dim") for t in kv)
        ys = (kv, hidden if capture_hidden else None)
        return (x, aux + a), ys

    body = _remat_wrap(body, h)
    xs = (params["blocks"], windows, hist_kv)
    (x, aux), ys = jax.lax.scan(body, (x, 0.0), xs)
    kv_stack, hidden_stack = ys
    x = apply_norm(params["final_norm"], x, c.norm, c.norm_eps)
    if final_logits_only:
        x = x[:, -1:]
    if skip_logits:     # training path: chunked vocab-parallel CE downstream
        return {"final_x": x, "kv": kv_stack, "hidden": hidden_stack,
                "aux": aux}
    lg = embed_logits(params["embed"], x, h.rules, softcap=c.logit_softcap,
                      true_vocab=c.vocab_size)
    return {"logits": lg, "kv": kv_stack, "hidden": hidden_stack, "aux": aux}


def lm_decode_step(params, cache, tokens, h: LMHyper):
    """One continuous-batching decode step.

    cache: dict(k (L,B,Smax,Kv,hd), v, lengths (B,)). tokens: (B,1).
    Returns (logits (B,1,V), new cache)."""
    c = h.cfg
    lengths = cache["lengths"]
    x = _embed_input(params, h, tokens, lengths[:, None])
    x = constrain(x, h.rules, "batch", None, "d_model")
    windows = layer_windows(h)

    def body(x, xs):
        bp, win, kc, vc = xs
        x, nk, nv, hidden = block_decode(bp, x, h, k_cache=kc, v_cache=vc,
                                         lengths=lengths, window=win)
        return x, (nk, nv, hidden)

    xs = (params["blocks"], windows, cache["k"], cache["v"])
    x, (nk, nv, hidden) = jax.lax.scan(body, x, xs)
    x = apply_norm(params["final_norm"], x, c.norm, c.norm_eps)
    lg = embed_logits(params["embed"], x, h.rules, softcap=c.logit_softcap,
                      true_vocab=c.vocab_size)
    new_cache = {"k": nk, "v": nv, "lengths": lengths + 1}
    return lg, new_cache, hidden


def lm_decode_step_paged(params, cache, tokens, h: LMHyper):
    """One continuous-batching decode step over a paged KV cache.

    cache: dict(k_pool/v_pool (L, NB, bs, Kv, hd), block_table (B, MB)
    int32, lengths (B,)). tokens: (B,1). Returns (logits, new cache,
    per-layer hidden) — same contract as ``lm_decode_step``.

    The pools are read in place: the layer scan carries only the
    activations, and each layer's attention reads its slice of the
    stacked pools by layer index. The new token's K/V come out of the
    scan, (L, B, Kv, hd), and land in their pages in one scatter after
    it (sentinel addresses drop); under a donated cache XLA makes that
    scatter in place, so no pool-sized copy is left in the step."""
    c = h.cfg
    lengths = cache["lengths"]
    bt = cache["block_table"]
    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    L, NB, bs = k_pool.shape[:3]
    x = _embed_input(params, h, tokens, lengths[:, None])
    x = constrain(x, h.rules, "batch", None, "d_model")
    windows = layer_windows(h)
    B = tokens.shape[0]
    MB = bt.shape[1]

    def body(x, xs):
        bp, win, layer = xs
        x, nk, nv, hidden = block_decode_paged(
            bp, x, h, k_pool=k_pool, v_pool=v_pool, layer=layer,
            block_table=bt, lengths=lengths, window=win)
        return x, (nk, nv, hidden)

    xs = (params["blocks"], windows, jnp.arange(L, dtype=jnp.int32))
    x, (nk, nv, hidden) = jax.lax.scan(body, x, xs)
    bidx = jnp.arange(B)
    li = lengths // bs
    # a logical page past the table (slot exactly full) must become a
    # dropped sentinel write, not clamp into the slot's last live page
    blk = jnp.where(li < MB, bt[bidx, jnp.minimum(li, MB - 1)], NB)
    off = lengths % bs
    k_pool = tp_lib.kv_seam(k_pool.at[:, blk, off].set(nk, mode="drop"), 3)
    v_pool = tp_lib.kv_seam(v_pool.at[:, blk, off].set(nv, mode="drop"), 3)
    x = apply_norm(params["final_norm"], x, c.norm, c.norm_eps)
    lg = embed_logits(params["embed"], x, h.rules, softcap=c.logit_softcap,
                      true_vocab=c.vocab_size)
    new_cache = {"k_pool": k_pool, "v_pool": v_pool, "block_table": bt,
                 "lengths": lengths + 1}
    return lg, new_cache, hidden


# -------------------------------------------------------------- HCache op
def lm_restore_kv(params, hidden, h: LMHyper, *, positions):
    """Restore stacked KV caches from stacked saved hidden states.

    hidden: (L, B, S, D) residual-stream inputs per layer (bf16 on the wire).
    positions: (B, S). Returns (k, v): (L, B, S, Kv, hd) each — exactly what
    the prefill with emit_kv=True would have produced for these layers."""
    c = h.cfg

    def one_layer(bp, hl):
        normed = apply_norm(bp["ln1"], hl.astype(h.dtype), c.norm, c.norm_eps)
        return attn_lib.restore_kv(
            bp["attn"]["wk"], bp["attn"]["wv"],
            bp["attn"].get("bk"), bp["attn"].get("bv"),
            normed, h.attn, positions)

    return jax.vmap(one_layer)(params["blocks"], hidden)
