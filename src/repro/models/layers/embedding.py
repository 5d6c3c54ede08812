"""Token embedding + (tied) output head.

The table is vocab-sharded (vocab-parallel logits); lookups use jnp.take —
the SPMD partitioner lowers the sharded-dim gather to a local gather +
all-reduce. See DESIGN.md (hillclimb candidate if the roofline shows the
lookup collective dominating).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingRules, constrain
from repro.models.module import box, normal_init


VOCAB_PAD = 128   # Megatron-style: physical vocab padded for TP divisibility


def padded_vocab(vocab: int) -> int:
    return (vocab + VOCAB_PAD - 1) // VOCAB_PAD * VOCAB_PAD


def init_embedding(rng, vocab: int, d_model: int, dtype, tie: bool,
                   max_positions: int = 0, learned_positions: bool = False):
    re, ru, rp = jax.random.split(rng, 3)
    vp = padded_vocab(vocab)
    p = {"table": box(normal_init(re, (vp, d_model), dtype, 1.0),
                      "vocab", "d_model")}
    if not tie:
        p["unembed"] = box(
            normal_init(ru, (d_model, vp), dtype, d_model ** -0.5),
            "d_model", "vocab")
    if learned_positions:
        p["positions"] = box(
            normal_init(rp, (max_positions, d_model), dtype, 0.02),
            None, "d_model")
    return p


def embed_tokens(p: dict, ids, rules: ShardingRules, *, scale: bool,
                 d_model: int):
    x = jnp.take(p["table"], ids, axis=0)
    if scale:
        x = x * jnp.asarray(d_model ** 0.5, x.dtype)
    return constrain(x, rules, "batch", "seq", "d_model")


def logits(p: dict, x, rules: ShardingRules, *,
           softcap: Optional[float] = None, true_vocab: Optional[int] = None):
    # f32 logits whatever the model dtype: bf16 keeps 8 significant bits,
    # so over a 150k vocabulary the top logits tie and greedy sampling
    # turns on rounding noise
    if "unembed" in p:
        out = jnp.einsum("bsd,dv->bsv", x, p["unembed"],
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bsd,vd->bsv", x, p["table"],
                         preferred_element_type=jnp.float32)
    out = constrain(out, rules, "batch", "seq", "vocab")
    if softcap is not None:
        out = jnp.tanh(out / softcap) * softcap
    vp = out.shape[-1]
    if true_vocab is not None and vp != true_vocab:
        # padded vocab columns must never win softmax/argmax
        mask = jnp.arange(vp) < true_vocab
        out = jnp.where(mask, out, jnp.asarray(-1e30, out.dtype))
    return out


def positional(p: dict, positions):
    """Learned absolute positions (whisper decoder / OPT)."""
    return jnp.take(p["positions"], positions, axis=0)
