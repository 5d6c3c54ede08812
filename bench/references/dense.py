"""Plain float32 reference of the dense decoder-only transformer family
(qwen2, opt), written from the published descriptions.

It imports nothing of the program under test. Its weights come from the
benchmark's own generator (``harness/weights.py``), one layer at a time,
so a transcript of ten thousand tokens at published widths fits on one
chip next to nothing else. Every matrix product runs at
``jax.default_matmul_precision("highest")``.

``precision="fp8"`` rounds every matrix-product operand to float8 e4m3
first (accumulation stays float32): the control that a comparison has to
fail.

Departures from the published models, shared with the program: OPT's
out-projection and feed-forward carry no bias (the program has none);
OPT's learned positions are indexed from 0, not offset by 2.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

ROW_BLOCK = 512             # rows per block of attention / feed-forward


def arch_from_config(cfg: dict) -> dict:
    """Normalized sizes from a Hugging Face style config (qwen2, opt)."""
    kind = cfg["model_type"]
    D = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    out = {"d_model": D, "n_heads": H, "n_layers": int(cfg["num_hidden_layers"]),
           "vocab": int(cfg["vocab_size"]),
           "vocab_padded": W.padded_vocab(int(cfg["vocab_size"]))}
    if kind == "qwen2":
        out.update(n_kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=D // H, d_ff=int(cfg["intermediate_size"]),
                   glu=True, act=cfg["hidden_act"], norm="rmsnorm",
                   eps=float(cfg["rms_norm_eps"]), qkv_bias=True,
                   rope_theta=float(cfg["rope_theta"]),
                   learned_positions=False, position_rows=0,
                   tie_embeddings=bool(cfg["tie_word_embeddings"]))
    elif kind == "opt":
        if not cfg["do_layer_norm_before"]:
            raise ValueError("post-norm OPT is not covered")
        out.update(n_kv_heads=H, head_dim=D // H, d_ff=int(cfg["ffn_dim"]),
                   glu=False, act=cfg["activation_function"],
                   norm="layernorm", eps=1e-5,
                   qkv_bias=bool(cfg.get("qkv_bias", cfg["enable_bias"])),
                   rope_theta=0.0, learned_positions=True,
                   position_rows=int(cfg["program_position_rows"]),
                   tie_embeddings=bool(cfg.get("tie_word_embeddings", True)))
    else:
        raise ValueError(f"no reference for model_type {kind!r}")
    return out


ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _rnd(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, precision: str):
    return jnp.matmul(_rnd(a, precision), _rnd(b, precision))


def _norm(x, p: Dict, prefix: str, arch: dict):
    if arch["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + arch["eps"]) * p[f"{prefix}/scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + arch["eps"]) * p[f"{prefix}/scale"]
            + p[f"{prefix}/bias"])


def _rope(x, pos, theta: float):
    """x (T, heads, hd), half-split rotation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _layer(x, p, arch: dict, precision: str):
    """One pre-norm block over a whole transcript x (T, D)."""
    T = x.shape[0]
    H, Kv, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    pos = jnp.arange(T)
    h = _norm(x, p, "ln1", arch)
    q = _mm(h, p["attn/wq"], precision)
    k = _mm(h, p["attn/wk"], precision)
    v = _mm(h, p["attn/wv"], precision)
    if arch["qkv_bias"]:
        q, k, v = q + p["attn/bq"], k + p["attn/bk"], v + p["attn/bv"]
    q, k, v = (q.reshape(T, H, hd), k.reshape(T, Kv, hd),
               v.reshape(T, Kv, hd))
    if not arch["learned_positions"]:
        q, k = _rope(q, pos, arch["rope_theta"]), _rope(k, pos, arch["rope_theta"])
    g = H // Kv
    kx = jnp.repeat(k, g, axis=1)               # head i reads kv head i // g
    vx = jnp.repeat(v, g, axis=1)
    outs = []
    for r0 in range(0, T, ROW_BLOCK):
        qb = q[r0:r0 + ROW_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", _rnd(qb, precision),
                       _rnd(kx, precision)) * hd ** -0.5
        mask = pos[None, r0:r0 + ROW_BLOCK, None] >= pos[None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _rnd(pr, precision),
                               _rnd(vx, precision)).reshape(-1, H * hd))
    x = x + _mm(jnp.concatenate(outs), p["attn/wo"], precision)
    act = ACTS[arch["act"]]
    ffs = []
    for r0 in range(0, T, ROW_BLOCK):
        hb = _norm(x[r0:r0 + ROW_BLOCK], p, "ln2", arch)
        up = _mm(hb, p["mlp/w_up"], precision)
        up = act(_mm(hb, p["mlp/w_gate"], precision)) * up if arch["glu"] \
            else act(up)
        ffs.append(_mm(up, p["mlp/w_down"], precision))
    return x + jnp.concatenate(ffs)


def _layer_from_items(x, p, arch_items, precision):
    return _layer(x, p, dict(arch_items), precision)


_layer_jit = jax.jit(_layer_from_items,
                     static_argnames=("arch_items", "precision"))


def logits_at(arch: dict, seed: int, transcripts: Sequence[np.ndarray],
              rows: Sequence[Sequence[int]], *,
              precision: str = "f32") -> List[np.ndarray]:
    """Next-token logits (len(rows[i]), vocab) at positions ``rows[i]`` of
    each transcript. Layer by layer over all transcripts, so each layer's
    weights are made once."""
    layout = W.dense_layout(arch)
    with jax.default_matmul_precision("highest"):
        glob = {k: v.astype(jnp.float32) for k, v in
                W.global_params(layout, seed, jnp.bfloat16).items()}
        xs = []
        for toks in transcripts:
            t = jnp.asarray(np.asarray(toks, np.int32))
            x = glob["embed/table"][t]
            if arch["learned_positions"]:
                x = x + glob["embed/positions"][jnp.arange(len(toks))]
            xs.append(x)
        for li in range(arch["n_layers"]):
            p = {k: v.astype(jnp.float32) for k, v in
                 W.layer_params(layout, seed, jnp.bfloat16, li).items()}
            xs = [_layer_jit(x, p, arch_items=tuple(sorted(arch.items())),
                             precision=precision) for x in xs]
            del p
        fin = {"final_norm/scale": glob["final_norm/scale"]}
        if arch["norm"] == "layernorm":
            fin["final_norm/bias"] = glob["final_norm/bias"]
        head = (glob["embed/table"].T if arch["tie_embeddings"]
                else glob["embed/unembed"])[:, :arch["vocab"]]
        out = []
        for x, r in zip(xs, rows):
            xr = _norm(x[jnp.asarray(np.asarray(r, np.int32))], fin,
                       "final_norm", arch)
            out.append(np.asarray(_mm(xr, head, precision), np.float32))
        return out
