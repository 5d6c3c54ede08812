"""Operations and bytes, computed from shapes (never measured).

``restore_kv`` is the restore projection kernel (one call projects a
group of G layers' normed hidden states (G, S, D) to K and V, (G, S, KV)
each). ``token_flops`` / ``logit_flops`` are the dense model's forward
operations for one processed token and one sampled position.
"""
from __future__ import annotations


def restore_kv(G: int, S: int, D: int, KV: int, *, bias: bool,
               rope_head_dim: int = 0, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one kernel call: two (S, D) x (D, KV) products
    per layer, plus bias and rotary passes over the outputs; reads the
    hidden states, both weights and biases (and the (S, head_dim) f32
    rotary tables when K is rotated), writes both outputs."""
    flops = 2 * 2 * G * S * D * KV
    elems_out = 2 * G * S * KV
    if bias:
        flops += elems_out
    if rope_head_dim:
        flops += 3 * G * S * KV
    nbytes = itemsize * (G * S * D + 2 * G * D * KV + elems_out)
    if bias:
        nbytes += itemsize * 2 * G * KV
    if rope_head_dim:
        nbytes += 4 * 2 * S * rope_head_dim
    return flops, nbytes


def token_flops(arch: dict, context: int) -> float:
    """Forward operations of one token attending over ``context`` keys
    (itself included), all layers, without the output head."""
    D, F = arch["d_model"], arch["d_ff"]
    qd = arch["n_heads"] * arch["head_dim"]
    kvd = arch["n_kv_heads"] * arch["head_dim"]
    proj = 2 * (D * qd + 2 * D * kvd + qd * D)
    ffn = 2 * (3 if arch["glu"] else 2) * D * F
    attn = 2 * 2 * context * qd
    return float(arch["n_layers"] * (proj + ffn + attn))


def logit_flops(arch: dict) -> float:
    return float(2 * arch["d_model"] * arch["vocab"])


def prefill_flops(arch: dict, chunk: int, hist: int) -> float:
    """A prefill chunk of ``chunk`` tokens after ``hist`` tokens, with
    one sampled position (the program computes the last row's logits)."""
    attn_ctx = chunk * hist + chunk * (chunk + 1) // 2
    base = token_flops(arch, 0) * chunk
    qd = arch["n_heads"] * arch["head_dim"]
    return base + arch["n_layers"] * 2 * 2 * qd * attn_ctx + logit_flops(arch)


def decode_flops(arch: dict, contexts) -> float:
    """One decode step over active sequences whose new token attends over
    ``contexts`` keys each."""
    return sum(token_flops(arch, c) + logit_flops(arch) for c in contexts)
