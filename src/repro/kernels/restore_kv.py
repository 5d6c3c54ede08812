"""Pallas TPU kernel: fused HCache restoration — K/V projection + RoPE.

The paper issues a cuBLAS GEMM then a separate RoPE+copy kernel (§5). On
TPU we fuse: each grid cell loads one hidden-state tile into VMEM, feeds
it to the MXU as bf16 with f32 accumulation, adds the bias, rotates K
per head and writes both outputs — one pass over HBM for H, no
intermediate K buffer.

One launch projects a *group* of layers: grid = (G, S / BLOCK_S,
KV / BLOCK_KV), the leading dimension indexes a stack of per-layer
weights, so the serving-path executor coalesces ready projection tasks
into one call instead of L per-layer dispatches (core/restoration.py).
The KV axis is innermost, so the hidden tile's block index is unchanged
across it and the pipeline fetches each hidden tile once per (g, i).

VMEM. The contraction dim D stays resident. Every input and output
block is double-buffered by the pipeline, so a cell holds

    2 · (BS·D + 2·D·BKV) · itemsize        hidden + wk + wv tiles
  + 2 · 2 · BS·BKV · itemsize              k, v output tiles
  + 2 · 2 · BS·hd · 4                      f32 cos / signed-sin tiles
  + 2 · 2 · BKV · itemsize                 biases (when present)
  + 2 · BS·BKV · 4                         f32 k, v accumulators

``_pick_blocks`` takes the tallest BLOCK_S, then the widest BLOCK_KV
(whole heads), that keep this under ``VMEM_BUDGET``, 3/4 of the 16 MiB
default scoped VMEM limit of a v5e core (the remainder is headroom for
the compiler's own temporaries). qwen2-7b (D=3584, KV=512, bf16) gets
256×128 tiles (8.0 MiB); llama2-7b (D=4096, KV=4096) 256×128
(9.0 MiB); opt-30b (D=7168, KV=7168, bias) 128×128 (11.0 MiB: a
256-row tile would need 15.0).

Weight re-read. Each (g, i) cell sweeps every KV tile, so both weight
stacks, 2·D·KV·itemsize bytes, are read once per BLOCK_S rows: the
kernel's arithmetic intensity is BLOCK_S flop/byte whatever D and KV
are. At opt-30b's 128-row tiles that is 26.3 GFLOP over 205 MB a tile,
128 flop/byte against the v5e ridge of 240 (197 TFLOP/s over 819 GB/s),
so the kernel cannot pass about 53% of its compute roofline; qwen2-7b's
256-row tiles allow all of it.

RoPE is the rotate-half form applied per head as
``x · [cos, cos] + roll(x, hd/2) · [-sin, sin]``: a lane rotation of one
head-wide slice instead of a lane-splitting reshape and a half-width
concat, and elementwise the same f32 arithmetic as ``ref.py``.

Under tensor parallelism (``kv_sharding``) the launch is wrapped in a
``shard_map`` over the mesh: each device runs the kernel on its own
slice of the KV axis (whole heads, since tp | n_kv_heads), with hidden
states and RoPE tables replicated — Mosaic kernels are never
partitioned by the SPMD partitioner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

VMEM_BUDGET = 12 * 1024 * 1024


def _vmem_bytes(block_s: int, block_kv: int, D: int, head_dim: int,
                itemsize: int, has_bias: bool) -> int:
    """Bytes of VMEM one grid cell holds (see the module docstring)."""
    ins = (block_s * D + 2 * D * block_kv) * itemsize \
        + 2 * block_s * head_dim * 4 \
        + (2 * block_kv * itemsize if has_bias else 0)
    outs = 2 * block_s * block_kv * itemsize
    return 2 * (ins + outs) + 2 * block_s * block_kv * 4


def _pick_blocks(S: int, KV: int, D: int, head_dim: int, itemsize: int,
                 has_bias: bool, block_s: int, block_kv: int):
    """(BLOCK_S, BLOCK_KV): BLOCK_S a divisor of S, BLOCK_KV a divisor
    of KV that covers whole heads (a head split across tiles would break
    the rotate-half pairing). The tallest BLOCK_S, then the widest
    BLOCK_KV, within ``VMEM_BUDGET``; ``block_s``/``block_kv`` cap the
    search. When nothing fits, the smallest tiles are returned."""
    n_heads = KV // head_dim
    cap_kv = block_kv or KV
    kv_opts = [h * head_dim for h in range(n_heads, 0, -1)
               if n_heads % h == 0 and h * head_dim <= max(cap_kv, head_dim)]
    s_opts = []
    b = min(block_s, S)
    while b >= 1:
        if S % b == 0:
            s_opts.append(b)
        b //= 2
    s_opts = s_opts or [S]
    for bs in s_opts:
        for bkv in kv_opts:
            if _vmem_bytes(bs, bkv, D, head_dim, itemsize,
                           has_bias) <= VMEM_BUDGET:
                return bs, bkv
    return s_opts[-1], kv_opts[-1]


def _rope_tables(cos, sin):
    """(S, hd/2) cos/sin -> (S, hd) [cos, cos] and [-sin, sin] in f32."""
    cos = cos.astype(jnp.float32)
    sin = sin.astype(jnp.float32)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _restore_kv_kernel(*refs, head_dim: int, use_rope: bool,
                       has_bias: bool):
    if has_bias:
        (h_ref, wk_ref, wv_ref, bk_ref, bv_ref, cos_ref, sin_ref,
         k_ref, v_ref) = refs
    else:
        h_ref, wk_ref, wv_ref, cos_ref, sin_ref, k_ref, v_ref = refs
    h = h_ref[...]
    k = jax.lax.dot(h, wk_ref[...], preferred_element_type=jnp.float32)
    v = jax.lax.dot(h, wv_ref[...], preferred_element_type=jnp.float32)
    if has_bias:
        k = k + bk_ref[...].astype(jnp.float32)
        v = v + bv_ref[...].astype(jnp.float32)
    v_ref[...] = v.astype(v_ref.dtype)
    if not use_rope:
        k_ref[...] = k.astype(k_ref.dtype)
        return
    cos = cos_ref[...]
    sin = sin_ref[...]
    for j in range(k.shape[1] // head_dim):
        sl = slice(j * head_dim, (j + 1) * head_dim)
        x = k[:, sl]
        rolled = pltpu.roll(x, shift=head_dim // 2, axis=1)
        k_ref[:, sl] = (x * cos + rolled * sin).astype(k_ref.dtype)


def _grouped_call(hidden, wk, wv, bk, bv, cos2, sin2, *, head_dim,
                  use_rope, block_s, block_kv, interpret):
    """The pallas_call over (possibly per-device) arrays: hidden
    (G, S, D); wk/wv (G, D, KV); bk/bv (G, 1, KV) or None; cos2/sin2
    (S, head_dim) f32."""
    G, S, D = hidden.shape
    KV = wk.shape[2]
    has_bias = bk is not None
    block_s, block_kv = _pick_blocks(S, KV, D, head_dim,
                                     jnp.dtype(hidden.dtype).itemsize,
                                     has_bias, block_s, block_kv)
    grid = (G, S // block_s, KV // block_kv)
    # leading None squeezes the group dim out of the per-cell refs
    in_specs = [
        pl.BlockSpec((None, block_s, D), lambda g, i, j: (g, i, 0)),
        pl.BlockSpec((None, D, block_kv), lambda g, i, j: (g, 0, j)),
        pl.BlockSpec((None, D, block_kv), lambda g, i, j: (g, 0, j)),
    ]
    args = [hidden, wk, wv]
    if has_bias:
        # (G, 1, KV): the block's last two dims (1, BKV) are (full dim,
        # lane multiple) — a squeezed (BKV,) row is not a legal TPU block
        in_specs += [pl.BlockSpec((None, 1, block_kv),
                                  lambda g, i, j: (g, 0, j))] * 2
        args += [bk, bv]
    in_specs += [pl.BlockSpec((block_s, head_dim),
                              lambda g, i, j: (i, 0))] * 2
    args += [cos2, sin2]
    out_spec = pl.BlockSpec((None, block_s, block_kv),
                            lambda g, i, j: (g, i, j))
    kernel = functools.partial(_restore_kv_kernel, head_dim=head_dim,
                               use_rope=use_rope, has_bias=has_bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((G, S, KV), hidden.dtype)] * 2,
        interpret=interpret,
        name="restore_kv_grouped",
    )(*args)


@functools.partial(jax.jit, static_argnames=("head_dim", "use_rope",
                                             "block_s", "block_kv",
                                             "interpret", "kv_sharding"))
def restore_kv_grouped_pallas(hidden, wk, wv, bk, bv, cos, sin, *,
                              head_dim: int, use_rope: bool = True,
                              block_s: int = 256, block_kv: int = 0,
                              interpret: bool = True, kv_sharding=None):
    """Stacked restoration projection for a *group* of layers.

    hidden (G, S, D); wk/wv (G, D, KV); bk/bv (G, KV) or None; cos/sin
    (S, head_dim//2) shared by all group members (same positions).
    Returns K, V: (G, S, KV), K rotated.

    ``kv_sharding`` (static NamedSharding placing a mesh axis on the KV
    output axis) runs the kernel under ``shard_map``: each device
    projects only its own heads from its slice of the weight stacks,
    and the outputs come back KV-sharded for the shard-local page-pool
    scatter (DESIGN.md §16)."""
    G = hidden.shape[0]
    cos2, sin2 = _rope_tables(cos, sin)
    if bk is not None:
        bk = bk.reshape(G, 1, -1)
        bv = bv.reshape(G, 1, -1)
    call = functools.partial(_grouped_call, head_dim=head_dim,
                             use_rope=use_rope, block_s=block_s,
                             block_kv=block_kv, interpret=interpret)
    if kv_sharding is None:
        return call(hidden, wk, wv, bk, bv, cos2, sin2)
    kv, rep = P(None, None, kv_sharding.spec[2]), P()
    return jax.shard_map(call, mesh=kv_sharding.mesh,
                         in_specs=(rep, kv, kv, kv, kv, rep, rep),
                         out_specs=(kv, kv), check_vma=False)(
        hidden, wk, wv, bk, bv, cos2, sin2)
