"""Pallas TPU kernels: single-token decode attention.

Two kernels:

  * ``decode_attention_pallas`` — contiguous (BKv, Smax, hd) caches.
    Grid = (batch·kv_heads, Skv/BK); each cell processes one KV block
    for all the query heads of that kv group, keeping a running max/sum
    in VMEM scratch. Blocks past the live length are skipped.

  * ``paged_decode_attention_pallas`` — the served paged kernel. It
    reads the engine's page pool ``(L, NB, bs, Kv, hd)`` in place: the
    pools stay in HBM (``memory_space=pl.ANY``) and the kernel DMAs only
    the pages that hold each slot's live tokens, ``pages`` of them per
    loop step, double-buffered. The block table, the lengths and the
    layer index ride in as scalar prefetch, so one compiled kernel
    serves every layer of the decode scan and every mix of lengths.

Paged layout. A page is ``(bs, Kv, hd)``: token-major, heads inside.
``pages`` pages land in VMEM as one contiguous ``(pages·bs·Kv, hd)``
tile whose row ``r`` is token ``r // Kv`` of the block and KV head
``r % Kv``. Every query head is scored against every row and the rows
of other KV heads are masked out, so GQA (qwen2-7b: 28 query heads on 4
KV heads) and MHA (opt-30b: 56 on 56) run the same body with no per-head
slicing; the masked products cost Kv× the score FLOPs, which are small
next to the bytes a decode step moves. Masked rows get exactly zero
probability, so each head's output is its own softmax over its own
rows.

The new token's K/V are not in the pool yet: they come in as a
separate ``(B, Kv, hd)`` operand and open each slot's online softmax
(the decode step writes them to the pool after its layer scan). A slot
whose table is full drops the token, as the cache write does.

``pages`` is picked from a page's bytes: as many pages as fill
``PAGE_BLOCK_BYTES`` of K (and the same of V). qwen2-7b's 16 KiB page
(16 tokens × 4 heads × 128 × bf16) gives 32 pages a step, opt-30b's
224 KiB page (56 heads) gives 2. Pages past a slot's length (or before
its window) are neither fetched nor computed: the loop runs
``ceil(live pages / pages)`` steps. Softmax runs in f32. A page is
fetched whole, so its KV heads must fill the pool's HBM tiles
(``pages_fit_tiles``); the model gathers instead where they do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

# K bytes one step of the paged kernel fetches per slot (the same of V);
# double-buffered K and V hold four times this in VMEM
PAGE_BLOCK_BYTES = 512 * 1024


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, scale: float, softcap,
                   window):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[0]
    k_start = ki * block_k
    visible = k_start < kv_len
    if window is not None:
        visible = jnp.logical_and(visible,
                                  k_start + block_k > kv_len - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
        k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, BK)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if window is not None:
            mask &= kpos > kv_len - 1 - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot(p.astype(v_ref.dtype), v_ref[0])
        acc_scr[...] = acc_scr[...] * corr + pv.astype(jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "window", "block_k",
                                             "interpret"))
def decode_attention_pallas(q, k, v, kv_len, *, softcap=None, window=None,
                            block_k: int = 512, interpret: bool = True):
    """q: (BKv, G, hd) — one query token, G = q heads per kv head;
    k/v: (BKv, Smax, hd); kv_len: (BKv,) live lengths (int32).
    Returns (BKv, G, hd)."""
    BKv, G, hd = q.shape
    Smax = k.shape[1]
    block_k = min(block_k, Smax)
    while Smax % block_k:
        block_k //= 2
    grid = (BKv, Smax // block_k)
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               scale=hd ** -0.5, softcap=softcap,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1,), lambda b, j: (b,)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BKv, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kv_len)


# ------------------------------------------------------------------- paged
def pages_fit_tiles(kv_heads: int, dtype) -> bool:
    """Whether the kernel can DMA a page of a pool with ``kv_heads`` KV
    heads (per device) in ``dtype``: XLA tiles a bf16 pool's (Kv, hd)
    rows by 2, 4 or 8, padding the rest of a tile, and a DMA slice must
    cover whole tiles. So bf16 needs 2, 4 or a multiple of 8 KV heads
    (v5e compiles: 1, 3, 6, 12 and 14 refused); f32 takes any count."""
    if jnp.dtype(dtype).itemsize >= 4:
        return True
    return kv_heads in (2, 4) or kv_heads % 8 == 0


def _paged_kernel(layer_ref, tbl_ref, len_ref, win_ref,
                  q_ref, kn_ref, vn_ref, row_head_ref, col_head_ref,
                  col_tok_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *,
                  pages: int, scale: float, softcap, windowed: bool):
    b = pl.program_id(0)
    MB = tbl_ref.shape[1]
    NB, bs = k_hbm.shape[1], k_hbm.shape[2]
    Kv, hd = kn_ref.shape[1], kn_ref.shape[2]
    R = pages * bs * Kv
    layer = layer_ref[0]
    n = len_ref[b]                       # tokens of slot b in the pool
    lo = jnp.maximum(n + 1 - win_ref[0], 0) if windowed else 0
    first = lo // bs                     # first page holding a visible token
    last = jnp.minimum((n + bs - 1) // bs, MB)
    nblk = jnp.maximum(last - first + pages - 1, 0) // pages

    @pl.when(b == 0)
    def _zero():
        # slots of a step's buffer past the slot's last page are not
        # fetched; keep them finite, since their zero probabilities still
        # multiply them
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(i, slot):
        base = first + i * pages
        for j in range(pages):
            # a table entry inside the live length is a page; the clamp
            # only keeps the DMA in bounds
            page = jnp.minimum(tbl_ref[b, jnp.minimum(base + j, MB - 1)],
                               NB - 1)
            yield base + j < last, (
                pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, j], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, j], sem.at[1, slot]))

    def fetch(i, slot):
        for live, pair in copies(i, slot):
            @pl.when(live)
            def _start():
                for c in pair:
                    c.start()

    def wait(i, slot):
        for live, pair in copies(i, slot):
            @pl.when(live)
            def _wait():
                for c in pair:
                    c.wait()

    q = q_ref[0]                                       # (Hp, hd)
    row_head = row_head_ref[...]                       # (Hp, 1)

    def scores(k):
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        return s

    # the new token opens the softmax: row c of kn is KV head c. Its
    # (Hp, Kv) products run in f32, where they are exact for bf16
    # inputs and a single KV head (a per-device shard) still lowers
    s = jax.lax.dot_general(q.astype(jnp.float32),
                            kn_ref[0].astype(jnp.float32),
                            (((1,), (1,)), ((), ()))) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    ok = row_head == jax.lax.broadcasted_iota(jnp.int32, (1, Kv), 1)
    ok = jnp.logical_and(ok, jnp.full((1, Kv), n, jnp.int32) < MB * bs)
    s = jnp.where(ok, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.dot(p, vn_ref[0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)

    @pl.when(nblk > 0)
    def _prime():
        fetch(0, 0)

    def step(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < nblk)
        def _next():
            fetch(i + 1, 1 - slot)

        wait(i, slot)
        k = kbuf.at[slot].reshape(R, hd)[...]
        v = vbuf.at[slot].reshape(R, hd)[...]
        s = scores(k)                                  # (Hp, R)
        t0 = (first + i * pages) * bs                  # block's first token
        tok = col_tok_ref[...]                         # (1, R)
        ok = jnp.logical_and(row_head == col_head_ref[...], tok < n - t0)
        if windowed:
            ok = jnp.logical_and(ok, tok >= lo - t0)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, nblk, step, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_call(q, k_new, v_new, k_pool, v_pool, block_table, lengths,
                layer, window, *, softcap, interpret):
    """The pallas_call over (possibly per-device) arrays; see
    ``paged_decode_attention_pallas``."""
    B, Hp, hd = q.shape
    L, NB, bs, Kv, _ = k_pool.shape
    MB = block_table.shape[1]
    g = Hp // Kv
    # pages a loop step fetches per slot: as many as fill
    # PAGE_BLOCK_BYTES, at least one, at most a table row
    page_bytes = bs * Kv * hd * k_pool.dtype.itemsize
    pages = max(1, min(MB, PAGE_BLOCK_BYTES // page_bytes))
    R = pages * bs * Kv
    rows = jnp.arange(R, dtype=jnp.int32)[None]
    windowed = window is not None
    win = jnp.reshape(jnp.asarray(window if windowed else 0, jnp.int32),
                      (1,))
    kernel = functools.partial(_paged_kernel, pages=pages,
                               scale=hd ** -0.5, softcap=softcap,
                               windowed=windowed)
    const = lambda *shape: pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))
    per_slot = lambda *shape: pl.BlockSpec((1,) + shape,
                                           lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[per_slot(Hp, hd), per_slot(Kv, hd), per_slot(Kv, hd),
                  const(Hp, 1), const(1, R), const(1, R),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot(Hp, hd),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, Kv, hd), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, Kv, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      block_table.astype(jnp.int32), lengths.astype(jnp.int32), win,
      q, k_new, v_new,
      (jnp.arange(Hp, dtype=jnp.int32) // g)[:, None], rows % Kv,
      rows // Kv, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret",
                                             "head_sharding"))
def paged_decode_attention_pallas(q, k_new, v_new, k_pool, v_pool,
                                  block_table, lengths, layer, window=None,
                                  *, softcap=None, interpret: bool = True,
                                  head_sharding=None):
    """Decode attention over a page pool read in place.

    q: (B, Hp, hd), query head h on KV head h // (Hp / Kv); k_new/v_new:
    (B, Kv, hd), the new token's K/V (attended at position
    ``lengths``); k_pool/v_pool: (L, NB, bs, Kv, hd); block_table
    (B, MB) int32, logical → physical page; lengths (B,) tokens of each
    slot already in the pool; layer: the pool's layer to read; window:
    None or a (traced) sliding-window width. Returns (B, Hp, hd).

    ``head_sharding`` (static NamedSharding of q over its head axis,
    DESIGN.md §16) runs the kernel under ``shard_map``: each device
    attends its own KV heads from its own slice of the pools, whole, and
    the output comes back head-sharded."""
    call = functools.partial(_paged_call, softcap=softcap,
                             interpret=interpret)
    if head_sharding is None:
        return call(q, k_new, v_new, k_pool, v_pool, block_table, lengths,
                    layer, window)
    ax = head_sharding.spec[1]
    heads, pool, rep = P(None, ax, None), P(None, None, None, ax, None), P()
    args = [q, k_new, v_new, k_pool, v_pool, block_table, lengths, layer]
    specs = [heads, heads, heads, pool, pool, rep, rep, rep]
    if window is not None:
        args.append(window)
        specs.append(rep)
    return jax.shard_map(
        lambda *a: call(*a[:8], a[8] if len(a) > 8 else None),
        mesh=head_sharding.mesh, in_specs=tuple(specs), out_specs=heads,
        check_vma=False)(*args)
