"""KV-cache backends behind a single ``CacheView`` seam (DESIGN.md §9).

The serving engine never touches cache buffers directly. All state lives
in a ``KVCacheBackend``:

  * ``ContiguousBackend`` — the classic layout: every batch slot owns
    ``max_seq`` contiguous positions of a stacked ``(L, B, Smax, Kv, hd)``
    buffer (decoder-only families: lm / ssm / hybrid).
  * ``PagedBackend``     — vLLM-style block tables over a physical page
    pool ``(L, num_blocks, block_size, Kv, hd)`` plus a ``BlockAllocator``
    free list. A slot reserves only the pages its session can actually
    use, so occupancy — not ``max_batch × max_seq`` — caps concurrency.
    LM family only (block tables have no SSM-state analog).
  * ``ShardedPagedBackend`` — the paged pool committed sharded on the
    KV-head axis over a tensor-parallel mesh (DESIGN.md §16): decode and
    restore-sink writes run as SPMD programs where each device touches
    only its own heads; page bookkeeping (allocator, block tables, CoW)
    is replicated structure, so it stays exactly the single-device code.
  * ``PagedEncDecBackend`` — the enc-dec pairing over pages: the decoder
    self-KV region rides the paged pool (same allocator/CoW machinery),
    while the cross context stays whole-object per slot — block tables
    have no analog for encoder state that never grows.
  * ``EncDecBackend``    — paired layout for enc-dec (whisper) models
    (DESIGN.md §11): a growing decoder self-KV region per slot (the
    contiguous machinery, keyed ``self_k``/``self_v``) PAIRED with
    whole-object per-slot cross state — ``cross_k``/``cross_v``
    ``(L, B, S_enc, H, hd)`` and a per-slot ``enc_len`` (B,) vector (the
    seed's scalar ``enc_len`` cannot batch sessions with different
    encoder lengths). Reservation/occupancy accounting is the
    contiguous slot model over decoder positions, so admission,
    back-pressure and PAUSED eviction work unchanged.

Consumers all go through a slot-bound ``CacheView`` handle:

    view.write_layer(row, k, v)   one restored layer (whole pages)
    view.write_kv(k, v, start)    stacked prefill KV at a token offset
    view.write_states(piece)      SSM / cross-attention whole objects
    view.gather_hist(hist)        restored-history KV for chunked prefill
    view.snapshot()               B=1 restorable dict for save_session_pause
    view.set_length(n)            live-length bookkeeping
    view.free()                   release the slot's pages (retire/evict)

``ViewSink`` adapts a ``CacheView`` to the restoration executor's
``RestoreSink`` protocol — the sink is layout-agnostic; the paged backend
lands restored layers as whole pages, the contiguous one as a donated
``dynamic_update_slice``. Decode runs through ``backend.decode``: the
paged step reads the pool in place by block table — on the TPU through
the Pallas kernel in ``kernels/decode_attention.py``, which fetches only
each slot's live pages — and writes the new tokens with one scatter
after its layer scan, into the donated pool (see
``transformer.lm_decode_step_paged``).

Greedy equivalence: off the TPU the paged step gathers the logical
layout and runs the contiguous step's attention; masked probabilities
are exactly zero past the live length, so it is byte-identical to the
contiguous layout (tested in tests/test_paged.py).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.restoration import RestoreSink, s_bucket
from repro.distributed import tp as tp_lib
from repro.models.model import Model


def _colocate(val, buf):
    """Bring a committed multi-device array (the SPMD restoration
    projection's output under tensor parallelism) onto the target
    buffer's device before a single-device donated update. This is the
    one deliberate gather on the TP restore path: it fires only for
    backends whose decode is unsharded (contiguous / hybrid), where the
    projected heads must land in one place anyway. Uncommitted and
    single-device values pass through untouched."""
    if isinstance(val, jax.Array) and len(val.sharding.device_set) > 1 \
            and len(buf.sharding.device_set) == 1:
        return jax.device_put(val, next(iter(buf.sharding.device_set)))
    return val


@dataclasses.dataclass
class OccupancyStats:
    """Gauges for EngineMetrics (and so ``/metrics``): how much of the
    reserved cache capacity holds live tokens."""

    live_tokens: int            # tokens of occupied slots (sum of lengths)
    reserved_tokens: int        # capacity handed out to occupied slots
    capacity_tokens: int        # total backend capacity
    free_blocks: int            # paged: free pages; contiguous: free slots

    @property
    def utilization(self) -> float:
        """live / reserved — 1.0 means no internal fragmentation."""
        return (self.live_tokens / self.reserved_tokens
                if self.reserved_tokens else 0.0)

    @property
    def fragmentation(self) -> float:
        return 1.0 - self.utilization if self.reserved_tokens else 0.0


class BlockAllocator:
    """Refcounted LIFO free list over ``num_blocks`` physical pages (LIFO
    so pages freed by an eviction are immediately reused — cache-warm on
    real hardware, and deterministic for the reuse tests).

    Pages are reference counted so several block-table rows (and the
    prefix index) may map the same physical page: ``alloc`` hands a page
    out at refcount 1, ``incref`` adds a holder, and ``free`` drops one
    holder per page — the page returns to the free list only when its
    last holder releases it. Freeing a page that has no live holders
    raises instead of silently corrupting the free list (a double free
    used to append the page twice, letting the allocator grant the same
    physical page to two sessions)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref: List[int] = [0] * num_blocks

    @property
    def free_count(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages, or None when the pool cannot satisfy the request
        (callers treat None as admission backpressure — never a partial
        grant)."""
        if n < 0 or n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        for b in taken:
            self._ref[b] = 1
        return taken

    def incref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise RuntimeError(
                f"incref of unallocated page {block} (refcount "
                f"{self._ref[block]}) — sharing a page that is already "
                f"on the free list")
        self._ref[block] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one holder per page; a page with no remaining holders
        returns to the free list (reversed, preserving LIFO reuse
        order for the common unshared case)."""
        for b in reversed(list(blocks)):
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"double free of page {b}: page is already free "
                    f"(refcount {self._ref[b]})")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)


# -------------------------------------------------------------------- views
class CacheView:
    """Slot-bound handle; the only way engine/restoration/save code
    touches cache state."""

    def write_layer(self, row: int, k, v, start: int = 0) -> None:
        """One attention layer's restored KV at tokens [start, start+n);
        k, v: (1, n, Kv, hd); row indexes the stacked-KV buffer. A
        nonzero ``start`` is the restore-skip path: tokens [0, start)
        are already resident via a shared prefix (DESIGN.md §12)."""
        raise NotImplementedError

    def write_layer_group(self, rows: Sequence[int], k, v,
                          start: int = 0) -> None:
        """A whole restoration group's KV in one scatter; rows are
        stacked-KV buffer rows, k/v: (G, 1, n, Kv, hd). Default falls
        back to per-layer writes; both backends override with a single
        donated device call (DESIGN.md §10)."""
        for g, row in enumerate(rows):
            self.write_layer(row, k[g], v[g], start)

    def write_kv(self, k, v, start: int) -> None:
        """Stacked prefill KV (L, 1, n, Kv, hd) at token offset start."""
        raise NotImplementedError

    def write_states(self, piece: dict) -> None:
        """Whole-object pieces: conv/ssm states, cross KV, enc_len."""
        raise NotImplementedError

    def gather_hist(self, hist: int):
        """Restored-history KV, stacked (L, 1, hist, Kv, hd) pair."""
        raise NotImplementedError

    def cross_state(self):
        """Enc-dec only: the slot's live cross context — (cross_k,
        cross_v) stacked (L, 1, enc_len, H, hd) plus enc_len."""
        raise NotImplementedError

    def snapshot(self) -> dict:
        """B=1 restorable dict (what ``save_session_pause`` dumps); KV
        buffers cover at least the slot's live length."""
        raise NotImplementedError

    def set_length(self, n: int) -> None:
        raise NotImplementedError

    def free(self) -> None:
        """Release the slot's reserved capacity (retire / mid-stream
        eviction). The view must not be used afterwards."""
        raise NotImplementedError


class ViewSink(RestoreSink):
    """Layout-agnostic RestoreSink: every restored piece goes through the
    CacheView, so the executor neither knows nor cares whether the slot
    is contiguous or paged (pages land whole)."""

    def __init__(self, view: CacheView):
        self.view = view

    def put_kv(self, row, k, v, start=0):
        self.view.write_layer(row, k, v, start)

    def put_kv_group(self, rows, k, v, start=0):
        self.view.write_layer_group(rows, k, v, start)

    def put_states(self, conv, ssm):
        self.view.write_states({"conv": conv, "ssm": ssm})

    def put_cross(self, ck, cv, enc_len):
        self.view.write_states({"cross_k": ck, "cross_v": cv,
                                "enc_len": jnp.asarray(enc_len, jnp.int32)})

    def finish(self, n_tokens):
        self.view.set_length(n_tokens)


# ----------------------------------------------------------------- backends
class KVCacheBackend:
    """Owns all decode-cache state for the engine's ``max_batch`` slots."""

    name = "backend"

    def view(self, slot: int) -> CacheView:
        raise NotImplementedError

    def can_reserve(self, n_tokens: int) -> bool:
        """Admission backpressure check: could a slot hold ``n_tokens``?"""
        raise NotImplementedError

    def reserve(self, slot: int, n_tokens: int) -> bool:
        """Bind capacity for up to ``n_tokens`` to ``slot``. False means
        the pool is exhausted (the caller must requeue, not proceed)."""
        raise NotImplementedError

    def free_slot(self, slot: int) -> None:
        raise NotImplementedError

    def decode(self, params, tokens):
        """One batched decode step; advances every slot's length by one.
        Returns (logits, per-layer hidden states)."""
        raise NotImplementedError

    def get_lengths(self) -> np.ndarray:
        raise NotImplementedError

    @property
    def kv_token_bytes(self) -> int:
        """K+V bytes one cached token holds over every layer, where decode
        attention reads only the live history (the paged backends); 0
        where it reads whole buffers."""
        return 0

    def set_lengths(self, lengths: np.ndarray) -> None:
        raise NotImplementedError

    def set_length(self, slot: int, n: int) -> None:
        raise NotImplementedError

    def occupancy(self) -> OccupancyStats:
        raise NotImplementedError

    def device_occupancy(self) -> List[dict]:
        """Per-device gauges for EngineMetrics (DESIGN.md §16): one row
        per mesh device — single-device backends report one row. Keys:
        ``device``, ``free_pages``, ``occupancy_pct`` (reserved capacity
        in use), ``util_pct`` (live tokens / reserved capacity)."""
        occ = self.occupancy()
        pct = int(round(100.0 * occ.reserved_tokens
                        / max(occ.capacity_tokens, 1)))
        return [{"device": 0, "free_pages": int(occ.free_blocks),
                 "occupancy_pct": pct,
                 "util_pct": int(round(100.0 * occ.utilization))}]


# ------------------------------------------------------------- contiguous
class _ContiguousView(CacheView):
    def __init__(self, backend: "ContiguousBackend", slot: int):
        self.b = backend
        self.slot = slot

    def write_layer(self, row, k, v, start=0):
        b = self.b
        k_name, v_name = b.model.adapter.kv_names
        row = jnp.asarray(row)              # traced: no recompile per row
        slot = jnp.asarray(self.slot)
        for name, val in ((k_name, k), (v_name, v)):
            buf = b.cache[name]
            val = jnp.asarray(_colocate(val, buf), buf.dtype)[None]
            b.cache[name] = b._slot_update(buf, val, row, slot,
                                           jnp.asarray(start))

    def write_layer_group(self, rows, k, v, start=0):
        b = self.b
        k_name, v_name = b.model.adapter.kv_names
        kbuf, vbuf = b.cache[k_name], b.cache[v_name]
        b.cache[k_name], b.cache[v_name] = b._group_update(
            kbuf, vbuf,
            jnp.asarray(_colocate(k, kbuf), kbuf.dtype)[:, 0],  # (G,n,Kv,hd)
            jnp.asarray(_colocate(v, vbuf), vbuf.dtype)[:, 0],
            jnp.asarray(np.asarray(rows, np.int32)),
            jnp.asarray(self.slot), jnp.asarray(start))

    def write_kv(self, k, v, start):
        b = self.b
        k_name, v_name = b.model.adapter.kv_names
        for name, val in ((k_name, k), (v_name, v)):
            b.cache[name] = jax.lax.dynamic_update_slice(
                b.cache[name], val.astype(b.cache[name].dtype),
                (0, self.slot, start, 0, 0))

    def write_states(self, piece):
        # conv/ssm recurrent states only — enc-dec cross state lives in
        # _EncDecView (this backend is decoder-only: lm / ssm / hybrid)
        b, slot = self.b, self.slot
        for key, val in piece.items():
            buf = b.cache.get(key)
            if buf is None or key not in ("conv", "ssm"):
                continue
            val = jnp.asarray(val, buf.dtype)
            bdim = buf.ndim - val.ndim + 1  # batch dim position
            b.cache[key] = jax.lax.dynamic_update_slice(
                buf, val, (0,) * (bdim - 1) + (slot,)
                + (0,) * (buf.ndim - bdim))

    def gather_hist(self, hist):
        k_name, v_name = self.b.model.adapter.kv_names
        i = self.slot
        return (self.b.cache[k_name][:, i:i + 1, :hist],
                self.b.cache[v_name][:, i:i + 1, :hist])

    def snapshot(self):
        b, i = self.b, self.slot
        cache_slice = {k: (v[:, i:i + 1] if k in
                           ("k", "v", "attn_k", "attn_v") else v)
                       for k, v in b.cache.items()
                       if k not in ("lengths", "enc_len")}
        if b.model.kind in ("ssm", "hybrid"):
            cache_slice["conv"] = b._slot_state(b.cache["conv"], i)
            cache_slice["ssm"] = b._slot_state(b.cache["ssm"], i)
        return cache_slice

    def set_length(self, n):
        self.b.set_length(self.slot, n)

    def free(self):
        self.b.free_slot(self.slot)


class ContiguousBackend(KVCacheBackend):
    """The seed layout: ``max_seq`` contiguous positions per slot. Every
    model family; a slot reservation always costs ``max_seq`` capacity
    regardless of the session's true length."""

    name = "contiguous"

    def __init__(self, model: Model, max_batch: int, max_seq: int):
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache = self._make_cache()
        self._reserved = [0] * max_batch
        self._decode_fn = jax.jit(model.decode_step_full)
        # donated so XLA updates the stacked KV buffer in place — a
        # per-layer restore write must not copy the whole (L,B,S,H,hd)
        # cache (retraces only per distinct restored length n). ``start``
        # is traced: restore-skip lands a suffix at the divergence token
        # without a new compile per offset
        self._slot_update = jax.jit(
            lambda buf, val, row, slot, start: jax.lax.dynamic_update_slice(
                buf, val, (row, slot, start, 0, 0)),
            donate_argnums=(0,))
        # grouped restore write: a whole projection group's K and V land
        # in one donated scatter (rows and start traced, so group
        # membership / token offset never retrace; retraces only per
        # distinct restored length n). Scatter grid rather than basic
        # slicing because the token offset is traced.
        def _gupd(kbuf, vbuf, kval, vval, rows, slot, start):
            pos = start + jnp.arange(kval.shape[1])
            return (kbuf.at[rows[:, None], slot, pos[None, :]].set(kval),
                    vbuf.at[rows[:, None], slot, pos[None, :]].set(vval))
        self._group_update = jax.jit(_gupd, donate_argnums=(0, 1))

    def _make_cache(self):
        return self.model.init_cache(self.max_batch, self.max_seq)

    def _slot_state(self, buf, slot):
        """Extract the batch=1 slice of a (…, B, …) state tensor."""
        if self.model.kind == "ssm":
            return buf[:, slot:slot + 1]
        return buf[:, :, slot:slot + 1]

    def view(self, slot):
        return _ContiguousView(self, slot)

    def can_reserve(self, n_tokens):
        # a free slot always implies a full max_seq reservation; sessions
        # longer than max_seq were never servable under this layout
        return True

    def reserve(self, slot, n_tokens):
        self._reserved[slot] = self.max_seq
        return True

    def free_slot(self, slot):
        self._reserved[slot] = 0

    def decode(self, params, tokens):
        lg, self.cache, hidden = self._decode_fn(params, self.cache, tokens)
        return lg, hidden

    def get_lengths(self):
        return np.array(self.cache["lengths"], copy=True)

    def set_lengths(self, lengths):
        self.cache["lengths"] = jnp.asarray(lengths, jnp.int32)

    def set_length(self, slot, n):
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(n)

    def occupancy(self):
        lengths = np.asarray(self.cache["lengths"])
        live = int(sum(int(lengths[i]) for i, r in enumerate(self._reserved)
                       if r))
        reserved = int(sum(self._reserved))
        free_slots = sum(1 for r in self._reserved if not r)
        return OccupancyStats(live, reserved, self.max_batch * self.max_seq,
                              free_slots)


# ----------------------------------------------------------------- encdec
class _CrossStateMixin:
    """Cross-context handling shared by both enc-dec views: the cross
    buffers are per-slot whole objects regardless of how the decoder
    self-KV region is laid out (contiguous slots or pages). The backend
    provides ``cache['cross_k'/'cross_v'/'enc_len']``, ``enc_seq``,
    ``enc_len_np`` and the donated ``_cross_update``."""

    def write_states(self, piece):
        b, slot = self.b, self.slot
        for key, val in piece.items():
            if key in ("cross_k", "cross_v"):
                buf = b.cache[key]
                val = jnp.asarray(val, buf.dtype)
                n = val.shape[2]
                if n > b.enc_seq:
                    # admission gates count decoder positions only — an
                    # oversized encoder context must fail loudly here,
                    # not as an opaque shape error inside the update
                    raise ValueError(
                        f"encoder context of {n} frames "
                        f"exceeds the backend's enc_seq={b.enc_seq}; "
                        f"raise --enc-seq (or InferenceEngine(enc_seq=))")
                # pad the encoder dim to its power-of-two bucket (same
                # rule as the restoration projections) so varied audio
                # lengths share one compiled donated update; the zero
                # tail sits beyond enc_len and is masked everywhere
                cap = min(s_bucket(max(n, 1)), b.enc_seq)
                if cap > n:
                    val = jnp.pad(val, ((0, 0), (0, 0), (0, cap - n),
                                        (0, 0), (0, 0)))
                b.cache[key] = b._cross_update(buf, val,
                                               jnp.asarray(slot))
            elif key == "enc_len":
                n = int(val)
                b.cache["enc_len"] = b.cache["enc_len"].at[slot].set(n)
                b.enc_len_np[slot] = n

    def cross_state(self):
        b, i = self.b, self.slot
        n = int(b.enc_len_np[i])
        return (b.cache["cross_k"][:, i:i + 1, :n],
                b.cache["cross_v"][:, i:i + 1, :n], n)


class _EncDecView(_CrossStateMixin, _ContiguousView):
    """Self-KV writes/gathers ride the contiguous machinery (keys
    ``self_k``/``self_v`` via the adapter); cross state is per-slot."""

    def snapshot(self):
        # self-KV only: the cross context restores from the session's
        # persisted encoder blob ('enc'), saved at first prefill — a
        # pause never has to dump the (large) cross buffers
        b, i = self.b, self.slot
        return {"self_k": b.cache["self_k"][:, i:i + 1],
                "self_v": b.cache["self_v"][:, i:i + 1]}

    def free(self):
        b, i = self.b, self.slot
        b.enc_len_np[i] = 0
        b.cache["enc_len"] = b.cache["enc_len"].at[i].set(0)
        super().free()


class EncDecBackend(ContiguousBackend):
    """Paired self/cross cache for enc-dec models (DESIGN.md §11).

    The decoder self-KV region is the contiguous layout over ``max_seq``
    decoder positions per slot. The cross context is whole-object
    per-slot state: ``cross_k``/``cross_v`` hold up to ``enc_seq``
    encoder positions, with a per-slot ``enc_len`` (B,) so sessions with
    different encoder lengths batch together (the seed cache's scalar
    ``enc_len`` could not). Decode runs the family decode step — the
    (B,) ``enc_len`` broadcasts through the cross-attention mask."""

    name = "encdec"

    def __init__(self, model: Model, max_batch: int, max_seq: int, *,
                 enc_seq: Optional[int] = None):
        if model.kind != "encdec":
            raise NotImplementedError(
                f"the encdec KV cache requires an encoder-decoder model; "
                f"{model.cfg.name} is {model.kind!r}")
        self.enc_seq = int(enc_seq or max_seq)
        super().__init__(model, max_batch, max_seq)
        self.enc_len_np = np.zeros((max_batch,), np.int64)
        # donated in-place cross write (slot traced): the cross buffers
        # are the backend's largest tensors at real whisper scale, so a
        # first-residency prefill / restore must not copy them whole —
        # same rule as the self-KV _slot_update above; retraces only per
        # distinct encoder length
        self._cross_update = jax.jit(
            lambda buf, val, slot: jax.lax.dynamic_update_slice(
                buf, val, (0, slot, 0, 0, 0)),
            donate_argnums=(0,))

    def _make_cache(self):
        c = self.model.cfg
        L, H, hd = c.n_layers, c.n_heads, c.head_dim_

        def kv(S):
            return jnp.zeros((L, self.max_batch, S, H, hd),
                             self.model.dtype)

        return {"self_k": kv(self.max_seq), "self_v": kv(self.max_seq),
                "cross_k": kv(self.enc_seq), "cross_v": kv(self.enc_seq),
                "enc_len": jnp.zeros((self.max_batch,), jnp.int32),
                "lengths": jnp.zeros((self.max_batch,), jnp.int32)}

    def view(self, slot):
        return _EncDecView(self, slot)


# ------------------------------------------------------------------ paged
class _PagedView(CacheView):
    def __init__(self, backend: "PagedBackend", slot: int):
        self.b = backend
        self.slot = slot

    def _addr(self, positions: np.ndarray):
        """(physical block ids, in-block offsets) for logical positions."""
        b = self.b
        row = b.table_np[self.slot]
        return (jnp.asarray(row[positions // b.block_size]),
                jnp.asarray(positions % b.block_size))

    def write_layer(self, row, k, v, start=0):
        b = self.b
        n = k.shape[1]
        positions = start + np.arange(n)
        b._ensure_private(self.slot, positions // b.block_size)
        blk, off = self._addr(positions)
        row = jnp.asarray(row)
        for name, val in (("k_pool", k), ("v_pool", v)):
            pool = b.cache[name]
            val = b._place_kv(jnp.asarray(val, pool.dtype)[0], 1)  # (n,Kv,hd)
            b.cache[name] = b._write_layer(pool, val, row, blk, off)

    def write_layer_group(self, rows, k, v, start=0):
        b = self.b
        n = k.shape[2]
        positions = start + np.arange(n)
        b._ensure_private(self.slot, positions // b.block_size)
        blk, off = self._addr(positions)
        kp, vp = b.cache["k_pool"], b.cache["v_pool"]
        b.cache["k_pool"], b.cache["v_pool"] = b._write_group(
            kp, vp,
            b._place_kv(jnp.asarray(k, kp.dtype)[:, 0], 2),  # (G, n, Kv, hd)
            b._place_kv(jnp.asarray(v, vp.dtype)[:, 0], 2),
            jnp.asarray(np.asarray(rows, np.int32)), blk, off)

    def write_kv(self, k, v, start):
        b = self.b
        n = k.shape[2]
        positions = start + np.arange(n)
        b._ensure_private(self.slot, positions // b.block_size)
        blk, off = self._addr(positions)
        for name, val in (("k_pool", k), ("v_pool", v)):
            pool = b.cache[name]
            # (L, n, Kv, hd) lands at [:, blk[i], off[i]] per token
            val = b._place_kv(
                jnp.asarray(val, pool.dtype)[:, 0], 2)
            b.cache[name] = pool.at[:, blk, off].set(val)

    def write_states(self, piece):
        raise NotImplementedError(
            "the paged backend holds attention-history KV only; SSM "
            "state has no block-table analog — use backend='contiguous' "
            "for ssm/hybrid (enc-dec cross state pages via the "
            "paged-encdec pairing)")

    def gather_hist(self, hist):
        # _finish_gather is the sharded backend's seam back into
        # unsharded code: the gathered history feeds the replicated
        # prefill program, so its head shards are replicated here
        # (identity on the single-device backend)
        b = self.b
        nb = -(-hist // b.block_size)
        blocks = jnp.asarray(b.table_np[self.slot][:nb])
        k = b.cache["k_pool"][:, blocks]          # (L, nb, bs, Kv, hd)
        v = b.cache["v_pool"][:, blocks]
        L = k.shape[0]
        shp = (L, 1, nb * b.block_size) + k.shape[3:]
        return (b._finish_gather(k.reshape(shp)[:, :, :hist]),
                b._finish_gather(v.reshape(shp)[:, :, :hist]))

    def snapshot(self):
        b = self.b
        k_name, v_name = b.model.adapter.kv_names
        blocks = jnp.asarray(b.slot_blocks[self.slot], jnp.int32)
        k = b.cache["k_pool"][:, blocks]
        v = b.cache["v_pool"][:, blocks]
        L = k.shape[0]
        shp = (L, 1, len(b.slot_blocks[self.slot]) * b.block_size) \
            + k.shape[3:]
        return {k_name: b._finish_gather(k.reshape(shp)),
                v_name: b._finish_gather(v.reshape(shp))}

    def set_length(self, n):
        self.b.set_length(self.slot, n)

    def free(self):
        self.b.free_slot(self.slot)


class PagedBackend(KVCacheBackend):
    """Block-table paged KV cache (ROADMAP "paged KV cache").

    Physical pages ``(L, num_blocks, block_size, Kv, hd)`` are shared by
    all slots; ``block_table[slot, j]`` maps a slot's logical page *j* to
    a physical page (entries == ``num_blocks`` are unallocated
    sentinels: decode-step scatter drops them, gathers clamp them and the
    attention mask zeroes whatever they alias). Reservations are made in
    whole pages for the session's worst-case final length, so admission
    is bounded by actual need, not ``max_batch × max_seq``.
    """

    name = "paged"

    def __init__(self, model: Model, max_batch: int, max_seq: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None):
        if not model.adapter.supports_paged:
            raise NotImplementedError(
                f"paged KV cache requires an attention-history model "
                f"(lm, or enc-dec decoder self-KV); {model.cfg.name} "
                f"is {model.kind!r}")
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.block_size = block_size
        self.blocks_per_seq = -(-max_seq // block_size)
        self.num_blocks = (max_batch * self.blocks_per_seq
                           if num_blocks is None else num_blocks)
        self.cache = model.init_paged_cache(max_batch, self.num_blocks,
                                            block_size, self.blocks_per_seq)
        self.table_np = np.asarray(self.cache["block_table"]).copy()
        self.allocator = BlockAllocator(self.num_blocks)
        self.slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        # set by the engine when --prefix-sharing is on: pages held by the
        # index are reclaimable under pressure (see _alloc_pages)
        self.prefix_index = None
        self.cow_copies = 0
        # the cache is donated: the step reads the pools in place and
        # writes the new tokens into them with one scatter, so it holds
        # one copy of the pool, not two
        self._decode_fn = jax.jit(model.decode_step_paged,
                                  donate_argnums=(1,))
        # donated in-place page scatter, retraced per restored length n
        self._write_layer = jax.jit(
            lambda pool, val, row, blk, off:
            pool.at[row, blk, off].set(val),
            donate_argnums=(0,))
        # grouped restore write: every member layer's whole pages land
        # in one donated scatter (rows (G,) × token addresses (n,)
        # broadcast to a (G, n) scatter grid)
        self._write_group = jax.jit(
            lambda kp, vp, kval, vval, rows, blk, off:
            (kp.at[rows[:, None], blk[None, :], off[None, :]].set(kval),
             vp.at[rows[:, None], blk[None, :], off[None, :]].set(vval)),
            donate_argnums=(0, 1))
        # copy-on-write page clone: one physical page (all layers) copied
        # inside the donated pool update; dst/src traced so divergence at
        # any page never retraces
        self._copy_page = jax.jit(
            lambda pool, dst, src: pool.at[:, dst].set(pool[:, src]),
            donate_argnums=(0,))

    def _push_table(self) -> None:
        self.cache["block_table"] = jnp.asarray(self.table_np)

    def _finish_gather(self, x):
        """Seam for host-bound / unsharded consumers of pool gathers
        (chunked-prefill history, pause snapshots). Identity here; the
        sharded backend replicates the head shards across the mesh."""
        return x

    def _place_kv(self, val, kv_axis: int):
        """Placement seam for values entering the pool. Identity here;
        the sharded backend reshards them to the pool's head sharding —
        prefill KV arrives replicated from the replicated prefill program
        (restored KV from the SPMD projection is already head-sharded,
        so its device_put is a no-op)."""
        return val

    def view(self, slot):
        return _PagedView(self, slot)

    # ---------------------------------------------- CoW page sharing
    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocator grant, spilling LRU prefix-index pages on shortfall
        (index-held pages are a cache, never a reservation)."""
        got = self.allocator.alloc(n)
        if got is None and self.prefix_index is not None:
            short = n - self.allocator.free_count
            if self.prefix_index.release(short) > 0:
                got = self.allocator.alloc(n)
        return got

    def _ensure_private(self, slot: int, logical_pages) -> None:
        """CoW barrier: every listed logical page of ``slot`` that maps a
        shared physical page (refcount > 1) is copied to a fresh private
        page before the caller writes through it. Copies only the pages
        actually written — the rest of the prefix stays shared."""
        blks = self.slot_blocks[slot]
        touched = False
        for lp in sorted(set(int(p) for p in np.atleast_1d(logical_pages))):
            if lp >= len(blks) or self.allocator.refcount(blks[lp]) <= 1:
                continue
            fresh = self._alloc_pages(1)
            if fresh is None:
                raise RuntimeError(
                    "page pool exhausted during copy-on-write divergence "
                    "(no free page to privatize a shared page); raise "
                    "cache_blocks or lower concurrency")
            dst = fresh[0]
            src = blks[lp]
            d, s = jnp.asarray(dst), jnp.asarray(src)
            for name in ("k_pool", "v_pool"):
                self.cache[name] = self._copy_page(self.cache[name], d, s)
            self.allocator.free([src])          # drop this slot's hold
            blks[lp] = dst
            self.table_np[slot, lp] = dst
            self.cow_copies += 1
            touched = True
        if touched:
            self._push_table()

    def adopt_shared(self, slot: int, blocks: Sequence[int], *,
                     owned: bool = False) -> None:
        """Map an already-populated shared page run as the slot's logical
        prefix (prefix-index hit or fork adoption). ``owned=False``
        increfs each page (the donor keeps its hold); ``owned=True``
        transfers holds that the caller already owns (parked fork pages).
        Must run before ``reserve`` tops the row up with private pages."""
        if self.slot_blocks[slot]:
            raise RuntimeError(f"adopt_shared on a non-empty slot {slot}")
        blocks = [int(b) for b in blocks]
        if not owned:
            for b in blocks:
                self.allocator.incref(b)
        self.slot_blocks[slot] = list(blocks)
        row = self.table_np[slot]
        row[:] = self.num_blocks
        row[:len(blocks)] = blocks
        self._push_table()

    def release_blocks(self, blocks: Sequence[int]) -> None:
        """Drop caller-owned holds not bound to any slot (e.g. parked
        fork pages that will never be adopted)."""
        self.allocator.free(list(blocks))

    def shared_page_stats(self):
        """(shared, private) physical page counts for the gauges: a page
        is shared when more than one holder maps it."""
        shared = private = 0
        for b in range(self.num_blocks):
            r = self.allocator.refcount(b)
            if r > 1:
                shared += 1
            elif r == 1:
                private += 1
        return shared, private

    def _blocks_needed(self, n_tokens: int) -> int:
        need = max(-(-max(n_tokens, 1) // self.block_size), 1)
        # a session whose worst case exceeds max_seq (or the whole pool)
        # gets at most one full table row — matching the contiguous
        # layout, where overflow decode writes past the reservation are
        # silently dropped rather than crashing or wedging admission
        return min(need, self.blocks_per_seq, self.num_blocks)

    def can_reserve(self, n_tokens):
        avail = self.allocator.free_count
        if self.prefix_index is not None:
            avail += self.prefix_index.releasable()
        return self._blocks_needed(n_tokens) <= avail

    def reserve(self, slot, n_tokens):
        need = self._blocks_needed(n_tokens)
        have = self.slot_blocks[slot]
        if len(have) >= need:
            return True
        blocks = self._alloc_pages(need - len(have))
        if blocks is None:
            return False
        have.extend(blocks)
        row = self.table_np[slot]
        row[:] = self.num_blocks
        row[:len(have)] = have
        self._push_table()
        return True

    def free_slot(self, slot):
        self.allocator.free(self.slot_blocks[slot])
        self.slot_blocks[slot] = []
        self.table_np[slot, :] = self.num_blocks
        self._push_table()
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(0)

    def decode(self, params, tokens):
        # CoW barrier before the batched scatter: the step writes one
        # token at lengths[slot] for EVERY occupied slot (the engine
        # rolls scratch writes back) — each slot's frontier page must be
        # private or the write would leak into a sibling's shared prefix
        lengths = np.asarray(self.cache["lengths"])
        for slot, blks in enumerate(self.slot_blocks):
            if blks:
                self._ensure_private(slot, [int(lengths[slot])
                                            // self.block_size])
        lg, self.cache, hidden = self._decode_fn(params, self.cache, tokens)
        return lg, hidden

    def get_lengths(self):
        return np.array(self.cache["lengths"], copy=True)

    @property
    def kv_token_bytes(self):
        L, _, _, Kv, hd = self.cache["k_pool"].shape
        return 2 * L * Kv * hd * self.cache["k_pool"].dtype.itemsize

    def set_lengths(self, lengths):
        self.cache["lengths"] = jnp.asarray(lengths, jnp.int32)

    def set_length(self, slot, n):
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(n)

    def occupancy(self):
        lengths = np.asarray(self.cache["lengths"])
        live = int(sum(int(lengths[i])
                       for i, blks in enumerate(self.slot_blocks) if blks))
        reserved = sum(len(b) for b in self.slot_blocks) * self.block_size
        return OccupancyStats(live, reserved,
                              self.num_blocks * self.block_size,
                              self.allocator.free_count)


# -------------------------------------------------------------- sharded
class DeviceAllocatorView:
    """Read-only per-device window onto the shared ``BlockAllocator``.

    Head-sharding replicates the page STRUCTURE: every mesh device holds
    the same page ids (1/tp of each page's bytes), so the free list and
    refcounts are common state — a per-device allocator would desync the
    block tables. The view therefore proxies the shared counts and
    scales only byte-denominated gauges by its shard."""

    def __init__(self, backend: "ShardedPagedBackend", device: int):
        self.b = backend
        self.device = device

    @property
    def num_blocks(self) -> int:
        return self.b.allocator.num_blocks

    @property
    def free_count(self) -> int:
        return self.b.allocator.free_count

    def refcount(self, block: int) -> int:
        return self.b.allocator.refcount(block)

    def pool_bytes(self) -> int:
        total = sum(int(self.b.cache[n].nbytes)
                    for n in ("k_pool", "v_pool"))
        return total // max(self.b.shards, 1)


class ShardedPagedBackend(PagedBackend):
    """Tensor-parallel paged backend (DESIGN.md §16).

    The physical page pool ``(L, NB, bs, Kv, hd)`` is committed sharded
    on the KV-head axis (3) over the TP mesh; block tables and lengths
    are replicated. Every jitted cache update — decode's token scatter,
    the restore sink's grouped page write, the CoW page clone — indexes
    only layer/page/offset axes, so under SPMD each device writes its
    own head slice with zero cross-device traffic; decode's one
    collective is the all-gather at the output-projection seam
    (``tp.logits_seam``). Allocator / table / CoW bookkeeping is Python
    over replicated structure: exactly the single-device code."""

    name = "paged-tp"

    def __init__(self, model: Model, max_batch: int, max_seq: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 tp_ctx: Optional[tp_lib.TPContext] = None):
        self.tp = tp_ctx if tp_ctx is not None else tp_lib.TPContext(1)
        self.shards = self.tp.tp if self.tp.spmd else 1
        if self.tp.spmd:
            self.tp.validate_heads(model.cfg.n_kv_heads)
        super().__init__(model, max_batch, max_seq, block_size=block_size,
                         num_blocks=num_blocks)
        if self.tp.spmd:
            sh = self.tp.kv_sharding(5, 3)
            for name in ("k_pool", "v_pool"):
                self.cache[name] = jax.device_put(self.cache[name], sh)
            for name in ("block_table", "lengths"):
                self.cache[name] = self.tp.replicate(self.cache[name])

    def _push_table(self):
        self.cache["block_table"] = self.tp.replicate(
            jnp.asarray(self.table_np))

    def set_lengths(self, lengths):
        self.cache["lengths"] = self.tp.replicate(
            jnp.asarray(lengths, jnp.int32))

    def _finish_gather(self, x):
        return self.tp.replicate(x)

    def _place_kv(self, val, kv_axis):
        return self.tp.shard_kv(val, kv_axis)

    def decode(self, params, tokens):
        # the seam context makes the jitted step constrain the pool
        # sharded and the attention output replicated — the same traced
        # program as tp=1 when the context is inactive
        with tp_lib.tp_seam(self.tp):
            return super().decode(params, tokens)

    def device_views(self) -> List[DeviceAllocatorView]:
        return [DeviceAllocatorView(self, d) for d in range(self.shards)]

    def device_occupancy(self):
        base = super().device_occupancy()[0]
        rows = []
        for view in self.device_views():
            row = dict(base)
            row["device"] = view.device
            row["pool_bytes"] = view.pool_bytes()
            rows.append(row)
        return rows


# -------------------------------------------------------- paged enc-dec
class _PagedEncDecView(_CrossStateMixin, _PagedView):
    """Decoder self-KV pages through the pool (keys ``self_k``/``self_v``
    in snapshots via the adapter); cross state is whole-object per slot,
    exactly the contiguous enc-dec pairing."""


class PagedEncDecBackend(PagedBackend):
    """Paged decoder self-KV + whole-object cross state (ROADMAP "paged
    KV for the enc-dec family").

    The self-KV region — the part that grows with decoded tokens —
    rides the page pool, so admission is bounded by actual decoder need
    and PAUSED eviction frees pages. The cross context never grows after
    the encoder runs, so it keeps the per-slot ``cross_k``/``cross_v``
    buffers and (B,) ``enc_len`` of ``EncDecBackend`` — there is no
    block-table analog for state with no append frontier."""

    name = "paged-encdec"

    def __init__(self, model: Model, max_batch: int, max_seq: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 enc_seq: Optional[int] = None):
        if model.kind != "encdec":
            raise NotImplementedError(
                f"the paged enc-dec KV cache requires an encoder-decoder "
                f"model; {model.cfg.name} is {model.kind!r}")
        self.enc_seq = int(enc_seq or max_seq)
        super().__init__(model, max_batch, max_seq, block_size=block_size,
                         num_blocks=num_blocks)
        c = model.cfg
        kv = jnp.zeros((c.n_layers, max_batch, self.enc_seq, c.n_heads,
                        c.head_dim_), model.dtype)
        self.cache["cross_k"] = kv
        self.cache["cross_v"] = jnp.zeros_like(kv)
        self.cache["enc_len"] = jnp.zeros((max_batch,), jnp.int32)
        self.enc_len_np = np.zeros((max_batch,), np.int64)
        # donated in-place cross write (slot traced) — see EncDecBackend
        self._cross_update = jax.jit(
            lambda buf, val, slot: jax.lax.dynamic_update_slice(
                buf, val, (0, slot, 0, 0, 0)),
            donate_argnums=(0,))

    def view(self, slot):
        return _PagedEncDecView(self, slot)

    def free_slot(self, slot):
        self.enc_len_np[slot] = 0
        self.cache["enc_len"] = self.cache["enc_len"].at[slot].set(0)
        super().free_slot(slot)


BACKENDS = {"contiguous": ContiguousBackend, "paged": PagedBackend,
            "encdec": EncDecBackend, "paged-tp": ShardedPagedBackend,
            "paged-encdec": PagedEncDecBackend}


def make_backend(spec: Union[str, KVCacheBackend], model: Model,
                 max_batch: int, max_seq: int, *, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 enc_seq: Optional[int] = None,
                 tp: Optional[tp_lib.TPContext] = None) -> KVCacheBackend:
    """Engine-facing factory: a name ('contiguous' | 'paged' | 'encdec')
    or an already-built backend instance (tests / custom layouts).
    Enc-dec models need the paired self/cross layout, so 'contiguous'
    transparently resolves to ``EncDecBackend`` for them and 'paged' to
    ``PagedEncDecBackend``. An SPMD ``tp`` context upgrades 'paged' to
    the mesh-sharded pool (``ShardedPagedBackend``); the contiguous
    family ignores ``tp`` — only its restoration pack shards, and the
    sink colocates projected heads back to the buffer's device."""
    if isinstance(spec, KVCacheBackend):
        return spec
    if spec not in BACKENDS:
        raise ValueError(f"unknown KV-cache backend {spec!r}; "
                         f"one of {sorted(BACKENDS)}")
    if spec in ("paged", "paged-tp", "paged-encdec"):
        if spec == "paged-encdec" or model.kind == "encdec":
            return PagedEncDecBackend(model, max_batch, max_seq,
                                      block_size=block_size,
                                      num_blocks=num_blocks,
                                      enc_seq=enc_seq)
        if spec == "paged-tp" or (tp is not None and tp.spmd):
            return ShardedPagedBackend(model, max_batch, max_seq,
                                       block_size=block_size,
                                       num_blocks=num_blocks, tp_ctx=tp)
        return PagedBackend(model, max_batch, max_seq,
                            block_size=block_size, num_blocks=num_blocks)
    if spec == "encdec" or model.kind == "encdec":
        return EncDecBackend(model, max_batch, max_seq, enc_seq=enc_seq)
    return ContiguousBackend(model, max_batch, max_seq)
