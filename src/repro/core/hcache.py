"""HCacheManager — the paper's system glued together.

Responsibilities (paper Fig 7):
  * decide the per-layer restoration schedule (bubble-free scheduler);
  * SAVE: prefill/decode hidden states into the chunk store
    (layer-before-token order, two-stage saving off the critical path),
    offloaded-KV layers and SSM state blobs, plus the token stream and a
    manifest (crash recovery);
  * RESTORE: rebuild the exact KV cache / SSM states for a session from
    host storage — recompute-prefix from tokens, projections from hidden
    states, raw reads for KV layers — delegated to the pipelined
    RestorationExecutor (core/restoration.py): the serving engine steps it
    incrementally into batch-slot buffers, while ``restore`` here runs it
    to completion into a B=1 cache for offline/test use. The reported
    timeline derives from the executed task order under a hardware
    profile (this container has no real accelerator/SSD).

Optional beyond-paper extension: int8 per-token quantization of stored
hidden states (`compress="int8"`), halving IO/storage again at a measured
(small) restoration error — the paper cites quantization as composable
future work (§7).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.config.arch import BlockKind
from repro.config.hardware import HardwareProfile, TPU_V5E
from repro.core.pipeline import Timeline
from repro.core.restoration import (CacheAssembler, RestorationExecutor,
                                    build_param_pack, quantize_hidden_int8)
from repro.core.scheduler import Schedule, solve
from repro.models.model import Model
from repro.storage.chunk_store import ChunkStore
from repro.storage.two_stage import SnapshotTask, TwoStageSaver


@dataclasses.dataclass
class RestoreResult:
    cache: dict                      # family-specific cache pieces (B=1)
    schedule: Schedule
    timeline: Timeline               # simulated restoration timing
    wall_time: float                 # actual CPU seconds (functional path)
    n_tokens: int


class HCacheManager:
    def __init__(self, model: Model, store: ChunkStore, *,
                 hw: HardwareProfile = TPU_V5E, saver: Optional[TwoStageSaver]
                 = None, compress: str = "none", dtype_bytes: int = 2,
                 schedule_override: Optional[str] = None,
                 store_dtype=np.float16, restore_group_size=8,
                 profile=None):
        self.model = model
        self.cfg = model.cfg
        self.store = store
        # plan caches must exist before the hw property setter (which
        # invalidates them) runs
        self._plans: Dict[tuple, Schedule] = {}
        self._group_plans: Dict[tuple, object] = {}
        self._hw = hw
        # online calibration (DESIGN.md §13): a MeasuredProfile the
        # executors fold observed task times into and every planning
        # call (plan / resolve_group_size / capacity.restore_makespan)
        # prices with. None (the default) keeps the static
        # HardwareProfile model exactly — planning stays deterministic.
        self.profile = profile
        # IO-stream multiplicity: how many sessions are restoring
        # concurrently (the engine updates this every step); admission
        # and scheduling price shared host-link/storage bandwidth with
        # it instead of assuming exclusive access
        self.io_streams = 1
        # distributed-store contention: per-NIC-link restore-stream
        # counts (cost_model.LinkLoad) reported by the engine; None on
        # one-host stores, where ``io_streams`` is the whole story
        self.link_load = None
        # projection group plan for the batched restoration data path
        # (DESIGN.md §10): one stacked device call per group instead of
        # one per layer; 1 recovers the per-layer graph exactly; "auto"
        # lets each restore pick the makespan-argmin over uniform widths
        # {1, 2, 4, 8, L} AND the fetch-aligned non-uniform partition;
        # "fetch" forces the fetch-aligned partition
        # (restoration.choose_group_size / fetch_aligned_partition); an
        # explicit tuple of widths pins a non-uniform plan directly
        if restore_group_size in ("auto", "fetch"):
            self.restore_group_size = restore_group_size
        elif isinstance(restore_group_size, tuple):
            self.restore_group_size = tuple(
                max(int(w), 1) for w in restore_group_size)
        else:
            self.restore_group_size = max(int(restore_group_size), 1)
        # once-per-(model, params) restoration weight pack, built lazily
        # on the first restore and shared by every executor; `_tp` is the
        # TPContext the pack's weight stacks are sharded under (None =
        # single device)
        self._pack = None
        self._pack_params = None
        self._tp = None
        # dtype of stored hidden states. fp16 is the paper's setting (its
        # models run fp16, so storage is lossless); when the functional
        # model runs fp32, passing float32 makes pause/restore cycles
        # bit-exact at 2x the 'h' footprint.
        self.store_dtype = store_dtype
        self.saver = saver or TwoStageSaver(store)
        self.compress = compress
        self.dtype_bytes = dtype_bytes
        self.schedule_override = schedule_override   # None|hidden|kv|recompute
        # per-session compression overrides (capacity demotion ladder);
        # synced from the manifest on resume so a fresh manager over a
        # demoted store keeps appending in the session's stored codec
        self._session_compress: Dict[str, str] = {}

    def _compress_for(self, session: str) -> str:
        return self._session_compress.get(session, self.compress)

    # ----------------------------------------------- plan-cache invalidation
    @property
    def hw(self) -> HardwareProfile:
        return self._hw

    @hw.setter
    def hw(self, value: HardwareProfile) -> None:
        # regression guard (ISSUE 7 satellite): schedules and group plans
        # are memoized against the hardware numbers — swapping the
        # profile without flushing them left restores running stale
        # widths/splits forever
        if value is not self._hw:
            self._hw = value
            self.invalidate_plans()

    def invalidate_plans(self) -> None:
        """Flush every memoized schedule and group plan. Called on any
        hardware-profile swap; measured-profile drift and IO-multiplicity
        changes need no flush because both are part of the cache keys
        (``_price_key``)."""
        self._plans.clear()
        self._group_plans.clear()

    def set_profile(self, profile) -> None:
        """Attach (or detach) a MeasuredProfile; memoized plans priced
        under the old profile are flushed."""
        if profile is not self.profile:
            self.profile = profile
            self.invalidate_plans()

    def set_io_streams(self, n: int) -> None:
        """Engine-reported restore multiplicity. No cache flush: plans
        are memoized per multiplicity (``_price_key``), so flipping
        between 1-way and 4-way reuses both sets of plans."""
        self.io_streams = max(int(n), 1)

    def set_link_load(self, load) -> None:
        """Engine-reported per-link restore multiplicity (distributed
        store). Memoized like ``io_streams``: the load's identity is part
        of ``_price_key``, so recurring fleet states reuse their plans."""
        self.link_load = load

    def shard_topology(self):
        """The store's placement policy, None for one-host stores (or
        stores without the distributed API)."""
        topo_fn = getattr(self.store, "shard_topology", None)
        return topo_fn() if topo_fn is not None else None

    def _price_key(self) -> tuple:
        """The planning-relevant calibration state: plans computed under
        a different profile epoch, IO multiplicity, per-link load or
        tensor-parallel mesh width must not be reused — resharding the
        engine (hw.with_mesh) changes the projection-compute price and
        invalidates every memoized schedule and group plan."""
        epoch = self.profile.epoch if self.profile is not None else -1
        load = self.link_load.key() if self.link_load is not None else None
        return (epoch, self.io_streams, load,
                getattr(self.hw, "mesh_devices", 1))

    def set_tp(self, tp_ctx) -> None:
        """Attach the engine's tensor-parallel context: the restoration
        weight pack is rebuilt sharded over its mesh (KV output axis) and
        the hardware profile is re-priced for the mesh width, which in
        turn flushes memoized plans (``hw`` setter + ``_price_key``)."""
        if tp_ctx is not self._tp:
            self._tp = tp_ctx
            self._pack = None
            self._pack_params = None
        self.hw = self._hw.with_mesh(tp_ctx.tp if tp_ctx is not None
                                     and tp_ctx.spmd else 1)

    def param_pack(self, params):
        """Device-stacked restoration weights (wk/wv/bk/bv/ln1 + RoPE
        tables) for ``params`` — built once, then reference-cached so no
        restoration task ever re-gathers params. Holding the params
        reference keeps the identity check sound (the cached object
        cannot be collected and aliased). Under an attached TPContext the
        stacks are committed sharded on the KV output axis, so the
        grouped projection runs SPMD with each device projecting only its
        heads (DESIGN.md §16)."""
        if self._pack is None or self._pack_params is not params:
            self._pack = build_param_pack(self.model, params,
                                          tp_ctx=self._tp)
            self._pack_params = params
        return self._pack

    # ------------------------------------------------------------- planning
    def resolve_group_size(self, n_tokens: int, methods, *,
                           enc_len: int = 0):
        """Concrete projection group plan for one restore: the fixed
        width, or — under ``restore_group_size="auto"``/``"fetch"`` —
        the bucket-stable makespan argmin over uniform widths plus the
        fetch-aligned non-uniform partition (``"fetch"`` forces the
        partition). Returns an int width or a tuple of widths. Memoized
        per (S-bucket, methods, enc-bucket, price state) like ``plan``'s
        ``_plans`` cache: a profile-epoch bump or multiplicity change
        re-plans, a converged profile memoizes again. The single
        resolution point for the executor and
        ``capacity.restore_makespan``."""
        if self.restore_group_size not in ("auto", "fetch"):
            return self.restore_group_size
        from repro.core.restoration import (choose_group_size,
                                            fetch_aligned_partition,
                                            s_bucket)
        adapter = self.model.adapter
        cross = adapter.has_cross and enc_len > 0
        key = (s_bucket(max(int(n_tokens), 1)), tuple(methods),
               s_bucket(enc_len) if cross else 0, self._price_key())
        got = self._group_plans.get(key)
        if got is None:
            if self.restore_group_size == "fetch":
                got = self._fetch_partition(n_tokens, methods)
            else:
                got = choose_group_size(self.cfg, self.hw, n_tokens,
                                        methods,
                                        dtype_bytes=self.dtype_bytes,
                                        n_blobs=adapter.n_state_blobs,
                                        cross=adapter.has_cross,
                                        enc_len=enc_len,
                                        profile=self.profile,
                                        io_streams=self.io_streams,
                                        topology=self.shard_topology(),
                                        link_load=self.link_load,
                                        fetch_aligned=True)
            self._group_plans[key] = got
        return got

    def _fetch_partition(self, n_tokens: int, methods):
        """The forced fetch-aligned partition (``restore_group_size=
        "fetch"``), priced at the S-bucket under the current profile and
        multiplicity; a degenerate all-equal partition collapses to its
        uniform int width."""
        from repro.core.cost_model import layer_costs, link_priced_times
        from repro.core.restoration import (fetch_aligned_partition,
                                            s_bucket)
        bucket = s_bucket(max(int(n_tokens), 1))
        times, layer_links = link_priced_times(
            layer_costs(self.cfg, bucket, self.dtype_bytes), self.hw,
            profile=self.profile, io_streams=self.io_streams,
            topology=self.shard_topology(), link_load=self.link_load)
        overhead = getattr(self.hw, "dispatch_overhead", 0.0)
        if self.profile is not None:
            measured = self.profile.dispatch_overhead(
                mesh=getattr(self.hw, "mesh_devices", 1))
            if measured is not None:
                overhead = measured
        part = fetch_aligned_partition(methods, times,
                                       dispatch_overhead=overhead,
                                       links=layer_links)
        if not part:
            return 1
        return part[0] if len(set(part)) == 1 else part

    def plan(self, n_tokens: int) -> Schedule:
        """Bucketed bubble-free schedule (power-of-two token buckets),
        priced under the measured profile and current IO multiplicity
        when calibration is on (part of the memoization key)."""
        if self.schedule_override:
            m = self.schedule_override
            methods = tuple(
                m if bk == BlockKind.ATTENTION else "hidden"
                for bk in self.cfg.block_kinds())
            return Schedule(methods, 0.0, 0.0, 0.0, 0.0)
        bucket = 1 << max(int(np.ceil(np.log2(max(n_tokens, 128)))), 7)
        key = (bucket, self._price_key())
        if key not in self._plans:
            # recompute-prefix is only defined where the adapter says so
            # (hybrid: an attention block's recompute would depend on
            # interleaved mamba layers; encdec: on the cross context)
            allow_re = self.model.adapter.supports_recompute
            self._plans[key] = solve(self.cfg, bucket, self.hw,
                                     dtype_bytes=self.dtype_bytes,
                                     allow_recompute=allow_re,
                                     profile=self.profile,
                                     io_streams=self.io_streams,
                                     topology=self.shard_topology(),
                                     link_load=self.link_load)
        return self._plans[key]

    # ----------------------------------------------------------------- save
    def save_prefill(self, session: str, tokens: np.ndarray, prefill_out:
                     dict, *, start: int = 0) -> None:
        """Persist one sequence's prefill state (B must be 1 in `out`).
        The mapping between prefill outputs and persisted pieces
        (hidden/KV row naming) is the FamilyAdapter's."""
        adapter = self.model.adapter
        prev = self.store.get_manifest(session) if start > 0 else None
        if prev and prev.get("methods"):
            # a resumed session must keep appending under its stored
            # per-layer methods and codec: re-planning could flip a layer
            # hidden<->kv across a bucket boundary (or fight a capacity
            # demotion) and leave the stream with a hole at [0, start)
            methods = list(prev["methods"])
            comp = prev.get("compress", self.compress)
            if comp != self.compress:
                self._session_compress[session] = comp
        else:
            methods = list(self.plan(start + tokens.shape[-1]).methods)
        toks = np.asarray(tokens).reshape(-1)
        self.store.put_blob(session, "tok", 0, toks if start == 0 else
                            np.concatenate([self._tokens(session), toks]))
        kinds = self.cfg.block_kinds()
        for li, method in enumerate(methods):
            if kinds[li] != BlockKind.ATTENTION:
                continue  # SSM layers handled via state blobs below
            if method == "hidden":
                self._append_hidden(session, li, start,
                                    adapter.prefill_hidden(prefill_out, li))
            elif method == "kv":
                k, v = adapter.prefill_kv(prefill_out, li)
                self.store.append_tokens(session, "kvk", li, start,
                                         k.reshape(k.shape[0], -1))
                self.store.append_tokens(session, "kvv", li, start,
                                         v.reshape(v.shape[0], -1))
        self._save_ssm_states(session, prefill_out)
        manifest = {
            "n_tokens": int(start + tokens.shape[-1]),
            "methods": methods,
            "arch": self.cfg.name, "compress": self._compress_for(session),
        }
        if adapter.has_cross:
            if "enc_out" in prefill_out:
                self.store.put_blob(session, "enc", 0,
                                    np.asarray(prefill_out["enc_out"][0]))
                manifest["enc_len"] = int(prefill_out["enc_out"].shape[1])
            elif prev:
                # resume prefill (no encoder pass): keep the stored
                # encoder length so restore cost modeling stays honest
                manifest["enc_len"] = int(prev.get("enc_len", 0))
        self.store.flush(session)
        self.store.put_manifest(session, manifest)

    def save_session_pause(self, session: str, cache: dict,
                           n_tokens: int, *, tokens_tail: np.ndarray) -> None:
        """On eviction after decoding: dump kv-layer tails + SSM states from
        the live cache (they are accelerator-resident; decode only streamed
        the hidden states). Keeps the store restorable at ``n_tokens``."""
        manifest = self.store.get_manifest(session) or {
            "methods": list(self.plan(n_tokens).methods),
            "compress": self.compress, "arch": self.cfg.name}
        prev_n = int(manifest.get("n_tokens", 0))
        methods = manifest["methods"]
        if tokens_tail is not None and len(tokens_tail):
            old = (self._tokens(session)
                   if self.store.get_manifest(session) else
                   np.zeros((0,), np.int32))
            self.store.put_blob(session, "tok", 0, np.concatenate(
                [old[:prev_n], np.asarray(tokens_tail).reshape(-1)]))
        kinds = self.cfg.block_kinds()
        adapter = self.model.adapter
        k_name, v_name = adapter.kv_names or ("k", "v")
        for li, method in enumerate(methods):
            if (kinds[li] != BlockKind.ATTENTION or method != "kv"
                    or n_tokens <= prev_n):
                continue        # (a one-token answer adds no KV rows)
            idx = adapter.kv_row(li)
            k = np.asarray(cache[k_name][idx][0][prev_n:n_tokens])
            v = np.asarray(cache[v_name][idx][0][prev_n:n_tokens])
            self.store.append_tokens(session, "kvk", li, prev_n,
                                     k.reshape(k.shape[0], -1))
            self.store.append_tokens(session, "kvv", li, prev_n,
                                     v.reshape(v.shape[0], -1))
        if "ssm" in cache:
            self.store.put_blob(session, "state_conv", 0,
                                np.asarray(cache["conv"]))
            self.store.put_blob(session, "state_ssm", 0,
                                np.asarray(cache["ssm"]))
        self.store.flush(session)
        manifest["n_tokens"] = int(n_tokens)
        self.store.put_manifest(session, manifest)

    def _append_hidden(self, session: str, layer: int, start: int,
                       h: np.ndarray) -> None:
        if self._compress_for(session) == "int8":
            q, scale = quantize_hidden_int8(h)
            self.store.append_tokens(session, "h", layer, start, q)
            self.store.append_tokens(session, "hs", layer, start, scale)
        else:
            self.store.append_tokens(session, "h", layer, start,
                                     h.astype(self.store_dtype))

    def _save_ssm_states(self, session: str, out: dict) -> None:
        states = out.get("states") or out.get("mamba_states")
        if states is None:
            return
        conv, ssm = states
        self.store.put_blob(session, "state_conv", 0, np.asarray(conv))
        self.store.put_blob(session, "state_ssm", 0, np.asarray(ssm))

    def save_decode_hidden(self, session_ids: Sequence[Optional[str]],
                           hidden, lengths: np.ndarray) -> float:
        """Two-stage save of one decode step's hidden states.

        hidden: (L, B, 1, D); lengths: (B,) position of the new token.
        Returns the stage-1 (snapshot) virtual cost in seconds.

        The whole step is ONE layer-stacked (L, B', 1, D) snapshot for
        the plain-codec rows (the device buffer is already layer-major —
        stage 1 is a single contiguous copy, not L ring submissions);
        the stage-2 daemon splits per (layer, sequence). The snapshot
        byte count — and so ``snapshot_cost`` accounting — is unchanged
        from the per-layer form."""
        h = np.asarray(hidden)
        L = h.shape[0]
        all_layers = list(range(L))
        cost = 0.0
        starts = [int(x) for x in lengths]
        ids = list(session_ids)
        # sessions demoted to the int8 codec must keep their 'h' stream
        # dtype-consistent: quantize their rows before the snapshot and
        # route the scales to 'hs' (per-token scales, so row-at-a-time
        # quantization matches the bulk codec exactly)
        int8_rows = [b for b, s in enumerate(ids)
                     if s is not None and self._compress_for(s) == "int8"]
        plain_rows = [b for b in range(len(ids)) if b not in int8_rows]
        plain_ids = [ids[b] for b in plain_rows]
        if plain_rows:
            # slice the demoted rows out of the bulk snapshot so the
            # stage-1 copy cost covers only bytes actually written
            data = h[:, plain_rows].astype(self.store_dtype)
            cost += self.saver.snapshot(SnapshotTask(
                session_ids=plain_ids, stream="h", layer=-1,
                start_tokens=[starts[b] for b in plain_rows], data=data,
                layers=all_layers))
        for b in int8_rows:
            # per-token scales make the row-major stacked quantization
            # identical to the per-layer form (each (li, b) row is
            # normalized independently along D)
            q, scale = quantize_hidden_int8(
                h[:, b:b + 1].astype(np.float32))
            cost += self.saver.snapshot(SnapshotTask(
                [ids[b]], "h", -1, [starts[b]], q, layers=all_layers))
            cost += self.saver.snapshot(SnapshotTask(
                [ids[b]], "hs", -1, [starts[b]], scale, layers=all_layers))
        return cost

    # -------------------------------------------------------------- restore
    def _tokens(self, session: str) -> np.ndarray:
        return np.asarray(self.store.get_blob(session, "tok", 0))

    def begin_restore(self, params, session: str, sink=None,
                      start_token: int = 0) -> RestorationExecutor:
        """Start an incremental restoration (serving path). The returned
        executor is stepped by the engine a bounded number of tasks per
        engine iteration; finished layers stream into ``sink``.

        ``start_token > 0`` is restore-skip (DESIGN.md §12): tokens
        [0, start_token) are already resident in the target slot via a
        shared prefix, so the task graph starts at the divergence token —
        makespan shrinks with the shared-prefix ratio."""
        return RestorationExecutor(self, params, session, sink=sink,
                                   start_token=start_token)

    def fork_session(self, src: str, dst: str, *, share: bool = True)\
            -> dict:
        """Clone ``src``'s persisted state under ``dst`` (conversation
        trees). ``share=True`` aliases chunks/blobs content-addressed in
        the store (dedup: the bytes exist once until either side
        diverges); ``share=False`` materializes real copies — identical
        semantics, used as the no-sharing reference. Returns the cloned
        manifest."""
        man = self.store.get_manifest(src)
        if man is None:
            raise KeyError(f"cannot fork {src!r}: no stored state")
        if self.store.get_manifest(dst) is not None:
            raise ValueError(f"fork target {dst!r} already has state")
        self.store.share_session(src, dst, copy=not share)
        self.store.put_manifest(dst, dict(man))
        if src in self._session_compress:
            self._session_compress[dst] = self._session_compress[src]
        return dict(man)

    def restore(self, params, session: str) -> RestoreResult:
        """Rebuild the session's accelerator state from host storage.

        Standalone (offline/test) API: runs the pipelined executor to
        completion into a B=1 ``CacheAssembler``. The serving engine
        instead steps the executor incrementally with a batch-slot sink
        (see serving/engine.py)."""
        t0 = time.perf_counter()
        sink = CacheAssembler(self.model)
        ex = self.begin_restore(params, session, sink=sink)
        ex.run()
        wall = time.perf_counter() - t0
        return RestoreResult(sink.cache, ex.schedule, ex.timeline(), wall,
                             ex.n_tokens)

    # --------------------------------------------------- capacity demotion
    def demote_hidden_int8(self, session: str) -> bool:
        """Re-encode a session's stored hidden states to the int8 codec
        (halves the 'h' footprint). Future appends for the session follow
        the codec (per-session override + manifest), and restoration
        dequantizes transparently. Returns False when not applicable."""
        man = self.store.get_manifest(session)
        if not man or man.get("compress", "none") == "int8":
            return False
        n = int(man.get("n_tokens", 0))
        kinds = self.cfg.block_kinds()
        layers = [li for li, m in enumerate(man["methods"])
                  if m == "hidden" and kinds[li] == BlockKind.ATTENTION
                  and self.store.layer_available(session, "h", li, n)]
        if n == 0 or not layers:
            return False
        # remember which tier the stream came from: re-appending always
        # lands hot, so a cold-demoted session's re-encode must be moved
        # back afterwards or the int8 stage *increases* budgeted bytes
        was_cold = self.store.stream_in_cold(session, "h")
        data = {li: np.asarray(self.store.read_layer(session, "h", li, n))
                for li in layers}
        self.store.drop_stream(session, "h")
        self.store.drop_stream(session, "hs")
        for li, h in data.items():
            q, scale = quantize_hidden_int8(h.astype(np.float32))
            self.store.append_tokens(session, "h", li, 0, q)
            self.store.append_tokens(session, "hs", li, 0, scale)
        self.store.flush(session)
        if was_cold:
            self.store.demote_stream_to_cold(session, "h")
            self.store.demote_stream_to_cold(session, "hs")
        man["compress"] = "int8"
        self.store.put_manifest(session, man)
        if was_cold:
            # put_manifest re-hots the manifest (hot copy authoritative);
            # a fully cold-demoted session's metadata follows its chunks
            # so the int8 stage leaves the budgeted tier untouched
            self.store.demote_stream_to_cold(session, "meta")
        self._session_compress[session] = "int8"
        return True

    def promote_hidden_fp16(self, session: str) -> bool:
        """Inverse of ``demote_hidden_int8`` (capacity anti-entropy):
        re-encode the session's int8 'h' stream at the manager's
        store_dtype and drop the scales, so future appends and restores
        run the full-fidelity codec again. The already-quantized prefix
        keeps its int8-level error (the fp16 values are dequantized int8)
        — promotion stops *further* loss, it cannot undo past loss.
        Returns False when not applicable."""
        man = self.store.get_manifest(session)
        if not man or man.get("compress", "none") != "int8":
            return False
        n = int(man.get("n_tokens", 0))
        kinds = self.cfg.block_kinds()
        layers = [li for li, m in enumerate(man["methods"])
                  if m == "hidden" and kinds[li] == BlockKind.ATTENTION
                  and self.store.layer_available(session, "h", li, n)
                  and self.store.layer_available(session, "hs", li, n)]
        if n == 0 or not layers:
            return False
        from repro.core.restoration import dequantize_hidden_int8
        data = {}
        for li in layers:
            q = np.asarray(self.store.read_layer(session, "h", li, n))
            s = np.asarray(self.store.read_layer(session, "hs", li, n))
            data[li] = dequantize_hidden_int8(q, s).astype(self.store_dtype)
        self.store.drop_stream(session, "h")
        self.store.drop_stream(session, "hs")
        for li, h in data.items():
            self.store.append_tokens(session, "h", li, 0, h)
        self.store.flush(session)
        man["compress"] = "none"
        self.store.put_manifest(session, man)
        self._session_compress[session] = "none"
        return True

    def degrade_to_recompute(self, session: str) -> bool:
        """Drop a session's hidden/KV streams entirely, keeping only the
        token blob + manifest: the session stays restorable by full
        recompute (LM stacks only — hybrid recompute is undefined).
        The cheapest possible storage state before dropping outright."""
        if not self.model.adapter.supports_recompute:
            return False
        man = self.store.get_manifest(session)
        if not man or all(m == "recompute" for m in man["methods"]):
            return False
        if not self.store.has_blob(session, "tok", 0):
            return False
        if self._tokens(session).shape[0] < int(man.get("n_tokens", 0)):
            return False
        for stream in ("h", "hs", "kvk", "kvv"):
            self.store.drop_stream(session, stream)
        man["methods"] = ["recompute"] * len(man["methods"])
        man["compress"] = "none"
        self._session_compress.pop(session, None)
        self.store.put_manifest(session, man)
        return True

    # -------------------------------------------------------------- eviction
    def evict(self, session: str) -> None:
        self._session_compress.pop(session, None)
        self.store.drop_session(session)

    def sessions(self) -> List[str]:
        return self.store.sessions()
