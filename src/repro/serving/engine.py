"""Inference engine: continuous batching with an HCache restoration phase
and a capacity-driven session lifecycle.

Request lifecycle (paper §5, DESIGN.md §6/§8):

    WAITING -> [RESTORING]   if the session has evicted state in the store,
                             an incremental RestorationExecutor runs a
                             bounded number of pipeline tasks per engine
                             step, writing each finished layer straight
                             into the sequence's batch-slot buffers. Any
                             number of sessions restore concurrently, and
                             restoring sessions never block the decode
                             batch of active ones. Queued sessions with
                             stored state get their first hidden-layer IO
                             prefetched before a slot even frees;
            -> PREFILL       chunked prompt prefill (SplitFuse-style: at most
                             ``prefill_chunk`` prompt tokens per engine step,
                             so decode iterations stay interleaved);
            -> DECODE        joins the continuous decode batch; every step
                             streams the new token's hidden states to the
                             two-stage saver;
            -> PAUSED        mid-stream eviction under slot pressure: after
                             ``preempt_quantum`` steps of residency a
                             victim (EvictionPolicy) is dumped via
                             ``save_session_pause``, its slot handed to a
                             queued session (AdmissionPolicy), and it
                             re-enters through RESTORING with the last
                             sampled token as a 1-token resume prefill —
                             N sessions >> max_batch slots time-share the
                             batch with no generation-visible difference;
            -> DONE          on EOS/max-tokens: KV-layer tails + SSM states
                             are dumped (``save_session_pause``) and the slot
                             is freed — the session remains restorable.

Cache state lives behind a ``KVCacheBackend`` (serving/kv_cache.py,
DESIGN.md §9/§11): the classic ``contiguous`` layout (max_seq positions
per slot), the block-table ``paged`` layout — where admission reserves
only the pages a session can actually use, so a full page pool, not a
full slot table, is what back-pressures the queue — or the paired
self/cross ``encdec`` layout for whisper-family models. The engine
touches cache state exclusively through per-slot ``CacheView`` handles
(restore writes, history gathers, pause/retire snapshots, frees), and
every family-specific decision goes through the ``FamilyAdapter`` seam
(models/adapter.py) — this module contains no per-family branching.

Admission is pluggable (FIFO / restore-cost-aware / priority — see
core/capacity.py), as is victim selection (LRU / restore-cost-weighted).
An optional CapacityManager enforces a host-storage byte budget by
degrading idle sessions (cold tier, int8, token-only, drop).

Crash recovery: a fresh engine over the same ChunkStore can resume any
session (`recoverable_sessions`) — serving-side fault tolerance is HCache
itself.

Metrics per request: wall TTFT, TBT; engine-level counters plus
occupancy/fragmentation gauges for the benchmark harness. Host spans
(core/trace.py) name the work inside a step in a profiler trace and
feed the wall-time counters.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Dict, List, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.core.capacity import (CapacityManager, EvictionPolicy,
                                 AdmissionPolicy, FIFOAdmission, LRUEviction)
from repro.core.hcache import HCacheManager
from repro.core.trace import (ADMIT, DECODE_FORWARD, DECODE_SAMPLE,
                              DECODE_SAVE, PREFILL_ABSORB, PREFILL_FORWARD,
                              PREFILL_SAMPLE, PREFILL_SAVE, RETIRE_SAVE,
                              span)
from repro.distributed import tp as tp_lib
from repro.models.model import Model
from repro.serving.kv_cache import (KVCacheBackend, PagedBackend, ViewSink,
                                    make_backend)
from repro.serving.prefix_index import HostPin, PrefixIndex
from repro.serving.request import Phase, Request, SequenceState
from repro.serving.sampling import sample


@dataclasses.dataclass
class EngineMetrics:
    ttft_wall: List[float] = dataclasses.field(default_factory=list)
    # two TTFT populations: sessions that went through restoration vs
    # cold starts
    ttft_wall_restored: List[float] = dataclasses.field(default_factory=list)
    ttft_wall_cold: List[float] = dataclasses.field(default_factory=list)
    tbt_wall: List[float] = dataclasses.field(default_factory=list)
    # every completed restoration's simulated makespan — includes resumes
    # of mid-stream-evicted sessions, not only first tokens; the resume
    # subset is the victim-selection bake-off metric in bench_capacity
    restore_sim_all: List[float] = dataclasses.field(default_factory=list)
    restore_sim_resume: List[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0                # mid-stream evictions (PAUSED)
    restored_tokens: int = 0
    decode_steps: int = 0
    # occupancy / fragmentation gauges (KVCacheBackend.occupancy, sampled
    # once per engine step while any slot is occupied). live = tokens in
    # occupied slots; reserved = capacity handed out to them — the gap is
    # internal fragmentation (max_seq over-reservation under contiguous,
    # page rounding under paged).
    live_tokens: int = 0                # last sample
    reserved_tokens: int = 0
    free_blocks: int = 0
    live_tokens_peak: int = 0
    reserved_tokens_peak: int = 0
    concurrent_peak: int = 0            # max sessions resident at once
    # running (sum, count) rather than a per-step list: a long-lived
    # serving process must not grow memory linearly with engine steps
    occupancy_sum: float = 0.0
    occupancy_count: int = 0
    alloc_stalls: int = 0               # admissions deferred: pool exhausted
    # cross-session prefix sharing gauges (DESIGN.md §12) — all zero
    # unless the engine runs with prefix_sharing=True
    prefix_lookups: int = 0
    prefix_hits: int = 0
    prefix_hit_tokens: int = 0
    restore_skipped_tokens: int = 0     # tokens adopted instead of
    #                                     restored/prefilled
    cow_copies: int = 0                 # pages privatized on divergence
    shared_pages: int = 0               # refcount > 1 (last sample)
    private_pages: int = 0              # refcount == 1 (last sample)
    dedup_host_bytes: int = 0           # host bytes sharing avoided
    forks: int = 0
    # self-calibrating scheduler gauges (DESIGN.md §13). Per completed
    # restore: the observed bubble fraction (idle share of the slack
    # stream in the measured-duration replay) and the relative error of
    # the planned makespan against the measured one. Running (sum, n)
    # pairs, same rationale as occupancy above. profiler_samples is the
    # MeasuredProfile's per-kind sample-count snapshot (empty when the
    # engine runs uncalibrated).
    restore_bubble_sum: float = 0.0
    restore_bubble_n: int = 0
    makespan_err_sum: float = 0.0
    makespan_err_n: int = 0
    io_streams_peak: int = 1            # max concurrent RESTORING slots
    profiler_samples: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # tensor-parallel gauges (DESIGN.md §16): one row per mesh device —
    # page-pool occupancy / free pages (replicated page structure, so
    # equal across devices) plus the restore-projection utilization of
    # the SPMD launches each device participates in. Single-device
    # engines report one row.
    device_gauges: List[dict] = dataclasses.field(default_factory=list)
    restore_project_wall: float = 0.0   # sum over completed restores
    restore_wall_sum: float = 0.0
    # host wall time and traffic measured where the work happens (spans of
    # core/trace.py), as running sums: a reader takes differences over
    # its window. Restore figures are folded in when a restore completes.
    queue_wait_sum: float = 0.0         # s from arrival (or pause) to a slot
    admitted: int = 0                   # successful placements
    prefill_wall_sum: float = 0.0       # s in _prefill_step, all chunks
    prefill_tokens: int = 0             # prompt tokens those chunks ran
    save_wall_sum: float = 0.0          # s in the prefill/decode/retire saves
    saved_tokens: int = 0               # tokens the saves made restorable
    save_d2h_bytes: int = 0             # bytes they copied off the device
    restore_read_wall: float = 0.0      # s in store reads and assembly
    restore_upload_wall: float = 0.0    # s handing host arrays to the device
    restore_write_wall: float = 0.0     # s writing restored pieces to cache
    restore_h2d_bytes: int = 0          # bytes restores handed to the device

    @property
    def restore_bubble_mean(self) -> float:
        return (self.restore_bubble_sum / self.restore_bubble_n
                if self.restore_bubble_n else 0.0)

    @property
    def makespan_err_mean(self) -> float:
        return (self.makespan_err_sum / self.makespan_err_n
                if self.makespan_err_n else 0.0)

    @property
    def prefix_hit_rate(self) -> float:
        return (self.prefix_hits / self.prefix_lookups
                if self.prefix_lookups else 0.0)

    @property
    def occupancy_mean(self) -> float:
        return (self.occupancy_sum / self.occupancy_count
                if self.occupancy_count else 0.0)

    @property
    def fragmentation_mean(self) -> float:
        return 1.0 - self.occupancy_mean if self.occupancy_count else 0.0

    @staticmethod
    def _summary(xs: List[float]) -> Dict[str, float]:
        if not xs:
            return {"n": 0, "mean": 0.0, "p50": 0.0, "p99": 0.0, "max": 0.0}
        a = np.asarray(xs, np.float64)
        return {"n": int(a.size), "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p99": float(np.percentile(a, 99)),
                "max": float(a.max())}

    def to_dict(self) -> dict:
        """JSON-serializable dump of every counter/gauge; per-request
        populations (TTFT/TBT/makespans) summarized as n/mean/p50/p99/max.
        This is what ``serve.py --metrics-json`` writes and what the SLO
        harness consumes — benches never scrape printed text."""
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "device_gauges":
                out[f.name] = [dict(r) for r in v]
            elif isinstance(v, list):
                out[f.name] = self._summary(v)
            elif isinstance(v, dict):
                out[f.name] = {str(k): int(n) for k, n in v.items()}
            else:
                out[f.name] = v
        for prop in ("restore_bubble_mean", "makespan_err_mean",
                     "prefix_hit_rate", "occupancy_mean",
                     "fragmentation_mean"):
            out[prop] = float(getattr(self, prop))
        return out


class InferenceEngine:
    def __init__(self, model: Model, params, manager: HCacheManager, *,
                 max_batch: int = 4, max_seq: int = 512,
                 prefill_chunk: int = 128, save_hidden: bool = True,
                 temperature: float = 0.0, restore_tasks_per_step: int = 8,
                 prefetch_sessions: int = 2,
                 admission: Optional[AdmissionPolicy] = None,
                 eviction: Optional[EvictionPolicy] = None,
                 preempt_quantum: Optional[int] = None,
                 capacity: Optional[CapacityManager] = None,
                 backend: Union[str, KVCacheBackend] = "contiguous",
                 block_size: int = 16,
                 cache_blocks: Optional[int] = None,
                 enc_seq: Optional[int] = None,
                 prefix_sharing: bool = False,
                 tp: int = 1):
        self.model = model
        # every family-specific decision (prefill chunk policy, output->
        # cache mapping, resume support, save naming) goes through the
        # FamilyAdapter seam — the engine itself is family-agnostic
        self.adapter = model.adapter
        self.mgr = manager
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.save_hidden = save_hidden
        self.temperature = temperature
        self.restore_tasks_per_step = restore_tasks_per_step
        self.prefetch_sessions = prefetch_sessions
        self.admission = admission or FIFOAdmission()
        self.eviction = eviction or LRUEviction()
        # preempt_quantum: minimum resident steps before a DECODE session
        # is eviction-eligible; None disables mid-stream eviction
        self.preempt_quantum = preempt_quantum
        self.capacity = capacity
        if capacity is not None:
            capacity.attach_engine(self)

        # tensor-parallel context (DESIGN.md §16): a paged lm backend
        # shards its page pool over the mesh and the manager prices /
        # shards its restoration packs the same way; the parameters are
        # replicated on every mesh device (a no-op when the caller
        # already initialized them there, or at tp=1). A tp wider than
        # the visible devices raises.
        self.tp = tp_lib.TPContext(tp)
        self.params = self.tp.replicate(params)
        set_tp = getattr(manager, "set_tp", None)
        if set_tp is not None:
            set_tp(self.tp)

        # all cache state (contiguous slots or a paged pool + block
        # tables) lives behind the backend; the engine only holds views
        self.kv = make_backend(backend, model, max_batch, max_seq,
                               block_size=block_size,
                               num_blocks=cache_blocks, enc_seq=enc_seq,
                               tp=self.tp)
        # cross-session prefix sharing (DESIGN.md §12): host chunk
        # aliasing on fork works on every backend; the device-side
        # token-hash index needs pages, so it exists only under paged
        self.prefix_sharing = bool(prefix_sharing)
        self.prefix_index: Optional[PrefixIndex] = None
        self._fork_pages: Dict[str, dict] = {}   # parked page holds
        if self.prefix_sharing and isinstance(self.kv, PagedBackend):
            self.prefix_index = PrefixIndex(self.kv)
            self.prefix_index.store = manager.store
            self.kv.prefix_index = self.prefix_index
        # token-callback seam (DESIGN.md §14): the front door's engine
        # pump fans emitted tokens out to per-request async queues
        # through these hooks. on_token fires exactly once per emitted
        # token (the resume feed after a pause replays an EXISTING token
        # through prefill and does not re-fire); on_finish fires exactly
        # once per request, at retire, with reason "stop" (EOS) or
        # "length"; on_pause fires at each mid-stream eviction. All run
        # on the engine-stepping thread.
        self.on_token = None               # fn(seq, tok)
        self.on_finish = None              # fn(seq, reason)
        self.on_pause = None               # fn(seq)
        self.queue: deque = deque()
        self.slots: List[Optional[SequenceState]] = [None] * max_batch
        self.sessions: Dict[str, SequenceState] = {}
        self._prefetch: Dict[str, object] = {}   # session -> warm executor
        self.metrics = EngineMetrics()
        self.step_count = 0

    # ----------------------------------------------------------- submission
    def submit(self, request: Request) -> SequenceState:
        seq = SequenceState(request=request)
        if request.arrival_time == 0.0:
            # the front door pre-stamps arrival at ingress so TTFT covers
            # its own queueing; direct callers are stamped here
            seq.request.arrival_time = time.perf_counter()
        if request.arrival_step < 0:
            seq.request.arrival_step = self.step_count
        seq.enqueue_step = self.step_count
        seq.enqueue_time = seq.request.arrival_time
        self.queue.append(seq)
        return seq

    def recoverable_sessions(self) -> List[str]:
        return self.mgr.sessions()

    # ------------------------------------------------------------ lifecycle
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _tokens_needed(self, seq: SequenceState) -> int:
        """Worst-case final token length of this residency: stored
        history + the pending prompt + the decode tokens still owed.
        What a paged reservation must cover (contiguous always reserves
        max_seq)."""
        manifest = self.mgr.store.get_manifest(seq.request.session_id)
        stored = (int(manifest["n_tokens"]) if manifest
                  else seq.history_len)
        need = (stored + len(seq.effective_prompt)
                + seq.request.max_new_tokens - len(seq.generated))
        fork = self._fork_pages.get(seq.request.session_id)
        if fork is not None and fork["partial"]:
            # adopting a fork's partial tail page shares it with the
            # donor; the resume-feed write privatizes it, costing one
            # extra pool page while both holds are live
            need += self.kv.block_size
        return need

    def _host_align(self, m: int) -> int:
        """Floor a device prefix match so its host analogue aliases only
        whole chunks (the adopted length must be page- AND chunk-
        aligned)."""
        C = self.mgr.store.chunk_tokens
        bs = self.kv.block_size
        align = bs * C // math.gcd(bs, C)
        return (m // align) * align

    def _shared_prefix_estimate(self, seq: SequenceState) -> int:
        """Tokens an admission of ``seq`` would cover via shared pages
        (parked fork pages or a prefix-index hit) — those pages arrive by
        incref, not from the free pool."""
        if self.prefix_index is None:
            return 0
        sid = seq.request.session_id
        man = self.mgr.store.get_manifest(sid)
        fork = self._fork_pages.get(sid)
        if (fork is not None and man is not None
                and fork["n_tokens"] == int(man["n_tokens"])):
            bs = self.kv.block_size
            return (fork["n_tokens"] // bs) * bs
        if man is not None:
            if (man.get("compress", self.mgr.compress) != "none"
                    or "recompute" in list(man["methods"])):
                return 0
            try:
                toks = self.mgr._tokens(sid)
            except KeyError:
                return 0
            n = int(man["n_tokens"])
            _, m, _ = self.prefix_index.match(toks[:n], limit=n,
                                              record=False)
            return m
        prompt = np.asarray(seq.effective_prompt).reshape(-1)
        _, m, _ = self.prefix_index.match(prompt, limit=len(prompt) - 1,
                                          need_host=self.save_hidden,
                                          record=False)
        return self._host_align(m) if self.save_hidden else m

    def _can_reserve_for(self, seq: SequenceState) -> bool:
        """Admission gate: ``kv.can_reserve``, made sharing-aware."""
        need = self._tokens_needed(seq)
        return self.kv.can_reserve(
            max(need - self._shared_prefix_estimate(seq), 1))

    def _admit(self) -> None:
        with span(ADMIT):
            while self.queue:
                slot = self._free_slot()
                if slot is None:
                    break
                seq = self.admission.select(tuple(self.queue), self)
                if seq is None:
                    break
                if not self._can_reserve_for(seq):
                    # allocator backpressure: a free slot exists but the
                    # page pool cannot hold the session — wait for
                    # retires/frees
                    self.metrics.alloc_stalls += 1
                    break
                self.queue.remove(seq)
                if not self._place(seq, slot):
                    break
            self._prefetch_queued()

    def _adopt_shared_prefix(self, seq: SequenceState, slot: int) -> int:
        """Map the longest shared prefix of this session into the free
        slot's block table before ``reserve`` tops it up with private
        pages. Three sources, tried in order: parked fork pages (the
        fork adopts the donor's saved history wholesale), a prefix-index
        hit on the session's stored token history (restore-skip), or a
        prefix-index hit on a fresh prompt (prefill-skip — the host
        analogue aliases the publisher's pinned chunks so the session
        is a complete stored session of the matched length). Returns the
        adopted token count."""
        if self.prefix_index is None:
            return 0
        sid = seq.request.session_id
        man = self.mgr.store.get_manifest(sid)
        fork = self._fork_pages.pop(sid, None)
        if fork is not None:
            if man is not None and fork["n_tokens"] == int(man["n_tokens"]):
                self.kv.adopt_shared(slot, fork["blocks"], owned=True)
                return fork["n_tokens"]
            # the source saved more state since the fork: the parked
            # pages are stale — drop the holds, fall back to the index
            self.kv.release_blocks(fork["blocks"])
        if man is not None:
            if (man.get("compress", self.mgr.compress) != "none"
                    or "recompute" in list(man["methods"])):
                # shared pages hold exact fp16 KV; a session whose
                # no-sharing restore would go through another codec must
                # not mix sources (byte-equivalence to the reference run)
                return 0
            try:
                toks = self.mgr._tokens(sid)
            except KeyError:
                return 0
            n = int(man["n_tokens"])
            blocks, m, _ = self.prefix_index.match(toks[:n], limit=n)
            if m:
                self.kv.adopt_shared(slot, blocks)
            return m
        prompt = np.asarray(seq.effective_prompt).reshape(-1)
        blocks, m, entry = self.prefix_index.match(
            prompt, limit=len(prompt) - 1, need_host=self.save_hidden)
        if m and self.save_hidden:
            m = self._host_align(m)
            blocks = blocks[:m // self.kv.block_size]
        if not m:
            return 0
        self.kv.adopt_shared(slot, blocks)
        if self.save_hidden:
            self._alias_host_prefix(sid, prompt[:m], entry)
        else:
            seq.history_len = m
        seq.pending_prompt = prompt[m:]
        return m

    def _alias_host_prefix(self, sid: str, prefix_tokens,
                           entry) -> None:
        """Host-side analogue of a fresh-prompt prefix hit: the new
        session's streams alias the publisher's pinned chunks for the
        matched tokens and a manifest is committed, so every later code
        path (resume prefill, pause, restore) sees an ordinary stored
        session of ``m`` tokens. The aliases cost no bytes until the
        session diverges onto its own chunks."""
        store = self.mgr.store
        m = len(prefix_tokens)
        pin: HostPin = entry.pin
        n_chunks = -(-m // store.chunk_tokens)
        store.put_blob(sid, "tok", 0, np.asarray(prefix_tokens, np.int32))
        for (stream, li), ids in pin.pins.items():
            for ci in range(min(n_chunks, len(ids))):
                store.alias_chunk(sid, stream, li, ci, ids[ci])
        store.put_manifest(sid, {"n_tokens": m,
                                 "methods": list(pin.methods),
                                 "arch": self.mgr.cfg.name,
                                 "compress": "none"})

    def _place(self, seq: SequenceState, slot: int) -> bool:
        """Bind a (possibly resuming) sequence to a free batch slot.
        False iff the backend could not reserve capacity (the sequence is
        requeued and the slot stays free)."""
        sid = seq.request.session_id
        adopted = self._adopt_shared_prefix(seq, slot)
        if not self.kv.reserve(slot, self._tokens_needed(seq)):
            if self.prefix_index is not None and self.kv.slot_blocks[slot]:
                self.kv.free_slot(slot)      # drop adopted page holds
            if adopted and self.mgr.store.get_manifest(sid) is None:
                # no-save fresh match: nothing persisted — undo the trim
                seq.pending_prompt = None
                seq.history_len = 0
            self.metrics.alloc_stalls += 1
            self.queue.appendleft(seq)
            return False
        seq.slot = slot
        seq.admit_step = self.step_count
        self.metrics.queue_wait_sum += time.perf_counter() - seq.enqueue_time
        self.metrics.admitted += 1
        seq.view = self.kv.view(slot)
        self.slots[slot] = seq
        self.sessions[sid] = seq
        if self.capacity is not None:
            self.capacity.touch(sid, self.step_count)
        manifest = self.mgr.store.get_manifest(sid)
        if manifest:
            n_man = int(manifest["n_tokens"])
            d = min(adopted, n_man)
            if d:
                self.metrics.restore_skipped_tokens += d
            if d >= n_man and n_man > 0:
                # the whole stored history is already resident via
                # shared pages — no restoration work at all
                self._prefetch.pop(sid, None)
                seq.restored = True
                seq.history_len = n_man
                seq.restore_sim = 0.0
                seq.restore_wall = 0.0
                self.kv.set_length(slot, n_man)
                seq.phase = Phase.PREFILL
                self._prefill_step(seq)
                return True
            seq.phase = Phase.RESTORING
            ex = self._prefetch.pop(sid, None)
            if ex is not None and (
                    ex.n_tokens != n_man
                    or list(ex.methods) != list(manifest["methods"])
                    or ex.compress != manifest.get("compress",
                                                   self.mgr.compress)
                    or getattr(ex, "start_token", 0) != d):
                # the session saved more state (or was demoted to another
                # codec by the capacity ladder) after the prefetch
                # started, or a shared prefix moved the start token: the
                # warm executor is stale — restart from the current
                # manifest
                ex = None
            if ex is None:
                # this restore joins the already-RESTORING slots on the
                # shared host link: plan it at the new multiplicity
                # (this slot already shows RESTORING — no extra)
                self._update_io_streams()
                ex = self.mgr.begin_restore(self.params, sid,
                                            start_token=d)
            ex.attach_sink(ViewSink(seq.view))
            seq.executor = ex
            # reserve [0, n) now: concurrent decode steps park their
            # scratch KV write at position n (later overwritten by
            # this session's own prefill), never inside the restored
            # range
            self.kv.set_length(slot, ex.n_tokens)
        else:
            seq.phase = Phase.PREFILL
            if seq.history_len:
                # no-save prefix hit: the adopted range is live history
                self.kv.set_length(slot, seq.history_len)
            self._prefill_step(seq)
        return True

    # ----------------------------------------------------------- preemption
    def _maybe_preempt(self) -> None:
        """Mid-stream eviction under slot pressure (one victim per step):
        pause a resident DECODE session past its quantum, hand its slot
        to the admission policy's next pick. The victim re-enters through
        the RESTORING pipeline."""
        # resume replays the last sampled token through a prefill over
        # restored state — families without that path (ssm/hybrid, whose
        # recurrent states would restart from zero) are not preemptable
        if (self.preempt_quantum is None or not self.save_hidden
                or not self.adapter.supports_resume or not self.queue):
            return
        if self._free_slot() is not None:
            # a slot is open, so preemption is only justified when the
            # second admission gate — the page pool — is what's blocking
            # the queue; pausing a victim recycles its pages
            seq = self.admission.select(tuple(self.queue), self)
            if seq is None or self._can_reserve_for(seq):
                return
        candidates = [s for s in self.slots
                      if s is not None and s.phase == Phase.DECODE
                      and s.generated and not s.finished()
                      and self.step_count - s.admit_step
                      >= self.preempt_quantum]
        victim = self.eviction.select_victim(candidates, self)
        if victim is None:
            return
        slot = victim.slot
        with span(ADMIT):
            self._pause_slot(slot)
            waiting = [s for s in self.queue if s is not victim]
            seq = self.admission.select(tuple(waiting), self)
            if seq is not None:
                self.queue.remove(seq)
                self._place(seq, slot)

    def _pause_slot(self, i: int) -> None:
        """Evict the resident of slot ``i`` mid-decode: dump restorable
        state (``view.snapshot()``), free the slot's pages, requeue the
        sequence as PAUSED. The last sampled token (whose KV does not
        exist yet) becomes the 1-token resume prefill after restoration."""
        s = self.slots[i]
        self._checkpoint(s)
        self._publish_slot(s)
        s.gen_absorbed = len(s.generated)
        s.pending_prompt = np.asarray([s.generated[-1]], np.int32)
        s.pending_from_gen = True
        s.prefill_done = 0
        s.history_len = 0              # re-set when restoration completes
        s.phase = Phase.PAUSED
        s.slot = -1
        s.executor = None
        s.view.free()
        s.view = None
        s.pauses += 1
        s.enqueue_step = self.step_count
        s.enqueue_time = time.perf_counter()
        self.slots[i] = None
        self.queue.append(s)
        self.metrics.preemptions += 1
        if self.on_pause is not None:
            self.on_pause(s)

    # ------------------------------------------------------ prefix sharing
    def _host_pin_fn(self, sid: str, man: dict):
        """``pin_fn`` for ``PrefixIndex.publish``: pins every persisted
        stream's chunks covering ``depth`` pages, or None when the
        coverage is not (fully) flushed — the entry then serves
        device-only consumers (restore-skip), not fresh-prompt hits."""
        if not self.save_hidden:
            return None
        methods = list(man["methods"])
        if any(m == "recompute" for m in methods):
            return None
        store = self.mgr.store
        C = store.chunk_tokens
        bs = self.kv.block_size

        def pin(depth: int):
            n_tok = depth * bs
            n_chunks = -(-n_tok // C)
            targets = []
            for li, m in enumerate(methods):
                for stream in (("h",) if m == "hidden" else ("kvk", "kvv")):
                    for ci in range(n_chunks):
                        if (store.chunk_rows(sid, stream, li, ci)
                                < min(C, n_tok - ci * C)):
                            return None
                    targets.append((stream, li))
            pins = {(stream, li): store.pin_chunks(sid, stream, li,
                                                   list(range(n_chunks)))
                    for stream, li in targets}
            return HostPin(methods=methods, pins=pins, n_chunks=n_chunks)
        return pin

    def _publish_slot(self, seq: SequenceState) -> None:
        """Index the slot's full pages for cross-session sharing — at
        prefill completion and again right before the slot frees at
        pause/retire (published pages are incref'd, so they outlive the
        publisher's residency)."""
        if self.prefix_index is None or seq.view is None or seq.slot < 0:
            return
        blks = self.kv.slot_blocks[seq.slot]
        if not blks:
            return
        sid = seq.request.session_id
        length = int(self.kv.get_lengths()[seq.slot])
        if self.save_hidden:
            man = self.mgr.store.get_manifest(sid)
            if not man or man.get("compress", self.mgr.compress) != "none":
                return                     # demoted codecs are not shared
            try:
                tokens = self.mgr._tokens(sid)
            except KeyError:
                return
            self.prefix_index.publish(tokens, min(length, len(tokens)),
                                      blks, self._host_pin_fn(sid, man))
        else:
            if seq.pending_from_gen:
                return       # token history not reconstructible sans store
            tokens = np.concatenate(
                [np.asarray(seq.request.prompt, np.int64).reshape(-1),
                 np.asarray(seq.generated, np.int64)])
            self.prefix_index.publish(tokens, min(length, len(tokens)),
                                      blks, None)

    def fork_session(self, src: str, new_id: str) -> dict:
        """Fork ``src``'s conversation state as ``new_id`` (DESIGN.md
        §12): host streams are shared content-addressed (bytes exist
        once until a side diverges; with prefix_sharing off they are
        materialized as real copies), and — with sharing on, a paged
        backend and the source resident — the saved history's device
        pages are parked for the fork to adopt CoW-shared at admission,
        making its restoration a no-op. A resident source is
        checkpointed first (the same dump as a pause, without losing its
        slot), so the fork point is the full history through the last
        sampled token's predecessor."""
        seq = self.sessions.get(src)
        if seq is not None and seq.view is not None:
            if seq.phase != Phase.DECODE or not seq.generated:
                raise ValueError(
                    f"cannot fork {src!r} mid-{seq.phase.value}; fork "
                    f"before admission or once it is decoding")
            if not self.save_hidden:
                raise ValueError(
                    "forking a resident session requires save_hidden "
                    "(its history lives only in streams it never saved)")
            self._checkpoint(seq)
        man = self.mgr.fork_session(src, new_id,
                                    share=self.prefix_sharing)
        if (self.prefix_index is not None and seq is not None
                and seq.view is not None):
            n_saved = int(man["n_tokens"])
            pages = -(-n_saved // self.kv.block_size)
            blocks = [int(b) for b in
                      self.kv.slot_blocks[seq.slot][:pages]]
            for b in blocks:
                self.kv.allocator.incref(b)
            self._fork_pages[new_id] = {
                "blocks": blocks, "n_tokens": n_saved,
                "partial": n_saved % self.kv.block_size != 0}
        self.metrics.forks += 1
        return man

    def release_fork(self, new_id: str) -> None:
        """Drop the parked page holds of a fork that will never be
        submitted (the host-side state stays forkable)."""
        fork = self._fork_pages.pop(new_id, None)
        if fork is not None:
            self.kv.release_blocks(fork["blocks"])

    # ----------------------------------------------------------- restoration
    def _prefetch_queued(self) -> None:
        """Warm the first IO reads of queued sessions with stored state
        before a slot frees (their executor starts part-done on admit)."""
        for seq in list(self.queue)[:self.prefetch_sessions]:
            sid = seq.request.session_id
            ex = self._prefetch.get(sid)
            if ex is None and self.mgr.store.get_manifest(sid):
                ex = self.mgr.begin_restore(self.params, sid)
                self._prefetch[sid] = ex
            if ex is not None:
                ex.prefetch_step(1)

    def _update_io_streams(self, extra: int = 0) -> None:
        """Report the restore multiplicity to the planner: how many
        sessions are (about to be) pulling the shared host link at once.
        ``extra`` counts a restore being placed this instant, before its
        slot shows RESTORING.

        Distributed store: additionally fold each restoring executor's
        touched NIC links into a per-link ``LinkLoad`` — contention is
        then charged only on the links a candidate restore shares with
        the in-flight ones, not globally (an ``extra`` placement has no
        executor yet and conservatively counts on every link)."""
        restoring = [s.executor for s in self.slots
                     if s is not None and s.phase == Phase.RESTORING
                     and s.executor is not None]
        n = max(len(restoring) + extra, 1)
        setter = getattr(self.mgr, "set_io_streams", None)
        if setter is not None:
            setter(n)
        load_setter = getattr(self.mgr, "set_link_load", None)
        topo_fn = getattr(self.mgr, "shard_topology", None)
        topo = topo_fn() if topo_fn is not None else None
        if load_setter is not None and topo is not None \
                and topo.n_shards > 1:
            from repro.core.cost_model import LinkLoad
            streams: Dict[int, int] = {}
            for ex in restoring:
                for link in ex.links_touched():
                    streams[link] = streams.get(link, 0) + 1
            for link in range(topo.n_shards):
                streams[link] = streams.get(link, 0) + extra
            load_setter(LinkLoad(streams))
        self.metrics.io_streams_peak = max(self.metrics.io_streams_peak, n)

    def _restore_step(self) -> None:
        """Advance every RESTORING session by a bounded number of pipeline
        tasks. Several sessions restore concurrently; the decode batch of
        active sessions runs in the same engine step regardless."""
        m = self.metrics
        for seq in self.slots:
            if seq is None or seq.phase != Phase.RESTORING:
                continue
            if seq.executor.step(self.restore_tasks_per_step):
                ex = seq.executor
                seq.executor = None
                seq.restored = True
                seq.history_len = ex.n_tokens
                seq.restore_sim = ex.timeline().makespan
                seq.restore_wall = ex.wall_time
                m.restored_tokens += ex.n_tokens - ex.start_token
                m.restore_sim_all.append(seq.restore_sim)
                if seq.pending_from_gen:       # resume of a paused session
                    m.restore_sim_resume.append(seq.restore_sim)
                m.restore_project_wall += ex.project_wall
                m.restore_wall_sum += ex.wall_time
                m.restore_read_wall += ex.read_wall
                m.restore_upload_wall += ex.upload_wall
                m.restore_write_wall += ex.write_wall
                m.restore_h2d_bytes += ex.h2d_bytes
                self._record_calibration(ex)
                seq.phase = Phase.PREFILL

    def _record_calibration(self, ex) -> None:
        """Scheduler-calibration gauges from one finished restore:
        observed bubble fraction and planned-vs-measured makespan error.
        Only meaningful when the executor observed task durations (a
        timed store and/or calibration on)."""
        if not getattr(ex, "observed", None):
            return
        m = self.metrics
        tl = ex.measured_timeline()
        if tl.makespan > 0:
            # the bottleneck stream's bubble is ~0 by construction; the
            # slack stream's idle share is the bubble the scheduler
            # exists to close
            m.restore_bubble_sum += max(tl.io_bubble, tl.compute_bubble)
            m.restore_bubble_n += 1
            predicted = getattr(ex, "predicted_makespan", 0.0)
            if predicted > 0:
                m.makespan_err_sum += (abs(predicted - tl.makespan)
                                       / tl.makespan)
                m.makespan_err_n += 1
        profile = getattr(self.mgr, "profile", None)
        if profile is not None:
            m.profiler_samples = profile.sample_counts()

    # -------------------------------------------------------------- prefill
    def _prefill_step(self, seq: SequenceState) -> None:
        """Process up to ``prefill_chunk`` prompt tokens (SplitFuse;
        families whose adapter is not ``chunkable`` — recurrent-state and
        enc-dec stacks — take the whole prompt in one step).

        After a mid-stream eviction the "prompt" is the resume feed
        (``effective_prompt``): the last sampled token, whose KV is
        recreated here on top of the restored [0, n) range."""
        if seq.phase != Phase.PREFILL:
            return
        ad = self.adapter
        prompt = seq.effective_prompt
        remaining = prompt[seq.prefill_done:]
        if len(remaining) == 0:
            seq.phase = Phase.DECODE
            return
        t0 = time.perf_counter()
        m = self.metrics
        chunk = remaining[:self.prefill_chunk] if ad.chunkable else remaining
        hist = seq.history_len + seq.prefill_done
        with span(PREFILL_FORWARD):
            out = ad.prefill_chunk(self.params, seq, chunk, hist,
                                   capture_hidden=self.save_hidden)
        with span(PREFILL_ABSORB):
            ad.absorb_prefill(seq.view, out, len(chunk), hist)
            seq.view.set_length(hist + len(chunk))
        if self.save_hidden:
            sid = seq.request.session_id
            with span(PREFILL_SAVE, m, "save_wall_sum"):
                saved = self.mgr.save_prefill(sid, np.asarray(chunk), out,
                                              start=hist)
            self._count_save(saved)
            self._after_save(sid)
        seq.prefill_done += len(chunk)
        if seq.pending_from_gen and self.save_hidden:
            seq.tok_saved += len(chunk)   # resume feed landed in tok blob
        if seq.prefill_done >= len(prompt):
            seq.phase = Phase.DECODE
            self._publish_slot(seq)
            with span(PREFILL_SAMPLE):
                tok = int(sample(out["logits"],
                                 temperature=self.temperature)[0])
            self._emit_token(seq, tok)
        m.prefill_tokens += len(chunk)
        m.prefill_wall_sum += time.perf_counter() - t0

    def _count_save(self, saved) -> None:
        self.metrics.saved_tokens += saved.tokens
        self.metrics.save_d2h_bytes += saved.d2h_bytes

    # --------------------------------------------------------------- decode
    def _emit_token(self, seq: SequenceState, tok: int) -> None:
        seq.generated.append(tok)
        if seq.first_token_step is None:
            seq.first_token_step = self.step_count
            seq.ttft_wall = time.perf_counter() - seq.request.arrival_time
            self.metrics.ttft_wall.append(seq.ttft_wall)
            if seq.restored:
                self.metrics.ttft_wall_restored.append(seq.ttft_wall)
            else:
                self.metrics.ttft_wall_cold.append(seq.ttft_wall)
        if self.on_token is not None:
            self.on_token(seq, tok)

    def _decode_batch(self) -> None:
        active = [s for s in self.slots
                  if s is not None and s.phase == Phase.DECODE
                  and not s.finished()]
        if not active:
            return
        t0 = time.perf_counter()
        m = self.metrics
        with span(DECODE_FORWARD):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            for s in self.slots:
                if s is not None and s.phase == Phase.DECODE and s.generated:
                    tokens[s.slot, 0] = s.generated[-1]
            lg, hidden = self.kv.decode(self.params, jnp.asarray(tokens))
        # the first read of the step's results waits for the device
        with span(DECODE_SAMPLE):
            toks = np.asarray(sample(lg, temperature=self.temperature))
        with span(DECODE_FORWARD):
            # inactive slots advanced their length too — undo
            mask = np.zeros((self.max_batch,), bool)
            for s in active:
                mask[s.slot] = True
            lengths = self.kv.get_lengths()
            lengths[~mask] -= 1
            self.kv.set_lengths(lengths)
        if self.save_hidden and hidden is not None:
            # only truly-active sessions: a session that finished at
            # prefill completion still sits in its slot in DECODE phase
            # until _retire, and saving its masked-out scratch step would
            # overwrite the last legitimate hidden row
            active_slots = {s.slot for s in active}
            sess = [s.request.session_id if (s is not None
                    and s.slot in active_slots) else None
                    for s in self.slots]
            with span(DECODE_SAVE, m, "save_wall_sum"):
                h = np.asarray(self.adapter.decode_hidden(hidden))
                self.mgr.save_decode_hidden(sess, h, lengths - 1)
            m.save_d2h_bytes += h.nbytes
        dt = time.perf_counter() - t0
        for s in active:
            self._emit_token(s, int(toks[s.slot]))
            self.metrics.tbt_wall.append(dt)
        self.metrics.decode_steps += 1

    def _retire(self) -> None:
        for i, s in enumerate(self.slots):
            if s is None or not s.finished():
                continue
            if self.save_hidden:
                self._checkpoint(s)
            self._publish_slot(s)
            s.phase = Phase.DONE
            s.view.free()
            s.view = None
            self.slots[i] = None
            if self.on_finish is not None:
                r = s.request
                reason = ("stop" if (r.eos_token is not None and s.generated
                                     and s.generated[-1] == r.eos_token)
                          else "length")
                self.on_finish(s, reason)

    def _checkpoint(self, s: SequenceState) -> None:
        """Dump a resident sequence's restorable state (KV-layer tails,
        SSM states, token tail) through the predecessor of its last
        sampled token, whose KV does not exist yet."""
        sid = s.request.session_id
        with span(RETIRE_SAVE, self.metrics, "save_wall_sum"):
            self.mgr.saver.drain()
            saved = self.mgr.save_session_pause(
                sid, s.view.snapshot(), s.total_len - 1,
                tokens_tail=np.asarray(s.generated[s.tok_saved:-1],
                                       np.int32))
        self._count_save(saved)
        self._after_save(sid)
        s.tok_saved = len(s.generated) - 1

    def _after_save(self, sid: str) -> None:
        """On-save capacity hook: a demoted session whose stream was just
        extended is the anti-entropy ladder's re-promotion candidate."""
        if self.capacity is not None:
            self.capacity.consider_promotion(sid)

    # ------------------------------------------------------------ main loop
    def _sample_occupancy(self) -> None:
        occ = self.kv.occupancy()
        m = self.metrics
        m.live_tokens = occ.live_tokens
        m.reserved_tokens = occ.reserved_tokens
        m.free_blocks = occ.free_blocks
        m.live_tokens_peak = max(m.live_tokens_peak, occ.live_tokens)
        m.reserved_tokens_peak = max(m.reserved_tokens_peak,
                                     occ.reserved_tokens)
        resident = sum(1 for s in self.slots if s is not None)
        m.concurrent_peak = max(m.concurrent_peak, resident)
        if occ.reserved_tokens:
            m.occupancy_sum += occ.utilization
            m.occupancy_count += 1
        if self.prefix_sharing:
            m.dedup_host_bytes = int(self.mgr.store.dedup_bytes)
        if self.prefix_index is not None:
            pi = self.prefix_index
            m.prefix_lookups = pi.lookups
            m.prefix_hits = pi.hits
            m.prefix_hit_tokens = pi.hit_tokens
            m.cow_copies = self.kv.cow_copies
            m.shared_pages, m.private_pages = self.kv.shared_page_stats()
        # per-device gauges: pool rows from the backend, plus the share
        # of completed-restore wall spent inside the SPMD projection
        # launches (every mesh device participates in each launch, so the
        # utilization is common to all rows)
        util = (int(round(100.0 * m.restore_project_wall
                          / m.restore_wall_sum))
                if m.restore_wall_sum > 0 else 0)
        rows = self.kv.device_occupancy()
        for r in rows:
            r["proj_util_pct"] = util
        m.device_gauges = rows

    def step(self) -> None:
        self.step_count += 1
        # refresh the planner's view of restore contention (completed
        # restores lower the multiplicity; admission below may raise it)
        self._update_io_streams()
        self._admit()
        self._maybe_preempt()
        self._restore_step()
        prefilled = False
        for s in list(self.slots):
            if s is not None and s.phase == Phase.PREFILL:
                self._prefill_step(s)
                prefilled = True
        decoded_before = self.metrics.decode_steps
        self._decode_batch()
        self._sample_occupancy()
        self._retire()
        if self.capacity is not None:
            self.capacity.maintain(self)
            if not prefilled and self.metrics.decode_steps == decoded_before:
                # idle step (nothing prefilled or decoded — at most
                # restores ticked): run the anti-entropy promotion sweep
                # so demoted-but-idle sessions recover fp16 fidelity
                # without waiting for their next save
                self.capacity.sweep_promotions()

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                break
            self.step()
        self.mgr.saver.drain()

    def close(self) -> None:
        """Stop the two-stage saver's daemon threads (and surface any
        write error they captured). Call when done with the engine —
        tests that build many engines would otherwise leak threads."""
        self.mgr.saver.close()

    # --------------------------------------------------------------- output
    def result(self, session_id: str) -> List[int]:
        return list(self.sessions[session_id].generated)
