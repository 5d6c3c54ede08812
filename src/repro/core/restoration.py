"""Pipelined restoration executor (paper §4.1, DESIGN.md §5, §10, §11).

One source of truth for restoration: a ``Schedule`` compiles into an
ordered task graph (``compile_tasks``) of per-layer steps — striped
chunk-store IO reads, hidden→KV projections, recompute-prefix segments,
SSM state blob loads, and for enc-dec sessions the ``io_enc``
encoder-blob read + ``project_cross`` cross-KV projection pair (both
charged via ``CrossTimes``, so the cross side is costed, not a zero-time
blob). The same graph serves three consumers:

  * ``replay``                — virtual two-stream replay of a task order
                                under a hardware profile → ``Timeline``.
                                ``core.pipeline.simulate`` is exactly
                                ``replay(compile_tasks(methods), times)``.
  * ``RestorationExecutor``   — executes the graph *incrementally*
                                (``step(max_tasks)``), interleaving the IO
                                and compute streams event-driven, writing
                                each finished layer straight into a
                                ``RestoreSink`` (the serving engine's batch
                                slot — no intermediate B=1 cache).
  * prefetch                  — an executor without a sink may run IO
                                tasks early (queued sessions warm their
                                layer-0 reads before a slot frees).

The executor records the order tasks actually executed in; its reported
``Timeline`` is ``replay`` over that executed order, so the engine's
numbers and the analytic simulation can never drift apart.

Batched data path (DESIGN.md §10): projection tasks are compiled into
*groups* of ``group_size`` layers. A group executes as ONE stacked
device call — hidden states for all members land in a single
host→device upload, weights come from a once-per-``(model, params)``
``RestoreParamPack`` (device-stacked wk/wv/bk/bv/ln1 + precomputed RoPE
tables; no per-task param re-gather), and the result flows to the sink
through ``put_kv_group`` (one scatter for the whole group). Projection
shapes are bucketed to powers of two over the token dimension with
zero-padded tails, so every session in a bucket reuses one compiled
projection — zero recompiles across a serving run. ``replay`` models
groups as single compute tasks charged ``dispatch_overhead`` once, so
group size is a measurable bubbles-vs-dispatch trade-off.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.arch import BlockKind
from repro.core.cost_model import (MethodTimes, layer_costs,
                                   link_priced_times, method_times)
from repro.core.scheduler import Schedule
from repro.core.trace import (RESTORE_PROJECT, RESTORE_READ, RESTORE_UPLOAD,
                              RESTORE_WRITE, span)
from repro.kernels import ops
from repro.models.layers.norm import apply_norm
from repro.models.layers.rope import rope_angles
from repro.models.layers import attention as attn_lib

# Task kinds. IO-stream: io_h (hidden fetch), io_kv (raw KV fetch),
# io_enc (enc-dec: the saved encoder-output blob, sized in S_enc), blob
# (SSM-state/token whole-object reads — O(1) in tokens, charged zero
# virtual time as in the paper's model). Compute-stream: recompute (one
# prefix layer from tokens), project (hidden → K,V GEMM for a GROUP of
# layers — one device dispatch per group), project_cross (enc-dec: the
# single encoder output → cross-KV for ALL decoder layers).
IO_KINDS = ("io_h", "io_kv", "io_enc", "blob")
COMPUTE_KINDS = ("recompute", "project", "project_cross")


@dataclasses.dataclass(frozen=True)
class Task:
    kind: str                 # io_h|io_kv|io_enc|blob|recompute|project|
    #                           project_cross
    layer: int                # global layer index (-1 for blob/enc tasks;
    #                           first member for project groups)
    dep: Optional[int] = None  # task-list index that must execute first
    layers: Optional[Tuple[int, ...]] = None   # project group members
    deps: Optional[Tuple[int, ...]] = None     # all fetches a group needs

    @property
    def stream(self) -> str:
        return "io" if self.kind in IO_KINDS else "compute"

    @property
    def members(self) -> Tuple[int, ...]:
        return self.layers if self.layers is not None else (self.layer,)

    @property
    def all_deps(self) -> Tuple[int, ...]:
        if self.deps is not None:
            return self.deps
        return () if self.dep is None else (self.dep,)


@dataclasses.dataclass(frozen=True)
class CrossTimes:
    """Virtual durations of the enc-dec cross-restoration pair: the
    encoder-blob read (one (S_enc, D) tensor) and the cross-KV
    projection (K,V GEMMs for every decoder layer from that one blob —
    the 1 → 2·L expansion DESIGN.md §3 describes)."""

    io: float
    compute: float


def group_widths(group_size, n_hidden: int) -> Tuple[int, ...]:
    """Normalize a group plan — a uniform width (int) or an explicit
    partition (sequence of widths, the fetch-aligned form) — into the
    tuple of group widths that covers ``n_hidden`` hidden layers
    exactly. A short partition is extended with its last width; a long
    one is truncated; widths are clamped positive."""
    if n_hidden <= 0:
        return ()
    if isinstance(group_size, (tuple, list)):
        widths: List[int] = []
        total = 0
        for w in group_size:
            if total >= n_hidden:
                break
            w = max(int(w), 1)
            widths.append(min(w, n_hidden - total))
            total += widths[-1]
        last = widths[-1] if widths else 1
        while total < n_hidden:
            widths.append(min(last, n_hidden - total))
            total += widths[-1]
        return tuple(widths)
    g = max(int(group_size), 1)
    return tuple(min(g, n_hidden - s) for s in range(0, n_hidden, g))


def compile_tasks(methods: Sequence[str], *, n_blobs: int = 0,
                  group_size=1, cross: bool = False) -> List[Task]:
    """Compile a per-layer method assignment into the ordered task graph.

    List order encodes per-stream priority (paper §4.1): the IO stream
    runs hidden fetches first (layer order) so projections can start,
    then the encoder blob (when ``cross`` — its projection gates the
    first cross-attention), then KV fetches fill the IO tail; the
    compute stream runs the recompute prefix from t=0, then projections
    in fetch order, then the cross projection. A projection group
    depends on *all* of its members' fetches; with ``group_size=1`` this
    degenerates exactly to the per-layer graph.

    ``group_size`` is either a uniform width (int) or an explicit
    partition — a tuple of widths, the fetch-aligned non-uniform form
    (small leading groups so projection starts the moment the first
    stripe lands, wide tail groups to amortize dispatch)."""
    tasks: List[Task] = []
    io_of: Dict[int, int] = {}
    hidden_layers = [i for i, m in enumerate(methods) if m == "hidden"]
    for i in hidden_layers:
        io_of[i] = len(tasks)
        tasks.append(Task("io_h", i))
    io_enc = None
    if cross:
        io_enc = len(tasks)
        tasks.append(Task("io_enc", -1))
    for i, m in enumerate(methods):
        if m == "kv":
            tasks.append(Task("io_kv", i))
    for _ in range(n_blobs):
        tasks.append(Task("blob", -1))
    for i, m in enumerate(methods):
        if m == "recompute":
            tasks.append(Task("recompute", i))
    s = 0
    for w in group_widths(group_size, len(hidden_layers)):
        grp = tuple(hidden_layers[s:s + w])
        s += w
        deps = tuple(io_of[i] for i in grp)
        tasks.append(Task("project", grp[0], dep=deps[-1], layers=grp,
                          deps=deps))
    if cross:
        tasks.append(Task("project_cross", -1, dep=io_enc))
    return tasks


def task_duration(task: Task, times: Sequence[MethodTimes],
                  dispatch_overhead: float = 0.0,
                  cross_times: Optional[CrossTimes] = None) -> float:
    """Virtual duration of one task. Compute-stream tasks carry the
    per-dispatch overhead once — a projection group amortizes it over
    all members (the whole point of grouping)."""
    if task.kind == "io_h":
        return times[task.layer].io_h
    if task.kind == "io_kv":
        return times[task.layer].io_kv
    if task.kind == "io_enc":
        return cross_times.io if cross_times else 0.0
    if task.kind == "recompute":
        return times[task.layer].c_token + dispatch_overhead
    if task.kind == "project":
        return (sum(times[li].c_h for li in task.members)
                + dispatch_overhead)
    if task.kind == "project_cross":
        return ((cross_times.compute if cross_times else 0.0)
                + dispatch_overhead)
    return 0.0                                 # blob reads: O(1) in tokens


def task_links(tasks: Sequence[Task],
               layer_links: Optional[Dict[int, int]])\
        -> Optional[Dict[int, int]]:
    """Task-index → NIC-link map for ``replay``: each per-layer IO task
    inherits the link its layer's stripes live on (layer placement only;
    chunk placement has no per-layer link and returns None)."""
    if not layer_links:
        return None
    out = {}
    for i, t in enumerate(tasks):
        if t.stream == "io" and t.layer >= 0:
            link = layer_links.get(t.layer)
            if link is not None:
                out[i] = link
    return out


def replay(tasks: Sequence[Task], times: Sequence[MethodTimes],
           order: Optional[Sequence[int]] = None,
           dispatch_overhead: float = 0.0,
           cross_times: Optional[CrossTimes] = None,
           durations: Optional[Dict[int, float]] = None,
           links: Optional[Dict[int, int]] = None):
    """Two-stream virtual replay of ``tasks`` in ``order`` → Timeline.

    Each stream is serial; a compute task with deps starts no earlier
    than the completion of ALL its deps on the IO stream. ``order``
    defaults to list order (the compiled priority); the executor passes
    the order it actually ran. ``durations`` overrides individual task
    durations (task index → seconds) with *measured* values — the
    executor's observed timeline replays the same graph under what each
    task actually took, so predicted-vs-measured makespan error is a
    like-for-like comparison.

    ``links`` (task index → NIC link, from ``task_links``) splits the IO
    stream into one serial queue PER LINK — the distributed store's
    layer-striped reads genuinely overlap across shards, so the IO
    finish is the max over link clocks, not their sum. Tasks without an
    entry share queue 0 (the one-host degenerate case)."""
    from repro.core.pipeline import Timeline
    if order is None:
        order = range(len(tasks))
    done = [0.0] * len(tasks)
    io_clocks: Dict[int, float] = {}
    comp_t = io_busy = comp_busy = 0.0
    for idx in order:
        t = tasks[idx]
        if durations is not None and idx in durations:
            dur = durations[idx]
        else:
            dur = task_duration(t, times, dispatch_overhead, cross_times)
        if t.stream == "io":
            link = links.get(idx, 0) if links else 0
            io_clocks[link] = io_clocks.get(link, 0.0) + dur
            io_busy += dur
            done[idx] = io_clocks[link]
        else:
            deps = t.all_deps
            start = comp_t if not deps else max(
                comp_t, max(done[d] for d in deps))
            comp_t = start + dur
            comp_busy += dur
            done[idx] = comp_t
    io_t = max(io_clocks.values(), default=0.0)
    return Timeline(max(io_t, comp_t), io_busy, comp_busy, io_t, comp_t)


def _cross_times_at(cfg, hw, dtype_bytes: int, enc_len: int, *,
                    profile=None, io_streams: int = 1)\
        -> Optional[CrossTimes]:
    if not enc_len:
        return None
    tms = [method_times(c, hw, profile=profile, io_streams=io_streams)
           for c in layer_costs(cfg, int(enc_len), dtype_bytes)]
    return CrossTimes(io=tms[0].io_h, compute=sum(t.c_h for t in tms))


def cross_restore_times(mgr, enc_len: int) -> Optional[CrossTimes]:
    """CrossTimes for an enc-dec session with ``enc_len`` stored encoder
    positions (None when unknown/zero — old manifests predate the
    ``enc_len`` field and fall back to the paper's zero-cost blob
    model). IO: one (S_enc, D) blob; compute: the K,V projection of
    that blob for every decoder layer."""
    return _cross_times_at(mgr.cfg, mgr.hw, mgr.dtype_bytes, enc_len,
                           profile=getattr(mgr, "profile", None),
                           io_streams=getattr(mgr, "io_streams", 1))


GROUP_SIZE_CANDIDATES = (1, 2, 4, 8)


def fetch_aligned_partition(methods: Sequence[str],
                            times: Sequence[MethodTimes], *,
                            dispatch_overhead: float = 0.0,
                            links: Optional[Dict[int, int]] = None)\
        -> Tuple[int, ...]:
    """Group boundaries at fetch-completion times (ROADMAP: "non-uniform
    groups aligned to fetch completions — the open half of group-size
    tuning").

    A projection group cannot start before its LAST member's hidden
    fetch lands, so a wide first group leaves the compute stream idle
    for the whole fetch ramp while a width-1 tail pays dispatch overhead
    per layer. The optimal shape is non-uniform: boundaries placed where
    the fetch stream has just caught up — small leading groups, wide
    tail groups. Exact O(n²) DP over the hidden layers: ``f(j)`` =
    earliest compute-stream completion of the first ``j`` projections,
    with fetch ``j`` landing at the io_h prefix sum and the compute
    stream starting busy for the recompute prefix (which replay runs
    before any projection).

    ``links`` (layer → NIC link, distributed store) makes the fetch
    completions per-shard: each link runs its own serial queue, so fetch
    ``j`` lands on its OWN link's running clock — much earlier than the
    one-host prefix sum when layers stripe round-robin. The DP gates a
    group ending at ``j`` on the prefix-max of the completions (the
    group needs ALL members' fetches; per-link clocks are not monotone
    in ``j``), which collapses to the plain prefix sum on one host."""
    hidden = [i for i, m in enumerate(methods) if m == "hidden"]
    n = len(hidden)
    if n <= 1:
        return (1,) * n
    fetch_done = [0.0] * (n + 1)            # per-fetch completion times
    link_clock: Dict[int, float] = {}
    for j, li in enumerate(hidden):
        link = links.get(li, 0) if links else 0
        link_clock[link] = link_clock.get(link, 0.0) + times[li].io_h
        fetch_done[j + 1] = link_clock[link]
    gate = [0.0] * (n + 1)                  # prefix max: all fetches <= j
    for j in range(1, n + 1):
        gate[j] = max(gate[j - 1], fetch_done[j])
    busy0 = sum(times[li].c_token + dispatch_overhead
                for li, m in enumerate(methods) if m == "recompute")
    c_h = [times[li].c_h for li in hidden]
    f = [0.0] * (n + 1)
    parent = [0] * (n + 1)
    f[0] = busy0
    for j in range(1, n + 1):
        best = None
        proj = 0.0
        for i in range(j - 1, -1, -1):      # group = hidden[i:j]
            proj += c_h[i]
            t = max(f[i], gate[j]) + dispatch_overhead + proj
            if best is None or t < best:
                best, parent[j] = t, i
        f[j] = best
    widths: List[int] = []
    j = n
    while j > 0:
        widths.append(j - parent[j])
        j = parent[j]
    return tuple(reversed(widths))


def choose_group_size(cfg, hw, n_tokens: int, methods: Sequence[str], *,
                      dtype_bytes: int = 2, n_blobs: int = 0,
                      cross: bool = False, enc_len: int = 0,
                      profile=None, io_streams: int = 1,
                      fetch_aligned: bool = False,
                      topology=None, link_load=None):
    """Auto group-size planning (ROADMAP "restoration group-size
    tuning", planning half): replay the grouped task graph over the
    hardware profile for g ∈ {1, 2, 4, 8, L} — plus, with
    ``fetch_aligned``, the non-uniform fetch-completion partition — and
    take the makespan argmin. The same group-aware cost model the
    executor's timeline and ``capacity.restore_makespan`` use, so the
    planner and the bake-off metric cannot disagree. Ties prefer fewer
    groups (equal modeled makespan, strictly fewer real device
    dispatches). Returns an int (uniform width) or a tuple of widths
    (non-uniform partition).

    ``profile``/``io_streams`` price the replay with measured rates and
    the current restore multiplicity — the self-calibrating half. The
    choice is computed at the ``s_bucket`` of ``n_tokens`` (and of
    ``enc_len``), NOT the exact lengths: the compiled projection shape
    is ``(G_pad, S_bucket, D)``, so every session in a bucket must pick
    the same plan or the auto knob would reintroduce the per-session
    recompiles the bucketing exists to prevent (DESIGN.md §10)."""
    n_hidden = sum(1 for m in methods if m == "hidden")
    if n_hidden <= 1:
        return 1
    n_bucket = s_bucket(max(int(n_tokens), 1))
    times, layer_links = link_priced_times(
        layer_costs(cfg, n_bucket, dtype_bytes), hw, profile=profile,
        io_streams=io_streams, topology=topology, link_load=link_load)
    cross_times = (_cross_times_at(cfg, hw, dtype_bytes, s_bucket(enc_len),
                                   profile=profile, io_streams=io_streams)
                   if cross and enc_len else None)
    # sharded pricing (DESIGN.md §16): ``times`` already divides the
    # projection compute across hw.mesh_devices (method_times), and the
    # per-launch dispatch overhead is read from the mesh's own profiler
    # cell — an SPMD launch pays it once, so under tp > 1 the compute
    # side of the argmin shrinks and the optimum shifts toward SMALLER
    # groups (less amortization needed per dispatch).
    overhead = getattr(hw, "dispatch_overhead", 0.0)
    if profile is not None:
        measured = profile.dispatch_overhead(
            mesh=getattr(hw, "mesh_devices", 1))
        if measured is not None:
            overhead = measured
    cands = sorted({g for g in GROUP_SIZE_CANDIDATES if g < n_hidden}
                   | {n_hidden})

    def makespan(g):
        tasks = compile_tasks(tuple(methods), n_blobs=n_blobs,
                              group_size=g, cross=cross)
        return replay(tasks, times, dispatch_overhead=overhead,
                      cross_times=cross_times,
                      links=task_links(tasks, layer_links)).makespan

    best = min(cands, key=lambda g: (makespan(g), -g))
    if not fetch_aligned:
        return best
    part = fetch_aligned_partition(methods, times,
                                   dispatch_overhead=overhead,
                                   links=layer_links)
    widths = set(part)
    if len(widths) == 1:                 # degenerate partition is uniform
        part = widths.pop()
        return part if makespan(part) < makespan(best) else best
    # prefer the uniform plan on ties: same modeled makespan, simpler
    return part if makespan(part) < makespan(best) else best


# ----------------------------------------------------- hidden-state codec
def quantize_hidden_int8(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-token int8 quantization of stored hidden states (save path in
    hcache, dequantized here on restore — one codec for both)."""
    scale = np.abs(x).max(axis=-1, keepdims=True).astype(np.float32) / 127.0
    scale = np.maximum(scale, 1e-8)
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_hidden_int8(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * scale.astype(np.float32)


# ------------------------------------------------------------------- sinks
class RestoreSink:
    """Receives restored state one piece at a time, in any order."""

    def put_kv(self, row: int, k, v, start: int = 0) -> None:
        """One attention layer's KV at token offset ``start`` (k, v:
        (1, n, kv_heads, head_dim)); row indexes the stacked-KV buffer.
        ``start > 0`` is the restore-skip path: tokens [0, start) are
        already resident (shared prefix) and the executor only ships the
        suffix."""
        raise NotImplementedError

    def put_kv_group(self, rows: Sequence[int], k, v,
                     start: int = 0) -> None:
        """A whole projection group's KV in one call; rows are the
        stacked-KV buffer rows, k/v: (G, 1, n, kv_heads, head_dim).
        Default: per-row fallback — batching sinks (ViewSink) override
        with a single scatter."""
        for g, row in enumerate(rows):
            self.put_kv(row, k[g], v[g], start)

    def put_states(self, conv, ssm) -> None:
        raise NotImplementedError

    def put_cross(self, ck, cv, enc_len: int) -> None:
        raise NotImplementedError

    def finish(self, n_tokens: int) -> None:
        raise NotImplementedError


class CacheAssembler(RestoreSink):
    """Builds the family-specific B=1 cache dict — the standalone
    ``HCacheManager.restore`` API (tests, offline tools). The serving
    engine does NOT use this: its sink writes batch-slot buffers."""

    def __init__(self, model):
        self.model = model
        self.k_parts: Dict[int, jnp.ndarray] = {}
        self.v_parts: Dict[int, jnp.ndarray] = {}
        self.states = None
        self.cross = None
        self.cache: Optional[dict] = None

    def put_kv(self, row, k, v, start=0):
        if start:
            raise ValueError(
                "CacheAssembler builds a standalone B=1 cache from token "
                "0 — restore-skip (start > 0) is a serving-engine path "
                "(ViewSink over a slot that already holds the prefix)")
        self.k_parts[row] = k
        self.v_parts[row] = v

    def put_states(self, conv, ssm):
        self.states = (conv, ssm)

    def put_cross(self, ck, cv, enc_len):
        self.cross = (ck, cv, enc_len)

    def finish(self, n_tokens):
        model = self.model
        lengths = jnp.asarray([n_tokens], jnp.int32)
        if model.kind == "ssm":
            conv, ssm = self.states
            self.cache = {"conv": conv, "ssm": ssm, "lengths": lengths}
            return
        rows = sorted(self.k_parts)
        k = jnp.stack([self.k_parts[r] for r in rows]).astype(model.dtype)
        v = jnp.stack([self.v_parts[r] for r in rows]).astype(model.dtype)
        if model.kind == "lm":
            self.cache = {"k": k, "v": v, "lengths": lengths}
        elif model.kind == "hybrid":
            conv, ssm = self.states
            self.cache = {"attn_k": k, "attn_v": v, "conv": conv,
                          "ssm": ssm, "lengths": lengths}
        else:                                   # encdec
            ck, cv, enc_len = self.cross
            self.cache = {"self_k": k, "self_v": v, "cross_k": ck,
                          "cross_v": cv,
                          "enc_len": jnp.asarray(enc_len, jnp.int32),
                          "lengths": lengths}


# ---------------------------------------------------------- param packing
def s_bucket(n: int, minimum: int = 16) -> int:
    """Power-of-two token bucket for projection shapes: all sessions in
    a bucket share one compiled projection (zero recompiles across a
    serving run); the padded tail is zeros and its outputs are sliced
    away before the sink."""
    b = max(int(minimum), 1)
    while b < n:
        b <<= 1
    return b


class RestoreParamPack:
    """Device-resident restoration weights for every attention layer,
    built once per ``(model, params)`` and shared by all executors.

    The stacks are (A, …) with A = number of attention layers, row
    order == the stacked-KV row order the sinks use — so a projection
    group gathers ``wk[rows]`` inside its jitted call instead of
    re-running ``jax.tree.map`` over the whole parameter stack per
    task. For lm/hybrid/encdec the per-layer params are already
    layer-stacked device arrays (scan-over-layers init), so building
    the pack is reference-taking, not copying. RoPE cos/sin tables are
    precomputed up to the largest bucket seen and sliced per bucket."""

    def __init__(self, *, ln_scale, ln_bias, wk, wv, bk, bv, norm_kind,
                 norm_eps, head_dim, use_rope, rope_theta, dtype,
                 tp_ctx=None):
        self.ln_scale = ln_scale        # (A, D)
        self.ln_bias = ln_bias          # (A, D) | None (rmsnorm)
        self.wk = wk                    # (A, D, KV)
        self.wv = wv                    # (A, D, KV)
        self.bk = bk                    # (A, KV) | None
        self.bv = bv                    # (A, KV) | None
        self.norm_kind = norm_kind
        self.norm_eps = float(norm_eps)
        self.head_dim = int(head_dim)
        self.use_rope = bool(use_rope)
        self.rope_theta = float(rope_theta)
        self.dtype = dtype
        self.n_rows = int(wk.shape[0])
        # tensor-parallel context the weight stacks are sharded under
        # (DESIGN.md §16): wk/wv/bk/bv live KV-axis-sharded across its
        # mesh (the flattened KV axis is heads-leading, so tp contiguous
        # chunks == head groups), hidden/norm/RoPE inputs replicate, and
        # the projection outputs carry the KV-head sharding straight into
        # the shard-local page-pool scatter. None = single device.
        self.tp_ctx = tp_ctx
        self._spmd = tp_ctx is not None and tp_ctx.spmd
        self._cos = None
        self._sin = None
        self._slices: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]] = {}

    @property
    def out_sharding(self):
        """NamedSharding of the projection outputs (G, S, KV) — KV-axis
        sharded over the mesh — or None on a single device."""
        if not self._spmd:
            return None
        return self.tp_ctx.kv_sharding(3, 2)

    def place_hidden(self, stack):
        """Commit one group's hidden stack to the device(s): replicated
        across the mesh under SPMD (every device projects its own heads
        from the full stack), a plain single upload otherwise."""
        if not self._spmd:
            return jnp.asarray(stack)
        return self.tp_ctx.replicate(jnp.asarray(stack))

    def rope_tables(self, n_pos: int,
                    start: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """cos/sin (n_pos, head_dim//2) for absolute positions
        [start, start + n_pos); the backing table grows by powers of two
        and per-(start, bucket) slices are cached so repeated restores
        reuse the same device arrays. ``start > 0`` serves restore-skip:
        a suffix restore applies RoPE at its true absolute positions."""
        got = self._slices.get((start, n_pos))
        if got is not None:
            return got
        end = start + n_pos
        if self._cos is None or self._cos.shape[0] < end:
            cap = s_bucket(end, minimum=128)
            cos, sin = rope_angles(jnp.arange(cap), self.head_dim,
                                   self.rope_theta)
            self._cos, self._sin = cos, sin
            self._slices.clear()
        sl = (self._cos[start:end], self._sin[start:end])
        if self._spmd:
            # replicated commit: the sliced tables feed an SPMD launch
            # whose weight inputs span the mesh
            sl = (self.tp_ctx.replicate(sl[0]),
                  self.tp_ctx.replicate(sl[1]))
        self._slices[(start, n_pos)] = sl
        return sl


def build_param_pack(model, params, tp_ctx=None)\
        -> Optional[RestoreParamPack]:
    """Pack the attention-restoration weights of ``params``. None for
    attention-free (ssm) stacks.

    With a live ``tp_ctx`` (distributed/tp.py) the weight stacks are
    committed sharded on the flattened KV output axis — tp contiguous
    chunks of (A, D, KV) == KV-head groups since the flatten is
    heads-leading — so ``_project_group_jit`` compiles to one SPMD
    program in which each device projects only its own heads, and the
    outputs land already sharded for the shard-local pool scatter."""
    kind = model.kind
    if kind == "ssm":
        return None
    if kind == "lm":
        blocks, attn_key, attn_h = params["blocks"], "attn", model.h.attn
    elif kind == "hybrid":
        blocks, attn_key, attn_h = params["attn"], "attn", model.h.lm.attn
    else:                                       # encdec (decoder self-attn)
        blocks, attn_key, attn_h = (params["dec_blocks"], "self_attn",
                                    model.h.attn)
    ap = blocks[attn_key]
    ln = blocks["ln1"]
    wk, wv = ap["wk"], ap["wv"]
    bk, bv = ap.get("bk"), ap.get("bv")
    ln_scale, ln_bias = ln["scale"], ln.get("bias")
    if tp_ctx is not None and tp_ctx.spmd:
        tp_ctx.validate_heads(wk.shape[-1] // attn_h.head_dim)
        wk = tp_ctx.shard_kv(wk, 2)
        wv = tp_ctx.shard_kv(wv, 2)
        bk = tp_ctx.shard_kv(bk, 1) if bk is not None else None
        bv = tp_ctx.shard_kv(bv, 1) if bv is not None else None
        ln_scale = tp_ctx.replicate(ln_scale)
        ln_bias = (tp_ctx.replicate(ln_bias)
                   if ln_bias is not None else None)
    return RestoreParamPack(
        ln_scale=ln_scale, ln_bias=ln_bias,
        wk=wk, wv=wv, bk=bk, bv=bv,
        norm_kind=model.cfg.norm, norm_eps=model.cfg.norm_eps,
        head_dim=attn_h.head_dim, use_rope=attn_h.use_rope,
        rope_theta=attn_h.rope_theta, dtype=model.dtype,
        tp_ctx=tp_ctx)


# number of times the grouped projection has been TRACED (== compiled):
# the body below runs once per compilation, so this is the recompile
# counter the bucketing regression test and bench_restore_batch read.
_PROJECTION_TRACES = [0]


def projection_trace_count() -> int:
    return _PROJECTION_TRACES[0]


@functools.partial(jax.jit, static_argnames=(
    "norm_kind", "eps", "head_dim", "use_rope", "dtype", "use_pallas",
    "interpret", "kv_sharding"))
def _project_group_jit(hidden, rows, ln_scale, ln_bias, wk, wv, bk, bv,
                       cos, sin, *, norm_kind, eps, head_dim, use_rope,
                       dtype, use_pallas, interpret, kv_sharding=None):
    """ONE device dispatch for a whole projection group.

    hidden (G, S_bucket, D) stored-dtype upload; rows (G,) pack-row ids
    (traced, so group membership never retraces); weight stacks are the
    full pack — the gather fuses into the compiled program. Returns
    (k, v): (G, S_bucket, Kv, hd) in the model dtype.

    ``kv_sharding`` (a NamedSharding, static — hashable, so each mesh
    width compiles exactly once per bucket and the zero-recompile
    invariant holds per (bucket, tp)) pins the outputs sharded on the
    flattened-KV axis: with the weight stacks committed the same way the
    whole call is one SPMD launch where each device projects only its
    heads and no gather ever crosses devices (DESIGN.md §16)."""
    _PROJECTION_TRACES[0] += 1
    h = hidden.astype(dtype)
    # the model's own norm, with per-group-row params broadcast over S —
    # restore must stay byte-equal to what project_qkv consumed
    ln = {"scale": ln_scale[rows][:, None, :]}
    if ln_bias is not None:
        ln["bias"] = ln_bias[rows][:, None, :]
    normed = apply_norm(ln, h, norm_kind, eps)
    k, v = ops.restore_kv_grouped(
        normed, wk[rows], wv[rows],
        bk[rows] if bk is not None else None,
        bv[rows] if bv is not None else None,
        cos, sin, head_dim=head_dim, use_rope=use_rope,
        use_pallas=use_pallas, interpret=interpret,
        kv_sharding=kv_sharding)
    G, S, KV = k.shape
    return (k.reshape(G, S, KV // head_dim, head_dim),
            v.reshape(G, S, KV // head_dim, head_dim))


# -------------------------------------------------------- param projections
def subset_blocks(model, params, idx: List[int]):
    """Stacked block params for the given global layer indices (legacy
    per-layer reference path — the executor now uses RestoreParamPack)."""
    arr = np.asarray(idx)
    blocks = (params["blocks"] if model.kind == "lm" else
              params["attn"] if model.kind == "hybrid" else
              params["dec_blocks"])
    if model.kind == "hybrid":
        # attn params are stacked per super-block; map layer->super idx
        k = model.h.k
        arr = np.asarray([i // k for i in idx])
    return jax.tree.map(lambda x: x[arr], blocks)


def project_hidden(model, blocks, hidden, pos):
    """K,V projection of saved hidden states (the paper's core GEMM).

    hidden: (L_sub, 1, n, D); returns (k, v): (L_sub, 1, n, Kv, hd).
    Reference implementation for the grouped device path above (the
    byte-equivalence tests compare the two)."""
    cfg, mh = model.cfg, model.h
    attn_h = mh.attn if hasattr(mh, "attn") else mh.lm.attn
    attn_key = "attn" if model.kind in ("lm", "hybrid") else "self_attn"

    def one(bp, hl):
        normed = apply_norm(bp["ln1"], hl, cfg.norm, cfg.norm_eps)
        ap = bp[attn_key] if attn_key in bp else bp
        return attn_lib.restore_kv(ap["wk"], ap["wv"], ap.get("bk"),
                                   ap.get("bv"), normed, attn_h,
                                   jnp.broadcast_to(pos, hl.shape[:2]))

    return jax.vmap(one)(blocks, hidden)


# --------------------------------------------------------------- executor
class RestorationExecutor:
    """Incremental, sink-directed execution of one session's restoration.

    Created by ``HCacheManager.begin_restore``. ``step(max_tasks)`` runs a
    bounded number of tasks, event-driven across the two virtual streams
    (whichever stream's clock is behind goes next, so layers finish in
    pipelined order); ``prefetch_step`` runs IO tasks only (no sink
    needed). All finished pieces flow to the sink immediately; pieces
    produced before a sink is attached are buffered (numpy/array handles,
    never a stacked B=1 cache) and flushed on ``attach_sink``.

    Projection tasks are GROUPS (``mgr.restore_group_size`` layers): one
    batched upload + one stacked projection + one grouped sink write per
    group. ``dispatch_count`` tallies the device dispatches the restore
    issued; ``project_wall`` the wall seconds inside projection calls —
    both surfaced by bench_restore_batch. ``read_wall``, ``upload_wall``
    and ``write_wall`` are the seconds of the executor's store-read,
    host->device and cache-write spans (core/trace.py), ``h2d_bytes``
    the bytes it handed to the device; the engine folds them into its
    metrics when the restore completes."""

    def __init__(self, mgr, params, session: str,
                 sink: Optional[RestoreSink] = None, start_token: int = 0):
        manifest = mgr.store.get_manifest(session)
        if manifest is None:
            raise KeyError(f"no stored state for session {session!r}")
        self.mgr = mgr
        self.model = mgr.model
        self.params = params
        self.session = session
        self.sink = sink
        self.n_tokens = int(manifest["n_tokens"])
        self.methods = tuple(manifest["methods"])
        # restore-skip (DESIGN.md §12): tokens [0, start_token) are
        # already resident in the slot via a shared prefix, so the task
        # graph restores only the suffix — IO reads start at the chunk
        # containing the divergence token, projections run at the
        # suffix's bucket, and sink writes land at the offset. The
        # recompute method rebuilds the residual stream from token 0 and
        # cannot skip (the engine passes start_token=0 for those).
        start_token = int(start_token)
        if start_token and any(m == "recompute" for m in self.methods):
            raise ValueError("restore-skip is incompatible with "
                             "recompute-method layers (the residual "
                             "stream rebuild starts at token 0)")
        if not 0 <= start_token < self.n_tokens:
            raise ValueError(f"start_token {start_token} outside "
                             f"[0, {self.n_tokens})")
        self.start_token = start_token
        self.n_eff = self.n_tokens - start_token
        self.schedule = Schedule(self.methods, 0.0, 0.0, 0.0, 0.0)
        self.compress = manifest.get("compress", mgr.compress)
        mgr.store.sync_clocks(0.0)

        kinds = mgr.cfg.block_kinds()
        adapter = self.model.adapter
        self._attn_layers = [i for i, k in enumerate(kinds)
                             if k == BlockKind.ATTENTION]
        self._row_of = {li: r for r, li in enumerate(self._attn_layers)}
        # enc-dec: cross restoration rides two dedicated tasks (io_enc +
        # project_cross) whose durations scale with the stored encoder
        # length; other families' state blobs stay zero-cost reads
        self.has_cross = adapter.has_cross
        self.enc_len = int(manifest.get("enc_len", 0))
        self.cross_times = (cross_restore_times(mgr, self.enc_len)
                            if self.has_cross else None)
        gs = mgr.resolve_group_size(self.n_eff, self.methods,
                                    enc_len=self.enc_len)
        # int = uniform width; tuple = fetch-aligned non-uniform partition
        self.group_size = (tuple(int(w) for w in gs)
                           if isinstance(gs, (tuple, list))
                           else max(int(gs), 1))
        self.pack: Optional[RestoreParamPack] = mgr.param_pack(params)
        # stable padded group width: every group in this restore uploads
        # and projects the same (G_pad, S_bucket, D) shape — for a
        # non-uniform partition that is the WIDEST group's width — so a
        # run compiles at most one projection per (bucket, codec)
        n_attn_hidden = sum(1 for i, m in enumerate(self.methods)
                            if m == "hidden" and i in self._row_of)
        max_w = (max(self.group_size) if isinstance(self.group_size, tuple)
                 else self.group_size)
        self._g_pad = min(max_w, max(n_attn_hidden, 1))
        # calibration inputs: the manager's measured profile (rates +
        # dispatch overhead, when sampled) and the engine-reported IO
        # multiplicity price this executor's virtual timeline the same
        # way the planner priced its schedule
        self.profile = getattr(mgr, "profile", None)
        self.io_streams = max(int(getattr(mgr, "io_streams", 1)), 1)
        # tensor-parallel mesh width (DESIGN.md §16): compute samples are
        # recorded into the mesh's own profiler cell and the per-launch
        # dispatch overhead is read back from it
        self.mesh = max(int(getattr(mgr.hw, "mesh_devices", 1)), 1)
        self.dispatch_overhead = getattr(mgr.hw, "dispatch_overhead", 0.0)
        if self.profile is not None:
            measured = self.profile.dispatch_overhead(mesh=self.mesh)
            if measured is not None:
                self.dispatch_overhead = measured
        self.tasks = compile_tasks(self.methods,
                                   n_blobs=adapter.n_state_blobs,
                                   group_size=self.group_size,
                                   cross=self.has_cross)
        self.costs = layer_costs(mgr.cfg, self.n_eff, mgr.dtype_bytes)
        # distributed store: per-layer IO priced on the links each
        # layer's stripes occupy; one-host stores degrade to the uniform
        # io_streams stretch inside link_priced_times
        topo_fn = getattr(mgr.store, "shard_topology", None)
        self.topology = topo_fn() if topo_fn is not None else None
        self.link_load = getattr(mgr, "link_load", None)
        self.times, self._layer_links = link_priced_times(
            self.costs, mgr.hw, profile=self.profile,
            io_streams=self.io_streams, topology=self.topology,
            link_load=self.link_load)
        self._task_links = task_links(self.tasks, self._layer_links)
        self.executed: List[int] = []
        self._done = [False] * len(self.tasks)
        # event-driven stream interleaving state (one virtual IO clock
        # per NIC link; one-host stores use the single queue 0)
        self._io_queue = [i for i, t in enumerate(self.tasks)
                          if t.stream == "io"]
        self._comp_queue = [i for i, t in enumerate(self.tasks)
                            if t.stream == "compute"]
        self._io_clock = 0.0
        self._io_clocks: Dict[int, float] = {}
        self._comp_clock = 0.0
        self._hbuf: Dict[int, np.ndarray] = {}
        # async submit/complete state: io_h tickets awaiting their
        # projection, io_kv tickets reaped as they land, the enc blob
        # ticket awaiting project_cross
        self._hio: Dict[int, tuple] = {}
        self._kvio: List[tuple] = []
        self._encio = None
        self._pending: List[Tuple[str, tuple]] = []   # sink-less buffer
        # recompute-prefix carry
        self._re_layers = [i for i, m in enumerate(self.methods)
                           if m == "recompute"]
        self._re_x = None
        self._re_pos = None
        self._re_windows = None
        self._re_next = 0
        self._finished = False
        self.wall_time = 0.0
        self.project_wall = 0.0
        self.read_wall = 0.0
        self.upload_wall = 0.0
        self.write_wall = 0.0
        self.h2d_bytes = 0
        self.dispatch_count = 0
        self._enc_out: Optional[np.ndarray] = None
        # online profiling (DESIGN.md §13): per-task observed durations.
        # IO tasks read the striped store's accumulated service time
        # (virtual seconds on SimulatedSSD, nothing on plain DRAM);
        # compute tasks are wall-clocked, skipping any call that traced
        # (compile time is not dispatch time). Each sample is folded
        # into mgr.profile and kept here for ``measured_timeline``.
        self.observed: Dict[int, float] = {}
        self._bucket = s_bucket(max(self.n_eff, 1))
        self._enc_bucket = s_bucket(self.enc_len) if self.enc_len else 0
        n_timed = getattr(mgr.store, "n_timed_devices", None)
        self._n_timed = n_timed() if n_timed is not None else 0
        # the plan this graph was compiled under, for the engine's
        # predicted-vs-measured gauge (list order == compiled priority)
        self.predicted_makespan = replay(
            self.tasks, self.times,
            dispatch_overhead=self.dispatch_overhead,
            cross_times=self.cross_times,
            links=self._task_links).makespan

    # ------------------------------------------------------------- plumbing
    @property
    def done(self) -> bool:
        # with async IO, a dispatched io_kv task is not finished until
        # its ticket is reaped and the KV emitted to the sink
        return all(self._done) and not self._kvio

    def links_touched(self) -> Tuple[int, ...]:
        """NIC links this restore's IO occupies — what the engine folds
        into the fleet ``LinkLoad`` for contention pricing."""
        topo = self.topology
        if topo is None or topo.n_shards <= 1:
            return (0,)
        if topo.placement == "chunk":
            return tuple(range(topo.n_shards))
        return tuple(sorted({topo.links_for_layer(li)[0]
                             for li, m in enumerate(self.methods)
                             if m in ("hidden", "kv")}))

    def attach_sink(self, sink: RestoreSink) -> None:
        self.sink = sink
        with span(RESTORE_WRITE, self, "write_wall"):
            for op, args in self._pending:
                getattr(sink, op)(*args)
        self._pending.clear()

    def _emit(self, op: str, *args) -> None:
        if self.sink is not None:
            with span(RESTORE_WRITE, self, "write_wall"):
                getattr(self.sink, op)(*args)
        else:
            self._pending.append((op, args))

    def _upload(self, host: np.ndarray):
        """One host array handed to the device."""
        with span(RESTORE_UPLOAD, self, "upload_wall"):
            out = jnp.asarray(host)
        self.h2d_bytes += host.nbytes
        return out

    def timeline(self):
        """Timeline derived from the order tasks actually executed in."""
        order = self.executed + [i for i in range(len(self.tasks))
                                 if not self._done[i]]
        return replay(self.tasks, self.times, order,
                      dispatch_overhead=self.dispatch_overhead,
                      cross_times=self.cross_times,
                      links=self._task_links)

    def measured_timeline(self):
        """``timeline()`` with each task's duration replaced by what it
        was *observed* to take (modeled values fill unmeasured tasks) —
        the "measured" side of the engine's predicted-vs-measured
        makespan gauge."""
        order = self.executed + [i for i in range(len(self.tasks))
                                 if not self._done[i]]
        return replay(self.tasks, self.times, order,
                      dispatch_overhead=self.dispatch_overhead,
                      cross_times=self.cross_times,
                      durations=self.observed,
                      links=self._task_links)

    # ------------------------------------------------------------ stepping
    def _ready(self, idx: int) -> bool:
        t = self.tasks[idx]
        if any(not self._done[d] for d in t.all_deps):
            return False
        if t.kind == "recompute":
            # prefix layers carry the residual stream in order
            return self._re_layers[self._re_next] == t.layer
        return True

    def _pick(self) -> Optional[int]:
        """Event-driven pick: advance whichever stream is behind."""
        io_idx = self._io_queue[0] if self._io_queue else None
        comp_idx = (self._comp_queue[0]
                    if self._comp_queue and self._ready(self._comp_queue[0])
                    else None)
        if io_idx is None:
            return comp_idx
        if comp_idx is None:
            return io_idx
        return comp_idx if self._comp_clock <= self._io_clock else io_idx

    def step(self, max_tasks: int = 4) -> bool:
        """Execute up to ``max_tasks`` tasks; True when restoration done.
        A projection group counts as one task."""
        t0 = time.perf_counter()
        for _ in range(max_tasks):
            idx = self._pick()
            if idx is None:
                break
            self._run_task(idx)
        # reap landed KV tickets opportunistically; once every task has
        # dispatched, block-drain the stragglers so done means done
        self._reap_kv(block=all(self._done))
        if self.done and not self._finished and self.sink is not None:
            with span(RESTORE_WRITE, self, "write_wall"):
                self.sink.finish(self.n_tokens)
            self._finished = True
        self.wall_time += time.perf_counter() - t0
        return self.done

    def prefetch_step(self, max_tasks: int = 1) -> int:
        """Run up to ``max_tasks`` IO tasks (no sink required); returns
        the number executed. Used to warm queued sessions' reads."""
        n = 0
        while n < max_tasks and self._io_queue:
            self._run_task(self._io_queue[0])
            n += 1
        return n

    def run(self) -> None:
        while not self.step(max_tasks=max(len(self.tasks), 1)):
            pass

    # ---------------------------------------------------------- task bodies
    def _run_task(self, idx: int) -> None:
        t = self.tasks[idx]
        self._cur_idx = idx
        dur = task_duration(t, self.times, self.dispatch_overhead,
                            self.cross_times)
        if t.stream == "io":
            self._io_queue.remove(idx)
            link = (self._task_links.get(idx, 0)
                    if self._task_links else 0)
            self._io_clocks[link] = self._io_clocks.get(link, 0.0) + dur
            self._io_clock = max(self._io_clock, self._io_clocks[link])
        else:
            self._comp_queue.remove(idx)
            start = (self._comp_clock if not t.all_deps else
                     max(self._comp_clock, self._io_clock))
            self._comp_clock = max(self._comp_clock, start) + dur
        if self.profile is not None:
            self._run_profiled(idx, t)
        else:
            getattr(self, "_exec_" + t.kind)(t)
        self._done[idx] = True
        self.executed.append(idx)

    def _task_work(self, t: Task) -> float:
        """Work units of one task: bytes for IO kinds, FLOPs for compute
        kinds — the x-axis of the profiler's time fits, on the same cost
        basis ``method_times`` predicts with."""
        if t.kind == "io_h":
            return self.costs[t.layer].io_hidden
        if t.kind == "io_kv":
            c = self.costs[t.layer]
            return c.io_kv or c.io_state
        if t.kind == "recompute":
            return self.costs[t.layer].c_token
        if t.kind == "project":
            return sum(self.costs[li].c_hidden for li in t.members
                       if self._is_attn(li))
        if t.kind in ("io_enc", "project_cross") and self.enc_len:
            costs = layer_costs(self.mgr.cfg, self.enc_len,
                                self.mgr.dtype_bytes)
            return (costs[0].io_hidden if t.kind == "io_enc"
                    else sum(c.c_hidden for c in costs))
        return 0.0

    def _run_profiled(self, idx: int, t: Task) -> None:
        """Execute one task with its real duration observed and folded
        into the manager's ``MeasuredProfile``.

        IO tasks: the striped store accumulates per-device read service
        time; the delta across this task, divided by the device count
        (stripes are read in parallel), is the contention-free stream
        seconds the cost model predicts. Plain DRAM backends accumulate
        nothing and record nothing. Compute tasks: wall seconds, thrown
        away when the call traced (JIT compile time is not dispatch
        time — folding it in would poison the overhead fit)."""
        bucket = (self._enc_bucket
                  if t.kind in ("io_enc", "project_cross")
                  else self._bucket)
        if t.kind in IO_KINDS:
            # with an IO engine attached, service accrues in the shard
            # workers — an inline delta would attribute racing reads of
            # other tasks to this one; those tasks record at reap time
            # from their tickets' own service (``_observe_read``)
            inline = (self._n_timed and
                      getattr(self.mgr.store, "io_engine", None) is None)
            base = self.mgr.store.read_service_total() if inline else 0.0
            getattr(self, "_exec_" + t.kind)(t)
            if inline:
                delta = ((self.mgr.store.read_service_total() - base)
                         / self._n_timed)
                if delta > 0.0:
                    self.observed[idx] = delta
                    self.profile.record(t.kind, bucket,
                                        self._task_work(t), delta)
            return
        traces = projection_trace_count()
        t0 = time.perf_counter()
        getattr(self, "_exec_" + t.kind)(t)
        wall = time.perf_counter() - t0
        if wall > 0.0 and projection_trace_count() == traces:
            self.observed[idx] = wall
            # a tp-sharded launch records into its mesh's own cell
            # (profiler.mesh_kind) so single-device fits stay clean
            self.profile.record(t.kind, bucket, self._task_work(t), wall,
                                mesh=self.mesh if self.mesh > 1 else None)

    def _is_attn(self, layer: int) -> bool:
        return layer in self._row_of

    def _observe_read(self, idx: int, kind: str, tickets,
                      work: float, bucket: int) -> None:
        """Fold a reaped async read into the profiler. The sync path
        records via ``_run_profiled``'s service-total delta; tickets
        completed by IO workers instead carry their own per-shard
        service seconds, measured inside the worker (thread-confined).
        Single-shard reads (layer placement) record the per-link cell
        too, so heterogeneous NICs get their own learned rates."""
        if self.profile is None or idx in self.observed:
            return
        # stripes across shards run in parallel: the task's stream
        # duration is the slowest shard's service, not the sum
        dur = max((tk.service for tk in tickets), default=0.0)
        if dur <= 0.0:
            return
        self.observed[idx] = dur
        shard_ids = {tk.shard_id for tk in tickets}
        link = (shard_ids.pop() if len(shard_ids) == 1
                and self.topology is not None else None)
        self.profile.record(kind, bucket, work, dur, link=link)

    def _collect_hidden(self, layer: int) -> np.ndarray:
        """Hidden states of one fetched layer: from the staging buffer
        (sync path) or by completing the layer's submitted tickets."""
        got = self._hio.pop(layer, None)
        if got is None:
            return self._hbuf.pop(layer)
        idx, lr, ls = got
        with span(RESTORE_READ, self, "read_wall"):
            ar = lr.wait()
            tickets = list(lr.tickets)
            if ls is not None:
                sr = ls.wait()
                tickets += list(ls.tickets)
                data = dequantize_hidden_int8(ar.data, sr.data)
            else:
                data = ar.data
        self._observe_read(idx, "io_h", tickets,
                           self._task_work(self.tasks[idx]), self._bucket)
        return data

    def _reap_kv(self, block: bool = False) -> None:
        """Complete landed io_kv tickets and emit their KV to the sink;
        ``block=True`` drains every outstanding ticket (end of graph)."""
        if not self._kvio:
            return
        remaining = []
        for entry in self._kvio:
            idx, layer, rk, rv = entry
            if not block and not (rk.ready() and rv.ready()):
                remaining.append(entry)
                continue
            with span(RESTORE_READ, self, "read_wall"):
                ak, av = rk.wait(), rv.wait()
            self._observe_read(idx, "io_kv",
                               list(rk.tickets) + list(rv.tickets),
                               self._task_work(self.tasks[idx]),
                               self._bucket)
            self._put_kv(layer, ak.data, av.data)
        self._kvio = remaining

    def _put_kv(self, layer: int, k_rows: np.ndarray,
                v_rows: np.ndarray) -> None:
        """Upload one fetched kv layer's rows and hand them to the sink."""
        cfg, dtype = self.mgr.cfg, self.model.dtype
        shape = (1, self.n_eff, cfg.n_kv_heads, cfg.head_dim_)
        with span(RESTORE_UPLOAD, self, "upload_wall"):
            k = jnp.asarray(k_rows).reshape(shape).astype(dtype)
            v = jnp.asarray(v_rows).reshape(shape).astype(dtype)
        self.h2d_bytes += k_rows.nbytes + v_rows.nbytes
        self.dispatch_count += 3           # 2 uploads + 1 sink write
        self._emit("put_kv", self._row_of[layer], k, v, self.start_token)

    def _exec_io_h(self, t: Task) -> None:
        if not self._is_attn(t.layer):
            return          # mamba layers restore via the state blob
        with span(RESTORE_READ, self, "read_wall"):
            self._read_h(t)

    def _read_h(self, t: Task) -> None:
        store, sess, n = self.mgr.store, self.session, self.n_tokens
        d = self.start_token
        submit = getattr(store, "submit_layer_read", None)
        if submit is None:                     # store without async API
            if self.compress == "int8":
                q = store.read_layer_async(sess, "h", t.layer, n,
                                           start_token=d)
                s = store.read_layer_async(sess, "hs", t.layer, n,
                                           start_token=d)
                self._hbuf[t.layer] = dequantize_hidden_int8(q.data, s.data)
            else:
                r = store.read_layer_async(sess, "h", t.layer, n,
                                           start_token=d)
                self._hbuf[t.layer] = r.data
            return
        # submit leg: tickets staged until the projection consumes them
        # (with the async engine attached the reads overlap compute on
        # the shard workers; without it they completed inline)
        lr = submit(sess, "h", t.layer, n, start_token=d)
        ls = (submit(sess, "hs", t.layer, n, start_token=d)
              if self.compress == "int8" else None)
        self._hio[t.layer] = (self._cur_idx, lr, ls)

    def _exec_io_kv(self, t: Task) -> None:
        if not self._is_attn(t.layer):
            return
        store, sess, n = self.mgr.store, self.session, self.n_tokens
        d = self.start_token
        submit = getattr(store, "submit_layer_read", None)
        if submit is None:
            with span(RESTORE_READ, self, "read_wall"):
                rk = store.read_layer_async(sess, "kvk", t.layer, n,
                                            start_token=d)
                rv = store.read_layer_async(sess, "kvv", t.layer, n,
                                            start_token=d)
            self._put_kv(t.layer, rk.data, rv.data)
            return
        with span(RESTORE_READ, self, "read_wall"):
            rk = submit(sess, "kvk", t.layer, n, start_token=d)
            rv = submit(sess, "kvv", t.layer, n, start_token=d)
        self._kvio.append((self._cur_idx, t.layer, rk, rv))

    def _exec_project(self, t: Task) -> None:
        members = [li for li in t.members if self._is_attn(li)]
        if not members:
            return          # hidden-method mamba layers restore via blob
        pack = self.pack
        n = self.n_eff
        S = s_bucket(n)
        G = max(self._g_pad, len(members))
        # completing the submitted tickets here (not at io_h dispatch) is
        # what lets reads of later layers stream on the shard workers
        # while this projection computes
        fetched = {li: self._collect_hidden(li) for li in members}
        with span(RESTORE_READ, self, "read_wall"):
            h0 = fetched[members[0]]
            stack = np.zeros((G, S, h0.shape[-1]), h0.dtype)
            for g, li in enumerate(members):
                stack[g, :n] = fetched.pop(li)
        rows = [self._row_of[li] for li in members]
        # pad to the stable group width with a repeated row id over zero
        # hidden states; padded outputs are sliced away below
        rows_pad = np.asarray(rows + [rows[-1]] * (G - len(rows)), np.int32)
        # RoPE at absolute positions: a suffix restore rotates with the
        # tables sliced at its divergence offset
        cos, sin = pack.rope_tables(S, self.start_token)
        # ONE host->device upload (replicated across the mesh under tp)
        with span(RESTORE_UPLOAD, self, "upload_wall"):
            hidden = pack.place_hidden(stack)
        self.h2d_bytes += stack.nbytes
        with span(RESTORE_PROJECT, self, "project_wall"):
            k, v = _project_group_jit(
                hidden, jnp.asarray(rows_pad), pack.ln_scale, pack.ln_bias,
                pack.wk, pack.wv, pack.bk, pack.bv, cos, sin,
                norm_kind=pack.norm_kind, eps=pack.norm_eps,
                head_dim=pack.head_dim, use_rope=pack.use_rope,
                dtype=pack.dtype, use_pallas=ops.on_tpu(), interpret=None,
                kv_sharding=pack.out_sharding)
            jax.block_until_ready((k, v))
        g_real = len(members)
        self.dispatch_count += 3     # upload + projection + grouped write
        self._emit("put_kv_group", tuple(rows),
                   k[:g_real, None, :n], v[:g_real, None, :n],
                   self.start_token)

    def _exec_recompute(self, t: Task) -> None:
        from repro.models import transformer as tfm
        model, params = self.model, self.params
        mh = model.h
        if self._re_x is None:
            toks = jnp.asarray(
                self.mgr.store.get_blob(self.session, "tok", 0)
            )[None, :self.n_tokens]
            B, S = toks.shape
            self._re_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            self._re_x = tfm._embed_input(params, mh, toks, self._re_pos)
            self._re_windows = tfm.layer_windows(mh)
        j = self._re_next
        bp = jax.tree.map(lambda a: a[j], params["blocks"])
        win = (self._re_windows[j] if self._re_windows is not None else None)
        x, _, kv, _ = tfm.block_forward(bp, self._re_x, mh,
                                        positions=self._re_pos, window=win,
                                        emit_kv=True)
        self._re_x = x
        self._re_next += 1
        k, v = kv
        self.dispatch_count += 2               # block forward + sink write
        self._emit("put_kv", self._row_of[t.layer],
                   k.astype(model.dtype), v.astype(model.dtype))

    def _exec_blob(self, t: Task) -> None:
        store, sess = self.mgr.store, self.session
        with span(RESTORE_READ, self, "read_wall"):
            conv = np.asarray(store.get_blob(sess, "state_conv", 0))
            ssm = np.asarray(store.get_blob(sess, "state_ssm", 0))
        self._emit("put_states", self._upload(conv), self._upload(ssm))

    def _exec_io_enc(self, t: Task) -> None:
        # the encoder blob lives whole on its owning shard; the submit
        # path overlaps the read with decoder-side restoration and the
        # cross-projection reaps it. Charged only on the virtual clock
        # (CrossTimes.io).
        submit = getattr(self.mgr.store, "submit_blob_read", None)
        with span(RESTORE_READ, self, "read_wall"):
            if submit is None:
                self._enc_out = np.asarray(
                    self.mgr.store.get_blob(self.session, "enc", 0))
                return
            self._encio = (self._cur_idx, submit(self.session, "enc", 0))

    def _exec_project_cross(self, t: Task) -> None:
        from repro.models import encdec as encdec_mod
        if self._encio is not None:
            idx, ticket = self._encio
            self._encio = None
            with span(RESTORE_READ, self, "read_wall"):
                parts = ticket.wait()
                self._enc_out = np.asarray(parts[0])
            self._observe_read(idx, "io_enc", [ticket],
                               self._task_work(self.tasks[idx]),
                               self._enc_bucket)
        enc_out = self._upload(self._enc_out)[None]
        self._enc_out = None
        with span(RESTORE_PROJECT):
            ck, cv = encdec_mod.cross_kv(self.params, enc_out, self.model.h)
        self.dispatch_count += 2             # upload+projection, sink write
        self._emit("put_cross", ck, cv, enc_out.shape[1])
