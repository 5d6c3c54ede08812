"""Serving driver: HCache-enabled engine over a synthetic conversation
trace, or behind the OpenAI-compatible front door (``--serve-http``).
Reduced configs unless ``--full``; bf16 with random weights.

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b \
        --sessions 4 --rounds 2

``build_engine`` is the one construction path: ``chip_smoke.py`` builds
its engine through it too.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PROFILES, profile_for_device
from repro.configs import get_arch
from repro.core.capacity import (ADMISSION_POLICIES, CapacityManager,
                                 EVICTION_POLICIES,
                                 RestoreCostAwareAdmission)
from repro.core.hcache import HCacheManager
from repro.distributed.sharding import default_rules
from repro.distributed.tp import TPContext
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.serving.kv_cache import BACKENDS
from repro.storage import (AsyncIOEngine, ChunkStore, make_array,
                           make_shards)


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama2-7b")
    p.add_argument("--sessions", type=int, default=3)
    p.add_argument("--rounds", type=int, default=2)
    p.add_argument("--prompt-len", type=int, default=24)
    p.add_argument("--gen", type=int, default=8)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=256)
    p.add_argument("--profile", default=None, choices=sorted(PROFILES),
                   help="hardware profile the restore planner prices "
                        "with (default: the device's own, looked up by "
                        "device_kind in config/hardware.py)")
    p.add_argument("--ssds", type=int, default=4)
    p.add_argument("--hosts", type=int, default=1,
                   help="distributed store: number of host shards, each "
                        "with --ssds simulated SSDs behind its own NIC "
                        "link (1 = classic one-host store)")
    p.add_argument("--nic-bw", type=float, default=None, metavar="GBPS",
                   help="per-shard NIC bandwidth in GB/s (default: the "
                        "hardware model's NIC_BW)")
    p.add_argument("--placement", default="layer",
                   choices=("layer", "chunk"),
                   help="shard placement: layer-striped (layer L on "
                        "shard L%%N, per-link scheduling) or token-chunk-"
                        "striped (every layer fans over all links)")
    p.add_argument("--async-io", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="attach the per-shard async IO engine (default: "
                        "on when --hosts > 1)")
    p.add_argument("--full", action="store_true")
    p.add_argument("--preempt-quantum", type=int, default=None,
                   help="enable mid-stream eviction after N resident steps")
    p.add_argument("--eviction", default="lru",
                   choices=sorted(EVICTION_POLICIES))
    p.add_argument("--admission", default="fifo",
                   choices=sorted(ADMISSION_POLICIES))
    p.add_argument("--budget-kb", type=int, default=None,
                   help="host hot-tier byte budget (KiB); enables the "
                        "capacity demotion ladder with a DRAM cold tier")
    p.add_argument("--backend", default="contiguous",
                   choices=sorted(BACKENDS),
                   help="KV-cache layout: contiguous slots or a "
                        "block-table page pool (lm models)")
    p.add_argument("--block-size", type=int, default=16,
                   help="paged backend: tokens per physical page")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel width: shard the paged KV pool "
                        "and the restoration projection over this many "
                        "devices (KV-head axis; an error when fewer are "
                        "visible — set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N on CPU)")
    p.add_argument("--cache-blocks", type=int, default=None,
                   help="paged backend: physical pages in the pool "
                        "(default max_batch * max_seq / block_size)")
    p.add_argument("--admission-aging", type=float, default=0.0,
                   help="restore_cost admission: seconds of makespan "
                        "credit per queued engine step (anti-starvation)")
    p.add_argument("--restore-group-size", default="8",
                   help="projection layers per stacked restoration "
                        "dispatch (1 = per-layer; see DESIGN.md §10), "
                        "'auto' to pick the restore_makespan argmin over "
                        "{1, 2, 4, 8, L} + the fetch-aligned partition "
                        "per restore, or 'fetch' to force fetch-aligned "
                        "non-uniform group boundaries (DESIGN.md §13)")
    p.add_argument("--hw-profile", default=None, metavar="PATH",
                   help="online scheduler calibration (DESIGN.md §13): "
                        "load a MeasuredProfile JSON from PATH if it "
                        "exists, fold every restore's observed task "
                        "times into it, re-plan from it, and save it "
                        "back on exit — restores converge to measured "
                        "hardware behavior instead of datasheet numbers")
    p.add_argument("--enc-seq", type=int, default=None,
                   help="enc-dec models: encoder positions per slot in "
                        "the paired self/cross cache (default max-seq)")
    p.add_argument("--prefix-sharing", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="cross-session prefix sharing (DESIGN.md §12): "
                        "refcounted CoW pages + token-hash prefix index "
                        "(paged backend) and content-addressed host chunk "
                        "dedup / session forking")
    p.add_argument("--metrics-json", default=None, metavar="PATH",
                   help="dump the final EngineMetrics counters/gauges as "
                        "JSON to PATH on exit (the dict /metrics serves)")
    p.add_argument("--serve-http", action="store_true",
                   help="serve the engine through the front door "
                        "(DESIGN.md §14): OpenAI-compatible HTTP API + "
                        "session router, instead of the synthetic trace; "
                        "Ctrl-C to stop")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080,
                   help="--serve-http listen port (0 = ephemeral)")
    p.add_argument("--max-pending", type=int, default=64,
                   help="--serve-http backpressure: queue-depth cap "
                        "before requests are shed with 429/overloaded")
    p.add_argument("--priority-levels", type=int, default=1,
                   help="synthetic trace: session s gets priority "
                        "s %% N (exercises --admission priority; 1 = all "
                        "equal)")
    return p


def init_params(model: Model, seed: int, sharding=None):
    """Random weights from ``seed``, initialized inside one jitted
    program so only the model dtype reaches device memory (an eager init
    materializes each stacked weight in f32 first). ``sharding`` places
    them directly (replicated over a tensor-parallel mesh)."""
    kw = {} if sharding is None else {"out_shardings": sharding}
    init = jax.jit(lambda key: split(model.init(key))[0], **kw)
    return init(jax.random.PRNGKey(seed))


def build_engine(args, cfg=None, seed: int = 0) -> InferenceEngine:
    """The serving stack from parsed ``make_parser`` options: bf16 model
    with random weights from ``seed``, host chunk store, HCache manager
    and engine. ``cfg`` overrides ``--arch``/``--full``. The store, the
    measured profile and the capacity manager are reachable from the
    engine (``engine.mgr.store``, ``engine.mgr.profile``,
    ``engine.capacity``)."""
    group_size = (args.restore_group_size
                  if args.restore_group_size in ("auto", "fetch")
                  else int(args.restore_group_size))
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh)
    if cfg is None:
        cfg = get_arch(args.arch)
        if not args.full:
            cfg = reduced_for_smoke(cfg)
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.bfloat16,
                  remat="none")
    tp = TPContext(args.tp)              # raises when devices are short
    params = init_params(model, seed, tp.replicated())
    hw = (PROFILES[args.profile] if args.profile
          else profile_for_device(jax.devices()[0]))
    cold = make_array("dram", args.ssds) if args.budget_kb else None
    if args.hosts > 1:
        from repro.config.hardware import NIC_BW
        nic_bw = (args.nic_bw * 1e9 if args.nic_bw else NIC_BW)
        store = ChunkStore(shards=make_shards(args.hosts, args.ssds, "ssd",
                                              nic_bw=nic_bw),
                           chunk_tokens=64, cold_devices=cold,
                           placement=args.placement)
        if args.async_io is not False:
            store.attach_io_engine(AsyncIOEngine(args.hosts))
    else:
        store = ChunkStore(make_array("ssd", args.ssds), chunk_tokens=64,
                           cold_devices=cold)
        if args.async_io:
            store.attach_io_engine(AsyncIOEngine(1))
    measured = None
    if args.hw_profile:
        import os
        from repro.core.profiler import MeasuredProfile
        measured = (MeasuredProfile.load(args.hw_profile)
                    if os.path.exists(args.hw_profile)
                    else MeasuredProfile())
    mgr = HCacheManager(model, store, hw=hw,
                        restore_group_size=group_size, profile=measured)
    capacity = (CapacityManager(mgr, host_budget_bytes=args.budget_kb * 1024)
                if args.budget_kb else None)
    admission = (RestoreCostAwareAdmission(aging=args.admission_aging)
                 if args.admission == "restore_cost"
                 else ADMISSION_POLICIES[args.admission]())
    return InferenceEngine(model, params, mgr, max_batch=args.max_batch,
                           max_seq=args.max_seq,
                           preempt_quantum=args.preempt_quantum,
                           eviction=EVICTION_POLICIES[args.eviction](),
                           admission=admission,
                           capacity=capacity,
                           backend=args.backend,
                           block_size=args.block_size,
                           cache_blocks=args.cache_blocks,
                           enc_seq=args.enc_seq,
                           prefix_sharing=args.prefix_sharing,
                           tp=args.tp)


def main() -> None:
    args = make_parser().parse_args()
    enable_compile_cache()
    engine = build_engine(args)
    model, store = engine.model, engine.mgr.store
    cfg, measured, capacity = model.cfg, engine.mgr.profile, engine.capacity

    if args.serve_http:
        import asyncio

        from repro.frontend import serve_engine
        try:
            asyncio.run(serve_engine(engine, args.host, args.port,
                                     max_pending=args.max_pending))
        except KeyboardInterrupt:
            pass
        _dump_metrics(engine, args.metrics_json)
        store.close()
        return

    rng = np.random.default_rng(0)
    for rnd in range(args.rounds):
        for s in range(args.sessions):
            prompt = rng.integers(0, cfg.vocab_size,
                                  args.prompt_len).astype(np.int32)
            # enc-dec sessions carry encoder frames on round 0 only —
            # later rounds restore the cross context from the store
            frames = None
            if model.kind == "encdec" and rnd == 0:
                frames = rng.standard_normal(
                    (args.prompt_len, cfg.d_model)).astype(np.float32) * 0.1
            engine.submit(Request(f"user{s}", prompt,
                                  max_new_tokens=args.gen, frames=frames,
                                  priority=s % max(args.priority_levels,
                                                   1)))
        engine.run()
        for s in range(args.sessions):
            seq = engine.sessions[f"user{s}"]
            print(f"round {rnd} user{s}: {len(seq.generated)} tokens, "
                  f"restore_wall {seq.restore_wall * 1e3:.2f} ms, "
                  f"ttft_wall {seq.ttft_wall:.3f} s")
    m = engine.metrics
    print(f"\nrestored {m.restored_tokens} tokens over "
          f"{len(m.ttft_wall)} requests; decode steps {m.decode_steps}; "
          f"preemptions {m.preemptions}; "
          f"store {store.bytes_used / 1e6:.1f} MB hot "
          f"/ {store.bytes_cold / 1e6:.1f} MB cold across "
          f"{len(store.devices)} devices")
    print(f"cache backend {engine.kv.name}: peak concurrency "
          f"{m.concurrent_peak} slots, peak live/reserved tokens "
          f"{m.live_tokens_peak}/{m.reserved_tokens_peak}, mean occupancy "
          f"{m.occupancy_mean:.2f} (fragmentation "
          f"{m.fragmentation_mean:.2f}), free blocks {m.free_blocks}, "
          f"alloc stalls {m.alloc_stalls}")
    if args.prefix_sharing:
        print(f"prefix sharing: hit rate {m.prefix_hit_rate:.2f} "
              f"({m.prefix_hits}/{m.prefix_lookups} lookups, "
              f"{m.prefix_hit_tokens} tokens), skipped "
              f"{m.restore_skipped_tokens} restore/prefill tokens, "
              f"{m.cow_copies} CoW copies, pages shared/private "
              f"{m.shared_pages}/{m.private_pages}, host dedup "
              f"{m.dedup_host_bytes / 1e6:.2f} MB, forks {m.forks}")
    for r in m.device_gauges:
        print(f"device {r['device']}: free pages {r['free_pages']}, "
              f"pool occupancy {r['occupancy_pct']}%, live/reserved "
              f"{r['util_pct']}%, restore-projection utilization "
              f"{r['proj_util_pct']}%"
              + (f", pool bytes {r['pool_bytes']}"
                 if "pool_bytes" in r else ""))
    if m.restore_bubble_n:
        print(f"scheduler calibration: observed bubble "
              f"{m.restore_bubble_mean:.1%} over {m.restore_bubble_n} "
              f"restores, planned-vs-measured makespan error "
              f"{m.makespan_err_mean:.1%}, peak restore concurrency "
              f"{m.io_streams_peak} streams")
    if measured is not None:
        counts = ", ".join(f"{k}={v}"
                           for k, v in measured.sample_counts().items())
        print(f"hw profile: epoch {measured.epoch}, samples "
              f"[{counts or 'none'}] -> {args.hw_profile}")
        measured.save(args.hw_profile)
    if capacity is not None and capacity.actions:
        print("capacity ladder actions:", capacity.actions)
    print("recoverable sessions:", engine.recoverable_sessions())
    _dump_metrics(engine, args.metrics_json)
    engine.close()
    store.close()                # joins the async IO workers, if attached


def _dump_metrics(engine, path) -> None:
    if not path:
        return
    import json
    with open(path, "w") as f:
        json.dump(engine.metrics.to_dict(), f, indent=2)
    print(f"metrics -> {path}")


if __name__ == "__main__":
    main()
