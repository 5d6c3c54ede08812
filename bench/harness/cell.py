"""One run of one cell: set-up, the measured window, the check.

``run`` is everything after the look for the chip, so a CPU test can
drive a whole run at a small size.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import os
import time
from typing import Callable, Dict, List, Optional

from bench.harness import check as check_lib
from bench.harness import client, stats, traffic
from bench.harness.instrument import CompileClock, Instrument, memory_peak

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def say(msg: str) -> None:
    print(msg, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                # configuration file
    mix: dict                # traffic file
    end_to_end: List[dict]
    per_layer: List[dict]    # metrics this cell reports


def load_cell(name: str, root: str = ROOT,
              workload: Optional[dict] = None) -> Cell:
    """The cell ``name`` of BENCHMARK.json, or the given ``workload``
    entry: a cell not listed there yet, with the listed metrics, whose
    configuration may be unlisted too (``bench/configs/<config>.json``)."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    files = {c["name"]: c["file"] for c in spec["configs"]}
    if workload is not None:
        cells[name] = dict(workload, name=name)
        files.setdefault(workload["config"], os.path.join(
            "bench", "configs", workload["config"] + ".json"))
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = _load_json(os.path.join(root, files[w["config"]]))
    mix = _load_json(os.path.join(root, "bench", "traffic",
                                  w["traffic"] + ".json"))
    traffic.check_mix(mix)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    per = [m for m in spec["per_layer"]
           if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), cfg, mix, e2e, per)


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = _load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


@dataclasses.dataclass
class Context:
    """What the per-layer metric readers read."""
    ref: dict
    peaks: Optional[dict]
    window: tuple                    # perf_counter bounds of the window
    traced: Optional[tuple]          # perf_counter bounds of the trace
    rec: object                      # instrument.Record
    counters: Dict[str, float]       # engine counters over the window
    trace: Optional[object] = None   # trace.Trace of the traced part
    mem_peak: Optional[int] = None
    setup_compile_s: float = 0.0
    window_compiles: int = 0


ENGINE_COUNTERS = ("restore_wall_sum", "restored_tokens")


def _counters(engine) -> Dict[str, float]:
    m = engine.metrics
    return {k: float(getattr(m, k)) for k in ENGINE_COUNTERS}


async def _alive(pump, coro):
    """Await ``coro``; raise if the engine pump dies meanwhile (an
    exception in the engine ends its thread and would leave every
    request waiting)."""
    task = asyncio.ensure_future(coro)
    while not task.done():
        await asyncio.wait([task], timeout=1.0)
        if not task.done() and not pump.alive:
            task.cancel()
            raise RuntimeError("the engine pump died (traceback above)")
    return task.result()


async def _serve(st, schedule, entry: str, seed: int, seconds: float,
                 cap_s: float, on_window):
    """Set-up (sessions, one warm conversation, shape warm-up), then the
    window. Returns (window turns, t0, t_end)."""
    from bench.harness import stack as stack_lib
    cl = client.Client(entry, schedule, st.ref["vocab"], seed, fd=st.fd,
                       pump=st.pump)
    opened = cl.openings()
    if opened:
        await _alive(st.pump, asyncio.wrap_future(st.pump.call(
            stack_lib.open_sessions, st, opened, entry)))
        cl.adopt(opened)
    # one conversation so that decode, sampling and the save path run
    warm = await _alive(st.pump, cl.turn(time.perf_counter(), traffic.Turn(
        0.0, -1, schedule.align - cl.template.first,
        schedule.turns[0].answer_tokens, 0, schedule.align)))
    await on_window("setup_done", (opened, warm))
    t0 = time.perf_counter()
    await on_window("start", t0)
    tasks = [asyncio.ensure_future(cl.turn(t0, t)) for t in schedule.turns]
    await asyncio.sleep(max(t0 + seconds - time.perf_counter(), 0.0))
    t_end = time.perf_counter()
    await on_window("end", t_end)
    done, pending = set(), set(tasks)
    while pending and st.pump.alive and time.perf_counter() < t_end + cap_s:
        d, pending = await asyncio.wait(pending, timeout=1.0)
        done |= d
    if not st.pump.alive:
        raise RuntimeError("the engine pump died (traceback above)")
    for t in pending:
        t.cancel()
    served = []
    for t, turn in zip(tasks, schedule.turns):
        if t in done and not t.cancelled() and t.exception() is None:
            served.append(t.result())
        else:
            sv = client.Served(turn, due=t0 + turn.due_s)
            sv.error = "no answer within the cap"
            served.append(sv)
    return served, t0, t_end


def run(cell: Cell, seed: int, seconds: float, *, trace: bool,
        t_start: float, trace_dir: Optional[str] = None,
        device_kind: Optional[str] = None, check: bool = True,
        control: bool = False) -> dict:
    """Everything after the look for the chip. Returns the result dict
    (the contract's last line) plus ``checks`` for standard error.
    ``check=False`` skips the reference (load sweeps); ``control`` also
    judges the float8 control on the same sample (``out["control"]``)."""
    import jax
    from bench.harness import stack as stack_lib
    from bench.harness import trace as trace_lib

    clock = CompileClock()
    mix, cfg = cell.mix, cell.cfg
    tmpl = client.template(int(cfg["config"]["vocab_size"]))
    schedule = traffic.make_schedule(mix, seconds, tmpl)
    say(f"phase start: {time.time() - t_start:.3f} s after process start; "
        f"{len(schedule.sessions)} sessions, {len(schedule.turns)} "
        f"requests due in {seconds} s")
    st = stack_lib.build(cfg, seed, len(schedule.sessions))
    say(f"phase weights: {st.weight_s:.3f} s (made on the device from "
        f"the seed); engine built at {time.time() - t_start:.3f} s")
    cap_s = float(mix["cap_s"])
    warm = {}
    c_setup = clock.snapshot()
    window_marks = {}
    inst = None

    async def on_window(event, arg):
        nonlocal inst, c_setup
        if event == "setup_done":
            opened, warm_turn = arg
            say(f"phase histories: {len(opened)} sessions opened, "
                f"{sum(len(o.history) for o in opened)} stored tokens, "
                f"warm conversation answered {len(warm_turn.tokens)} tokens; "
                f"{time.time() - t_start:.3f} s after process start")
            if not warm_turn.ok:
                raise RuntimeError(f"set-up conversation failed: "
                                   f"{warm_turn.error}")
            methods = _methods(st.engine, schedule)
            t = time.perf_counter()
            warm.update(await asyncio.wrap_future(st.pump.call(
                stack_lib.warm_shapes, st.engine, schedule, methods)))
            say(f"phase shape warm-up: {time.perf_counter() - t:.3f} s, "
                f"{warm}; stored layers by method: "
                f"{ {m: methods.count(m) for m in sorted(set(methods))} }")
            inst = Instrument(st.engine, annotate=trace)
            c_setup = clock.snapshot()
        elif event == "start":
            window_marks["t0"] = arg
            window_marks["setup_s"] = time.time() - t_start
            window_marks["c0"] = _counters(st.engine)
            window_marks["stalls0"] = st.engine.metrics.alloc_stalls
            if trace:
                loop = asyncio.get_running_loop()
                lo = _trace_offset(seconds)
                loop.call_later(lo, _start_trace, trace_dir, window_marks)
                loop.call_later(lo + min(TRACE_S, seconds), _stop_trace,
                                window_marks)
        elif event == "end":
            _stop_trace(window_marks)

    finished = False
    try:
        served, t0, t_end = asyncio.run(_serve(
            st, schedule, mix["entry"], seed, seconds, cap_s, on_window))
        c_end = clock.snapshot()
        counters = _counters(st.engine)
        c0 = window_marks["c0"]
        delta = {k: counters[k] - c0[k] for k in counters}
        pool = _pool(st.engine, inst.rec, window_marks["stalls0"])
        mem = memory_peak()
        finished = True
    finally:
        if inst is not None:
            inst.close()
        st.close(force=not finished)
    report = _report(served, t0, t_end, cap_s, window_marks["setup_s"])
    for line in report["lines"]:
        say(line)
    ctx = Context(ref=st.ref, peaks=(peaks_for(device_kind)
                                     if device_kind else None),
                  window=(t0, t_end), traced=window_marks.get("traced"),
                  rec=inst.rec, counters=delta, mem_peak=mem,
                  setup_compile_s=c_setup["compile_s"],
                  window_compiles=c_end["misses"] - c_setup["misses"])
    say(f"compiles: set-up {c_setup['compile_s']:.3f} s "
        f"({c_setup['misses']} compiled, {c_setup['hits']} loaded from "
        f"the cache); window {ctx.window_compiles} compiled, "
        f"{c_end['hits'] - c_setup['hits']} loaded")
    say(f"device memory peak: {mem} bytes")
    say(f"page pool: {pool}")
    breakdown = None
    if trace and window_marks.get("traced"):
        path = trace_lib.find_xplane(trace_dir)
        ctx.trace = trace_lib.load(path, host_spans=["engine.*"])
        breakdown = trace_lib.breakdown(ctx.trace)
    # free the program's state before the reference runs on the chip
    del st, inst
    gc.collect()
    jax.clear_caches()
    if not check:
        line, growth = _sweep_line(served, report)
        return {"sweep": line, "pool": pool,
                "sustained": report["failed"] == 0
                and growth < BACKLOG_GROWTH_S}
    verdict = check_lib.compare(cell, seed, served)
    ctl = None
    if control and "sample" in verdict:
        ctl = check_lib.control(cell, seed, *verdict["sample"])
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": report["metrics"][m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": verdict["correct"],
           "attempted": report["attempted"], "failed": report["failed"],
           "metrics": metrics}
    if breakdown is not None:
        out["breakdown"] = breakdown
        out["trace_window"] = ctx.trace.window_s
        out["busy_s"] = trace_lib.busy_s(ctx.trace)
    out["memory_peak_bytes"] = mem
    out["pool"] = pool
    out["checks"] = verdict["checks"]
    if ctl is not None:
        out["control"] = ctl
    return out


def _pool(engine, rec, stalls0: int) -> str:
    """How full the page pool got from the window's start to the last
    answer: the most pages held at a decode step, of the pool."""
    kv = engine.kv
    n = kv.num_blocks
    return (f"peak {rec.pages_peak} of {n} pages held "
            f"({100.0 * rec.pages_peak / n:.1f}%, "
            f"{rec.pages_peak * kv.block_size} tokens), "
            f"peak {rec.live_peak} sequences decoding, "
            f"{engine.metrics.alloc_stalls - stalls0} admissions waited "
            f"for pages")


# a load sustains when every request is answered and the last quarter's
# mean TTFT exceeds the first quarter's by less than this (a queue that
# grows through the window adds a service time per request it holds)
BACKLOG_GROWTH_S = 2.0


def _sweep_line(served, report) -> tuple:
    """Load test summary: answered share, TTFT quartiles, and the first
    and last quarters' mean TTFT (a backlog grows between them); and that
    growth in seconds."""
    ok = [s for s in served if s.ok]
    ttft = [s.times[0] - s.due for s in ok]
    q = len(ttft) // 4 or 1
    quart = (stats.percentile(ttft, 25), stats.percentile(ttft, 50),
             stats.percentile(ttft, 75)) if ttft else (None,) * 3
    head = sum(ttft[:q]) / q if ttft else 0.0
    tail = sum(ttft[-q:]) / q if ttft else float("inf")
    m = report["metrics"]
    return (f"due {len(served)} answered {len(ok)}; ttft q1/q2/q3 "
            f"{quart}; first-quarter mean {head} last-quarter mean {tail}; "
            f"tokens_per_s {m['tokens_per_s']}; tbt_p99_s "
            f"{m['tbt_p99_s']}", tail - head)


def _methods(engine, schedule) -> List[str]:
    """Per-layer methods the window's sessions were saved under (fresh
    conversations: what the manager plans for a first chunk)."""
    store = engine.mgr.store
    for sid in store.sessions():
        man = store.get_manifest(sid)
        if man and man.get("methods"):
            return list(man["methods"])
    return list(engine.mgr.plan(schedule.align).methods)


TRACE_S = 8.0       # seconds traced, from the middle of the window


def _trace_offset(seconds: float) -> float:
    return max((seconds - TRACE_S) / 2.0, 0.0)


def _start_trace(trace_dir, marks) -> None:
    import jax
    jax.profiler.start_trace(trace_dir)
    marks["span"] = jax.profiler.TraceAnnotation("bench.window")
    marks["span"].__enter__()
    marks["trace_lo"] = time.perf_counter()


def _stop_trace(marks) -> None:
    import jax
    if "trace_lo" not in marks or "traced" in marks:
        return
    hi = time.perf_counter()
    marks["span"].__exit__(None, None, None)
    jax.profiler.stop_trace()
    marks["traced"] = (marks["trace_lo"], hi)


def _report(served, t0, t_end, cap_s, setup_s):
    ttft = stats.ttft_values(served, cap_s)
    tbt = stats.gaps(served)
    ok = [s for s in served if s.ok]
    late = [s.sent - s.due for s in served if s.sent and not s.held]
    held = sum(1 for s in served if s.held)
    metrics = {
        "ttft_p50_s": stats.percentile(ttft, 50),
        "ttft_p90_s": stats.percentile(ttft, 90),
        "tbt_p99_s": stats.percentile(tbt, 99) if tbt else cap_s,
        "tokens_per_s": stats.tokens_in(served, t0, t_end) / (t_end - t0),
        "setup_s": setup_s,
    }
    bad_route = [s for s in ok if s.turn.session >= 0
                 and s.matched != s.turn.history]
    lines = [
        f"window: {len(served)} requests due, {len(ok)} answered in full, "
        f"{len(served) - len(ok)} failed; {held} waited for their "
        f"session's previous answer",
        f"generator lateness p99: "
        f"{stats.percentile(late, 99) if late else 0.0:.6f} s over "
        f"{len(late)} requests sent on time",
        "end-to-end: " + ", ".join(f"{k} {v}" for k, v in metrics.items()),
    ]
    if bad_route:
        s = bad_route[0]
        raise RuntimeError(
            f"router restored {s.matched} tokens where the schedule "
            f"stored {s.turn.history}: the chat template or router no "
            f"longer matches the benchmark's alignment")
    return {"metrics": metrics, "attempted": len(served),
            "failed": len(served) - len(ok), "lines": lines}
