"""Enc-dec (whisper) through the continuous-batching engine via the
FamilyAdapter seam + paired self/cross EncDecBackend (DESIGN.md §11):
byte-identical greedy outputs vs the direct Model.prefill/decode_step
path, including save→evict→restore rounds and pause→resume over
constrained slots; per-slot enc_len batching; cross restoration task
modeling; the adapter seam's no-branching acceptance criterion; and the
hybrid unchunked-prefill regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.capacity import restore_makespan, session_restore_cost
from repro.core.hcache import HCacheManager
from repro.models import Model
from repro.models.module import split
from repro.serving import (EncDecBackend, InferenceEngine, Request,
                           make_backend)
from repro.storage import ChunkStore, make_array


@pytest.fixture(scope="module")
def setup():
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced_for_smoke(get_arch("whisper-medium"))
    model = Model(cfg, rules=default_rules(mesh), model_axis=1,
                  dtype=jnp.float32, remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def fresh_engine(setup, **kw):
    cfg, model, params = setup
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    # fp32 storage → pause/restore cycles are lossless and greedy
    # equivalence is bit-exact (same convention as test_capacity)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden",
                        store_dtype=np.float32)
    defaults = dict(max_batch=2, max_seq=96, prefill_chunk=8)
    defaults.update(kw)
    return InferenceEngine(model, params, mgr, **defaults), mgr


def _frames(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, cfg.d_model)) * 0.1).astype(np.float32)


def _prompts(cfg, n, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(k)).astype(np.int32)
            for k in rng.integers(6, 20, size=n)]


def direct_greedy(model, params, frames, prompt, n_new, ctx=96):
    """Ground truth: Model.prefill + decode_step, greedy (the path
    test_models::test_decode_matches_forward validates)."""
    batch = {"tokens": jnp.asarray(prompt)[None],
             "frames": jnp.asarray(frames)[None]}
    pre = model.prefill(params, batch)
    S = len(prompt)

    def padkv(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, ctx - x.shape[2]),
                           (0, 0), (0, 0)))

    ck, cv = pre["cross_kv"]
    cache = {"self_k": padkv(pre["kv"][0]), "self_v": padkv(pre["kv"][1]),
             "cross_k": ck, "cross_v": cv,
             "enc_len": jnp.asarray(ck.shape[2], jnp.int32),
             "lengths": jnp.asarray([S], jnp.int32)}
    out = [int(jnp.argmax(pre["logits"][0, -1]))]
    for _ in range(n_new - 1):
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        lg, cache = model.decode_step(params, cache, tok)
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


# --------------------------------------------------------- basic serving
def test_engine_matches_direct_greedy_mixed_enc_lens(setup):
    """Two whisper sessions with different encoder AND decoder lengths
    batch together; each matches the direct path byte-for-byte (the
    per-slot enc_len the seed's scalar cache could not express)."""
    cfg, model, params = setup
    jobs = [(np.arange(7, dtype=np.int32) % cfg.vocab_size,
             _frames(cfg, 16, seed=3)),
            (np.arange(11, dtype=np.int32)[::-1] % cfg.vocab_size,
             _frames(cfg, 24, seed=4))]
    eng, _ = fresh_engine(setup)
    assert isinstance(eng.kv, EncDecBackend)
    for i, (p, f) in enumerate(jobs):
        eng.submit(Request(f"w{i}", p, max_new_tokens=6, frames=f))
    eng.run()
    for i, (p, f) in enumerate(jobs):
        want = direct_greedy(model, params, f, p, 6)
        assert eng.result(f"w{i}") == want, f"w{i}"
    assert [int(x) for x in eng.kv.enc_len_np] == [0, 0]  # freed on retire
    eng.close()


def test_first_residency_requires_frames(setup):
    eng, _ = fresh_engine(setup)
    eng.submit(Request("nof", np.arange(5, dtype=np.int32),
                       max_new_tokens=2))
    with pytest.raises(ValueError, match="frames"):
        eng.run()
    eng.close()


# ------------------------------------------------- save → evict → restore
def test_round2_after_retire_restores_and_matches_direct(setup):
    """Round 2 on a retired whisper session: self-KV restores through
    the grouped hidden→KV projection, the cross context through the
    encoder blob, and generation matches a never-evicted direct run."""
    cfg, model, params = setup
    p1 = np.arange(9, dtype=np.int32) % cfg.vocab_size
    frames = _frames(cfg, 20, seed=5)
    eng, mgr = fresh_engine(setup)
    eng.submit(Request("alice", p1, max_new_tokens=5, frames=frames))
    eng.run()
    g1 = eng.result("alice")
    man = mgr.store.get_manifest("alice")
    assert int(man["enc_len"]) == 20

    p2 = (np.arange(6, dtype=np.int32) + 3) % cfg.vocab_size
    eng.submit(Request("alice", p2, max_new_tokens=4))   # no frames: restore
    eng.run()
    g2 = eng.result("alice")
    assert eng.metrics.restored_tokens > 0

    # ground truth: one decoder prefill over the whole history (the last
    # round-1 token's KV was never computed — see test_serving's
    # multi-round convention), greedy from there
    full = np.concatenate([p1, np.asarray(g1[:-1], np.int32), p2])
    want = direct_greedy(model, params, frames, full, 4)
    assert g2 == want
    eng.close()


# ------------------------------------------------------- pause → resume
@pytest.mark.parametrize("quantum", [3])
def test_preemption_equivalence_8_sessions_2_slots(setup, quantum):
    """The capacity acceptance workload on whisper: 8 interleaved
    enc-dec sessions over 2 slots, mid-stream eviction + pipelined
    restoration, byte-for-byte equal to the unconstrained 8-slot run."""
    cfg, model, params = setup
    prompts = _prompts(cfg, 8)
    frames = [_frames(cfg, 12 + 2 * i, seed=20 + i) for i in range(8)]

    ref, _ = fresh_engine(setup, max_batch=8)
    for i, p in enumerate(prompts):
        ref.submit(Request(f"s{i}", p, max_new_tokens=5, frames=frames[i]))
    ref.run()
    want = {f"s{i}": ref.result(f"s{i}") for i in range(8)}
    ref.close()

    eng, _ = fresh_engine(setup, max_batch=2, preempt_quantum=quantum)
    for i, p in enumerate(prompts):
        eng.submit(Request(f"s{i}", p, max_new_tokens=5, frames=frames[i]))
    eng.run()
    got = {f"s{i}": eng.result(f"s{i}") for i in range(8)}
    assert eng.metrics.preemptions > 0
    assert all(s.phase.value == "done" for s in eng.sessions.values())
    assert got == want
    eng.close()


# ------------------------------------------------ restoration cost model
def test_cross_restore_tasks_modeled(setup):
    """The executor's graph carries the io_enc/project_cross pair; the
    replayed makespan charges the encoder blob read and the 1→2L cross
    projection (no longer a zero-cost blob), and the admission policy's
    session_restore_cost sees it through the manifest's enc_len."""
    cfg, model, params = setup
    eng, mgr = fresh_engine(setup)
    p = np.arange(8, dtype=np.int32)
    eng.submit(Request("c", p, max_new_tokens=3, frames=_frames(cfg, 24, 1)))
    eng.run()
    eng.close()
    ex = mgr.begin_restore(params, "c")
    kinds = [t.kind for t in ex.tasks]
    assert kinds.count("io_enc") == 1 and kinds.count("project_cross") == 1
    assert ex.cross_times is not None and ex.cross_times.compute > 0
    n = ex.n_tokens
    with_cross = restore_makespan(mgr, n, ex.methods, enc_len=24)
    without = restore_makespan(mgr, n, ex.methods, enc_len=0)
    assert with_cross > without
    assert session_restore_cost(mgr, "c") == pytest.approx(with_cross)


def test_engine_restore_timeline_includes_cross(setup):
    """Serving-path restore of an enc-dec session plans a makespan ≥
    the cross-only lower bound: the executor the engine restores with
    carries the cross tasks in its task graph."""
    cfg, model, params = setup
    eng, mgr = fresh_engine(setup)
    p = np.arange(10, dtype=np.int32)
    eng.submit(Request("t", p, max_new_tokens=3, frames=_frames(cfg, 16, 9)))
    eng.run()
    eng.submit(Request("t", np.arange(4, dtype=np.int32), max_new_tokens=2))
    eng.run()
    assert eng.sessions["t"].restored
    from repro.core.restoration import cross_restore_times
    ct = cross_restore_times(mgr, 16)
    ex = mgr.begin_restore(params, "t")
    kinds = [t.kind for t in ex.tasks]
    assert kinds.count("io_enc") == 1 and kinds.count("project_cross") == 1
    assert ex.predicted_makespan >= ct.compute
    eng.close()


# --------------------------------------------------------- adapter seam
def test_engine_has_no_family_branches():
    """Acceptance criterion: all family dispatch goes through the
    FamilyAdapter — the engine contains no ``model.kind`` branching."""
    import inspect
    import repro.serving.engine as engine_mod
    src = inspect.getsource(engine_mod)
    assert "model.kind" not in src
    assert 'kind ==' not in src


@pytest.mark.parametrize("arch,expect", [
    ("llama2-7b", ("chunkable", "supports_resume", "supports_paged",
                   "supports_recompute")),
    ("falcon-mamba-7b", ()),
    ("zamba2-2.7b", ()),
    ("whisper-medium", ("chunkable", "supports_resume", "supports_paged")),
])
def test_adapter_capability_matrix(arch, expect, rules):
    cfg = reduced_for_smoke(get_arch(arch))
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    flags = ("chunkable", "supports_resume", "supports_paged",
             "supports_recompute")
    got = tuple(f for f in flags if getattr(model.adapter, f))
    assert got == expect


# ------------------------------------------- hybrid unchunked regression
def test_encdec_chunked_prefill_matches_whole_prompt(setup):
    """Chunked decoder-prompt prefill (DESIGN.md §13 satellite): the
    encoder runs once on the FIRST chunk, later chunks attend to the
    already-resident cross context with the right position offset —
    greedy output is byte-identical to a single-chunk prefill and to
    the direct path."""
    cfg, model, params = setup
    prompt = (np.arange(19, dtype=np.int32) * 3) % cfg.vocab_size
    frames = _frames(cfg, 16, seed=11)
    outs = {}
    for chunk in (4, 64):                 # 19 tokens: 5 chunks vs 1
        eng, _ = fresh_engine(setup, prefill_chunk=chunk)
        eng.submit(Request("c", prompt, max_new_tokens=6, frames=frames))
        eng.run()
        outs[chunk] = eng.result("c")
        eng.close()
    assert outs[4] == outs[64]
    assert outs[4] == direct_greedy(model, params, frames, prompt, 6)


def test_hybrid_prefill_ignores_chunk_knob(rules):
    """Hybrid prefill must stay unchunked (recurrent conv/ssm states are
    computed in one scan with no carry-in): with prefill_chunk smaller
    than the prompt the engine still takes the whole prompt in one step
    and matches the direct path byte-for-byte."""
    cfg = reduced_for_smoke(get_arch("zamba2-2.7b"))
    model = Model(cfg, rules=rules, model_axis=1, dtype=jnp.float32,
                  remat="none")
    params, _ = split(model.init(jax.random.PRNGKey(0)))
    store = ChunkStore(make_array("dram", 4), chunk_tokens=16)
    mgr = HCacheManager(model, store, hw=PAPER_A100,
                        schedule_override="hidden", store_dtype=np.float32)
    eng = InferenceEngine(model, params, mgr, max_batch=1, max_seq=64,
                          prefill_chunk=4)
    prompt = (np.arange(17, dtype=np.int32) * 5) % cfg.vocab_size
    eng.submit(Request("h", prompt, max_new_tokens=5))
    eng.run()
    got = eng.result("h")
    # one engine step consumed the whole 17-token prompt
    assert eng.sessions["h"].prefill_done == len(prompt)

    pre = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    conv, ssm = pre["mamba_states"]

    def padkv(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, 64 - x.shape[2]),
                           (0, 0), (0, 0)))

    cache = {"attn_k": padkv(pre["kv"][0]), "attn_v": padkv(pre["kv"][1]),
             "conv": conv, "ssm": ssm,
             "lengths": jnp.asarray([len(prompt)], jnp.int32)}
    want = [int(jnp.argmax(pre["logits"][0, -1]))]
    for _ in range(4):
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        lg, cache = model.decode_step(params, cache, tok)
        want.append(int(jnp.argmax(lg[0, -1])))
    assert got == want
    eng.close()


def test_eviction_prices_cross_side(setup):
    """RestoreCostAwareEviction must see the enc-dec cross restoration
    cost (from the manifest's enc_len), exactly like admission does: of
    two sessions with equal decoder history, the one with the SMALL
    encoder context is the cheaper victim — without the enc_len plumb
    the makespans tie and the request_id tie-break would pick 'big'."""
    from types import SimpleNamespace

    from repro.core.capacity import RestoreCostAwareEviction

    cfg, model, params = setup
    eng, mgr = fresh_engine(setup)
    prompt = np.arange(6, dtype=np.int32)
    for sid, n_enc, seed in (("big", 48, 1), ("small", 8, 2)):
        eng.submit(Request(sid, prompt, max_new_tokens=3,
                           frames=_frames(cfg, n_enc, seed)))
    eng.run()
    seqs = [SimpleNamespace(total_len=9,
                            request=SimpleNamespace(session_id="big",
                                                    request_id=0)),
            SimpleNamespace(total_len=9,
                            request=SimpleNamespace(session_id="small",
                                                    request_id=1))]
    victim = RestoreCostAwareEviction().select_victim(seqs, eng)
    assert victim.request.session_id == "small"
    eng.close()


def test_enc_seq_capacity_overflow_fails_loudly(setup):
    """An encoder context larger than the backend's enc_seq must raise
    an actionable error naming the knob, not an opaque shape error."""
    cfg, model, params = setup
    eng, _ = fresh_engine(setup, enc_seq=8)
    eng.submit(Request("o", np.arange(4, dtype=np.int32), max_new_tokens=2,
                       frames=_frames(cfg, 16, seed=1)))
    with pytest.raises(ValueError, match="enc_seq"):
        eng.run()
    eng.close()
