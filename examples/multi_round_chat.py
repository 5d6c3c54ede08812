"""Multi-round conversation serving (paper §6.1.1, ShareGPT-like).

    PYTHONPATH=src python examples/multi_round_chat.py

Drives the continuous-batching engine with a small synthetic conversation
trace. Sessions are evicted after every round (as in the paper's setup) and
restored through HCache when the user returns; TTFT decomposition and
storage use are reported per round.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.config.arch import reduced_for_smoke
from repro.config.hardware import PAPER_A100
from repro.configs import get_arch
from repro.core.hcache import HCacheManager
from repro.distributed.sharding import default_rules
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.models.module import split
from repro.serving import InferenceEngine, Request
from repro.storage import ChunkStore, make_array
from repro.training.data import sharegpt_trace

mesh = make_mesh((1, 1), ("data", "model"))
rules = default_rules(mesh)
cfg = reduced_for_smoke(get_arch("llama2-7b"))
model = Model(cfg, rules=rules, dtype=jnp.float32, remat="none")
params, _ = split(model.init(jax.random.PRNGKey(0)))
store = ChunkStore(make_array("ssd", 4), chunk_tokens=16)
mgr = HCacheManager(model, store, hw=PAPER_A100)
engine = InferenceEngine(model, params, mgr, max_batch=4, max_seq=512,
                         prefill_chunk=16)

rng = np.random.default_rng(0)
trace = sharegpt_trace(3, rounds_per_session=3, seed=0)
for r in trace:
    n_in = min(r.input_len, 24)                 # CPU-friendly sizes
    n_out = min(r.output_len, 8)
    prompt = rng.integers(0, cfg.vocab_size, n_in).astype(np.int32)
    engine.submit(Request(r.session_id, prompt, max_new_tokens=n_out))
    engine.run()
    seq = engine.sessions[r.session_id]
    print(f"{r.session_id}: +{n_in} prompt, {len(seq.generated)} generated, "
          f"history {seq.history_len}, restore(wall) "
          f"{seq.restore_wall * 1e3:.3f} ms, TTFT(wall) {seq.ttft_wall:.3f} s")

m = engine.metrics
print(f"\n{len(m.ttft_wall)} requests; {m.restored_tokens} tokens restored; "
      f"{m.decode_steps} decode steps; store {store.bytes_used / 1e6:.1f} MB")
print(f"recoverable sessions after 'shutdown': "
      f"{engine.recoverable_sessions()}")
