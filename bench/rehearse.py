#!/usr/bin/env python3
"""Compile a configuration's device programs for a described TPU v5e
(no chip needed) and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py qwen2-7b [opt-30b ...]

Per configuration: the paged decode step at the configuration's batch
and page pool (the step holds the pool twice: it is not donated), one
prefill chunk at the longest history, and the restore projection
(``_project_group_jit`` with the Pallas kernel) at the largest token
bucket. Weights + two pools + the largest temporaries must stay under
the chip's 16 GB; this is where ``cache_blocks`` in each configuration
file comes from.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def rehearse(name: str, topo=None) -> dict:
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import stack
    from repro.core.restoration import _project_group_jit, s_bucket
    from repro.distributed.sharding import default_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    if topo is None:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    prog = cfg["program"]
    arch, ref = stack.program_arch(cfg)
    model = Model(arch, rules=default_rules(make_mesh((1, 1),
                                                      ("data", "model"))),
                  model_axis=1, dtype=jnp.bfloat16, remat="none")
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
    params = jax.tree.map(sds, model.abstract_params()[0])
    B, bs = prog["max_batch"], prog["block_size"]
    NB, MB = prog["cache_blocks"], -(-prog["max_seq"] // bs)
    L, Kv, hd = arch.n_layers, arch.n_kv_heads, arch.head_dim_
    pool = jax.ShapeDtypeStruct((L, NB, bs, Kv, hd), jnp.bfloat16,
                                sharding=one)
    cache = {"k_pool": pool, "v_pool": pool,
             "block_table": jax.ShapeDtypeStruct((B, MB), jnp.int32,
                                                 sharding=one),
             "lengths": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)}
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
    out = {}
    dec = jax.jit(model.decode_step_paged).lower(params, cache, toks).compile()
    out["decode"] = dec.memory_analysis()
    hist = prog["max_seq"] - 128
    hkv = jax.ShapeDtypeStruct((L, 1, hist, Kv, hd), jnp.bfloat16,
                               sharding=one)
    chunk = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one)
    pre = jax.jit(lambda p, t, k, v: model.adapter.prefill(
        p, {"tokens": t}, capture_hidden=True, hist_kv=(k, v),
        hist_len=hist)).lower(params, chunk, hkv, hkv).compile()
    out["prefill"] = pre.memory_analysis()
    S = s_bucket(prog["max_seq"])
    G = prog["restore_group_size"]
    D, KV = arch.d_model, Kv * hd
    A = L
    w = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
    bias = w(A, KV) if arch.qkv_bias else None
    ln_b = w(A, D) if arch.norm == "layernorm" else None
    cos = jax.ShapeDtypeStruct((S, hd // 2), jnp.float32, sharding=one)
    proj = _project_group_jit.lower(
        jax.ShapeDtypeStruct((G, S, D), jnp.float16, sharding=one),
        jax.ShapeDtypeStruct((G,), jnp.int32, sharding=one),
        w(A, D), ln_b, w(A, D, KV), w(A, D, KV), bias, bias,
        cos, cos,
        norm_kind=arch.norm, eps=arch.norm_eps, head_dim=hd,
        use_rope=arch.use_rope, dtype=jnp.bfloat16, use_pallas=True,
        interpret=False, kv_sharding=None)
    text = proj.as_text()
    out["projection"] = proj.compile().memory_analysis()
    out["projection_has_kernel"] = "tpu_custom_call" in text
    w_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    out["weights_bytes"] = w_bytes
    out["pool_bytes"] = 2 * pool.size * pool.dtype.itemsize
    return out


def main() -> None:
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:] or ["qwen2-7b", "opt-30b"]:
        r = rehearse(name)
        print(f"{name}: weights {r['weights_bytes']} B, K+V page pool "
              f"{r['pool_bytes']} B (twice during decode)")
        for k in ("decode", "prefill", "projection"):
            m = r[k]
            print(f"  {k}: arguments {m.argument_size_in_bytes}, outputs "
                  f"{m.output_size_in_bytes}, temporaries "
                  f"{m.temp_size_in_bytes}, aliased {m.alias_size_in_bytes}")
        print(f"  projection lowers to the Pallas kernel: "
              f"{r['projection_has_kernel']}")
        peak = (r["decode"].argument_size_in_bytes
                + r["decode"].output_size_in_bytes
                + r["decode"].temp_size_in_bytes)
        print(f"  decode step: weights + pool in + pool out + temporaries "
              f"= {peak} B ({peak / 1e9:.2f} GB)")


if __name__ == "__main__":
    main()
