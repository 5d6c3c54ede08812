"""The served restore kernel compiled for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` slice and the TPU compiler, which is installed here,
compiles for it. This catches what interpret mode cannot — block shapes
the TPU lowering refuses, kernels over the scoped VMEM limit, Mosaic
calls left to the SPMD partitioner — at real widths, in a few seconds.
The topology is described inside a fixture (never at import), so only
the test worker that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

G, S, HD = 8, 2048, 128
# (name, d_model, KV = n_kv_heads * head_dim, QKV bias, RoPE, the tile
# (BLOCK_S, BLOCK_KV) the kernel takes)
WIDTHS = [("qwen2-7b", 3584, 4 * HD, True, True, (256, 128)),
          ("llama2-7b", 4096, 32 * HD, False, True, (256, 128)),
          ("opt-30b", 7168, 56 * HD, True, False, (128, 128))]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 - no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("model",))


def test_v5e_kind_has_a_profile(topo):
    from repro.config.hardware import TPU_V5E, profile_for_device
    assert profile_for_device(topo.devices[0]) is TPU_V5E


@pytest.mark.parametrize("name,D,KV,bias,rope,tile", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_restore_kernel_compiles_at_real_width(one_chip, name, D, KV, bias,
                                               rope, tile):
    from repro.kernels.restore_kv import (VMEM_BUDGET, _pick_blocks,
                                          _vmem_bytes,
                                          restore_kv_grouped_pallas)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b = sds((G, KV)) if bias else None
    compiled = restore_kv_grouped_pallas.lower(
        sds((G, S, D)), sds((G, D, KV)), sds((G, D, KV)), b, b,
        sds((S, HD // 2), jnp.float32), sds((S, HD // 2), jnp.float32),
        head_dim=HD, use_rope=rope, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    bs, bkv = _pick_blocks(S, KV, D, HD, 2, bias, 256, 0)
    assert (bs, bkv) == tile
    assert _vmem_bytes(bs, bkv, D, HD, 2, bias) <= VMEM_BUDGET


def test_sharded_restore_projection_compiles_per_device(mesh4):
    """tp=4: the served projection (norm + grouped kernel) over KV-sharded
    weight stacks runs the Mosaic kernel under shard_map — one custom
    call per device on its own heads, and no collective at all (the
    weight stacks are never all-gathered)."""
    from repro.core.restoration import _project_group_jit
    A, D, KV = 14, 3584, 4 * HD
    rep = NamedSharding(mesh4, P())
    kv3 = NamedSharding(mesh4, P(None, None, "model"))
    kv2 = NamedSharding(mesh4, P(None, "model"))

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = _project_group_jit.lower(
        sds((G, S, D), jnp.float16, rep), sds((G,), jnp.int32, rep),
        sds((A, D), jnp.bfloat16, rep), None,
        sds((A, D, KV), jnp.bfloat16, kv3), sds((A, D, KV), jnp.bfloat16, kv3),
        sds((A, KV), jnp.bfloat16, kv2), sds((A, KV), jnp.bfloat16, kv2),
        sds((S, HD // 2), jnp.float32, rep),
        sds((S, HD // 2), jnp.float32, rep),
        norm_kind="rmsnorm", eps=1e-6, head_dim=HD, use_rope=True,
        dtype=jnp.bfloat16, use_pallas=True, interpret=False,
        kv_sharding=kv3).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter", text)
    for out in compiled.output_shardings:       # (G, S, Kv, hd) k and v
        assert out.spec[2] == "model"
