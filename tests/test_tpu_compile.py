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


# the paged pools of the two benchmark cells: (name, slots, table row,
# pages, query heads, KV heads) at pages of 16 tokens and head size 128
POOLS = [("qwen2-7b", 16, 784, 2400, 28, 4),
         ("opt-30b", 8, 128, 1000, 56, 56)]


@pytest.mark.parametrize("name,B,MB,NB,Hp,Kv", POOLS,
                         ids=[p[0] for p in POOLS])
def test_paged_decode_kernel_compiles_at_cell_pools(one_chip, name, B, MB,
                                                    NB, Hp, Kv):
    """The served paged kernel at a cell's whole pool, windowed and
    softcapped too: the pools enter the custom call as they are, with
    no copy or relayout in front of it."""
    from repro.kernels.decode_attention import paged_decode_attention_pallas

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((6, NB, 16, Kv, HD))
    args = (sds((B, Hp, HD)), sds((B, Kv, HD)), sds((B, Kv, HD)), pool,
            pool, sds((B, MB), jnp.int32), sds((B,), jnp.int32),
            sds((), jnp.int32))
    for window, softcap in ((None, None), (sds((), jnp.int32), 30.0)):
        text = paged_decode_attention_pallas.lower(
            *args, window, softcap=softcap, interpret=False).compile() \
            .as_text()
        assert "tpu_custom_call" in text
        assert not re.search(rf"\[6,{NB},16,{Kv},{HD}\]\S* (copy|convert)",
                             text)


@pytest.mark.parametrize("kv", [1, 2, 4, 14, 56])
def test_pages_fit_tiles_says_what_the_compiler_takes(one_chip, kv):
    """The kernel's page DMA compiles exactly where ``pages_fit_tiles``
    says it does (bf16 pools); the model gathers elsewhere."""
    from repro.kernels.decode_attention import (pages_fit_tiles,
                                                paged_decode_attention_pallas)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, MB, NB = 4, 16, 80
    pool = sds((2, NB, 16, kv, HD))
    lower = paged_decode_attention_pallas.lower(
        sds((B, kv, HD)), sds((B, kv, HD)), sds((B, kv, HD)), pool, pool,
        sds((B, MB), jnp.int32), sds((B,), jnp.int32), sds((), jnp.int32),
        interpret=False)
    if pages_fit_tiles(kv, jnp.bfloat16):
        assert "tpu_custom_call" in lower.compile().as_text()
    else:
        with pytest.raises(Exception, match="aligned to tiling"):
            lower.compile()


def test_sharded_paged_decode_compiles_per_device(topo):
    """tp=2 over qwen2-7b's 4 KV heads: the kernel runs under shard_map
    on each device's 2 heads of a head-sharded pool — a custom call per
    device and no collective."""
    from repro.kernels.decode_attention import paged_decode_attention_pallas
    mesh = Mesh(np.asarray(topo.devices[:2]), ("model",))
    B, MB, NB, Hp, Kv = POOLS[0][1:]
    rep = NamedSharding(mesh, P())
    heads = NamedSharding(mesh, P(None, "model", None))
    pool = jax.ShapeDtypeStruct((14, NB, 16, Kv, HD), jnp.bfloat16,
                                sharding=NamedSharding(
                                    mesh, P(None, None, None, "model")))

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    compiled = paged_decode_attention_pallas.lower(
        sds((B, Hp, HD), jnp.bfloat16, heads),
        sds((B, Kv, HD), jnp.bfloat16, heads),
        sds((B, Kv, HD), jnp.bfloat16, heads), pool, pool,
        sds((B, MB), jnp.int32, rep), sds((B,), jnp.int32, rep),
        sds((), jnp.int32, rep), interpret=False,
        head_sharding=heads).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter", text)
    assert compiled.output_shardings.spec[1] == "model"


@pytest.mark.parametrize("name,B,MB,NB,Hp,Kv", POOLS,
                         ids=[p[0] for p in POOLS])
def test_decode_step_reads_the_pool_in_place(one_chip, monkeypatch, name, B,
                                             MB, NB, Hp, Kv):
    """The served decode step (two layers at the cell's widths, its whole
    pool, the cache donated) on its TPU branch: the kernel reads the
    pools, and nothing in the program is as large as one layer's pages
    but the pools themselves, passed through the layer scan, and the one
    in-place scatter of the new tokens. So no gather of a slot's logical
    cache, no copy or convert of the pool, and the pools alias the
    step's outputs."""
    import dataclasses
    from repro.configs import get_arch
    from repro.distributed.sharding import default_rules
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    arch = dataclasses.replace(get_arch(name), n_layers=2)
    model = Model(arch, rules=default_rules(make_mesh((1, 1),
                                                      ("data", "model"))),
                  model_axis=1, dtype=jnp.bfloat16, remat="none")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          model.abstract_params()[0])
    pool = sds((2, NB, 16, Kv, HD))
    cache = {"k_pool": pool, "v_pool": pool,
             "block_table": sds((B, MB), jnp.int32),
             "lengths": sds((B,), jnp.int32)}
    compiled = jax.jit(lambda p, c, t: model.decode_step_paged(p, c, t),
                       donate_argnums=(1,)).lower(
        params, cache, sds((B, 1), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer = NB * 16 * Kv * HD
    scatters, comp = set(), None                  # fusions rooted in a scatter
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[0]
        elif line.lstrip().startswith("ROOT") and " scatter(" in line:
            scatters.add(comp)
    for m in re.finditer(r"%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(([^\n]*)",
                         text):
        dims = [int(d) for d in m.group(1).split(",")]
        if dims[-2:] != [Kv, HD] or np.prod(dims) < layer:
            continue
        op = m.group(2)
        if op == "fusion":
            assert re.search(r"calls=(%\S+?)[,}\s]", m.group(3)).group(1) \
                in scatters, m.group(0)[:200]
        else:
            assert op in ("parameter", "get-tuple-element", "scatter"), \
                m.group(0)[:200]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool.size * 2
    assert mem.temp_size_in_bytes < layer * 2


def test_sharded_decode_step_gathers_only_attention_output(topo,
                                                          monkeypatch):
    """tp=2, qwen2-7b widths (two layers): the served step runs the
    kernel per device on a head-sharded pool, and its one collective is
    the all-gather of the attention output at the logits seam — the
    pool never crosses devices and stays sharded through the step."""
    import dataclasses
    from repro.configs import get_arch
    from repro.distributed import tp as tp_lib
    from repro.distributed.sharding import default_rules
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    ctx = tp_lib.TPContext(2)
    ctx.mesh = Mesh(np.asarray(topo.devices[:2]), (ctx.axis,))
    arch = dataclasses.replace(get_arch("qwen2-7b"), n_layers=2)
    model = Model(arch, rules=default_rules(make_mesh((1, 1),
                                                      ("data", "model"))),
                  model_axis=1, dtype=jnp.bfloat16, remat="none")
    rep = NamedSharding(ctx.mesh, P())
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        model.abstract_params()[0])
    B, MB, NB, Hp, Kv = POOLS[0][1:]
    pool = jax.ShapeDtypeStruct((2, NB, 16, Kv, HD), jnp.bfloat16,
                                sharding=ctx.kv_sharding(5, 3))
    cache = {"k_pool": pool, "v_pool": pool,
             "block_table": jax.ShapeDtypeStruct((B, MB), jnp.int32,
                                                 sharding=rep),
             "lengths": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)}
    with tp_lib.tp_seam(ctx):
        compiled = jax.jit(
            lambda p, c, t: model.decode_step_paged(p, c, t),
            donate_argnums=(1,)).lower(
            params, cache,
            jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    collectives = re.findall(r"= (\w+)\[([\d,]+)\]\S* (all-gather|all-reduce|"
                             r"all-to-all|collective-permute|reduce-scatter)"
                             r"\(", text)
    assert collectives == [("bf16", f"{B},{Hp},{HD}", "all-gather")]
    _, k_out, _, v_out = jax.tree.leaves(compiled.output_shardings)[1:5]
    assert k_out.spec == v_out.spec == P(None, None, None, "model")
