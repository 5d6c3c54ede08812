"""Random weights from a seed, in the program's parameter layout.

Every leaf is a pure function of ``(seed, leaf name, layer)``: uniform
values from ``jax.random.bits`` scaled by one multiply (a single
rounding) and cast to the served dtype. The program's stacked tree is
built from it in one jitted call on the device; the float32 reference
rebuilds any one layer from the same function, so it shares no array
with the program and still sees the same numbers bit for bit. The seed's
key and the layer enter those calls as arguments, so one compiled
program serves every seed and layer.

``dense_layout`` names the leaves of the dense transformer family from
the published sizes alone; ``check_layout`` holds the program's own
parameter tree to it, so a change of the program's layout is an error
here and never a silent mismatch with the reference.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SQRT3 = 3.0 ** 0.5


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including those wider than
    32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_init(name: str, shape: Tuple[int, ...]) -> Tuple[float, float]:
    """(offset, standard deviation) of one leaf, by its last name part."""
    last = name.rsplit("/", 1)[-1]
    if last in ("table", "positions"):
        # small, as OPT and GPT initialise them: with a tied head a large
        # input embedding makes every next token an echo of the last
        return 0.0, 0.02
    if last == "scale":
        return 1.0, 0.1
    if last == "bias":
        return 0.0, 0.1
    if last in ("bq", "bk", "bv"):
        return 0.0, 0.5
    return 0.0, float(shape[-2]) ** -0.5        # matrices: 1/sqrt(fan-in)


def gen_leaf(key, name: str, shape, dtype, layer=0):
    """One leaf (or one layer of a stacked leaf) from the seed's key."""
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    bits = jax.random.bits(key, tuple(shape), jnp.uint16)
    offset, std = leaf_init(name, shape)
    mult = np.float32(std * SQRT3 / 32768.0)
    x = (bits.astype(jnp.int32) - 32768).astype(jnp.float32) * mult
    x = x.astype(dtype)
    if offset:
        x = x + jnp.asarray(offset, dtype)
    return x


def padded_vocab(vocab: int, multiple: int = 128) -> int:
    return -(-vocab // multiple) * multiple


def dense_layout(arch: dict) -> List[Tuple[str, Tuple[int, ...], bool]]:
    """(name, shape, stacked over layers) of every leaf of the dense
    family, from the reference's normalized sizes (``references/dense``).
    Stacked leaves carry a leading layer axis."""
    D, L = arch["d_model"], arch["n_layers"]
    qd = arch["n_heads"] * arch["head_dim"]
    kvd = arch["n_kv_heads"] * arch["head_dim"]
    F, Vp = arch["d_ff"], arch["vocab_padded"]
    out = [("embed/table", (Vp, D), False)]
    if not arch["tie_embeddings"]:
        out.append(("embed/unembed", (D, Vp), False))
    if arch["learned_positions"]:
        out.append(("embed/positions", (arch["position_rows"], D), False))
    norm = ["scale"] + (["bias"] if arch["norm"] == "layernorm" else [])
    for ln in ("ln1", "ln2"):
        out += [(f"blocks/{ln}/{p}", (L, D), True) for p in norm]
    out += [("blocks/attn/wq", (L, D, qd), True),
            ("blocks/attn/wk", (L, D, kvd), True),
            ("blocks/attn/wv", (L, D, kvd), True),
            ("blocks/attn/wo", (L, qd, D), True)]
    if arch["qkv_bias"]:
        out += [("blocks/attn/bq", (L, qd), True),
                ("blocks/attn/bk", (L, kvd), True),
                ("blocks/attn/bv", (L, kvd), True)]
    out += [("blocks/mlp/w_up", (L, D, F), True),
            ("blocks/mlp/w_down", (L, F, D), True)]
    if arch["glu"]:
        out.append(("blocks/mlp/w_gate", (L, D, F), True))
    out += [(f"final_norm/{p}", (D,), False) for p in norm]
    return out


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def check_layout(abstract_params, layout) -> None:
    """Raise unless the program's parameter tree has exactly the layout's
    leaves, names and shapes."""
    got = {_path_name(p): tuple(v.shape) for p, v in
           jax.tree_util.tree_flatten_with_path(abstract_params)[0]}
    want = {n: tuple(s) for n, s, _ in layout}
    if got != want:
        missing = sorted(set(want) - set(got))
        extra = sorted(set(got) - set(want))
        shapes = sorted(n for n in set(got) & set(want)
                        if got[n] != want[n])
        raise RuntimeError(
            f"program parameter layout differs from the benchmark's: "
            f"missing {missing}, unexpected {extra}, shape differs "
            f"{[(n, got[n], want[n]) for n in shapes]}")


def program_params(abstract_params, layout, seed: int, dtype):
    """The program's whole parameter tree, made on the device in one
    jitted call. Stacked leaves are built for every layer at once, mapped
    over the layer index (the same keys as ``layer_params``)."""
    stacked = {n: s for n, _, s in layout}

    def build(key):
        def leaf(path, sds):
            name = _path_name(path)
            if stacked[name]:
                return jax.vmap(lambda li: gen_leaf(
                    key, name, sds.shape[1:], dtype, layer=li))(
                        jnp.arange(sds.shape[0], dtype=jnp.int32))
            return gen_leaf(key, name, sds.shape, dtype)
        return jax.tree_util.tree_map_with_path(leaf, abstract_params)

    return jax.jit(build)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _leaf_program(names, dtype):
    """A jitted ``(key, layer) -> {name: leaf}`` for fixed names, shapes
    and dtype."""
    @jax.jit
    def build(key, layer):
        return {n: gen_leaf(key, n, s, dtype, layer=layer)
                for n, s in names}
    return build


def layer_params(layout, seed: int, dtype, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, keyed by their last two name parts
    (``attn/wq``, ``ln1/scale``…), as the program holds them."""
    names = tuple((n, tuple(s[1:])) for n, s, st in layout if st)
    out = _leaf_program(names, jnp.dtype(dtype))(seed_key(seed),
                                            jnp.int32(layer))
    return {n.split("/", 1)[1]: v for n, v in out.items()}


def global_params(layout, seed: int, dtype) -> Dict[str, jax.Array]:
    """The unstacked leaves (embedding, positions, final norm)."""
    names = tuple((n, tuple(s)) for n, s, st in layout if not st)
    return _leaf_program(names, jnp.dtype(dtype))(seed_key(seed),
                                                  jnp.int32(0))
