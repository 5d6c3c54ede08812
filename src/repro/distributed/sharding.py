"""Logical-axis sharding rules (GSPMD style).

Model code annotates parameters and activations with *logical* axis names;
this module maps them onto the physical mesh axes of the production meshes
``(data=16, model=16)`` / ``(pod=2, data=16, model=16)``.

Key decisions (see DESIGN.md §4):
  * batch            -> (pod,) data        (pure DP; pods are DP islands)
  * heads / qkv_out  -> model              (TP attention; heads padded to a
                                            multiple of the model axis)
  * d_ff / vocab     -> model              (TP FFN + vocab-parallel CE)
  * kv_seq           -> model              (decode KV cache sharded along the
                                            context; flash-decoding style)
  * fsdp             -> data               (ZeRO-1/3: master params + optimizer
                                            state sharded over the data axis)
  * long-context batch=1 cells additionally shard kv_seq over (data, model).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Dict[str, MeshAxes]

    def spec(self, axes: Sequence[Optional[str]]) -> P:
        """Logical axes tuple -> PartitionSpec, dropping unknown axes."""
        parts, used = [], set()
        for ax in axes:
            m = self.rules.get(ax) if ax is not None else None
            if m is None:
                parts.append(None)
                continue
            ms = (m,) if isinstance(m, str) else tuple(m)
            ms = tuple(a for a in ms if a not in used)
            used.update(ms)
            parts.append(ms if len(ms) != 1 else ms[0])
            if not ms:
                parts[-1] = None
        return P(*parts)

    def named(self, mesh: Mesh, axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(axes))

    def tree_specs(self, axes_tree):
        """Axes tree (from module.split) -> PartitionSpec tree."""
        return jax.tree.map(self.spec, axes_tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    def tree_shardings(self, mesh: Mesh, axes_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s),
                            self.tree_specs(axes_tree),
                            is_leaf=lambda s: isinstance(s, P))

    def with_rules(self, **updates) -> "ShardingRules":
        new = dict(self.rules)
        new.update(updates)
        return ShardingRules(new)


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def default_rules(mesh: Mesh, *, seq_shard: bool = False,
                  long_context: bool = False) -> ShardingRules:
    """Baseline rules; ``seq_shard`` enables sequence-parallel prefill
    (beyond-paper perf variant), ``long_context`` spreads the KV/context of
    batch=1 cells over both data and model axes."""
    data = data_axes_of(mesh)
    rules: Dict[str, MeshAxes] = {
        # activations — long-context cells have batch=1: replicate batch and
        # spread the context over (data, model) instead
        "batch": None if long_context else data,
        "seq": data if seq_shard else None,
        "kv_seq": (*data, "model") if long_context else "model",
        "d_model": None,
        "heads": "model",
        "kv_heads": None,           # kv heads < model axis: replicated
        "head_dim": None,
        # parameters
        "qkv_out": "model",
        "kv_out": "model",          # flattened kv projection out dim
        "o_in": "model",
        "d_ff": "model",
        "vocab": "model",
        "experts": None,
        "layers": None,
        "fsdp": None,               # weight-dim data sharding, enabled per-arch
        "opt_fsdp": data,           # optimizer state is ALWAYS data-sharded (ZeRO-1)
        # ssm
        "ssm_inner": "model",
        "ssm_state": None,
        "ssm_heads": "model",
        "conv_w": None,
        "dt_rank": None,
    }
    return ShardingRules(rules)


def fsdp_rules(mesh: Mesh, **kw) -> ShardingRules:
    """Weights 2D-sharded — required for grok-1-314b (628 GB bf16).

    The expert FFN width is sharded over (data × model) — 32768/256 = 128 —
    so the dominant weights (301B of 314B params) are consumed *sharded* and
    XLA never materializes a gathered expert stack. The residual "fsdp" axis
    handles optimizer-state/master-param ZeRO sharding."""
    data = data_axes_of(mesh)
    return default_rules(mesh, **kw).with_rules(
        fsdp=data, d_ff=(*data, "model"))


def current_mesh():
    """The ambient (abstract) mesh; empty outside a mesh context."""
    return jax.sharding.get_abstract_mesh()


def mesh_context(mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def constrain(x, rules: ShardingRules, *axes: Optional[str]):
    """with_sharding_constraint by logical axes (no-op outside a mesh
    context, so layer code runs unchanged in single-device tests)."""
    mesh = current_mesh()
    if mesh.empty:
        return x
    spec = rules.spec(axes)
    if all(p is None for p in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pad_heads(n_heads: int, n_kv_heads: int, axis_size: int) -> Tuple[int, int]:
    """Pad q heads so (group size × kv heads) is divisible by the model axis.

    Returns (padded_heads, group_size). KV head count is never padded — KV
    tensors stay at their true width (they are replicated or kv_seq-sharded).
    """
    if n_heads == 0:
        return 0, 0
    group = max(n_heads // n_kv_heads, 1)
    padded = n_kv_heads * group
    while padded % axis_size:
        group += 1
        padded = n_kv_heads * group
    return padded, group


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
