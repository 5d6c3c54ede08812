"""Sharding rules: head padding invariants (hypothesis), spec dedup,
vocab padding."""
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import default_rules, pad_heads
from repro.launch.mesh import make_mesh
from repro.models.layers.embedding import padded_vocab


@settings(max_examples=200, deadline=None)
@given(
    kv=st.sampled_from([1, 2, 4, 8, 16, 32]),
    ratio=st.integers(1, 16),
    axis=st.sampled_from([1, 2, 4, 8, 16]),
)
def test_pad_heads_properties(kv, ratio, axis):
    n_heads = kv * ratio
    padded, group = pad_heads(n_heads, kv, axis)
    assert padded % axis == 0                 # shardable
    assert padded == kv * group               # GQA grouping preserved
    assert padded >= n_heads                  # never shrinks
    assert padded - n_heads < axis * kv       # bounded waste


def test_pad_heads_assigned_archs():
    from repro.configs import ASSIGNED
    for cfg in ASSIGNED.values():
        if cfg.n_heads == 0:
            continue
        padded, group = pad_heads(cfg.n_heads, cfg.n_kv_heads, 16)
        assert padded % 16 == 0
        waste = padded / cfg.n_heads
        assert waste <= 1.25, f"{cfg.name}: {waste}"


def test_spec_dedup_never_reuses_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh).with_rules(a=("data", "model"),
                                           b=("data",))
    spec = rules.spec(("a", "b"))
    flat = []
    for part in spec:
        if part is None:
            continue
        flat.extend([part] if isinstance(part, str) else list(part))
    assert len(flat) == len(set(flat))


def test_long_context_rules_replicate_batch():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(mesh, long_context=True)
    assert rules.spec(("batch",)) == P(None)
    kv = rules.spec(("kv_seq",))
    assert kv != P(None)


def test_padded_vocab():
    assert padded_vocab(49155) == 49280
    assert padded_vocab(152064) == 152064
    assert padded_vocab(51865) % 128 == 0
    for v in (49155, 51865, 92553):
        assert padded_vocab(v) % 16 == 0
