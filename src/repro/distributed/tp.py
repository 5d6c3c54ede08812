"""Tensor-parallel mesh context for the device-sharded serving path
(DESIGN.md §16).

One ``TPContext`` describes the 1-D tensor-parallel mesh the serving
engine shards device state over: the KV-head axis of the paged page
pool, the KV output axis of the ``RestoreParamPack`` weight stacks, and
the head axis of decode attention. With ``tp == 1`` there is no mesh
(``spmd`` is False and every placement helper is the identity); a
``tp`` wider than the visible devices is an error, never a silent
fallback to one device.

Where each array lives under ``tp > 1``:

  * model parameters — replicated on every mesh device (the engine
    commits them so), as are the hidden-state uploads and RoPE tables
    the restore projection reads, and whatever leaves the pool for
    unsharded code (chunked-prefill history, pause snapshots): that
    code then runs as one replicated program over the mesh;
  * the page pool and the projection weight stacks (wk/wv/bk/bv) —
    sharded on the KV-head axis, 1/tp of the bytes per device.

Sharding discipline (the byte-identity invariant the tests pin):

  * every sharded tensor is sharded on a NON-contracted dimension (KV
    heads / flattened KV outputs), so each output element is still one
    full-depth contraction computed on exactly one device — restored
    caches and attention outputs are bitwise identical to the
    single-device program;
  * the restore sink path never crosses devices: projections emit
    KV-head-sharded values and the page pool is sharded the same way,
    so ``write_layer_group`` scatters are shard-local;
  * the ONE collective on the decode path is the all-gather the
    ``logits_seam`` constraint forces right before the attention output
    projection — replicating ``attn_out`` there keeps the ``wo``
    contraction (and everything downstream, through the logits) an
    unsharded full-depth matmul instead of a partial-sum + psum whose
    float reorder would break bitwise identity.

Tests and benches force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
imports) so the SPMD path runs everywhere.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

TP_AXIS = "model"


class TPContext:
    """A 1-D tensor-parallel mesh over the first ``tp`` local devices.

    ``spmd`` is True iff ``tp > 1``; all placement helpers are identities
    otherwise, so callers never branch. Raises when fewer than ``tp``
    devices are visible.
    """

    def __init__(self, tp: int = 1, *, axis: str = TP_AXIS):
        self.tp = max(int(tp), 1)
        self.axis = axis
        n_dev = len(jax.devices())
        if self.tp > n_dev:
            raise ValueError(
                f"tensor-parallel width tp={self.tp} needs {self.tp} "
                f"devices; {n_dev} visible (on the CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.tp} "
                f"before jax is imported)")
        self.spmd = self.tp > 1
        self.mesh = None
        if self.spmd:
            from repro.launch.mesh import make_mesh
            self.mesh = make_mesh((self.tp,), (axis,))

    def __repr__(self):
        return f"TPContext(tp={self.tp}, spmd={self.spmd})"

    # hashable identity for plan-cache keys
    def key(self):
        return (self.tp, self.spmd)

    def validate_heads(self, n_kv_heads: int) -> None:
        if self.spmd and n_kv_heads % self.tp:
            raise ValueError(
                f"tensor-parallel width tp={self.tp} must divide the "
                f"model's n_kv_heads={n_kv_heads} (each device owns an "
                f"equal slice of the KV-head axis)")

    # ----------------------------------------------------------- shardings
    def kv_sharding(self, ndim: int, kv_axis: int)\
            -> Optional[NamedSharding]:
        """NamedSharding placing the mesh axis on dimension ``kv_axis``
        of an ``ndim``-rank tensor (None when not SPMD)."""
        if not self.spmd:
            return None
        spec = [None] * ndim
        spec[kv_axis] = self.axis
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> Optional[NamedSharding]:
        return NamedSharding(self.mesh, P()) if self.spmd else None

    # ----------------------------------------------------------- placement
    def shard_kv(self, x, kv_axis: int):
        """Commit ``x`` sharded on ``kv_axis`` across the mesh."""
        if not self.spmd:
            return x
        return jax.device_put(x, self.kv_sharding(x.ndim, kv_axis))

    def replicate(self, x):
        """Commit ``x`` (an array or a pytree of them) replicated across
        the mesh — also the seam from sharded state back into unsharded
        code (gather_hist feeding prefill, snapshots feeding the host
        store)."""
        if not self.spmd:
            return x
        return jax.device_put(x, self.replicated())


# --------------------------------------------------------------- seam hooks
# The decode/restore jits of a sharded backend trace under the active
# context (``tp_seam``); the model code calls the seam functions below at
# the points where the sharding discipline must be pinned. With no
# active SPMD context both are identities, so unsharded callers compile
# the exact pre-TP program.
_ACTIVE: List[Optional[TPContext]] = [None]


@contextlib.contextmanager
def tp_seam(ctx: Optional[TPContext]):
    prev = _ACTIVE[0]
    _ACTIVE[0] = ctx if (ctx is not None and ctx.spmd) else None
    try:
        yield
    finally:
        _ACTIVE[0] = prev


def active() -> Optional[TPContext]:
    return _ACTIVE[0]


def kv_seam(x, kv_axis: int):
    """Constrain ``x`` sharded over KV heads on ``kv_axis`` (page pools
    and K/V tensors inside a sharded decode step)."""
    ctx = _ACTIVE[0]
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, ctx.kv_sharding(x.ndim, kv_axis))


def logits_seam(x):
    """The single small all-gather of the sharded decode path: replicate
    the per-head attention output right before the output projection, so
    the ``wo`` contraction and the logits stay bitwise identical to the
    single-device program (see module docstring)."""
    ctx = _ACTIVE[0]
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.replicated())
