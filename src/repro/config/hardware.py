"""Hardware profiles.

Two uses:
  1. Roofline analysis of the compiled dry-run (TPU v5e constants).
  2. The HCache cost model / bubble-free scheduler, which needs
     (FLOPS, host-link BW, storage BW) tuples — including the paper's own
     GPU platforms so the analytical replication of the paper's figures uses
     the paper's numbers.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    name: str
    flops: float                 # peak dense FLOP/s (bf16/fp16)
    hbm_bw: float                # bytes/s on-chip HBM
    interconnect_bw: float       # bytes/s per ICI/NVLink link
    host_link_bw: float          # bytes/s accelerator<->host (PCIe / v5e host DMA)
    storage_bw: float            # bytes/s aggregate storage backend read BW
    hbm_capacity: float          # bytes per chip
    chips: int = 1
    # fixed per-device-dispatch cost charged to every compute-stream task
    # in the restoration replay (kernel launch + host-side framework
    # overhead). 0.0 keeps the paper's pure-bandwidth/FLOPs model; the
    # grouped restoration path amortizes this over group_size layers
    # (tests/test_restore_batch.py checks the effect on makespan).
    dispatch_overhead: float = 0.0
    # tensor-parallel mesh width the restoration compute runs SPMD over
    # (DESIGN.md §16): projection FLOPs divide across the shards, the
    # dispatch overhead is charged once per launch (one XLA program, not
    # one per device). 1 = the classic single-device model.
    mesh_devices: int = 1

    def with_mesh(self, tp: int) -> "HardwareProfile":
        """A copy priced for a ``tp``-wide tensor-parallel mesh. The name
        changes too, so profiles for different meshes never alias in
        caches keyed by profile identity."""
        tp = max(int(tp), 1)
        if tp == self.mesh_devices:
            return self
        base = self.name.split("-tp")[0]
        return dataclasses.replace(
            self, name=base if tp == 1 else f"{base}-tp{tp}",
            mesh_devices=tp)

    def derated(self, *, storage: float = 1.0, host_link: float = 1.0,
                flops: float = 1.0,
                dispatch_overhead: float = None) -> "HardwareProfile":
        """A copy with bandwidths/FLOPs scaled and an optional dispatch
        overhead — the shape real hardware diverges from its datasheet in
        (shared PCIe lanes, filesystem overhead on the SSDs, sustained
        vs peak GEMM throughput). Every profile here is a guess until
        the online profiler (core/profiler.py) measures it; derated
        copies stand in for "what the machine actually does" in the
        calibration tests."""
        return dataclasses.replace(
            self, name=self.name + "-derated",
            storage_bw=self.storage_bw * storage,
            host_link_bw=self.host_link_bw * host_link,
            flops=self.flops * flops,
            dispatch_overhead=(self.dispatch_overhead
                               if dispatch_overhead is None
                               else dispatch_overhead))


TB = 1e12
GB = 1e9

# --- TPU target (assignment constants) --------------------------------------
TPU_V5E = HardwareProfile(
    name="tpu_v5e",
    flops=197e12,
    hbm_bw=819 * GB,
    interconnect_bw=50 * GB,
    host_link_bw=32 * GB,
    storage_bw=4 * 6.9 * GB,     # same 4×PM9A3 backend as the paper testbed
    hbm_capacity=16 * GB,
)

# --- paper platforms (Table 2; FP16 FLOPS, PCIe transmission) ----------------
PAPER_A100 = HardwareProfile("a100", 312e12, 2039 * GB, 600 * GB, 32 * GB,
                             4 * 6.9 * GB, 40 * GB)
PAPER_A30 = HardwareProfile("a30", 165e12, 933 * GB, 200 * GB, 32 * GB,
                            4 * 6.9 * GB, 24 * GB)
PAPER_4090 = HardwareProfile("4090", 330e12, 1008 * GB, 64 * GB, 32 * GB,
                             4 * 6.9 * GB, 24 * GB)
PAPER_L20 = HardwareProfile("l20", 120e12, 864 * GB, 64 * GB, 32 * GB,
                            4 * 6.9 * GB, 48 * GB)
PAPER_H800 = HardwareProfile("h800", 990e12, 3350 * GB, 400 * GB, 64 * GB,
                             4 * 6.9 * GB, 80 * GB)

PROFILES = {p.name: p for p in
            (TPU_V5E, PAPER_A100, PAPER_A30, PAPER_4090, PAPER_L20, PAPER_H800)}

# The profile of each accelerator this repo runs on, keyed by the
# ``device_kind`` JAX reports for it.
DEVICE_PROFILES = {"TPU v5 lite": TPU_V5E}


def profile_for_device(device) -> HardwareProfile:
    """The planner's hardware profile for a JAX device. An accelerator
    must be in ``DEVICE_PROFILES``; the CPU backend (tests and local
    rehearsals) plans for the TPU v5e target."""
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return DEVICE_PROFILES[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no hardware profile for device kind {device.device_kind!r} "
            f"(known: {sorted(DEVICE_PROFILES)}); add it to "
            f"DEVICE_PROFILES or pass --profile") from None

# MXU efficiency assumed for the cost model's GEMM estimates (cuBLAS/MXU
# sustained fraction on well-shaped GEMMs).
GEMM_EFFICIENCY = 0.65

# Storage devices for the chunk store simulation (paper's PM9A3).
SSD_READ_BW = 6.9 * GB
SSD_WRITE_BW = 4.0 * GB
DRAM_BW = 80 * GB

# Cross-host NIC link defaults for the distributed chunk store (100 GbE
# per host shard; RTT covers the request round-trip + kernel stack).
NIC_BW = 12.5 * GB
NIC_RTT = 30e-6

# TPU-native chunk size: 128 tokens (lane-aligned), vs the paper's 64.
TPU_CHUNK_TOKENS = 128
