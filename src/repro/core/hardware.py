"""Hardware spec registry for estimator-mode profiling and roofline analysis.

The paper measures on A6000 / Jetson AGX Thor / Orin Nano; the assignment
targets TPU v5e pods.  Peak numbers below are vendor-published; the TPU
constants are the ones fixed by the assignment (197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI).  ``eta_*`` are achievable-fraction derates used by
the latency estimator (sustained / peak — published MLPerf-class systems
typically sustain 60-80% of peak HBM bandwidth and 40-70% of peak matmul
throughput at LLM shapes).

Specs are found two ways: by ``name`` (``get_hardware``, what the
estimator's ``--hardware`` flag takes) and by the ``device_kind`` string
the JAX runtime reports for a device (``hardware_for_device``, what a
measured run uses for the device it actually ran on).  A device kind
missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    kind: str                  # gpu | edge | tpu | cpu
    peak_flops_bf16: float     # FLOP/s per chip (bf16/fp16 tensor)
    hbm_bw: float              # bytes/s per chip
    link_bw: float             # bytes/s per inter-chip link (ICI / NVLink / PCIe)
    num_links: int             # links per chip contributing to collectives
    tdp_watts: float           # board power at full load
    idle_watts: float          # board power at idle
    mem_bytes: int             # HBM / unified memory per chip
    eta_compute: float = 0.6   # sustained fraction of peak FLOP/s
    eta_memory: float = 0.75   # sustained fraction of peak HBM BW
    eta_link: float = 0.8      # sustained fraction of peak link BW
    launch_overhead_s: float = 30e-6  # per-step dispatch overhead
    # power as seen by the paper's sensor. Jetson numbers come from the GPU
    # rail (jtop), which excludes DRAM/SoC power -> much lower than board TDP.
    rail_tdp_watts: float = 0.0   # 0 -> use tdp_watts
    rail_idle_watts: float = -1.0  # <0 -> use idle_watts
    # ``jax.Device.device_kind`` this platform reports, as seen on it
    # ("" = not run here yet: estimator only)
    device_kind: str = ""

    def power_at(self, utilization: float) -> float:
        """Board power at a given utilization (linear idle->TDP model).

        This mirrors the paper's measurement method: they average sampled
        instantaneous power over the latency window; we model that average.
        """
        u = min(max(utilization, 0.0), 1.0)
        return self.idle_watts + (self.tdp_watts - self.idle_watts) * u


REGISTRY: Dict[str, HardwareSpec] = {}
BY_DEVICE_KIND: Dict[str, HardwareSpec] = {}


def _reg(spec: HardwareSpec) -> HardwareSpec:
    REGISTRY[spec.name] = spec
    if spec.device_kind:
        BY_DEVICE_KIND[spec.device_kind] = spec
    return spec


# --- the paper's platforms --------------------------------------------------

A6000 = _reg(HardwareSpec(
    # NVIDIA RTX A6000: 38.7 TF fp32 / 154.8 TF fp16 tensor (dense),
    # 768 GB/s GDDR6, 300 W board, NVLink3 112.5 GB/s (2 bricks).
    name="a6000", kind="gpu",
    peak_flops_bf16=154.8e12, hbm_bw=768e9,
    link_bw=56.25e9, num_links=2,
    tdp_watts=300.0, idle_watts=22.0, mem_bytes=48 * 1000**3,
    eta_compute=0.65, eta_memory=0.85,  # calibrated on paper Table 3 rows
))

JETSON_ORIN_NANO = _reg(HardwareSpec(
    # Orin Nano 8GB: 40 INT8 sparse TOPS ≈ 10 TF fp16 dense, 68 GB/s LPDDR5,
    # 15 W module (7-15 W envelope), unified memory.
    name="jetson-orin-nano", kind="edge",
    peak_flops_bf16=10e12, hbm_bw=68e9,
    link_bw=0.0, num_links=0,
    tdp_watts=15.0, idle_watts=4.0, mem_bytes=8 * 1000**3,
    eta_compute=0.45, eta_memory=0.75,   # calibrated on paper Table 4
    rail_tdp_watts=5.5, rail_idle_watts=0.1,
))

JETSON_AGX_THOR = _reg(HardwareSpec(
    # AGX Thor 128GB devkit: 1 PFLOP fp8 *sparse* -> ~250 TF fp16 dense
    # (Blackwell), 273 GB/s LPDDR5X, 40-130 W envelope.  eta calibrated on
    # paper Table 4 (power-capped devkit sustains ~22% of dense peak).
    name="jetson-agx-thor", kind="edge",
    peak_flops_bf16=250e12, hbm_bw=273e9,
    link_bw=0.0, num_links=0,
    tdp_watts=130.0, idle_watts=15.0, mem_bytes=128 * 1000**3,
    eta_compute=0.22, eta_memory=0.60,
    rail_tdp_watts=78.0, rail_idle_watts=1.0,
))

# --- the assignment's target ------------------------------------------------

TPU_V5E = _reg(HardwareSpec(
    # Assignment constants: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
    # v5e: 16 GB HBM2, ~2D torus with 4 ICI links/chip. Power: ~200 W-class
    # accelerator envelope (Google reports v5e at roughly half v4's ~192 W
    # measured average; we use 170 W board TDP, 60 W idle).
    name="tpu-v5e", kind="tpu",
    peak_flops_bf16=197e12, hbm_bw=819e9,
    link_bw=50e9, num_links=4,
    tdp_watts=170.0, idle_watts=60.0, mem_bytes=16 * 1000**3,
    device_kind="TPU v5 lite",  # what a v5e chip reports to JAX
))

CPU_DEV = _reg(HardwareSpec(
    # The CPU dev container (measured-mode sanity runs only).
    name="cpu", kind="cpu",
    peak_flops_bf16=0.2e12, hbm_bw=20e9,
    link_bw=0.0, num_links=0,
    tdp_watts=65.0, idle_watts=10.0, mem_bytes=32 * 1000**3,
    eta_compute=0.5, eta_memory=0.5,
    device_kind="cpu",
))


def get_hardware(name: str) -> HardwareSpec:
    if name not in REGISTRY:
        raise KeyError(f"unknown hardware {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]


def hardware_for_device(device=None) -> HardwareSpec:
    """The spec of ``device`` (default: the first JAX device), looked up by
    the ``device_kind`` the runtime reports."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in BY_DEVICE_KIND:
        raise KeyError(
            f"no hardware spec for device_kind {kind!r}; known: "
            f"{sorted(BY_DEVICE_KIND)} (add one to core/hardware.py)")
    return BY_DEVICE_KIND[kind]
