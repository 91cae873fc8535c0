"""Hardware specifications and energy coefficients.

All energy numbers are *model* coefficients, not measurements: the container
is CPU-only, so Joules are derived from a roofline-timed power model
(DESIGN.md §2).  The coefficients are chosen so that

  E_op = P_flops * t_compute + P_hbm * t_memory + P_ici * t_collective
         + P_static * t_op,       t_op = max(t_compute, t_memory, t_coll)

reproduces the public chip TDP at full utilization.  What matters for
differential energy debugging is the *relative* energy between two
implementations of the same task; the model preserves ordering because both
sides are priced by the same coefficients.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip capability + energy model for one accelerator generation."""

    name: str
    # --- capability (roofline denominators) ---
    peak_flops_bf16: float      # FLOP/s
    peak_flops_fp32: float      # FLOP/s (MXU fp32-accurate passes)
    hbm_bw: float               # bytes/s
    hbm_bytes: float            # capacity, bytes
    vmem_bytes: float           # on-chip vector memory, bytes
    ici_bw_per_link: float      # bytes/s per ICI link (one direction)
    ici_links: int              # links per chip participating in a 2D/3D torus
    dcn_bw: float               # bytes/s per chip for cross-pod (data-center net)
    # --- energy model ---
    tdp_watts: float            # package power at full load
    idle_watts: float           # static/idle floor
    compute_watts: float        # dynamic power attributable to MXU/VPU at peak
    hbm_watts: float            # dynamic power attributable to HBM at peak bw
    ici_watts: float            # dynamic power attributable to interconnect

    # Derived energy coefficients -------------------------------------------------
    @property
    def joules_per_flop(self) -> float:
        return self.compute_watts / self.peak_flops_bf16

    @property
    def joules_per_hbm_byte(self) -> float:
        return self.hbm_watts / self.hbm_bw

    @property
    def joules_per_ici_byte(self) -> float:
        return self.ici_watts / (self.ici_bw_per_link * self.ici_links)

    # Roofline times ---------------------------------------------------------------
    def compute_time(self, flops: float, *, fp32: bool = False) -> float:
        peak = self.peak_flops_fp32 if fp32 else self.peak_flops_bf16
        return flops / peak

    def memory_time(self, hbm_bytes: float) -> float:
        return hbm_bytes / self.hbm_bw

    def collective_time(self, ici_bytes: float) -> float:
        return ici_bytes / (self.ici_bw_per_link * self.ici_links)


# TPU v5e ("efficiency") — the primary target of this repro.
# 197 TFLOP/s bf16, 819 GB/s HBM, 16 GiB, ~50 GB/s/link ICI (4 links, 2D torus).
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_fp32=197e12 / 3.0,   # fp32-accurate matmul ≈ 3 bf16 passes on MXU
    hbm_bw=819e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
    ici_bw_per_link=50e9,
    ici_links=4,
    dcn_bw=6.25e9,                   # ~50 Gb/s effective per chip across pods
    tdp_watts=220.0,
    idle_watts=60.0,
    compute_watts=110.0,
    hbm_watts=35.0,
    ici_watts=15.0,
)

# TPU v5p ("performance") — used for what-if roofline comparisons.
TPU_V5P = HardwareSpec(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_fp32=459e12 / 3.0,
    hbm_bw=2765e9,
    hbm_bytes=95 * 1024**3,
    vmem_bytes=128 * 1024**2,
    ici_bw_per_link=100e9,
    ici_links=6,
    dcn_bw=6.25e9,
    tdp_watts=350.0,
    idle_watts=90.0,
    compute_watts=180.0,
    hbm_watts=55.0,
    ici_watts=25.0,
)

# A generic CPU host — prices wall time that the ReplayProfiler measured on
# a CPU device (DEVICE_SPECS), so analytic and replayed numbers compare.
CPU_HOST = HardwareSpec(
    name="cpu_host",
    peak_flops_bf16=5e11,
    peak_flops_fp32=2.5e11,
    hbm_bw=2.0e10,
    hbm_bytes=64 * 1024**3,
    vmem_bytes=32 * 1024**2,
    ici_bw_per_link=1e10,
    ici_links=1,
    dcn_bw=1e9,
    tdp_watts=120.0,
    idle_watts=40.0,
    compute_watts=60.0,
    hbm_watts=15.0,
    ici_watts=5.0,
)

_SPECS = {s.name: s for s in (TPU_V5E, TPU_V5P, CPU_HOST)}

# The spec that prices time measured on a device, keyed by the
# ``device_kind`` JAX reports for it.  A kind missing here is an error:
# measured time converted with another device's coefficients is wrong
# without looking wrong.
DEVICE_SPECS = {"TPU v5 lite": TPU_V5E, "cpu": CPU_HOST}


def get_spec(name: str) -> HardwareSpec:
    try:
        return _SPECS[name]
    except KeyError:
        raise KeyError(f"unknown hardware spec {name!r}; have {sorted(_SPECS)}")


def spec_for_device_kind(kind: str) -> HardwareSpec:
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise KeyError(f"no hardware spec for device kind {kind!r}; "
                       f"have {sorted(DEVICE_SPECS)}") from None


def local_device_spec() -> HardwareSpec:
    """The spec of the device this process runs on (``jax.devices()[0]``)."""
    import jax
    return spec_for_device_kind(jax.devices()[0].device_kind)
