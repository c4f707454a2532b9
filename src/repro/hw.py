"""TPU v5e hardware constants — single source of truth for the overhead model
and the roofline analysis.

The container runs on CPU; these numbers describe the TARGET hardware
(TPU v5e) and are used analytically by default.  The CostEngine's
calibration layer (core/costs/calibration.py) can REPLACE individual fields
with values microbenchmarked on the running backend; ``to_dict`` /
``from_dict`` exist so calibrated specs persist to a JSON cache keyed by
backend fingerprint.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip TPU hardware description."""

    name: str = "tpu-v5e"
    # Compute
    peak_flops_bf16: float = 197e12  # FLOP/s per chip (bf16 MXU)
    peak_flops_f32: float = 49.25e12  # ~1/4 of bf16 on v5e
    # Memory
    hbm_bytes: float = 16e9  # 16 GB HBM per chip
    hbm_bw: float = 819e9  # bytes/s
    vmem_bytes: float = 128 * 1024 * 1024  # ~128 MiB VMEM
    # Interconnect (feeds every collective term, incl. the serve_shard
    # shard-vs-replicate site; calibration can replace ici_bw_per_link and
    # collective_base_s with measured backend values)
    ici_bw_per_link: float = 50e9  # bytes/s per ICI link direction
    ici_links: int = 4  # 2D torus: 4 links per chip
    dcn_bw: float = 25e9 / 8  # inter-pod DCN, bytes/s per host share
    # Fixed overheads (the paper's "thread creation" analogue)
    kernel_launch_s: float = 2e-6  # per dispatched program
    collective_base_s: float = 1e-5  # per collective setup/sync latency
    host_sync_s: float = 5e-6  # per device->host round trip (fetch + bookkeeping)
    prefix_lookup_s: float = 1e-7  # per-block radix-trie lookup/pin (host side)
    # Host IPC (serving front end: parent <-> pinned worker processes).
    # Round trip = enqueue + wake + dequeue + reply through a bounded
    # multiprocessing queue; bandwidth = pickle serialization + pipe
    # transit for message payloads.  Both feed the serve_ipc cost site.
    ipc_round_trip_s: float = 50e-6  # per-message queue round trip
    ipc_bytes_per_s: float = 1e9  # serialization + transport bandwidth
    # MXU tiling
    mxu_dim: int = 128  # systolic array native tile
    lane_dim: int = 128  # VPU lane count
    sublane_dim: int = 8  # f32 sublanes

    @property
    def vmem_limit_bytes(self) -> int:
        """Scoped VMEM every Pallas kernel asks the compiler for
        (``vmem_limit_bytes``) and the bound the tuner holds each
        candidate's working-set estimate to.  Three quarters of physical
        VMEM: the rest is the compiler's internal scratch.  Without an
        explicit limit Mosaic applies a much smaller default scope."""
        return int(self.vmem_bytes * 0.75)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "HardwareSpec":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


V5E = HardwareSpec()

# Specs of the chips this repo has run on, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s ICI per chip); "TPU v5 lite" is the kind a
# v5e reports.  A kind that is not here is an error, not a default.
DEVICE_SPECS = {"TPU v5 lite": V5E}


def spec_for_device_kind(kind: str) -> HardwareSpec:
    """The row of ``DEVICE_SPECS`` for ``kind``; unknown kinds raise."""
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise ValueError(
            f"no HardwareSpec for device kind {kind!r} (known: "
            f"{sorted(DEVICE_SPECS)}); add its datasheet row to "
            f"repro.hw.DEVICE_SPECS") from None


def running_spec() -> HardwareSpec:
    """The spec of the attached chip on a TPU backend; on any other backend
    ``V5E``, the named target the analytic model prices for."""
    import jax

    if jax.default_backend() != "tpu":
        return V5E
    return spec_for_device_kind(jax.devices()[0].device_kind)

# Which HardwareSpec fields dominate each CostQuery site's prediction.
# This is the dispatch table for TARGETED recalibration (DESIGN.md §10):
# when a site shows sustained out-of-band drift, only the probes for ITS
# fields re-run — re-measuring the whole spec to fix one drifted constant
# would perturb every other site's healthy calibration for nothing.
# Fields without a calibration probe on the running backend (probe returns
# None) keep their current value; that is the probe layer's concern, not
# this table's.
SITE_FIELDS = {
    "matmul": ("peak_flops_bf16", "peak_flops_f32", "hbm_bw",
               "kernel_launch_s"),
    "sort": ("hbm_bw", "kernel_launch_s"),
    "scan_chunk": ("hbm_bw", "kernel_launch_s"),
    "moe_dispatch": ("ici_bw_per_link", "collective_base_s"),
    "layer_shard": ("peak_flops_bf16", "ici_bw_per_link",
                    "collective_base_s"),
    "autotune": ("kernel_launch_s", "hbm_bw"),
    "serve": ("peak_flops_bf16", "hbm_bw", "kernel_launch_s"),
    "serve_macro": ("host_sync_s", "kernel_launch_s"),
    "serve_shard": ("ici_bw_per_link", "collective_base_s"),
    "serve_admit": ("peak_flops_bf16", "hbm_bw"),
    "serve_prefix": ("prefix_lookup_s", "hbm_bw"),
    "serve_ipc": ("ipc_round_trip_s", "ipc_bytes_per_s"),
}


def mxu_aligned(n: int, spec: HardwareSpec = V5E) -> bool:
    """True if a matmul dim is MXU-tile aligned."""
    return n % spec.mxu_dim == 0


def dtype_bytes(dtype) -> int:
    import numpy as np

    return np.dtype(dtype).itemsize
