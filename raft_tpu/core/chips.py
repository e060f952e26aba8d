"""Per-chip constants, keyed by ``jax.Device.device_kind``.

One table holds what the kernels and the benchmark assume about a TPU
generation: the Mosaic VMEM budget a kernel may request and the
published peaks a roofline share is taken against. A TPU whose kind is
not in the table is an error, never a default — a guessed budget or
peak silently changes tile sizes or every reported share.

Off the TPU (CPU tier-1 runs, where Pallas kernels run in interpret
mode) there is no VMEM; :func:`vmem_budget_mb` returns the fixed
interpret-mode budget that only sizes the kernels' tiles.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """What one TPU generation offers a kernel and a roofline."""

    vmem_budget_mb: int        # per-kernel Mosaic vmem_limit_bytes we ask
    hbm_bytes_per_s: float     # published HBM bandwidth
    bf16_flops_per_s: float    # published dense bf16 peak
    source: str


CHIPS = {
    # 128 MiB of physical VMEM per core; 64 MiB per kernel leaves the
    # compiler its own scratch (the budget the kernels were sized on)
    "TPU v5 lite": ChipSpec(
        vmem_budget_mb=64, hbm_bytes_per_s=819e9,
        bf16_flops_per_s=197e12,
        source="Google Cloud documentation, 'TPU v5e'"),
}

# interpret mode has no VMEM: this only sizes tiles off the TPU
INTERPRET_VMEM_MB = 16


def chip_spec(device: Optional[jax.Device] = None) -> ChipSpec:
    """The :class:`ChipSpec` of ``device`` (default: the first local
    device). Raises on a non-TPU device or a TPU kind not in
    :data:`CHIPS`."""
    device = device if device is not None else jax.local_devices()[0]
    if device.platform != "tpu":
        raise ValueError(f"no chip spec for platform {device.platform!r}")
    spec = CHIPS.get(device.device_kind)
    if spec is None:
        raise ValueError(
            f"unknown TPU device_kind {device.device_kind!r}: add it to "
            f"raft_tpu.core.chips.CHIPS (known: {sorted(CHIPS)})")
    return spec


def vmem_budget_mb() -> int:
    """Per-kernel Mosaic VMEM budget (MB) for the attached device —
    resolved OUTSIDE jit so ``RAFT_TPU_VMEM_MB`` is honored per call,
    not frozen into the first trace. The chip table on a TPU; the
    interpret-mode budget elsewhere."""
    env = os.environ.get("RAFT_TPU_VMEM_MB")
    if env:
        return int(env)
    device = jax.local_devices()[0]
    if device.platform != "tpu":
        return INTERPRET_VMEM_MB
    return chip_spec(device).vmem_budget_mb
