"""Published per-chip peaks: the one table MFU, roofline shares and the
planner's cost model price against, keyed by the exact ``device_kind`` string
jax reports for the chip.

A chip that is not in the table is an error, not a default: a peak matched by
substring prices any "v5" part as a v5e, and a guessed peak turns every
utilization figure downstream into fiction. Add a row — with its source — when
the program first runs on new hardware.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float  # dense matmul FLOP/s
    int8_ops: float  # dense matmul OP/s
    hbm_bytes_per_sec: float
    source: str


PEAKS: Dict[str, DevicePeaks] = {
    # what jax.devices()[0].device_kind says on a v5e chip (chip run, PR 21)
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_sec=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


class UnknownDeviceError(LookupError):
    """A TPU whose ``device_kind`` has no row in :data:`PEAKS`."""


def device_peaks(
    device_kind: str, platform: Optional[str] = None
) -> Optional[DevicePeaks]:
    """The published peaks of ``device_kind``; ``None`` for a device that is
    not a TPU (nothing here is priced against a CPU's peak); raises
    :class:`UnknownDeviceError` for a TPU the table does not know. Without
    ``platform`` (a hand-built what-if topology), a kind jax would report for
    a TPU — they all start with ``TPU`` — counts as one."""
    peaks = PEAKS.get(device_kind)
    if peaks is not None:
        return peaks
    on_tpu = platform == "tpu" if platform else device_kind.startswith("TPU")
    if on_tpu:
        raise UnknownDeviceError(
            f"no published peaks for TPU device_kind {device_kind!r} — add a "
            f"row (with its source) to {__name__}.PEAKS; known: "
            f"{sorted(PEAKS)}"
        )
    return None
