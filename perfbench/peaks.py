"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports. A
device that is not in the table is an error, not a default. (A copy of the
program's ``utils/peaks.py`` table, so that no later PR can move the
yardstick.)"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # dense matmul FLOP/s
    int8_ops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 2**30,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_of(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]
