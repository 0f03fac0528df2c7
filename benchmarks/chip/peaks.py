"""Published peaks of each chip the benchmark may run on, keyed by the
`device_kind` JAX reports.  A chip that is not listed is an error: a share
of a peak taken against another chip's numbers would be wrong, not rough.
"""

from __future__ import annotations

import dataclasses

V5E_SOURCE = 'Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e)'


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B
    source: str


_V5E = Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9, source=V5E_SOURCE)

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
