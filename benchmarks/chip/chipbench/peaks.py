"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind missing here is an error: a share of
the wrong chip's peak is a wrong number."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    int8_ops: float      # OP/s on the MXU in int8
    bf16_flops: float    # FLOP/s on the MXU in bf16
    hbm_bw: float        # HBM bytes/s
    source: str


V5E = Peaks(int8_ops=393e12, bf16_flops=197e12, hbm_bw=819e9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {"TPU v5 lite": V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
