"""Per-chip peaks, keyed by JAX's `device_kind`.

Source: Google Cloud TPU documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. Copied from
`repro.perf.roofline.PEAKS` (which has no int8 entry) so that the
benchmark's yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: int


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         hbm_bytes=16 * 10**9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
