"""Chip peaks and the bytes a kernel must move, for roofline shares.

Peaks are keyed by ``device_kind`` as JAX reports it.  Source: Google
Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s).  A device kind that is not in the table is
an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                        int8_ops_per_s=393e12, hbm_bytes=16e9),
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind][key]


def stage_b_chunk_bytes(lanes: int, chunk: int) -> int:
    """Bytes one call of the event engine's stage-B kernel must move on
    one device holding ``lanes`` lanes, for a chunk of ``chunk`` requests:
    it reads the gaps and services (f32) and the record flags (bool) of
    every request, reads the Lindley carry ``W`` and the two per-lane
    terms (admission bound and base latency, f32), and writes ``W`` back
    (f32) and one int32 histogram index per request."""
    per_request = 4 + 4 + 1 + 4
    per_lane = 4 + 4 + 4 + 4
    return chunk * lanes * per_request + lanes * per_lane
