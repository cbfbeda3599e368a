"""The table of peaks and the arithmetic every roofline share rests on.

Copied from `deeplearning4j_tpu/observability/perf.py` (PEAKS,
matmul_flops, conv2d_flops) so that no later PR can move the yardstick;
the original is listed in PERF.md's Open questions for deletion.
The per-architecture counts (operations one image or one token requires,
bytes one step must move) live beside each plain reference in
`benchmark/reference/<arch>.py`.
"""

from __future__ import annotations

# Per-chip peaks keyed by `device_kind`. Source: Google Cloud TPU
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM.
# A device that is not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def device_peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]


def matmul_flops(m: int, k: int, n: int) -> float:
    """[m,k] @ [k,n]: one multiply and one add per MAC."""
    return 2.0 * m * k * n


def conv2d_flops(batch: int, out_h: int, out_w: int, c_out: int,
                 kh: int, kw: int, c_in: int) -> float:
    """Direct convolution, 2 per MAC, padded taps counted."""
    return 2.0 * batch * out_h * out_w * c_out * kh * kw * c_in


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     chips: int = 1) -> float:
    """The least time the chips could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / (peaks["flops"] * chips),
               nbytes / (peaks["bytes_per_s"] * chips))
