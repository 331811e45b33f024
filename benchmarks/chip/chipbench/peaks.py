"""Peaks of the chips the benchmark may run on, and the search's least time.

One table, keyed by ``device_kind`` as jax reports it. A device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s per chip. jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


def search_work(nq: int, k: int, rows: int, dim: int) -> tuple[float, float]:
    """(FLOPs, bytes) an exact f32 MIPS of ``nq`` queries over ``rows``
    valid rows of width ``dim`` needs: every row read once, the queries read
    and the (score, id) pairs written once; ``2·nq·rows·dim`` FLOPs."""
    flops = 2.0 * nq * rows * dim
    bytes_ = 4.0 * rows * dim + 4.0 * nq * dim + 8.0 * nq * k
    return flops, bytes_


def search_least_seconds(nq: int, k: int, rows: int, dim: int, peaks: dict) -> float:
    """The least time the chip could take for one such search: the larger
    of FLOPs over the bf16 peak and bytes over the HBM peak. The served
    work is float32 at the highest precision, so the bf16 peak makes this a
    lower bound."""
    flops, bytes_ = search_work(nq, k, rows, dim)
    return max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
