"""The yardstick for kernel rooflines: chip peaks and the bytes each
operation must move.

``PEAKS`` is keyed by the ``device_kind`` JAX reports.  Source: Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip.  A
device missing from the table is an error, never a default.

The bytes functions count only the words any implementation of the
operation has to move, at the device word width (int32, 4 bytes):
no addresses, no 128-word rows, no padding, no version ring and no
upload of the heap.  So a later implementation that moves less than the
present kernel reads higher, and none can read above 100%.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1.6e12},
}

#: bytes of one device word (balances live on the device as int32)
WORD_BYTES = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def gather_bytes(words: int) -> int:
    """A gather of ``words`` words: each read once and written once."""
    return 2 * WORD_BYTES * int(words)


def commit_bytes(written_words: int) -> int:
    """A commit of ``written_words`` words: each written word in once
    and into the live row once."""
    return 2 * WORD_BYTES * int(written_words)


def roofline_share(nbytes: float, kernel_s: float, device_kind: str):
    """Percent of the memory roofline: the least time the chip could
    take to move ``nbytes`` over the measured kernel time.  ``None``
    when there is nothing to read (no kernel time or no bytes)."""
    if kernel_s <= 0 or nbytes <= 0:
        return None
    least = nbytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
