"""Bytes a kernel must move, and the chip's peaks to hold them against.

The limiter's kernels work in int64 (nanotoken PN lanes). No int64 peak
is published for the TPU v5e, so a kernel's roofline here is its byte
bound alone: the least time is the bytes the useful rows require over
the HBM bandwidth. Each function counts only the rows the kernel was
given (never the padding of its power-of-two shape), so a share cannot
exceed what the chip can do.

The per-row formulas start from ``bench.py``'s ``_roofline`` call sites
(take: ``rows * (N*2*8 + 96)``) and are written out from each kernel's
inputs and outputs below.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

INT64 = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A kind that
    is not in the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to peaks.json")
    return table[device_kind]


def take_bytes(rows: int, lanes: int) -> int:
    """``ops/take.py take_n_batch`` over ``rows`` useful rows of a state
    with ``lanes`` PN lanes: per row it reads the row's lanes (lanes x
    added/taken int64) and its elapsed counter, reads the 8 packed
    request fields, writes back the own lane (2 int64) and elapsed, and
    writes the 7 packed result fields."""
    per_row = lanes * 2 * INT64 + INT64 + 8 * INT64 + 2 * INT64 + INT64 + 7 * INT64
    return rows * per_row


def share(bytes_moved: int, kernel_s: float, device_kind: str) -> float:
    """Percent of the byte roofline: least time over measured time."""
    least = bytes_moved / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
