"""Peaks of the chip and the bytes a grouped aggregation needs.

The bytes are counted from the plan and the data sizes alone — the rows a
call reads, the columns its plan reads plus one group id per input row,
four bytes each, and its result — never from the kernel's grid or
operand layout, so the count stays the same whatever implements the
aggregation.  Requests coalesced into one launch share its input: the
input is counted once per launch, the result once per request.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
WORD = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that
    is not in the table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def input_bytes(reads: dict, rows: dict) -> int:
    """Bytes one pass over the input needs: every column the plan reads
    from each table (``reads``: table → columns; the first table is the
    aggregated one), at its ``rows``, plus a group id per aggregated row."""
    total = 0
    for i, (table, cols) in enumerate(reads.items()):
        total += rows[table] * (len(cols) + (1 if i == 0 else 0)) * WORD
    return total


def output_bytes(groups: int, columns: int) -> int:
    """Bytes of one result: ``groups`` rows of ``columns`` values."""
    return groups * columns * WORD


def min_seconds(nbytes: float, device_kind: str) -> float:
    """The least time the chip's HBM takes to move ``nbytes``."""
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
