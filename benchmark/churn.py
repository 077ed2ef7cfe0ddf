"""What the churn cell's driver and readers share: the flows and the
spoofed flows a run of sealed batches held, and the bytes the aging
sweep needs.

A spoofed source is one ``fsxd --sim --spoof-fraction`` made: the top
bit of its key is set (the pools lie below 2^25) and it sends one
record, so in the source tap's sealed rows a spoofed flow is a row with
that bit.  A flow is a (batch, key) pair: what ``GlobalStats.untracked``
counts the rowless ones of.
"""

from __future__ import annotations

import numpy as np

from benchmark import peaks

SPOOFED_BIT = np.uint32(0x80000000)


def flows_between(words: list, start: int, stop: int) -> tuple[int, int]:
    """``(flows, spoofed flows)`` of the sealed batches ``words[start:
    stop]`` (``[n, 4]`` u32 each, key in column 0)."""
    flows = spoofed = 0
    for w in words[start:stop]:
        key = w[:, 0]
        flows += len(np.unique(key))
        spoofed += int(np.count_nonzero(key & SPOOFED_BIT))
    return flows, spoofed


def sweep_bytes(table: dict, batches: int, evicted: int) -> int:
    """HBM bytes the aging sweep needs for ``batches`` batches that
    freed ``evicted`` rows: its window of ``ceil(capacity /
    evict_every)`` rows read a batch, and each freed row written, a row
    at the schema's width (``peaks.TABLE_ROW_BYTES``)."""
    window = -(-table["capacity"] // table["evict_every"])
    return (batches * window + evicted) * peaks.TABLE_ROW_BYTES
