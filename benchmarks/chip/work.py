"""The roofline's least time of one call, from the operations and bytes
that the cell's architecture counts (``archs/<arch>.py``:
``flops_per_row`` and ``call_bytes``), and the chip's peaks keyed by
``device_kind``.

An architecture counts the algorithm's work: no padding of a batch or of
a width, every weight once, every input and output row once, in f32.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32_BYTES = 4


def least_time_s(flops, nbytes, peak: dict) -> float:
    """The roofline's least time for one call of ``flops`` operations
    moving ``nbytes`` bytes: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])


def peak_for(device_kind: str, path=PEAKS_FILE) -> dict:
    """The chip's peaks; a device kind with no entry is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
