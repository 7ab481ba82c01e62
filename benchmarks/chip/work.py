"""Operations and bytes of one surrogate call, computed from the net's
widths, and the chip's peaks keyed by ``device_kind``.

Counts are the algorithm's: a dense layer of fan-in ``a`` and width ``b``
costs ``2 a b`` FLOPs a row (one multiply and one add per weight); bias,
activation and normalization are left out.  Bytes are what one call must
move at least: every weight and bias once, every input and output row
once, in f32.  Padding of a batch or of a width is never counted.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32_BYTES = 4


def flops_per_row(widths) -> int:
    return 2 * sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def weight_bytes(widths) -> int:
    params = sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
    return F32_BYTES * params


def call_bytes(widths, rows: int) -> int:
    """Least bytes one call over ``rows`` rows moves to and from HBM."""
    return weight_bytes(widths) + F32_BYTES * rows * (widths[0] + widths[-1])


def least_time_s(widths, rows: int, peak: dict) -> float:
    """The roofline's least time for one call: the larger of FLOPs over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops_per_row(widths) * rows / peak["flops_per_s"],
               call_bytes(widths, rows) / peak["bytes_per_s"])


def peak_for(device_kind: str, path=PEAKS_FILE) -> dict:
    """The chip's peaks; a device kind with no entry is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
