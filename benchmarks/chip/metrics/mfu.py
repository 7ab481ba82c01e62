"""Whole step: the FLOPs of the rows returned per second over the peak of
the chips the cell uses, in percent, over the traced window.  FLOPs per
row come from ``work.py`` at the net's widths."""
import work


def read(rec):
    if rec["trace"] is None or rec["window_s"] <= 0:
        return None
    flops = rec["rows"] * work.flops_per_row(rec["widths"])
    return 100.0 * flops / rec["window_s"] / (
        rec["chips"] * work.peak_for(rec["device_kind"])["flops_per_s"])
